"""Per traced step that decodes: from ``serving/step``'s start to the
round's ``serving/dispatch`` — what admissions and chunk-32 prefills add
to every decoding lane's gap. 90th percentile, open-loop cells."""
from chiplib import progspans


def read(obs):
    return progspans.hold_ms(obs, "open", 0.9)
