"""Device time of the PREFILL program's executions over the traced
rounds, open-loop cells: what chunked prefill adds to a decoding round on
the device. Reading it also prints the cell's ``device_by_scope`` note
(``chiplib/devscopes.py``)."""
from chiplib import devscopes


def read(obs):
    return devscopes.prefill_ms_per_round(obs, "open")
