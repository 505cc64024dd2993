"""How late the load generator sent requests (99th percentile): it shares
one thread with the engine, so a long engine step delays submission. The
end-to-end times count from the due time, so lateness is never hidden."""
from chiplib.common import quantile


def read(obs):
    if obs["job"] != "serve" or obs["loop"] != "open" or not obs["late_ms"]:
        return None
    return quantile(obs["late_ms"], 0.99)
