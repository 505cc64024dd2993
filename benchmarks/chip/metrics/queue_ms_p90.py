"""90th percentile of the wait from a request's due time to its admission:
generator lateness plus the engine's own queue (Request.attribution)."""
from chiplib.common import quantile


def read(obs):
    if obs["job"] != "serve" or obs["loop"] != "open":
        return None
    v = [r["queue_ms"] + late for r, late in
         zip(obs["requests"], obs["late_ms"])]
    return quantile(v, 0.9) if v else None
