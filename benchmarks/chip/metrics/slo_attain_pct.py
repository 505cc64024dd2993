"""Share of the window's requests that met both limits of the traffic
file's ``slo`` (a request that did not finish misses)."""


def read(obs):
    if obs["job"] != "serve" or obs["loop"] != "open" or not obs["slo"]:
        return None
    slo, reqs = obs["slo"], obs["requests"]
    met = sum(1 for r in reqs if r["finished"]
              and r.get("ttft_ms", 1e30) <= slo["ttft_ms"]
              and r.get("tpot_ms", 0.0) <= slo["tpot_ms"])
    return 100.0 * met / len(reqs) if reqs else None
