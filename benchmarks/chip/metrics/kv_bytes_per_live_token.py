"""Device bytes of cache HELD per live token, over the traced rounds:
what the running lanes hold in the paged pool as stored (the full layers
alone take blocks; whole blocks, a lane's last one half full on average)
plus their rings (one a window layer a lane, whatever the lane's length),
over the tokens those lanes hold (the architecture's ``cache_bytes_held``;
lanes and live tokens from the benchmark's round records). 5,120 B a
token of pool at the published widths plus 3.7 MB of ring a lane: ~6-7 KB
at contexts of 3k, where window layers that kept every token would read
30,720 B (``every_layer_kv_bytes_per_token``). Falls as contexts grow."""


def read(obs):
    arch = obs.get("arch")
    if obs.get("job") != "serve" or not hasattr(arch, "cache_bytes_held"):
        return None
    rounds = [r for r in obs.get("rounds", ()) if r["live_kv_tokens"]]
    tokens = sum(r["live_kv_tokens"] for r in rounds)
    if not tokens:
        return None
    return sum(arch.cache_bytes_held(obs["model"], obs["layers"],
                                     r["live_kv_tokens"], r["lanes"])
               for r in rounds) / tokens
