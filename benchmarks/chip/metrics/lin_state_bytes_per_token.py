"""Delta-rule state bytes the algorithm requires read and written in the
window per token it emitted: the engine's ``lin_state_lane_moves`` (live
lanes x the state reads and writes a round requires: 2 a plain decode
round, 3 a verify round, which reads the state once for the round's
outputs and once more to apply what was accepted) x the architecture's
``lin_state_bytes_per_lane``, over ``tokens_out``. 83.9 MB where every
round is plain and emits one token a lane; falls with accepted drafts,
rises with verify rounds that accept nothing. Prefill chunks move one
lane's state each and are left out."""


def read(obs):
    c = obs.get("counters") or {}
    if obs.get("job") != "serve" or not c.get("lin_state_lane_moves") \
            or not obs.get("tokens_out"):
        return None
    per_lane = obs["arch"].lin_state_bytes_per_lane(obs["model"],
                                                    obs["layers"])
    return c["lin_state_lane_moves"] * per_lane / obs["tokens_out"]
