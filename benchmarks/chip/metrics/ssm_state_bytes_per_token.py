"""Recurrent-state bytes read and written in the window per token it
emitted: the engine's ``ssm_state_lane_moves`` (live lanes x the times a
round read or wrote their state: 2 a plain decode round, 3 a verify round,
which reads it once for the round's outputs and once more to apply what
was accepted) x the architecture's ``ssm_state_bytes_per_lane``, over
``tokens_out``. 151 MB where every round is plain and emits one token a
lane; falls with accepted drafts, rises with verify rounds that accept
nothing. Prefill chunks move one lane's state each and are left out."""


def read(obs):
    c = obs.get("counters") or {}
    if obs.get("job") != "serve" or not c.get("ssm_state_lane_moves") \
            or not obs.get("tokens_out"):
        return None
    per_lane = obs["arch"].ssm_state_bytes_per_lane(obs["model"],
                                                    obs["layers"])
    return c["ssm_state_lane_moves"] * per_lane / obs["tokens_out"]
