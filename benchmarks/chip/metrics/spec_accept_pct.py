"""Draft tokens accepted over draft tokens proposed (engine counters).
On random weights this is not a deployment's acceptance (ROADMAP A5)."""


def read(obs):
    if obs["job"] != "serve" or obs["loop"] != "backlog":
        return None
    c = obs["counters"]
    if not c["spec_proposed_tokens"]:
        return None
    return 100.0 * c["spec_accepted_tokens"] / c["spec_proposed_tokens"]
