"""How uneven the held experts' load is: the fullest held expert's
assignments over the mean held expert's, each summed over the window's
expert-layer calls (``moe_load_max_sum`` over ``moe_assignments_held`` /
experts held). 1.0 is even; a dropless layer's grouped products take as
long as their fullest group's tiles."""


def read(obs):
    c = obs.get("counters") or {}
    held = c.get("moe_assignments_held")
    if obs.get("job") != "serve" or not held:
        return None
    return c["moe_load_max_sum"] * obs["model"]["n_routed_experts"] / held
