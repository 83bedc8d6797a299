"""Expert layer: tokens that one held expert computes per expert layer per
step, ``EngineStats.expert_assignments`` over ``moe_layer_steps`` times
the experts held (``num_local_experts``), over the window. Only a
configuration that holds experts counts them; elsewhere, and in a program
without the counters, there is nothing to read."""


def read(run):
    s = run.timeline.stats
    held = run.cell.conf["model"].get("num_local_experts")
    if not held or not s.get("moe_layer_steps"):
        return None
    return s["expert_assignments"] / (s["moe_layer_steps"] * held)
