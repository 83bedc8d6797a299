"""Cache: the share of the reserved KV cache rows that the window's steps
read live, ``EngineStats.live_row_steps`` over ``slot_steps`` times the
cell's ``max_len``, in percent. Only a dense configuration has a per-slot
KV cache."""


def read(run):
    s = run.timeline.stats
    if (run.cell.conf.get("family") != "dense" or not s.get("slot_steps")
            or "live_row_steps" not in s):
        return None
    return 100.0 * s["live_row_steps"] / (s["slot_steps"]
                                          * run.cell.params["max_len"])
