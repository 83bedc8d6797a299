"""Scheduler: EngineStats.active_slot_steps over slot_steps in the window,
in percent."""


def read(run):
    s = run.timeline.stats
    return 100.0 * s["active_slot_steps"] / s["slot_steps"] \
        if s.get("slot_steps") else None
