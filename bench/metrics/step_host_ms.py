"""Engine host loop: host milliseconds a step in which the host does its own
work, the window's ``EngineStats`` phase seconds (``engine.admit``,
``engine.reset``, ``engine.feed``, ``engine.decode``, ``engine.emit``; not
``engine.pull``, where the host waits for the device) over its steps."""

PHASES = ("admit_s", "reset_s", "feed_s", "decode_s", "emit_s")


def read(run):
    s = run.timeline.stats
    if not s.get("steps") or any(p not in s for p in PHASES):
        return None
    return 1e3 * sum(s[p] for p in PHASES) / s["steps"]
