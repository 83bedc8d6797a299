"""Model step: device busy time inside each ``stream_step`` span of the
traced part of the window, averaged over its steps."""


def read(run):
    if run.trace is None or not run.trace.step_busy_s:
        return None
    busy = run.trace.step_busy_s
    return 1e3 * sum(busy) / len(busy)
