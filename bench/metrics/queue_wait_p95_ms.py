"""Scheduler: 95th percentile of the time from when a request was due to
the start of the ``stream_step`` that admitted it, over every request due
in the window, those admitted in the drain after it included. A request
still queued at the drain's end counts with the time it had waited by
then."""
from bench.readout import p95


def read(run):
    tl = run.timeline
    return p95([(r.admit_t if r.admit_step >= 0 else tl.t_last) - r.due
                for r in tl.records]) * 1e3 if tl.records else None
