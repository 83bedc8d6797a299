"""95th percentile over every request due in the window of the time from
when it was due to its first output token. The run goes on after the window
until each has one; a request with none by the drain's end counts with the
time it had waited by then."""
from bench.readout import p95


def read(run):
    tl = run.timeline
    return p95([(r.tokens_t[0] if r.tokens_t else tl.t_last) - r.due
                for r in tl.records]) * 1e3 if tl.records else None
