"""95th percentile of every gap between two consecutive output tokens of a
request that both appeared in the window, over all requests."""
from bench.readout import p95


def read(run):
    tl = run.timeline
    gaps = []
    for r in tl.records:
        times = tl.in_window(r.tokens_t)
        gaps += [b - a for a, b in zip(times, times[1:])]
    return p95(gaps) * 1e3 if gaps else None
