"""Model step, prefill one token a step: 95th percentile (nearest rank) over
the requests due in the window that were admitted, in the window or in the
drain after it, of the engine's stamps ``Request.admit_t`` to
``Request.first_token_t``. A request with no first token by the drain's end
counts with the time since its admission by then."""
from bench.readout import p95


def read(run):
    tl = run.timeline
    times = []
    for r in tl.records:
        admit = getattr(r.req, "admit_t", None)
        if admit is None:
            continue
        first = getattr(r.req, "first_token_t", None)
        times.append((tl.t_last if first is None else first) - admit)
    return p95(times) * 1e3 if times else None
