"""Output tokens that appeared in the window over the window's length."""


def read(run):
    tl = run.timeline
    return sum(len(tl.in_window(r.tokens_t)) for r in tl.records) \
        / (tl.t1 - tl.t0)
