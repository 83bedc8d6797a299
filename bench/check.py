"""Whether the timed window served the right tokens.

Once the window has closed and the engine is gone, a sample of the requests
it served, drawn from the seed and holding the one with the most served
tokens, goes through the plain float32 reference (``bench/reference/<family>.py``), with
weights made again from the seed. At every position where the engine chose
a token (the last prompt token onwards), the gap is how far the reference's
logit of that token lies below the reference's best logit there. Greedy
decoding that agrees with the reference up to rounding gives gaps near 0;
a cache, a state or a slot reset that went wrong gives gaps of whole logits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights


def same_layout(cfg, params) -> None:
    """The bench's weights have the tree, shapes and dtypes that the system
    under test initialises itself."""
    from repro.models import transformer as T
    want = jax.eval_shape(lambda: T.init_params(cfg, jax.random.key(0)))
    got_s = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want_s = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if got_s != want_s:
        raise SystemExit(f"bench: weight layout differs from the program's:\n"
                         f"bench   {got_s}\nprogram {want_s}")


@jax.jit
def gap_stats(logits, targets):
    """Over positions with a target (>= 0): the widest gap of the target's
    logit below the best, the number of positions, the sum of the gaps and
    the number of positions where the target is not the best."""
    best = jnp.max(logits, -1)
    picked = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[:, None],
                                 -1)[:, 0]
    valid = targets >= 0
    gap = jnp.where(valid, best - picked, 0.0)
    return (jnp.max(gap), jnp.sum(valid), jnp.sum(gap),
            jnp.sum(gap > 0))


def sample(timeline, seed: int, size: int) -> list:
    """The requests to compare: the one with the most served tokens, then
    ``size - 1`` others drawn from the seed. A request still decoding when
    the window closed counts with the tokens it had streamed: with long
    outputs, few requests finish inside one window."""
    done = [r for r in timeline.records if r.req.output]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.req.output), -r.job.index))
    others = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed % 2**63, 0xC4EC])
    pick = rng.choice(len(others), size=min(size - 1, len(others)),
                      replace=False)
    return [longest] + [others[i] for i in sorted(pick)]


def positions(prompt: list, output: list, length: int):
    """Tokens padded to ``length`` and, at each position, the token the
    engine chose there (-1 where it chose none)."""
    tokens = np.zeros(length, np.int32)
    seq = list(prompt) + list(output)
    tokens[:len(seq)] = seq
    targets = np.full(length, -1, np.int32)
    p = len(prompt)
    targets[p - 1:p - 1 + len(output)] = output
    return tokens, targets


def summarize(stats) -> dict:
    """The numbers a check can compare, from ``gap_stats`` of each sampled
    request: the widest gap anywhere and the mean gap per compared token."""
    stats = [tuple(float(v) for v in st) for st in stats]
    count = sum(n for _, n, _, _ in stats)
    return {"max_logit_gap": max((g for g, _, _, _ in stats), default=0.0),
            "mean_logit_gap": sum(t for _, _, t, _ in stats) / max(count, 1),
            "not_best_tokens": sum(m for _, _, _, m in stats),
            "compared_tokens": count}


def compare(cell, timeline, seed: int, ref, layout, *,
            control: bool = False) -> dict:
    """The numbers compared, each beside its limit (``limits`` in the cell's
    file names which). With ``control``, the int8 reference stands in the
    program's place: at each of the same positions its first choice is
    compared instead of the engine's token."""
    chosen = sample(timeline, seed, cell.params["check_sample"])
    found = {"compared_tokens": 0}
    if chosen:
        params = weights.make_params(layout, seed)
        logits_of = ref.logits_fn(cell.conf["model"])
        lowered_of = ref.logits_fn(cell.conf["model"], int8=True) \
            if control else None
        stats = []
        for r in chosen:
            tokens, targets = positions(r.job.prompt, r.req.output,
                                        cell.params["max_len"])
            tokens, targets = jnp.asarray(tokens), jnp.asarray(targets)
            if control:
                first = jnp.argmax(lowered_of(params, tokens), -1)
                targets = jnp.where(targets >= 0, first.astype(jnp.int32), -1)
            stats.append(gap_stats(logits_of(params, tokens), targets))
        found = summarize(stats)
    out = {name: {"value": found.get(name, float("inf")), "limit": limit}
           for name, limit in cell.params["limits"].items()}
    out["compared_tokens"] = {"value": found["compared_tokens"], "min": 1}
    return out


def verdict(checks: dict) -> bool:
    """``correct``: every number within its limit or at least its least."""
    return all(c["value"] <= c["limit"] for c in checks.values()
               if "limit" in c) and all(
        c["value"] >= c["min"] for c in checks.values() if "min" in c)
