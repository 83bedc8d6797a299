"""Slot-stream continuous batching (``scheduler="stream"``) + the PR-4
serving-ledger fixes.

Exactness: per-slot position streams with masked slot resets
(``models/transformer.py:reset_decode_slots``) must make the stream
scheduler's decoded outputs token-identical to the wave scheduler's for any
fixed request set — across architecture families, including the recurrent
(RWKV/Mamba) ones whose state carries history densely. Ledger fixes:
prefill/decode attribution, finish reasons, deque queue draining,
placement-epoch energy attribution, SLO-aware admission.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro import models as M
from repro.models import attention as attn_mod
from repro.models import transformer as T
from repro.runtime import Placement, Request, ServingEngine


@pytest.fixture(scope="module")
def small_model():
    cfg = reduced(get_config("llama3.2-3b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _ragged(n=6):
    """Deliberately ragged lengths: the wave scheduler idles slots on these."""
    reqs = []
    for i in range(n):
        plen = 2 + (i * 5) % 13
        reqs.append(Request(rid=i,
                            prompt=[1 + (i + j) % 11 for j in range(plen)],
                            max_new_tokens=1 + (i * 3) % 7))
    return reqs


def _serve(cfg, params, reqs, scheduler, **kw):
    eng = ServingEngine(cfg, params, scheduler=scheduler, **kw)
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    return eng, done


# ---------------------------------------------------------------------------
# Exactness: stream == wave, token for token
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["llama3.2-3b", "rwkv6-1.6b", "zamba2-7b"])
def test_stream_matches_wave_token_identical(arch):
    """Dense (KV cache), SSM (recurrent) and hybrid (both): mid-stream
    admission with per-slot resets changes scheduling only, never tokens."""
    cfg = reduced(get_config(arch))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    _, wave_done = _serve(cfg, params, _ragged(5), "wave",
                          slots=2, max_len=24)
    _, stream_done = _serve(cfg, params, _ragged(5), "stream",
                            slots=2, max_len=24)
    assert len(wave_done) == len(stream_done) == 5
    wave_out = {r.rid: r.output for r in wave_done}
    stream_out = {r.rid: r.output for r in stream_done}
    assert wave_out == stream_out


def test_stream_beats_wave_occupancy_on_ragged_lengths(small_model):
    """The point of slot streams: freed slots re-admit the next step instead
    of idling until the wave's longest request drains."""
    cfg, params = small_model
    wave_eng, _ = _serve(cfg, params, _ragged(8), "wave",
                         slots=3, max_len=32)
    stream_eng, _ = _serve(cfg, params, _ragged(8), "stream",
                           slots=3, max_len=32)
    # identical work ...
    assert stream_eng.stats.total_tokens == wave_eng.stats.total_tokens
    # ... on fewer steps at strictly higher occupancy
    assert stream_eng.stats.steps < wave_eng.stats.steps
    assert stream_eng.stats.occupancy > wave_eng.stats.occupancy
    assert stream_eng.stats.waves == 0
    assert stream_eng.stats.admissions == 8


def test_reset_decode_slots_isolates_streams():
    """Model-level admission primitive: resetting one slot restarts its
    stream exactly (logits match a fresh state) while its neighbor's stream
    is untouched — the recurrent family is the hard case."""
    for arch in ("rwkv6-1.6b", "llama3.2-3b"):
        cfg = reduced(get_config(arch))
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        st = M.init_decode_state(cfg, 2, 16)
        for t in (3, 5, 7):  # both slots decode some prefix
            _, st = M.decode_step(cfg, params, st,
                                  jnp.array([t, t + 1], jnp.int32))
        st_reset = M.reset_decode_slots(cfg, st,
                                        jnp.array([True, False]))
        fresh = M.init_decode_state(cfg, 2, 16)
        for t in (2, 4):  # slot 0 restarts; slot 1 continues with token 9
            la, st_reset = M.decode_step(cfg, params, st_reset,
                                         jnp.array([t, 9], jnp.int32))
            lf, fresh = M.decode_step(cfg, params, fresh,
                                      jnp.array([t, 0], jnp.int32))
            lc, st = M.decode_step(cfg, params, st,
                                   jnp.array([t, 9], jnp.int32))
            np.testing.assert_allclose(np.asarray(la[0]), np.asarray(lf[0]),
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(np.asarray(la[1]), np.asarray(lc[1]),
                                       rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Ledger fix: prefill/decode attribution
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scheduler", ["wave", "stream"])
def test_prefill_tokens_equal_prompt_lengths(small_model, scheduler):
    """Regression for the off-by-one: the step consuming the LAST prompt
    token is prefill, so prefill_tokens == sum of (served) prompt lengths."""
    cfg, params = small_model
    reqs = _ragged(6)
    prompt_total = sum(len(r.prompt) for r in reqs)
    gen_total = sum(r.max_new_tokens for r in reqs)
    eng, done = _serve(cfg, params, reqs, scheduler, slots=3, max_len=32)
    assert len(done) == 6
    assert eng.stats.prefill_tokens == prompt_total
    # each request's first generated token rides on its last prefill step
    assert eng.stats.decode_tokens == gen_total - len(reqs)
    assert eng.stats.steps * 1 <= eng.stats.slot_steps


# ---------------------------------------------------------------------------
# Ledger fix: finish reasons (silent length-cap completions)
# ---------------------------------------------------------------------------


def test_finish_reason_max_new_tokens_and_eos(small_model):
    cfg, params = small_model
    eng, done = _serve(cfg, params,
                       [Request(rid=0, prompt=[3, 4], max_new_tokens=3)],
                       "stream", slots=1, max_len=32)
    assert done[0].finish_reason == "max_new_tokens"
    assert eng.stats.length_capped == 0
    first = done[0].output[0]
    eng2, done2 = _serve(cfg, params,
                         [Request(rid=1, prompt=[3, 4], max_new_tokens=3,
                                  eos_id=first)],
                         "stream", slots=1, max_len=32)
    assert done2[0].finish_reason == "eos"
    assert done2[0].output == [first]


@pytest.mark.parametrize("scheduler", ["wave", "stream"])
def test_length_cap_finish_is_not_a_clean_completion(small_model, scheduler):
    """A request stopped by the cache filling up used to be marked done
    identically to a clean finish; now it carries finish_reason="length_cap"
    and is counted in stats.length_capped."""
    cfg, params = small_model
    # prompt 10 + wanting 32 more tokens cannot fit max_len=16: the cache
    # caps generation well before max_new_tokens
    req = Request(rid=0, prompt=list(range(1, 11)), max_new_tokens=32)
    eng, done = _serve(cfg, params, [req], scheduler, slots=1, max_len=16)
    assert done == [req] and req.done
    assert req.finish_reason == "length_cap"
    assert len(req.output) < req.max_new_tokens
    assert eng.stats.length_capped == 1
    assert eng.stats.completed == 1


# ---------------------------------------------------------------------------
# Ledger fix: O(n^2) queue draining -> deque
# ---------------------------------------------------------------------------


def test_large_queue_drains_in_order(small_model):
    """Per-step admission pops the queue once per freed slot; with
    list.pop(0) this was quadratic. Smoke a few thousand requests through a
    stubbed decode step and check FIFO admission order is preserved."""
    cfg, _ = small_model
    eng = ServingEngine(cfg, None, slots=8, max_len=8, scheduler="stream")
    eng._step = lambda params, state, tokens: (
        jnp.zeros((tokens.shape[0], 8), jnp.float32), state)
    n = 3000
    for i in range(n):
        eng.submit(Request(rid=i, prompt=[1], max_new_tokens=1))
    done = eng.run(max_steps=n)
    assert len(done) == n
    assert [r.rid for r in done] == list(range(n))  # FIFO admission
    assert eng.stats.steps == n // 8
    assert eng.stats.occupancy == 1.0


# ---------------------------------------------------------------------------
# Placement-epoch energy attribution
# ---------------------------------------------------------------------------


def _placement(kind, e, t=0.0):
    return Placement(kind=kind, cell="c", destination="d", decisions=None,
                     clock=1.0, energy_per_token_ws=e, time_per_token_s=t)


def test_tokens_costed_under_admission_epoch(small_model):
    """Reconfigure while a slot is mid-stream: its tokens keep the epoch it
    was admitted under; the next admission picks up the new placements.
    This is the invariant that replaces the wave-boundary rule."""
    cfg, params = small_model
    epoch_a = {"prefill": _placement("prefill", 2.0),
               "decode": _placement("decode", 1.0)}
    epoch_b = {"prefill": _placement("prefill", 20.0),
               "decode": _placement("decode", 10.0)}
    eng = ServingEngine(cfg, params, slots=1, max_len=32)
    eng.reconfigure(epoch_a)

    def swap_early(engine):
        if engine.stats.steps == 1:  # mid-stream of request 0
            engine.reconfigure(epoch_b)

    eng.on_step_end = swap_early
    eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=3))
    eng.submit(Request(rid=1, prompt=[4, 5], max_new_tokens=2))
    done = eng.run()
    assert len(done) == 2
    assert eng.stats.reconfigurations == 1
    # r0 (epoch A): 3 prefill * 2.0 + 2 decode * 1.0 = 8
    # r1 (epoch B, admitted after the swap): 2 * 20.0 + 1 * 10.0 = 50
    assert eng.stats.energy_ws == pytest.approx(58.0)


def test_epoch_attribution_composes_with_energy_correction(small_model):
    """energy_correction is live telemetry calibration: it scales the
    admission epoch's rate at its CURRENT value, across epochs."""
    cfg, params = small_model
    eng = ServingEngine(cfg, params, slots=1, max_len=32)
    eng.reconfigure({"prefill": _placement("prefill", 2.0),
                     "decode": _placement("decode", 1.0)})
    eng.energy_correction["decode"] = 2.0  # metered says decode is 2x hotter
    eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=3))
    eng.run()
    # 3 prefill * 2.0 + 2 decode * (1.0 * 2.0) = 10
    assert eng.stats.energy_ws == pytest.approx(10.0)


# ---------------------------------------------------------------------------
# Placement-aware (SLO) admission
# ---------------------------------------------------------------------------


def test_slo_aware_admission_models_completion_latency(small_model):
    cfg, params = small_model
    eng = ServingEngine(cfg, params, slots=2, max_len=32)
    eng.reconfigure({"prefill": _placement("prefill", 1.0, t=0.1),
                     "decode": _placement("decode", 1.0, t=0.2)})
    ok = Request(rid=0, prompt=[1, 2, 3, 4], max_new_tokens=3, slo_s=10.0)
    tight = Request(rid=1, prompt=[5, 6, 7, 8], max_new_tokens=3, slo_s=0.5)
    eng.submit(ok)
    eng.submit(tight)
    # queued SLOs imply a per-step budget: both need 4+2=6 steps, the
    # tighter one budgets 0.5/6 per step
    assert eng.slo_time_per_step_s() == pytest.approx(0.5 / 6)
    eng.run()
    # modeled completion: 4 prefill steps * 0.1 + 2 decode steps * 0.2
    assert ok.modeled_latency_s == pytest.approx(0.8)
    assert tight.modeled_latency_s == pytest.approx(0.8)
    assert eng.stats.slo_at_risk == 1  # 0.8 > 0.5 only for the tight one
    assert eng.slo_time_per_step_s() is None  # nothing pending anymore


def test_mid_run_submit_is_admitted_next_step(small_model):
    """Continuous batching admits from the queue every step, including
    requests submitted while the engine is running."""
    cfg, params = small_model
    eng = ServingEngine(cfg, params, slots=2, max_len=32)
    late = Request(rid=99, prompt=[7, 8], max_new_tokens=2)

    def submit_late(engine):
        if engine.stats.steps == 2 and not late.done \
                and late.status == "queued":
            engine.submit(late)

    eng.on_step_end = submit_late
    eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=4))
    done = eng.run()
    assert {r.rid for r in done} == {0, 99}


# ---------------------------------------------------------------------------
# Exactness: the in-place cache path == the scatter-and-stack path
# ---------------------------------------------------------------------------


def _scatter_row(cache, row, slot):
    """The row write the in-place path replaced: a scatter of each batch
    row's new K or V row at its slot."""
    rows = jnp.arange(cache.shape[0])
    return cache.at[rows, slot].set(row[:, 0].astype(cache.dtype))


def _scan_layers_xs_ys(body, x, xs, cache):
    """The layer loop the in-place path replaced: the stacked cache passes
    through the scan as ``xs`` and comes back as ``ys``."""
    def step(x, inp):
        xs_l, cache_l = inp
        x, cache_l, ys = body(x, xs_l, cache_l)
        return x, (cache_l, ys)

    x, (cache, ys) = jax.lax.scan(step, x, (xs, cache))
    return x, cache, ys


@pytest.mark.parametrize("arch,layers", [
    ("stablelm-1.6b", 2),        # dense
    ("mixtral-8x7b", 2),         # MoE, sliding-window ring of 32
    ("zamba2-7b", 5),            # hybrid: two shared-attention groups, a tail
    ("seamless-m4t-medium", 2),  # encoder-decoder self-attention
])
def test_in_place_cache_matches_scatter_and_stack(arch, layers, monkeypatch):
    """Every family with a self-attention cache gives the same logits and
    the same state, leaf for leaf and bit for bit, as the scatter write and
    the ``xs``/``ys`` layer scan: both write the same bfloat16 row at the
    same position, and the attention reads the same values. Slots run at
    positions that differ, one is reset to 0 mid-stream and re-admitted,
    and in the ring two slots wrap past its 32 rows."""
    cfg = dataclasses.replace(reduced(get_config(arch)), num_layers=layers)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    slots, cache_len, steps = 3, 48, 44
    state0 = M.init_decode_state(cfg, slots, cache_len)
    if cfg.is_encdec:  # an encoder memory to attend to, not zeros
        k1, k2 = jax.random.split(jax.random.PRNGKey(1))
        shape = state0["cross_k"].shape
        state0["cross_k"] = jax.random.normal(k1, shape).astype(jnp.bfloat16)
        state0["cross_v"] = jax.random.normal(k2, shape).astype(jnp.bfloat16)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (steps, slots), 0,
                                cfg.vocab_size)
    # slots 1 and 2 (re)start at steps 5 and 9; slot 0 restarts at step 20
    resets = {5: [False, True, False], 9: [False, False, True],
              20: [True, False, False]}

    def serve():
        step = jax.jit(functools.partial(M.decode_step, cfg))
        st, logits = state0, []
        for t in range(steps):
            if t in resets:
                st = M.reset_decode_slots(cfg, st, jnp.array(resets[t]))
            lg, st = step(params, st, tokens[t])
            logits.append(lg)
        return jnp.stack(logits), st

    logits, state = serve()
    with monkeypatch.context() as m:
        m.setattr(T, "_scan_layers_in_place", _scan_layers_xs_ys)
        m.setattr(attn_mod, "_write_row", _scatter_row)
        ref_logits, ref_state = serve()

    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
    assert jax.tree.structure(state) == jax.tree.structure(ref_state)
    for leaf, ref in zip(jax.tree.leaves(state), jax.tree.leaves(ref_state)):
        assert leaf.shape == ref.shape and leaf.dtype == ref.dtype
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(ref))
    # the next positions: slots 1 and 2 have wrapped mixtral's ring of 32
    np.testing.assert_array_equal(np.asarray(state["pos"]), [24, 39, 35])
