"""Serving engine + LM offload search + analytic cell cost model."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import SHAPES, get_config, reduced
from repro.core import Decisions, analyze_cell, measure_cell, search_lm_cell
from repro.core.ga import GAConfig
from repro.core.offload_search import decisions_from, lm_genome_space
from repro import models as M
from repro.runtime import Request, ServingEngine

MESH = {"data": 16, "model": 16}
MESH_MP = {"pod": 2, "data": 16, "model": 16}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_model():
    cfg = reduced(get_config("llama3.2-3b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_serving_batched_requests(small_model):
    """Default scheduler (slot streams): every slot admits the next request
    the step after its previous one finishes; no wave barrier."""
    cfg, params = small_model
    eng = ServingEngine(cfg, params, slots=4, max_len=48)
    for i in range(6):
        eng.submit(Request(rid=i, prompt=[1 + i, 2, 3], max_new_tokens=5))
    done = eng.run()
    assert len(done) == 6
    assert all(len(r.output) == 5 for r in done)
    assert eng.stats.waves == 0  # no waves under slot streams
    assert eng.stats.admissions == 6
    # each request: 3 prompt tokens billed as prefill (2 chunk-fed at
    # admission, the last by the admitting step, which already emits the
    # first output token) + 4 more generated tokens
    assert eng.stats.prefill_tokens == 18
    assert eng.stats.decode_tokens == 24
    assert eng.stats.prefill_chunks == 6
    # 5 steps per request over 4 slots, packed back-to-back: 4 slots serve
    # {2,2,1,1} requests -> 10 steps
    assert eng.stats.steps == 10


def test_serving_wave_scheduler_still_available(small_model):
    """scheduler="wave" keeps the legacy wave-barrier behavior so existing
    comparisons stay reproducible."""
    cfg, params = small_model
    eng = ServingEngine(cfg, params, slots=4, max_len=48, scheduler="wave")
    for i in range(6):
        eng.submit(Request(rid=i, prompt=[1 + i, 2, 3], max_new_tokens=5))
    done = eng.run()
    assert len(done) == 6
    assert eng.stats.waves == 2  # 6 requests over 4 slots
    assert eng.stats.prefill_tokens == 18
    assert eng.stats.decode_tokens == 24
    # both waves run their longest request: 1 admitting step + 4 more
    assert eng.stats.steps == 10


def test_serving_greedy_matches_manual_decode(small_model):
    cfg, params = small_model
    prompt = [5, 9, 2]
    eng = ServingEngine(cfg, params, slots=2, max_len=32)
    eng.submit(Request(rid=0, prompt=list(prompt), max_new_tokens=4))
    done = eng.run()
    # manual greedy decode
    st = M.init_decode_state(cfg, 2, 32)
    toks = list(prompt)
    out = []
    for t in range(len(prompt) + 3):
        cur = toks[t] if t < len(prompt) else out[-1]
        logits, st = M.decode_step(cfg, params, st,
                                   jnp.array([cur, 0], jnp.int32))
        if t >= len(prompt) - 1:
            out.append(int(jnp.argmax(logits[0])))
    assert done[0].output == out[:4]


def test_serving_eos_stops(small_model):
    cfg, params = small_model
    eng = ServingEngine(cfg, params, slots=1, max_len=64)
    # find the first greedy token, then use it as EOS so generation stops at 1
    eng.submit(Request(rid=0, prompt=[3, 4], max_new_tokens=8))
    first = eng.run()[0].output[0]
    eng2 = ServingEngine(cfg, params, slots=1, max_len=64)
    eng2.submit(Request(rid=1, prompt=[3, 4], max_new_tokens=8, eos_id=first))
    done = eng2.run()
    # eos on the very first generated token: exactly one token, marked done.
    # The step that emitted it consumed the LAST PROMPT token, so it bills
    # as prefill — a 2-token prompt contributes 2 prefill and 0 decode
    # tokens (the pre-PR-4 accounting billed it as decode).
    assert done[0].output == [first]
    assert done[0].done and done[0].status == "done"
    assert done[0].finish_reason == "eos"
    assert eng2.stats.completed == 1
    assert eng2.stats.prefill_tokens == 2 and eng2.stats.decode_tokens == 0


def test_serving_empty_queue_is_noop(small_model):
    cfg, params = small_model
    eng = ServingEngine(cfg, params, slots=2, max_len=32)
    assert eng.run() == []
    assert eng.stats.waves == 0 and eng.stats.steps == 0


def test_serving_rejects_prompt_at_or_over_max_len(small_model):
    """Pre-PR-2, a prompt >= max_len burned a full wave without completing
    and was still returned in the done list."""
    cfg, params = small_model
    eng = ServingEngine(cfg, params, slots=2, max_len=16)
    over = Request(rid=0, prompt=list(range(1, 21)), max_new_tokens=4)
    ok = eng.submit(over)
    assert not ok and over.status == "rejected" and not over.done
    assert eng.stats.rejected == 1 and eng.rejected == [over]
    fits = Request(rid=1, prompt=[1, 2, 3], max_new_tokens=4)
    assert eng.submit(fits)
    done = eng.run()
    assert done == [fits]  # the rejected request is never served
    assert eng.stats.completed == 1 and eng.stats.incomplete == 0


def test_serving_rejects_empty_prompt(small_model):
    """An empty prompt has no token to condition on; admitting it used to
    crash the whole wave (output[-1] on an empty list), taking co-batched
    requests down with it."""
    cfg, params = small_model
    for policy in ("reject", "truncate"):
        eng = ServingEngine(cfg, params, slots=2, max_len=16,
                            overflow=policy)
        empty = Request(rid=0, prompt=[], max_new_tokens=4)
        assert not eng.submit(empty)
        assert empty.status == "rejected" and eng.stats.rejected == 1
        ok = Request(rid=1, prompt=[1, 2], max_new_tokens=2)
        assert eng.submit(ok)
        assert eng.run() == [ok]  # the healthy request still serves


def test_serving_truncate_policy_serves_clipped_prompt(small_model):
    cfg, params = small_model
    eng = ServingEngine(cfg, params, slots=1, max_len=16, overflow="truncate")
    req = Request(rid=0, prompt=list(range(1, 21)), max_new_tokens=4)
    assert eng.submit(req)
    assert req.status == "truncated"
    assert len(req.prompt) == 12 and req.truncated_tokens == 8
    done = eng.run()
    assert done == [req] and req.done and len(req.output) == 4
    assert req.status == "truncated"  # clip marker survives completion
    assert req.finish_reason == "max_new_tokens"
    # stats consistent: every clipped-prompt token bills as prefill; the
    # remaining max_new-1 generation steps bill as decode
    assert eng.stats.prefill_tokens == 12
    assert eng.stats.decode_tokens == 3
    assert eng.stats.completed == 1 and eng.stats.incomplete == 0


def test_serving_occupancy_tracks_idle_slots(small_model):
    cfg, params = small_model
    eng = ServingEngine(cfg, params, slots=4, max_len=32)
    eng.submit(Request(rid=0, prompt=[1, 2], max_new_tokens=3))
    eng.run()
    # 1 of 4 slots busy the whole wave
    assert eng.stats.slot_steps == 4 * eng.stats.steps
    assert eng.stats.occupancy == 0.25


# ---------------------------------------------------------------------------
# Analytic cell model
# ---------------------------------------------------------------------------


def test_analyze_cell_terms_positive():
    for arch in ("qwen1.5-110b", "mixtral-8x7b", "rwkv6-1.6b"):
        for shape in ("train_4k", "prefill_32k", "decode_32k"):
            cost = analyze_cell(get_config(arch), SHAPES[shape], MESH)
            assert cost.step_time > 0
            assert cost.energy > 0
            assert cost.breakdown["dominant"] in ("compute", "memory",
                                                  "collective")


def test_train_is_compute_bound_decode_memory_bound():
    train = analyze_cell(get_config("qwen1.5-110b"), SHAPES["train_4k"], MESH)
    dec = analyze_cell(get_config("qwen1.5-110b"), SHAPES["decode_32k"], MESH)
    assert train.breakdown["dominant"] == "compute"
    assert dec.breakdown["dominant"] == "memory"  # KV-cache streaming


def test_remat_tradeoff_visible():
    base = Decisions(remat="none")
    full = Decisions(remat="full")
    c_none = analyze_cell(get_config("qwen1.5-110b"), SHAPES["train_4k"],
                          MESH, base)
    c_full = analyze_cell(get_config("qwen1.5-110b"), SHAPES["train_4k"],
                          MESH, full)
    assert c_full.terms.flops > c_none.terms.flops  # recompute costs FLOPs
    assert c_full.bytes_per_device < c_none.bytes_per_device  # but saves HBM


def test_multi_pod_scales_terms_down():
    c1 = analyze_cell(get_config("qwen1.5-110b"), SHAPES["train_4k"], MESH)
    c2 = analyze_cell(get_config("qwen1.5-110b"), SHAPES["train_4k"], MESH_MP)
    assert c2.terms.t_compute < c1.terms.t_compute


# ---------------------------------------------------------------------------
# LM offload search (the paper's GA on TPU execution genomes)
# ---------------------------------------------------------------------------


def test_lm_genome_masks_inapplicable_genes():
    train_space = lm_genome_space(get_config("qwen1.5-110b"),
                                  SHAPES["train_4k"])
    names = {g.name for g in train_space.genes}
    assert "remat" in names and "attn_impl" in names
    rwkv_space = lm_genome_space(get_config("rwkv6-1.6b"), SHAPES["train_4k"])
    assert "attn_impl" not in {g.name for g in rwkv_space.genes}
    dec_space = lm_genome_space(get_config("qwen1.5-110b"),
                                SHAPES["decode_32k"])
    dnames = {g.name for g in dec_space.genes}
    assert "seq_shard_decode" in dnames and "remat" not in dnames


def test_search_lm_cell_improves_or_matches_baseline():
    res = search_lm_cell(get_config("qwen1.5-110b"), SHAPES["train_4k"], MESH,
                         GAConfig(population=8, generations=8, seed=0))
    from repro.core.fitness import fitness

    assert res.ga.best.fitness >= fitness(res.baseline) * 0.999
    assert res.ga.evaluations <= 64


def test_search_respects_memory_feasibility():
    """Genomes that don't fit HBM must be penalized like the paper's
    timeouts (a compile-OOM 'never finishes'). grok-314B training does NOT
    fit a single 256×16GB pod (the compiled dry-run agrees) — the analytic
    model must say so; on 512 chips feasible genomes exist and the GA finds
    one."""
    cfg = get_config("grok-1-314b")
    base = analyze_cell(cfg, SHAPES["train_4k"], MESH)
    assert not base.fits  # capacity limit, documented in EXPERIMENTS.md
    res = search_lm_cell(cfg, SHAPES["train_4k"], MESH_MP,
                         GAConfig(population=8, generations=6, seed=1))
    cost = analyze_cell(cfg, SHAPES["train_4k"], MESH_MP, res.best_decisions)
    assert cost.fits  # the GA never picks an infeasible winner at 512


def test_decisions_roundtrip():
    space = lm_genome_space(get_config("qwen1.5-110b"), SHAPES["train_4k"])
    g = space.zeros()
    dec = decisions_from(space, g)
    assert dec.remat == "full"  # first choice is the paper-faithful default
