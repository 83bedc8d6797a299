"""The benchmark's pieces for granite-4.0-h-small (``granite-h-small-chat``):
its configuration file keeps every published width, bench/work/ counts by
hand, bench/reference/ against the system's forward pass and its decode
through the state, and whole runs of bench/run.py's path on the CPU at a
tiny size (sound, broken underneath, int8 control), as the other families'
tests do beside this file."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_bench_run as R
from bench import control, harness, weights
from bench.reference import granite_hybrid
from bench.reference.granite_hybrid import kinds
from bench.work import granite_hybrid as work
from repro.configs import get_config
from repro.models import transformer as T

ROOT = harness.ROOT
CONFIG = ROOT / "bench/configs/granite-4.0-h-small.json"
# Mean gap per compared token at tiny_cell's size on the CPU. granite's
# logits span about 0.02 (divided by 16, tied head drawn small:
# reference/granite_hybrid.py's layout): sound 0 to 0.00001, broken 0.0036
# (state_unchanged) and 0.018 (token_altered), seeds 1 and 2**33 + 1.
LIMIT = 0.001


def granite_conf(d=64, layers=4, vocab=512, hd=16, experts=8, held=4, f=32):
    """granite's configuration file cut to a CPU size: periods of (Mamba2,
    attention), GQA, ``held`` of ``experts`` experts, top 2."""
    conf = harness.read_json(CONFIG)
    heads = d // hd
    conf["overrides"] = dict(
        num_layers=layers, d_model=d, num_heads=heads, num_kv_heads=heads // 2,
        head_dim=hd, d_ff=f, shared_expert_ff=2 * f, vocab_size=vocab,
        num_experts=experts, experts_held=held, experts_per_token=2,
        ssm_state=16, ssm_head_dim=hd, layer_pattern="MA")
    conf["model"].update(
        num_hidden_layers=layers, hidden_size=d, num_attention_heads=heads,
        num_key_value_heads=heads // 2, intermediate_size=f,
        shared_intermediate_size=2 * f, vocab_size=vocab,
        router_experts=experts, num_local_experts=held, num_experts_per_tok=2,
        mamba_d_state=16, mamba_d_head=hd, mamba_n_heads=2 * d // hd,
        layer_types=["mamba", "attention"] * (layers // 2))
    return conf


def tiny_cell() -> harness.Cell:
    """The dense tiny cell's traffic and slots, granite's model and its own
    cell's metric entries."""
    base = R.tiny_cell("dense")
    spec = harness.load_cell("granite-h-small-chat")
    params = dict(base.params, limits={"mean_logit_gap": LIMIT})
    return harness.Cell("tiny", granite_conf(), base.mix, params, 1,
                        spec.end_to_end, spec.per_layer)


# -- configuration -----------------------------------------------------------

# granite-4.0-h-small's published config.json (the model catalog's entry)
PUBLISHED = {
    "hidden_size": 4096, "num_attention_heads": 32, "num_key_value_heads": 8,
    "intermediate_size": 768, "shared_intermediate_size": 1536,
    "mamba_n_heads": 128, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
    "num_experts_per_tok": 10, "vocab_size": 100352,
    "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 16, "rms_norm_eps": 1e-05,
    "num_hidden_layers": 40, "num_local_experts": 72,
}


def test_configuration_keeps_every_width_and_states_its_cut():
    conf = json.loads(CONFIG.read_text())
    model, cut = conf["model"], set(conf["reduced"])
    assert cut == {"num_hidden_layers", "num_local_experts"}
    for key, value in PUBLISHED.items():
        assert conf[key] == model[key], key  # top level and model agree
        if key not in cut:
            assert model[key] == value, key
    assert {k: model[k] for k in cut} == {"num_hidden_layers": 20,
                                          "num_local_experts": 9}
    assert conf["published"] == {k: PUBLISHED[k] for k in cut}
    assert model["router_experts"] == PUBLISHED["num_local_experts"]
    cfg = harness.build_config(conf)  # checks every arch field against model
    assert list(cfg.layer_types) == kinds(model)
    assert kinds(model) == model["layer_types"][:20]
    assert kinds(model).count("attention") == 2  # two whole periods
    assert not cfg.rope and model["position_embedding_type"] == "nope"
    assert get_config(conf["arch"]).num_layers == 40


# -- work --------------------------------------------------------------------

SMALL = {"hidden_size": 4, "num_attention_heads": 2,
         "num_key_value_heads": 1, "mamba_expand": 2, "mamba_d_state": 2,
         "mamba_n_heads": 2, "mamba_n_groups": 1, "mamba_d_conv": 2,
         "intermediate_size": 3, "shared_intermediate_size": 5,
         "router_experts": 4, "num_local_experts": 2,
         "num_experts_per_tok": 2, "vocab_size": 5, "num_hidden_layers": 2,
         "layer_types": ["mamba", "attention"]}


def test_work_two_layers_by_hand():
    # Mamba: in_proj 4 x (2*8 + 2*2 + 2) = 88, out_proj 8 x 4 = 32; conv
    # 2 taps + bias over 8 + 2*2 = 12 channels, A, D, dt_bias (bf16), the
    # gated norm's 8 (f32). Attention (head 2): wq 16, wk 8, wv 8, wo 16.
    # Each layer: 2 held experts of 3 x 4 x 3 = 36, a shared one of
    # 3 x 4 x 5 = 60, a router of 4 x 4 and two norms of 4 (f32).
    bf16 = 88 + 32 + (2 * 12 + 12 + 3 * 2) + 48 + 2 * (2 * 36 + 60) + 5 * 4
    f32 = 8 + 2 * (16 + 2 * 4) + 4
    weights_ = 2 * bf16 + 4 * f32
    state = 2 * 4 * 2 * 4 + 1 * 12 * 2  # SSM (f32), conv's last input (bf16)
    kv_row = 2 * 1 * 2 * 2  # k and v, one head of 2, bf16
    assert work.step_bytes(SMALL, 1, 3) == (
        weights_ + 4 * 2 + 2 * state + (3 + 1) * kv_row)
    assert work.step_bytes(SMALL, 2, 8) == (
        weights_ + 2 * 4 * 2 + 4 * state + (8 + 2) * kv_row)
    # a token: Mamba 2 * 120 + 6 * 8 * 2 (SSM), attention 2 * 48, each
    # layer's router 2 * 16, one of two held experts' share of its 2
    # choices (2 * 2 / 4 = 1 expert) 2 * 36 and the shared 2 * 60; logits
    # 2 * 20; scores and values over rows + 1 positions, 2 heads of 2
    per_token = 240 + 96 + 96 + 2 * (32 + 72 + 120) + 40
    assert work.step_flops(SMALL, 1, 3) == per_token + 2 * 2 * 2 * 2 * 4
    assert work.step_flops(SMALL, 2, 8) == 2 * per_token + 2 * 2 * 2 * 2 * 10


# -- reference ---------------------------------------------------------------

def _reference():
    conf = granite_conf()
    cfg = dataclasses.replace(harness.build_config(conf), dtype="float32")
    return cfg, conf["model"]


def test_reference_matches_the_program_forward():
    cfg, model = _reference()
    params = weights.make_params(granite_hybrid.layout(model), 2**40 + 3)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    tokens = jax.random.randint(jax.random.key(1), (48,), 0, model["vocab_size"])
    want = np.asarray(granite_hybrid.logits_fn(model)(params, tokens))
    got, _ = T.forward(cfg, params, {"tokens": tokens[None]}, remat="none")
    got = np.asarray(got[0], np.float32)
    assert np.max(np.abs(got - want)) <= 1e-4 * np.max(np.abs(want))


def test_int8_control_departs_from_float32():
    _, model = _reference()
    params = weights.make_params(granite_hybrid.layout(model), 5)
    tokens = jnp.arange(32) % model["vocab_size"]
    a = np.asarray(granite_hybrid.logits_fn(model)(params, tokens))
    b = np.asarray(granite_hybrid.logits_fn(model, int8=True)(params, tokens))
    assert 1e-3 < np.max(np.abs(a - b)) / np.max(np.abs(a)) < 0.5


def test_decode_through_the_state_matches_the_reference():
    """Two slots fed one token a step through ``decode_step``, with their
    Mamba state and KV rows carried in the decode state, against the
    reference's full forward over each slot's tokens."""
    cfg, model = _reference()
    params = weights.make_params(granite_hybrid.layout(model), 2**40 + 3)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    tokens = jax.random.randint(jax.random.key(2), (2, 24), 0,
                                model["vocab_size"])
    step = jax.jit(lambda s, t: T.decode_step(cfg, params, s, t))
    st = T.init_decode_state(cfg, 2, 32)
    got = []
    for t in range(tokens.shape[1]):
        logits, st = step(st, tokens[:, t])
        got.append(np.asarray(logits))
    got = np.stack(got, 1)
    logits_of = granite_hybrid.logits_fn(model)
    for b in range(2):
        want = np.asarray(logits_of(params, tokens[b]))
        # float32 both, the same equations: rounding of sums in another
        # order, and the cache's bfloat16 keys and values (the system
        # keeps them so): 1% of the largest logit
        assert np.max(np.abs(got[b] - want)) <= 1e-2 * np.max(np.abs(want))
        assert np.mean(np.argmax(got[b], -1) == np.argmax(want, -1)) >= 0.9


# -- whole runs ----------------------------------------------------------------

def test_sound_run_is_correct():
    res = R.run(tiny_cell())
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 30
    assert res["window"]["compiles"] == 0
    assert set(res["metrics"]) == {"ttft_p95_ms", "itl_p95_ms", "setup_s"}
    assert res["window"]["drain_s"] >= 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["compared_tokens"]["value"] > 0


@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    R._broken(monkeypatch, fault)
    res = R.run(tiny_cell())
    assert not res["correct"]
    assert res["checks"]["mean_logit_gap"]["value"] > LIMIT


def test_traced_run_reports_held_expert_batch():
    res = R.run(tiny_cell(), traced=True)
    assert res["correct"]
    # the CPU trace holds no TPU plane: only the host-side readers report
    assert set(res["metrics"]) == {"queue_wait_p95_ms", "slot_occupancy",
                                   "step_host_ms", "prefill_p95_ms",
                                   "held_expert_batch"}
    # 2 choices a token over 8 experts, 4 held: each held expert computes
    # about a quarter of the active tokens a step, at most 4
    assert 0 < res["metrics"]["held_expert_batch"]["value"] <= 4


# Mean gap per compared token at d 256, 4 layers, vocab 4096 on the CPU
# (seeds 1-3; logits divided by 16, tied head drawn small): program
# 0.000012-0.000028, int8 control 0.000074-0.000112. The limit lies
# between, as the cells' limits do at their own size (PERF.md).
SMALL_MEAN_GAP_LIMIT = 0.00005


def test_int8_control_comes_out_not_correct():
    cell = tiny_cell()
    cell.conf = granite_conf(d=256, layers=4, vocab=4096, hd=64, f=128)
    cell.mix["prompt_tokens"].update(median=16, max=48)
    cell.mix["output_tokens"].update(median=16, max=48)
    cell.params.update(max_len=128, rate_rps=10, check_sample=6,
                       limits={"mean_logit_gap": SMALL_MEAN_GAP_LIMIT})
    out = control.readings(cell, 2, 3.0)
    program, lowered = out["program"], out["control"]
    assert program["checks"]["compared_tokens"]["value"] > 100
    assert program["correct"], program["checks"]
    assert not lowered["correct"], lowered["checks"]
    assert lowered["checks"]["mean_logit_gap"]["value"] > SMALL_MEAN_GAP_LIMIT
