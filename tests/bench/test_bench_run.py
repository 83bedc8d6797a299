"""Whole runs of bench/run.py's path on the CPU at a tiny size: refusals
without a chip or without the system, a sound run that comes out correct,
and the timed path broken underneath, which must come out not correct."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from bench import control, harness
from repro.models import transformer as T

ROOT = harness.ROOT
LIMIT = 0.05  # mean gap: a sound tiny run reads ~0.001, a broken step whole logits


def tiny_cell(fam: str) -> harness.Cell:
    if fam == "dense":
        conf = harness.read_json(ROOT / "bench/configs/stablelm-1.6b.json")
        conf["overrides"] = dict(qkv_bias=True, num_layers=2, d_model=64,
                                 num_heads=4, num_kv_heads=4, head_dim=16,
                                 d_ff=128, vocab_size=512)
        conf["model"].update(num_hidden_layers=2, hidden_size=64,
                             num_attention_heads=4, num_key_value_heads=4,
                             head_dim=16, intermediate_size=128,
                             vocab_size=512)
    else:
        conf = harness.read_json(ROOT / "bench/configs/rwkv6-1.6b.json")
        conf["overrides"] = dict(num_layers=2, d_model=64, rwkv_head_size=16,
                                 d_ff=128, rwkv_decay_rank=8, vocab_size=512)
        conf["model"].update(num_hidden_layers=2, hidden_size=64, head_size=16,
                             attention_hidden_size=64, intermediate_size=128,
                             time_decay_extra_dim=8, vocab_size=512)
    mix = {"arrival": "poisson",
           "prompt_tokens": {"median": 8, "sigma": 0.8, "min": 2, "max": 24},
           "output_tokens": {"median": 8, "sigma": 0.8, "min": 2, "max": 24}}
    spec = harness.load_cell("stablelm-chat")
    return harness.Cell("tiny", conf, mix,
                        {"slots": 4, "max_len": 64, "rate_rps": 20,
                         "check_sample": 4,
                         "limits": {"mean_logit_gap": LIMIT}},
                        1, spec.end_to_end, spec.per_layer)


PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def run(cell, seed=2**33 + 1, traced=False):
    return harness.run_cell(cell, seed, 1.5, traced, time.perf_counter(), PEAK)


def _script(*args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


ARGS = ("--workload", "stablelm-chat", "--seed", "1", "--seconds", "1",
        "--trace", "0")


def test_refuses_without_a_tpu_and_names_the_platform():
    p = _script(*ARGS)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr and "'cpu'" in p.stderr
    assert p.stdout.strip() == ""


def test_refuses_without_the_system_under_test(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _script(*ARGS, cwd=tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "system under test" in p.stderr


@pytest.mark.parametrize("fam", ["dense", "rwkv6"])
def test_sound_run_is_correct(fam):
    res = run(tiny_cell(fam))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 30
    assert res["window"]["compiles"] == 0
    assert set(res["metrics"]) == {"ttft_p95_ms", "itl_p95_ms", "setup_s"}
    assert res["window"]["drain_s"] >= 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["compared_tokens"]["value"] > 0


def _broken(monkeypatch, fault):
    make = harness.make_engine

    def make_broken(cfg, params, cell):
        engine = make(cfg, params, cell)
        sound = jax.jit(lambda p, s, t: T.decode_step(cfg, p, s, t))
        if fault == "state_unchanged":
            engine._step = lambda p, s, t: (sound(p, s, t)[0], s)
        else:  # every token altered where it is produced
            def altered(p, s, t):
                logits, s = sound(p, s, t)
                other = (jnp.argmax(logits, -1) + 1) % logits.shape[-1]
                rows = jnp.arange(logits.shape[0])
                return logits.at[rows, other].set(logits.max() + 1.0), s
            engine._step = altered
        return engine

    monkeypatch.setattr(harness, "make_engine", make_broken)


@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered"])
@pytest.mark.parametrize("fam", ["dense", "rwkv6"])
def test_broken_timed_path_is_not_correct(monkeypatch, fam, fault):
    _broken(monkeypatch, fault)
    res = run(tiny_cell(fam))
    assert not res["correct"]
    assert res["checks"]["mean_logit_gap"]["value"] > LIMIT


def test_traced_run_reports_per_layer_metrics_it_can_read():
    res = run(tiny_cell("dense"), traced=True)
    assert res["correct"]
    # the CPU trace holds no TPU plane: only the host-side readers report
    assert set(res["metrics"]) == {"queue_wait_p95_ms", "slot_occupancy"}


def small_cell(fam: str) -> harness.Cell:
    """d 256, 4 layers, vocab 4096: the smallest size at which the int8
    control's gaps stand clear of the bf16 program's on the CPU."""
    cell = tiny_cell(fam)
    d, n, v = 256, 4, 4096
    if fam == "dense":
        cell.conf["overrides"].update(num_layers=n, d_model=d, num_heads=4,
                                      num_kv_heads=4, head_dim=64, d_ff=4 * d,
                                      vocab_size=v)
        cell.conf["model"].update(num_hidden_layers=n, hidden_size=d,
                                  num_attention_heads=4, num_key_value_heads=4,
                                  head_dim=64, intermediate_size=4 * d,
                                  vocab_size=v)
    else:
        cell.conf["overrides"].update(num_layers=n, d_model=d,
                                      rwkv_head_size=64, d_ff=4 * d,
                                      rwkv_decay_rank=16, vocab_size=v)
        cell.conf["model"].update(num_hidden_layers=n, hidden_size=d,
                                  head_size=64, attention_hidden_size=d,
                                  intermediate_size=4 * d,
                                  time_decay_extra_dim=16, vocab_size=v)
    cell.mix["prompt_tokens"].update(median=16, max=48)
    cell.mix["output_tokens"].update(median=16, max=48)
    cell.params.update(max_len=128, rate_rps=10, check_sample=6)
    return cell


# Mean gap per compared token at small_cell's size on the CPU (seeds 1-3):
# program 0.00017-0.00046, int8 control 0.0019-0.0041. The limit lies
# between, as the cells' limits do at their own size (PERF.md).
SMALL_MEAN_GAP_LIMIT = 0.0012


@pytest.mark.parametrize("fam", ["dense", "rwkv6"])
def test_int8_control_comes_out_not_correct(fam):
    cell = small_cell(fam)
    cell.params["limits"] = {"mean_logit_gap": SMALL_MEAN_GAP_LIMIT}
    out = control.readings(cell, 2, 3.0)
    program, lowered = out["program"], out["control"]
    assert program["checks"]["compared_tokens"]["value"] > 100
    assert program["correct"], program["checks"]
    assert not lowered["correct"], lowered["checks"]
    assert lowered["checks"]["mean_logit_gap"]["value"] > SMALL_MEAN_GAP_LIMIT
