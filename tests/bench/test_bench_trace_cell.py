"""``bench/trace_cell.py`` on the CPU at a tiny size: the readers of the
engine's spans, counters and stamps report what a CPU run can give, and the
run is checked as ``bench/run.py`` checks it. The CPU trace holds no TPU
plane, so the device readings are absent."""
from test_bench_run import tiny_cell

from bench import harness, trace_cell


def test_traced_run_reports_the_engine_readings_a_cpu_run_can_give():
    out = trace_cell.traced_run(tiny_cell("rwkv6"), 2**33 + 7, 1.5, {},
                                harness.CompileCounter(), None)
    assert out["correct"]
    assert out["checks"]["compared_tokens"]["value"] >= 1
    assert {"step_host_ms", "prefill_p95_ms"} <= set(out["metrics"])
    # rwkv6 has no KV cache; the CPU trace has no device plane
    for name in ("kv_live_share", "reset_device_ms", "decode_device_ms",
                 "step_device_ms"):
        assert name not in out["metrics"]
    assert "idle_gaps" not in out
    assert set(out["host_ms_per_step"]) == set(trace_cell.PHASES)
    assert out["gc_s"] >= 0.0
    assert out["longest_step_s"] > 0.0
    assert out["traced_steps"] > 0


def test_main_refuses_a_device_other_than_a_tpu(monkeypatch, capsys):
    monkeypatch.setattr(harness, "use_compile_cache", lambda: None)
    assert trace_cell.main(["--workload", "rwkv6-chat", "--seed", "1",
                            "--seconds", "1"]) == 1
    assert "needs a TPU" in capsys.readouterr().err
