"""chip_smoke.py on the CPU: its refusal to run anywhere but a TPU, and its
phases at reduced width (Pallas in interpret mode), so that a broken phase
shows here before it costs a chip call."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402
from repro.configs.himeno import GRIDS  # noqa: E402


def test_refuses_a_host_without_tpu(monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    with pytest.raises(SystemExit, match="JAX found platform 'cpu'"):
        chip_smoke.main()
    assert '"ok"' not in capsys.readouterr().out


def test_serve_and_parity_phases_at_reduced_width():
    cfg, output = chip_smoke.serve_phase(chip_smoke.ARCH, use_reduced=True)
    assert len(output) == chip_smoke.MAX_NEW_TOKENS
    chip_smoke.parity_phase(cfg, output)


def test_himeno_phase_interpret():
    chip_smoke.himeno_phase(GRIDS["tiny"], 3, impl="interpret")


@pytest.mark.slow
def test_four_chip_phase_on_host_devices():
    env = {**os.environ, "PYTHONPATH": str(ROOT / "src"),
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run(
        [sys.executable, "-c",
         "import chip_smoke; chip_smoke.four_chip_phase("
         "chip_smoke.ARCH, use_reduced=True)"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "four chips: mesh" in proc.stdout
