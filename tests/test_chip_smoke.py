"""`chip_smoke.py`'s phases at smoke size on the CPU (interpret mode), so
the parity and trainer phases the chip runs stay guarded here, plus its
refusals: no TPU, or no checkout around it, means a non-zero exit and
no ``ok`` line."""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env.update(extra)
    return env


def test_bit_mismatch_sees_one_ulp(smoke):
    x = np.linspace(-1, 1, 64, dtype=np.float32)
    assert smoke._bit_mismatch(x, x.copy()) == ""
    y = x.copy()
    y[3] = np.nextafter(y[3], np.float32(2))
    assert smoke._bit_mismatch(x, y).startswith("1/64 elements differ")
    assert smoke._bit_mismatch(x, x.astype(np.float64)) != ""


def test_parity_phase_at_smoke_size(smoke):
    compared = smoke.parity_phase(rows=16, d=256, group_d=128,
                                  ragged_rows=5, log=lambda *a: None)
    assert compared == 4 * 22


def test_trainer_phase_at_smoke_size(smoke):
    lines = []
    runs = smoke.trainer_phase(num_layers=4, batch=2, seq=32, steps=4,
                               smoke=True, log=lines.append)
    assert set(runs) == {"aqsgd", "fp32"}
    assert all(len(v) == 4 for v in runs.values())
    assert any("DEPTH CUT to 4 of the published 48" in ln for ln in lines)


def test_four_chip_phases_on_four_cpu_devices(smoke):
    # the pipeline at its chip depth (12 layers per stage) and past the
    # warm-up epoch into the second compressed step, where noise on the
    # bubble ticks' backward once overflowed into NaN parameters
    kw = "batch=4, seq=16, smoke=True"
    code = (f"import chip_smoke as cs; "
            f"cs.pipeline_phase(layers=48, steps=4, {kw}); "
            f"cs.dp_wire_phase(layers=4, steps=3, {kw})")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=600, env=_cpu_env(
            XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "ring and psum losses bit-identical" in out.stdout


def test_main_refuses_a_cpu_platform(smoke, capsys):
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_refuses(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = _cpu_env()
    env.pop("PYTHONPATH")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
