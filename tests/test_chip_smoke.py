"""The chip smoke script (``chip_smoke.py``) off the chip: it refuses to run
without a TPU, and its phases pass the CPU rehearsal (smoke configs, Pallas
kernels in interpret mode) — the same checks the chip run makes."""
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke

ROOT = os.path.join(os.path.dirname(__file__), "..")


def test_refuses_without_a_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) == 2
    out, err = capsys.readouterr()
    assert "no TPU found" in err and '"ok"' not in out


@pytest.mark.parametrize("phase", ["serve", "cnn"])
def test_rehearsal_phase(phase, capsys):
    fn = getattr(chip_smoke, f"phase_{phase}")
    fn(chip_smoke.REHEARSAL, 0, jax.devices()[0])
    out = capsys.readouterr().out
    assert "logits vs reference" in out and "pallas_interpret" in out


def test_rehearsal_train4_on_four_host_devices(tmp_path):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), "--rehearse",
         "--chips", "4"], capture_output=True, text=True, env=env,
        timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "phase train4 passed" in r.stdout
    assert "devices per array min 4" in r.stdout
