"""chip_smoke.py and the entry points' compile cache, on the CPU.

The script's phases run here at tiny sizes (interpret-mode kernels, smoke
configs); its real sizes need the chip. The script itself must refuse to
run without a TPU, and outside a checkout. Anything that calls
``enable_compile_cache`` runs in a child process, so this process's JAX
configuration stays untouched.
"""
import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

from repro.config import get_smoke
from repro.kernels.chip_cases import flash_case, ssd_case

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _child(args, *, env=None, cwd=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, env=env, cwd=cwd, timeout=300)


def test_kernel_phase_at_tiny_size(smoke):
    smoke.kernel_phase([flash_case("smollm-360m", batch=2, seq=256),
                        ssd_case("mamba2-1.3b", batch=1, seq=512)])


def test_train_phase_restores_and_replays_losses(smoke):
    smoke.train_phase(get_smoke("smollm-360m"), batch=2, seq=32)


def test_serve_phase_matches_teacher_forcing(smoke):
    smoke.serve_phase(get_smoke("smollm-360m"), batch=2, prompt=32, steps=6)


def test_check_failure_raises(smoke):
    with pytest.raises(smoke.CheckFailed, match="the thing"):
        smoke.check(False, "the thing")


def test_refuses_to_run_without_a_tpu(tmp_path):
    r = _child([SCRIPT], env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "TPU" in r.stderr


def test_refuses_to_run_outside_a_checkout(tmp_path):
    shutil.copy(SCRIPT, tmp_path)
    r = _child([str(tmp_path / "chip_smoke.py")], cwd=tmp_path)
    assert r.returncode != 0
    assert r.stdout == ""


def test_compile_cache_goes_where_the_environment_says(tmp_path):
    code = ("from repro.launch.cache import enable_compile_cache as e\n"
            "print(e())\n"
            "import jax, jax.numpy as jnp\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
            "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(4)).block_until_ready()\n")
    src = os.path.join(ROOT, "src")
    r = _child(["-c", code], env={"PYTHONPATH": src,
                                  "JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [str(tmp_path)]
    assert os.listdir(tmp_path)


def test_compile_cache_defaults_to_the_checkout():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run(
        [sys.executable, "-c", "from repro.launch.cache import "
         "enable_compile_cache as e; print(e())"],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [os.path.join(ROOT, ".jax_cache")]
