"""Without a TPU the benchmark exits non-zero and prints no result; the
peaks table refuses an unknown device."""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import common  # noqa: E402

RUN = os.path.join(common.HERE, "run.py")


def test_cpu_only_exits_non_zero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", "smollm-360m.eval-score",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=common.CHECKOUT)
    assert proc.returncode != 0
    assert "needs 1 TPU chip" in proc.stderr
    for line in proc.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_unknown_workload_is_an_error():
    with pytest.raises(common.BenchError):
        common.cell_files(common.manifest(), "no-such-cell")


def test_peaks_by_device_kind():
    p = common.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(common.BenchError, match="no published peaks"):
        common.peaks("cpu")


def test_benchmark_files_alone_exit_non_zero(tmp_path):
    """A directory with BENCHMARK.json and chipbench/ but no program."""
    import shutil
    shutil.copy(os.path.join(common.CHECKOUT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(common.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "chipbench" / "run.py"),
         "--workload", "smollm-360m.train", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        cwd=tmp_path)
    assert proc.returncode != 0
    assert "no program sources" in proc.stderr
    assert not proc.stdout.strip()
