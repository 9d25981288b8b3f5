"""Architecture families as files (``reference/<family>.py``): the weights
of every configuration pinned to the last bit; a tree of two segments keyed
by global layer and scaled by a family's table; and a family added to a copy
of the benchmark as new files only, run to ``correct``."""
import copy
import hashlib
import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_testlib as tl  # noqa: E402
import common  # noqa: E402

BIG = 2 ** 31 + 11
# sha256 of every leaf's bytes in the tree's order, made from the program's
# smoke trees at seed BIG before weights were keyed by global layer
CHECKSUMS = {
    "smollm-360m":
        "782199467f5852aa6049078a2a4ff8dffaca084939942d60e8fe74385b697b66",
    "internlm-7b":
        "913320169e7577e352791679eaa370d4328f6a5073d765e3330a4f12f5c9fb5e",
}


@pytest.mark.parametrize("arch", sorted(CHECKSUMS))
def test_weights_of_every_configuration_are_pinned(arch):
    import jax
    from repro.config import get_smoke
    from repro.models import Model
    w = common.make_weights(BIG, tl.smoke_conf(arch),
                            Model(get_smoke(arch)).abstract())
    h = hashlib.sha256()
    for x in jax.tree_util.tree_leaves(w):
        h.update(np.asarray(x).tobytes())
    assert h.hexdigest() == CHECKSUMS[arch]


# a weight table for the program's deepseek-v2-lite-16b tree (MLA, routed
# and shared experts); an expert stack's fan-in is its axis 1, not its 0
MOE_SCALES = {"wq": (0,), "w_dkv": (0,), "w_uk": (0,), "w_uv": (0,),
              "wo": (0, 1), "w1": (0,), "w2": (0,), "w3": (0,), "w": (0,),
              "router": (0,), "moe/w1": (1,), "moe/w2": (1,), "moe/w3": (1,),
              "tok": (1,), "scale": common.NORM, "kv_norm": common.NORM}


def test_two_segments_are_keyed_by_global_layer(monkeypatch):
    """deepseek-v2-lite's smoke tree: a dense first layer as segment 0,
    two MoE layers as segment 1; both segments hold 'segments/attn/w_dkv'."""
    import jax
    import jax.numpy as jnp
    from repro.config import get_smoke
    from repro.models import Model
    tree = Model(get_smoke("deepseek-v2-lite-16b")).abstract()
    assert len(tree["segments"]) == 2
    monkeypatch.setattr(common, "family", lambda name: types.SimpleNamespace(
        WEIGHT_SCALES=MOE_SCALES))
    conf = {"reference": "moe"}
    w = common.make_weights(BIG, conf, tree)
    seg0 = np.asarray(w["segments"][0][0]["attn"]["w_dkv"])
    seg1 = np.asarray(w["segments"][1][0]["attn"]["w_dkv"])
    assert seg0.shape[0] == 1 and seg1.shape[0] == 2
    assert not np.array_equal(seg0[0], seg1[0])
    name = "segments/attn/w_dkv"
    for layer, got in ((0, seg0[0]), (1, seg1[0]), (2, seg1[1])):
        want = jax.jit(lambda k, i: common.leaf_value(
            common.leaf_key(k, name, i), name, got.shape, "moe"))(
            common.base_key(BIG), jnp.int32(layer))
        np.testing.assert_array_equal(np.asarray(want), got)
    moe = w["segments"][1][0]["moe"]
    d, experts = tree["embed"]["tok"].shape[1], moe["w1"].shape[1]
    assert d != experts
    for x, fan_in in ((moe["w1"], d), (moe["shared"]["w1"], d),
                      (moe["w2"], moe["w2"].shape[2])):
        assert abs(float(np.asarray(x).std()) * fan_in ** 0.5 - 1) < 0.1


def test_a_repeated_name_and_layer_is_refused(monkeypatch):
    import jax
    import jax.numpy as jnp
    s = jax.ShapeDtypeStruct((2, 4), jnp.float32)
    tree = {"segments": [({"mlp": ({"w1": s}, {"w1": s})},)]}
    with pytest.raises(common.BenchError, match="repeats"):
        common.make_weights(BIG, {"reference": "dense"}, tree)


# files of the benchmark that a family added as files leaves as they are
UNTOUCHED = ("common.py", "flops.py", "run.py", "kinds", "metrics")


def files_of(path: str) -> dict:
    """The bytes of each file under ``path`` (or of ``path`` itself), by its
    name relative to ``path``."""
    if os.path.isfile(path):
        with open(path, "rb") as f:
            return {"": f.read()}
    out = {}
    for d, dirs, names in os.walk(path):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for n in names:
            with open(os.path.join(d, n), "rb") as f:
                out[os.path.relpath(os.path.join(d, n), path)] = f.read()
    return out


def test_a_family_added_as_files_runs(tmp_path, monkeypatch):
    """Copy the benchmark and add, as new files only, a family
    (``reference/dense_twin.py``), a configuration that names it, a traffic
    mix, limits and a workload; run the new cell through run.run_cell on
    the CPU, past the look for a chip, to ``correct`` true."""
    root = tmp_path / "checkout"
    bench_dir = root / "chipbench"
    shutil.copytree(common.HERE, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(common.SRC, root / "src")
    shutil.copy(os.path.join(tl.HERE, "family_twin.py"),
                bench_dir / "reference" / "dense_twin.py")
    bench = copy.deepcopy(common.manifest())
    _, conf = tl.test_model(monkeypatch)
    conf["reference"] = "dense_twin"
    files = {"configs/tiny-twin.json": conf,
             "traffic/eval-tiny-twin.json": dict(tl.EVAL_GEN_MIX, gen_len=4),
             "limits/tiny-twin.eval.json": tl.TEST_EVAL_LIMITS}
    for path, data in files.items():
        assert not (bench_dir / path).exists()
        (bench_dir / path).write_text(json.dumps(data))
    bench["configs"].append({"name": "tiny-twin", "source": "test",
                             "file": "chipbench/configs/tiny-twin.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-twin.eval",
                               "config": "tiny-twin",
                               "traffic": "eval-tiny-twin", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    script = f"""
import json, sys, dataclasses
sys.path.insert(0, {str(bench_dir)!r})
import common, flops, jax
from repro.config import get_smoke
cfg = dataclasses.replace(get_smoke("internlm-7b"), vocab_size=2048)
common.program_config = lambda conf: cfg
run = common.load_module("", "run")
out = run.run_cell(common.manifest(), "tiny-twin.eval", 4, 0.2, 0,
                   jax.devices()[:1], {{}})
_, conf, _, _ = common.cell_files(common.manifest(), "tiny-twin.eval")
out["prefill_flops"] = [flops.prefill_flops(c, 4, 32) for c in (
    conf, dict(conf, reference="dense"))]
print(json.dumps(out))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(root / "src"))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    assert out["checks"]["widest_logit_gap"]["limit"] == 0.04
    a, b = out["prefill_flops"]
    assert a == b > 0
    for name in UNTOUCHED:
        assert files_of(os.path.join(common.HERE, name)) == files_of(
            str(bench_dir / name)), name
