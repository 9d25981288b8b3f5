"""BENCHMARK.json against the benchmark's contract, and cells found as data:
a cell added in a copy of the benchmark, with nothing but data files, runs."""
import copy
import json
import math
import os
import re
import shutil
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import common  # noqa: E402

BENCH = common.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    assert len(BENCH["command"]) <= 32
    assert all(one_line(w) for w in BENCH["command"])
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_every_name_unit_and_text():
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"])
        assert one_line(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        names.append(c["name"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and one_line(w["why"])
        names.append(w["name"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        names.append(m["name"])
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_end_to_end_bounds_and_setup():
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    setup = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert setup and "workloads" not in setup[0]


def test_every_cell_finds_its_files_and_reports_enough():
    kinds = {os.path.basename(p)[:-3] for p in os.listdir(
        os.path.join(common.HERE, "kinds")) if p.endswith(".py")}
    for w in BENCH["workloads"]:
        cell, conf, mix, limits = common.cell_files(BENCH, w["name"])
        assert mix["kind"] in kinds
        assert w["chips"] % conf["model_axis"] == 0
        assert limits and all(isinstance(v, (int, float)) and math.isfinite(v)
                              for v in limits.values())
        e2e = [m["name"] for m in BENCH["end_to_end"]
               if "workloads" not in m or w["name"] in m["workloads"]]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = [m for m in BENCH["per_layer"] if w["name"] in
                 m.get("workloads", [])]
        assert layer, w["name"]


def test_per_layer_metrics_have_readers_and_their_moves_are_reported():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    layers = {}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert one_line(m["layer"])
        assert os.path.exists(os.path.join(common.HERE, "metrics",
                                           m["name"] + ".py"))
        target = e2e[m["moves"]]
        for w in m["workloads"]:
            assert "workloads" not in target or w in target["workloads"], (
                m["name"], w)
        layers.setdefault(m["layer"], []).append(m["name"])


def test_config_files_state_what_the_program_runs():
    for c in BENCH["configs"]:
        conf = common.load_json(os.path.join(common.CHECKOUT, c["file"]))
        # source, reduced and chips live in BENCHMARK.json alone
        assert not {"source", "reduced", "chips"} & set(conf)
        common.check_program_config(common.program_config(conf), conf)


@pytest.mark.parametrize("change", [
    {"embed_scale_sqrt_hidden": False},
    {"bias": True},
    {"rms_norm_eps": 1e-6},
    {"departures": {"attention_window": "not a key the check reads"}},
])
def test_check_program_config_refuses_an_unstated_departure(change):
    conf = common.load_json(os.path.join(common.HERE, "configs",
                                         "internlm-7b.json"))
    cfg = common.program_config(conf)
    bad = dict(conf, **change)
    with pytest.raises(common.BenchError, match=next(iter(change))):
        common.check_program_config(cfg, bad)


def test_a_weight_the_reference_lacks_is_refused():
    with pytest.raises(common.BenchError, match="bias"):
        common.leaf_value(common.base_key(1), "segments/attn/bias", (4,),
                          "dense")


def test_check_program_config_refuses_a_changed_width():
    conf = common.load_json(os.path.join(common.HERE, "configs",
                                         "smollm-360m.json"))
    cfg = common.program_config(conf)
    bad = dict(conf, intermediate_size=2048)
    with pytest.raises(common.BenchError, match="intermediate_size"):
        common.check_program_config(cfg, bad)


def test_run_time_of_a_full_check_fits():
    # 24 cells: 2 + 14 runs each, run_seconds + 60 each, 180 s more per
    # cell to compile, 1200 s spare
    cells = 24
    total = ((2 + 14 * cells) * (BENCH["run_seconds"] + 60)
             + cells * 180 + 1200)
    assert total <= 43200


def test_a_cell_added_as_data_runs(tmp_path, monkeypatch):
    """Copy the benchmark, add a configuration, a traffic mix, limits and a
    workload (data only), and run the new cell through run.run_cell on the
    CPU, past the look for a chip."""
    import chipbench_testlib as tl
    root = tmp_path / "checkout"
    shutil.copytree(common.HERE, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(common.SRC, root / "src")
    bench = copy.deepcopy(BENCH)
    _, conf = tl.test_model(monkeypatch)
    (root / "chipbench" / "configs" / "tiny.json").write_text(
        json.dumps(conf))
    mix = dict(tl.EVAL_GEN_MIX, gen_len=4)
    (root / "chipbench" / "traffic" / "eval-tiny.json").write_text(
        json.dumps(mix))
    (root / "chipbench" / "limits" / "tiny.eval.json").write_text(
        json.dumps(tl.TEST_EVAL_LIMITS))
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "chipbench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.eval", "config": "tiny",
                               "traffic": "eval-tiny", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "smollm-360m.eval-gen" in m.get("workloads", []):
            m["workloads"].append("tiny.eval")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    script = f"""
import json, sys, dataclasses
sys.path.insert(0, {str(root / 'chipbench')!r})
import common, jax
from repro.config import get_smoke
cfg = dataclasses.replace(get_smoke("internlm-7b"), vocab_size=2048)
common.program_config = lambda conf: cfg
run = common.load_module("", "run")
out = run.run_cell(common.manifest(), "tiny.eval", 4, 0.2, 0,
                   jax.devices()[:1], {{}})
print(json.dumps(out))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(root / "src"))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    assert set(out["metrics"]) == {"eval_tokens_per_s", "tpot_ms",
                                   "setup_s"}
    assert out["checks"]["widest_logit_gap"]["limit"] == 0.04
