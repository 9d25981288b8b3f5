"""The trace reduction: a small trace recorded on the chip (one TPU v5 lite,
a smoke-size prefill and two decode steps under the benchmark's spans,
``record_trace.py``), and interval cases worked out by hand."""
import gzip
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import common  # noqa: E402

trace = common.load_module("", "trace")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("xplane") / "eval_1chip.xplane.pb"
    with gzip.open(os.path.join(DATA, "eval_1chip.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    return trace.load(str(path))


def test_recorded_trace_loads_devices_and_spans(recorded):
    assert len(recorded["devices"]) == 1
    dev = recorded["devices"][0]
    assert dev["ops"] and dev["modules"]
    names = {n for n, _, _ in dev["modules"]}
    assert {"prefill", "step"} <= names
    assert [n for n, _, _ in recorded["spans"]] == [
        "eval.prefill", "eval.decode", "eval.handoff"]


def test_recorded_trace_reduces(recorded):
    r = trace.reduce(recorded, 1)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["modules"]["prefill"]["runs"] == 1
    assert r["modules"]["step"]["runs"] == 2
    # self times of the top operations never exceed the busy time
    assert sum(s for _, s in r["top_ops"]) <= r["busy_s"] * (1 + 1e-9)
    assert all(n.split("/")[0] in r["modules"] or n.startswith("-/")
               for n, _ in r["top_ops"])
    idle = dict(r["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert set(idle) <= {"eval.prefill", "eval.decode", "eval.handoff",
                         "host.other"}
    assert r["modules"]["step"]["collective_s"] == 0.0


def test_hand_made_trace():
    """Two devices, ns: a prefill run 0-100 and a step run 150-300; on
    device 0 an all-reduce 200-260 of which 240-260 overlaps a fusion."""
    fusion = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    reduce_ = ("%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %x), "
               "replica_groups={{0,1}}")
    while_ = "%while.2 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)"
    dev0 = {"id": 0, "modules": [("prefill", 0, 100), ("step", 150, 300)],
            "ops": [(while_, 0, 100), (fusion, 10, 60), (fusion, 160, 200),
                    (reduce_, 200, 260), (fusion, 240, 300)],
            "async_ops": []}
    dev1 = {"id": 1, "modules": [("prefill", 0, 100), ("step", 150, 300)],
            "ops": [(fusion, 0, 100), (fusion, 150, 300)], "async_ops": []}
    spans = [("eval.prefill", 0, 105), ("eval.decode", 105, 320)]
    r = trace.reduce({"devices": [dev0, dev1], "spans": spans}, 2)
    assert r["window_s"] == pytest.approx(320e-9)
    # device 0 busy 0-100, 160-300 (240 ns); device 1 0-100, 150-300 (250)
    assert r["busy_s"] == pytest.approx(245e-9)
    assert r["modules"]["step"]["s"] == pytest.approx(150e-9)
    assert r["modules"]["step"]["runs"] == 1
    # 60 ns of all-reduce on device 0, 40 of it with nothing else running;
    # averaged over the two devices
    assert r["modules"]["step"]["collective_s"] == pytest.approx(30e-9)
    assert r["modules"]["step"]["collective_exposed_s"] == pytest.approx(
        20e-9)
    assert r["modules"]["prefill"]["collective_s"] == 0.0
    # device 0 idle: 100-105 in eval.prefill, 105-160 and 300-320 in decode
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"eval.decode": 75e-9, "eval.prefill": 5e-9})
    # self time: the while holds a 50 ns fusion
    top = dict(r["top_ops"])
    assert top["prefill/while.2"] == pytest.approx(25e-9)
    assert trace.op_code(reduce_) == "all-reduce"
    assert trace.op_code(while_) == "while"
    assert trace.module_name("jit_prefill(1214243491042212009)") == "prefill"


def test_interval_helpers():
    a = trace.union([(5, 9), (0, 3), (2, 4), (8, 12)])
    assert a == [(0, 4), (5, 12)]
    assert trace.intersect(a, [(3, 6), (11, 20)]) == [(3, 4), (5, 6),
                                                      (11, 12)]
    assert trace.subtract([(0, 20)], a) == [(4, 5), (12, 20)]
    assert trace.length(a) == 11
