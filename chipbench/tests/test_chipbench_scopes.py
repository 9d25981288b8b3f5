"""scopes.py: the program's scopes and spans read from profiler traces.

Three kinds of trace: a small one recorded on the chip with the program's
own names (one TPU v5 lite; a smoke-size train step, prefill and two decode
steps, ``record_program_trace.py``), the older one recorded before the
program named its work (``record_trace.py``), and CPU traces of the
benchmark's own kinds; besides, cases worked out by hand."""
import gzip
import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_testlib as tl  # noqa: E402
import common  # noqa: E402
import record_program_trace as rec  # noqa: E402
import scopes  # noqa: E402

trace = scopes.trace
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SCOPES, SPANS = scopes.program_names()
NEW_METRICS = ("kv_cache_share.decode", "layer_scan_share.decode",
               "idle_share.decode", "attn_mfu.prefill",
               "layer_scan_share.train")


def unpack(factory, name: str) -> str:
    d = factory.mktemp(name.split(".")[0])
    with gzip.open(os.path.join(DATA, name)) as f:
        (d / "trace.xplane.pb").write_bytes(f.read())
    return str(d)


@pytest.fixture(scope="module")
def program_dir(tmp_path_factory):
    return unpack(tmp_path_factory, rec.FILE)


@pytest.fixture(scope="module")
def program(program_dir):
    return scopes.reduce_dir(program_dir, 1)


@pytest.fixture(scope="module")
def old_dir(tmp_path_factory):
    return unpack(tmp_path_factory, "eval_1chip.xplane.pb.gz")


def view(trace_dir: str, conf: dict) -> types.SimpleNamespace:
    """What the harness gives a metric's reader, and the trace's
    directory."""
    return types.SimpleNamespace(
        trace=trace.reduce_dir(trace_dir, 1), counters={}, conf=conf,
        peaks=common.peaks("TPU v5 lite"), chips=1, trace_dir=trace_dir)


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
     "rematted_computation/attn/bsd,dhk->bshk/dot_general:", "attn"),
    ("jit(step)/transpose(jvp(layers))/while/body/dynamic_update_slice:",
     "layers"),
    ("jit(step)/layers/while/body/closed_call/attn/kv_cache/"
     "dynamic_update_slice:", "kv_cache"),
    ("jit(step)/transpose(jvp(loss))/while/body/closed_call/lm_head/"
     "dot_general:", "lm_head"),
    ("jit(step)/checkpoint(remat(mlp))/dot_general:dot", "mlp"),
    ("jit(step)/optimizer/jit(clip)/sqrt:", "optimizer"),
    ("jit(step)/layers/while/body/attn/le;jit(step)/norm/mul:", "attn"),
    ("jit(step)/jvp(norm)/mul:", "norm"),
    ("jit(prefill)/jit(_take)/gather:", None),
    ("jit(step)/while/body/closed_call/dynamic_slice:", None),
    ("", None),
])
def test_innermost_scope_reads_through_transforms(tf_op, scope):
    assert scopes.innermost_scope(tf_op, SCOPES) == scope


def test_the_program_lists_every_name_the_metrics_read(monkeypatch):
    assert {"kv_cache", "layers", "attn"} <= set(SCOPES)
    assert {"serve.prefill", "serve.decode", "trainer.step"} <= set(SPANS)
    # a program from before it listed them: no names, nothing found
    monkeypatch.setitem(sys.modules, "repro.utils.profiling", None)
    assert scopes.program_names() == ((), ())


def test_hand_made_trace():
    """One device, ns: a step run 0-100 of a while (its own 50 ns in
    ``layers``) holding attn 10-40, kv_cache 50-60 and a copy with no
    path 70-80; a decode span -20-130 holding a prefill span -10-5, and a
    second decode span 200-210."""
    dev = {"id": 0, "modules": [("step", 0, 100)], "ops": [
        ("%while.1 = (s32[]) while((s32[]) %t)", 0, 100,
         "jit(step)/layers/while:"),
        ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)", 10, 40,
         "jit(step)/layers/while/body/attn/dot_general:"),
        ("%dynamic-update-slice.3 = f32[8]{0} dynamic-update-slice(%a)",
         50, 60, "jit(step)/layers/while/body/attn/kv_cache/"
         "dynamic_update_slice:"),
        ("%copy.4 = f32[8]{0} copy(f32[8]{0} %a)", 70, 80, ""),
    ]}
    spans = [("serve.decode", -20, 130, {"batch": 4, "start": 8, "steps": 2}),
             ("serve.prefill", -10, 5, {"batch": 4, "prompt_len": 8}),
             ("serve.decode", 200, 210, {"batch": 4, "start": 10,
                                         "steps": 2})]
    r = scopes.reduce({"devices": [dev], "spans": spans}, 1, SCOPES)
    assert r["scopes"] == {"step": pytest.approx(
        {"layers": 50e-9, "attn": 30e-9, "kv_cache": 10e-9,
         "unscoped.copy": 10e-9})}
    dec = r["program_spans"]["serve.decode"]
    assert dec["count"] == 2 and dec["s"] == pytest.approx(160e-9)
    # idle in decode's own time: -20 to -10, 100 to 130, 200 to 210
    assert dec["idle_s"] == pytest.approx(50e-9)
    assert dec["args"] == {"batch": 8, "start": 18, "steps": 4}
    assert dec["calls"] == [spans[0][3], spans[2][3]]
    assert r["program_spans"]["serve.prefill"]["idle_s"] == pytest.approx(
        10e-9)
    # a trace with no device: spans only, their idle unknown
    r = scopes.reduce({"devices": [], "spans": spans[:1]}, 1, SCOPES)
    assert r["scopes"] == {}
    assert r["program_spans"]["serve.decode"]["idle_s"] is None


def test_loader_reads_what_trace_load_reads(old_dir):
    path = trace.find_xplane(old_dir)
    ours = scopes.load(path, ("eval.prefill", "eval.decode", "eval.handoff"))
    theirs = trace.load(path)
    dev, ref = ours["devices"][0], theirs["devices"][0]
    assert [o[:3] for o in dev["ops"]] == ref["ops"]
    assert dev["modules"] == ref["modules"]
    assert [s[:3] for s in ours["spans"]] == theirs["spans"]
    # idle by span, as the accepted reduction puts it
    r = scopes.reduce(ours, 1, SCOPES)
    idle = dict(trace.reduce(theirs, 1)["idle_gaps"])
    for name, sp in r["program_spans"].items():
        assert sp["idle_s"] == pytest.approx(idle[name], abs=1e-12)


def test_old_trace_reduces_as_before_and_new_metrics_find_nothing(old_dir):
    """The accepted reduction of the trace recorded before the program
    named its work is pinned; that program's operations are unscoped,
    it has no program spans, and each new metric reads None."""
    with open(os.path.join(DATA, "eval_1chip.reduce.json")) as f:
        pinned = json.load(f)
    assert json.loads(json.dumps(trace.reduce_dir(old_dir, 1))) == pinned
    r = scopes.reduce_dir(old_dir, 1)
    assert r["program_spans"] == {}
    assert all(b.startswith("unscoped.") for p in r["scopes"].values()
               for b in p)
    v = view(old_dir, tl.smoke_conf("smollm-360m"))
    for name in NEW_METRICS:
        assert common.load_module("metrics", name).read(v) is None, name


def test_program_trace_scopes_add_up_to_each_programs_busy_time(
        program_dir, program):
    events = trace.load(trace.find_xplane(program_dir))
    dev = events["devices"][0]
    busy = trace.union((s, e) for _, s, e in dev["ops"])
    for name in {n for n, _, _ in dev["modules"]}:
        runs = trace.union((s, e) for n, s, e in dev["modules"] if n == name)
        in_runs = trace.length(trace.intersect(busy, runs)) / 1e9
        # an operation is its program's by its start; the trace's whole-ns
        # times may let its end pass the run's by a nanosecond
        assert sum(program["scopes"].get(name, {}).values()) == pytest.approx(
            in_runs, rel=0, abs=1e-9 * len(runs)), name
    total = sum(sum(b.values()) for b in program["scopes"].values())
    assert total == pytest.approx(trace.reduce(events, 1)["busy_s"],
                                  rel=1e-9)


def test_program_trace_names_each_programs_work(program):
    step, prefill = program["scopes"]["step"], program["scopes"]["prefill"]
    # "step" is both the train step and the decode step here
    assert {"embed", "layers", "norm", "attn", "kv_cache", "mlp", "lm_head",
            "loss", "optimizer"} <= set(step)
    # prefill's cache build fuses into the attention's projections
    assert {"embed", "layers", "norm", "attn", "mlp", "lm_head"} <= set(
        prefill)
    assert not {"loss", "optimizer"} & set(prefill)


def test_program_span_idle_within_the_devices_idle(program_dir, program):
    r = trace.reduce_dir(program_dir, 1)
    idle = r["window_s"] - r["busy_s"]
    spans = program["program_spans"]
    assert set(spans) == set(SPANS) - {"serve.restart"}
    for name, sp in spans.items():
        assert 0 <= sp["idle_s"] <= sp["s"], name
    assert sum(sp["idle_s"] for sp in spans.values()) <= idle


def test_program_span_arguments_are_what_the_recorder_served(program):
    spans = program["program_spans"]
    assert spans["trainer.step"]["calls"] == [
        {"step_num": 2, "batch": rec.TRAIN_BATCH, "seq_len": rec.SEQ_LEN}]
    assert spans["serve.prefill"]["calls"] == [
        {"batch": rec.SERVE_BATCH, "prompt_len": rec.PROMPT_LEN}]
    assert spans["serve.decode"]["calls"] == [
        {"batch": rec.SERVE_BATCH, "start": rec.PROMPT_LEN,
         "steps": rec.DECODE_STEPS}]
    for name in ("trainer.data", "trainer.sync", "trainer.host",
                 "trainer.ckpt"):
        assert spans[name]["count"] == 1 and spans[name]["calls"] == [{}]


def test_new_metrics_read_the_program_trace_through_the_harness(
        program_dir):
    """run.per_layer gives readers no trace directory: they find it on the
    harness's tracer."""
    run = common.load_module("", "run")
    layer = [m for m in common.manifest()["per_layer"]
             if m["name"] in NEW_METRICS]
    tracer = types.SimpleNamespace(dir=program_dir, counters={})
    out = run.per_layer(layer, trace.reduce_dir(program_dir, 1), tracer,
                        tl.smoke_conf("smollm-360m"),
                        common.peaks("TPU v5 lite"), 1)
    assert set(out) == set(NEW_METRICS)
    for name, m in out.items():
        assert m["unit"] == "%" and 0 < m["value"] < 100, (name, m)


def test_attention_sublayer_flops_by_hand():
    attn = common.load_module("metrics", "attn_mfu.prefill")
    flops = common.load_module("", "flops")
    conf = common.load_json(os.path.join(common.HERE, "configs",
                                         "smollm-360m.json"))
    # q and o 960 x 15 x 64, k and v 960 x 5 x 64; 32 layers
    proj = 2 * 960 * 960 + 2 * 960 * 320
    core = 32 * 32 * (512 * 513 // 2) * 15 * 64 * 4
    assert attn.attention_sublayer_flops(conf, 32, 512) == (
        2 * 32 * proj * 32 * 512 + core)
    assert (attn.attention_sublayer_flops(conf, 32, 512)
            < flops.prefill_flops(conf, 32, 512))


@pytest.fixture
def short_trace(monkeypatch):
    """The harness's tracer, stopping after a fraction of a second."""
    monkeypatch.setattr(common, "TRACE_SECONDS", 0.3)
    run = common.load_module("", "run")
    tracer = run.Tracer()
    yield tracer
    tracer.stop()
    tracer.close()


def test_serve_span_counts_equal_the_harness_counters(monkeypatch,
                                                      short_trace):
    import jax
    kind = common.load_module("kinds", "eval")
    tl.use_smoke(monkeypatch, "smollm-360m")
    conf, mix = tl.smoke_conf("smollm-360m"), tl.GEN_MIX
    sess = kind.build(conf, mix, 5, jax.devices()[:1])
    traffic = kind.EvalTraffic(5, conf["vocab_size"], mix["batch"],
                               mix["prompt_len"])
    kind.window(sess, traffic, mix["gen_len"], 1.0, tracer=short_trace)
    spans = scopes.reduce_dir(short_trace.dir, 1)["program_spans"]
    counters = short_trace.counters
    assert counters["prefill"]
    assert [(c["batch"], c["prompt_len"])
            for c in spans["serve.prefill"]["calls"]] == counters["prefill"]
    assert [(c["batch"], c["start"] + j) for c in spans["serve.decode"][
        "calls"] for j in range(c["steps"])] == counters["decode"]


def test_train_step_spans_equal_the_harness_counters(monkeypatch,
                                                     short_trace, tmp_path):
    """The harness starts the trace inside the job's last warm-up call and
    stops it inside the call that ends the traced units: a span cut by
    either is not recorded, so the last unit counted has no span."""
    import jax
    kind = common.load_module("kinds", "train")
    tl.use_smoke(monkeypatch, "smollm-360m")
    conf, mix = tl.smoke_conf("smollm-360m"), tl.TRAIN_MIX
    trainer = kind.build(conf, mix, 5, jax.devices()[:1], str(tmp_path))
    hook = kind.StepHook(trainer, trainer.step_fn, mix, 1.0,
                         tracer=short_trace)
    kind.run_job(trainer, hook, 5)
    hook.finish()
    spans = scopes.reduce_dir(short_trace.dir, 1)["program_spans"]
    counters = short_trace.counters["train"]
    assert len(counters) >= 2
    calls = spans["trainer.step"]["calls"]
    assert [(c["batch"], c["seq_len"]) for c in calls] == [
        tuple(s) for s in counters[:-1]]
    assert [c["step_num"] for c in calls] == list(
        range(mix["warmup_calls"], mix["warmup_calls"] + len(calls)))
