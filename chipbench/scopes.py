"""The program's own names in a profiler trace: device time by scope, and
the host spans with their counts.

The program lists its names once (``repro.utils.profiling``): ``SCOPES``,
the ``jax.named_scope`` names inside its jitted programs, and ``SPANS``, the
host spans its launchers record with the work's counts as arguments. A
program that lists none (one from before it named its work) yields empty
results here, and the metrics that read them find nothing.

``jax.profiler.ProfileData`` does not show an operation's metadata, so the
``.xplane.pb`` is read here through its protobuf schema (``XSpace``, built
below from its field numbers; nothing generated is imported). Each device
operation's event metadata carries ``tf_op``, the operation's name-stack
path (``jit(step)/transpose(jvp(layers))/while/body/checkpoint/attn/...``).

``reduce`` returns:

- ``scopes``: per program (``prefill``, ``step``, ...), device self time in
  seconds by the innermost program scope of each operation's path, averaged
  over the devices. Operations in no program scope, such as the copies and
  converts the compiler adds, go to ``unscoped.<opcode>``. Per program the
  buckets add up to the time in which one of its operations ran.
- ``program_spans``: per span name, ``count``, ``s`` (host seconds),
  ``idle_s`` (device 0's idle time inside the span; the innermost span owns
  each instant, so nothing counts twice; None without a device), ``args``
  (each argument summed) and ``calls`` (each span's arguments).
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

import common

trace = common.load_module("", "trace")

# name-stack transforms around a scope: jvp(attn), transpose(jvp(layers))
_WRAPPER = re.compile(r"[\w.-]*\(|\)")


# ---------------------------------------------------------------------------
# the XSpace schema (tsl/profiler/protobuf/xplane.proto), by field number
# ---------------------------------------------------------------------------

def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    i64, u64, dbl, text, raw, sub = (F.TYPE_INT64, F.TYPE_UINT64,
                                     F.TYPE_DOUBLE, F.TYPE_STRING,
                                     F.TYPE_BYTES, F.TYPE_MESSAGE)
    schema = {
        "XStat": [("metadata_id", 1, i64), ("double_value", 2, dbl, "value"),
                  ("uint64_value", 3, u64, "value"),
                  ("int64_value", 4, i64, "value"),
                  ("str_value", 5, text, "value"),
                  ("bytes_value", 6, raw, "value"),
                  ("ref_value", 7, u64, "value")],
        "XEvent": [("metadata_id", 1, i64), ("offset_ps", 2, i64),
                   ("duration_ps", 3, i64), ("stats", 4, "XStat")],
        "XLine": [("name", 2, text), ("timestamp_ns", 3, i64),
                  ("events", 4, "XEvent")],
        "XEventMetadata": [("id", 1, i64), ("name", 2, text),
                           ("stats", 5, "XStat")],
        "XStatMetadata": [("id", 1, i64), ("name", 2, text)],
        "XPlane": [("name", 2, text), ("lines", 3, "XLine"),
                   ("event_metadata", 4, "XPlane.EventMetadataEntry"),
                   ("stat_metadata", 5, "XPlane.StatMetadataEntry")],
        "XSpace": [("planes", 1, "XPlane")],
    }
    maps = {"EventMetadataEntry": "XEventMetadata",
            "StatMetadataEntry": "XStatMetadata"}
    repeated = {"stats", "events", "lines", "planes", "event_metadata",
                "stat_metadata"}
    fp = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench_xplane",
        syntax="proto3")

    def field(msg, name, number, kind, oneof=None):
        f = msg.field.add(name=name, number=number,
                          label=F.LABEL_REPEATED if name in repeated
                          else F.LABEL_OPTIONAL)
        if isinstance(kind, str):
            f.type, f.type_name = sub, ".chipbench_xplane." + kind
        else:
            f.type = kind
        if oneof:
            if not msg.oneof_decl:
                msg.oneof_decl.add(name=oneof)
            f.oneof_index = 0

    for name, fields in schema.items():
        msg = fp.message_type.add(name=name)
        for spec in fields:
            field(msg, *spec)
        if name == "XPlane":
            for entry, value in maps.items():
                m = msg.nested_type.add(name=entry)
                m.options.map_entry = True
                field(m, "key", 1, i64)
                field(m, "value", 2, value)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench_xplane.XSpace"))


XSpace = _xspace_class()


def _stats(stats, stat_names) -> dict:
    out = {}
    for st in stats:
        kind = st.WhichOneof("value")
        if kind is None:
            continue
        value = getattr(st, kind)
        out[stat_names.get(st.metadata_id, "")] = (
            stat_names.get(value) if kind == "ref_value" else value)
    return out


# ---------------------------------------------------------------------------
# names
# ---------------------------------------------------------------------------

def program_names() -> tuple[tuple, tuple]:
    """(SCOPES, SPANS) as the program lists them; empty for a program that
    lists none."""
    common.use_program()
    try:
        from repro.utils.profiling import SCOPES, SPANS
    except ImportError:
        return (), ()
    return tuple(SCOPES), tuple(SPANS)


def innermost_scope(tf_op: str, scopes) -> str | None:
    """The last program scope in an operation's name-stack path.

    ``tf_op`` is ``<path>:<op type>``; a fused operation may hold several
    paths joined by ``;``, of which the first is read. Each part of the path
    is read through its transforms: ``transpose(jvp(layers))`` is
    ``layers``."""
    path = tf_op.split(";", 1)[0]
    if ":" in path:
        path = path.rsplit(":", 1)[0]
    found = None
    for part in path.split("/"):
        name = _WRAPPER.sub("", part)
        if name in scopes:
            found = name
    return found


# ---------------------------------------------------------------------------
# load and reduce
# ---------------------------------------------------------------------------

def _interval(line, event) -> tuple[int, int]:
    """Whole ns, as jax.profiler.ProfileData gives them, so that these are
    the intervals trace.load reads."""
    start = line.timestamp_ns + event.offset_ps // 1000
    return start, start + event.duration_ps // 1000


def load(path: str, span_names) -> dict:
    """{"devices": [{"id", "modules": [(name, start, end)], "ops": [(text,
    start, end, tf_op)]}], "spans": [(name, start, end, args)]}, in ns."""
    space = XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    devices, spans = [], []
    for plane in space.planes:
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        meta = plane.event_metadata
        if plane.name.startswith("/device:TPU:"):
            tf_ops = {k: _stats(m.stats, stat_names).get("tf_op") or ""
                      for k, m in meta.items()}
            dev = {"id": int(plane.name.rsplit(":", 1)[1]), "modules": [],
                   "ops": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] += [
                        (trace.module_name(meta[e.metadata_id].name),
                         *_interval(line, e)) for e in line.events]
                elif line.name == "XLA Ops":
                    dev["ops"] += [
                        (meta[e.metadata_id].name, *_interval(line, e),
                         tf_ops[e.metadata_id]) for e in line.events]
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    name = meta[e.metadata_id].name
                    if name in span_names:
                        args = {k: v for k, v in
                                _stats(e.stats, stat_names).items()
                                if not k.startswith("_")}
                        spans.append((name, *_interval(line, e), args))
    devices.sort(key=lambda d: d["id"])
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


def own_intervals(spans) -> list[tuple[str, list]]:
    """(name, the span's interval less those of the spans nested in it)."""
    out, stack = [], []          # stack: [name, start, end, children]

    def close(top):
        out.append((top[0], trace.subtract([(top[1], top[2])],
                                           trace.union(top[3]))))

    for name, s, e, *_ in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3].append((s, e))
        stack.append([name, s, e, []])
    for top in reversed(stack):
        close(top)
    return out


def reduce(events: dict, n_devices: int, scopes) -> dict:
    devices = events["devices"][:n_devices]
    n = len(devices)
    by_program: dict = defaultdict(lambda: defaultdict(float))
    innermost: dict = {}         # tf_op -> scope, read once per path
    for d in devices:
        runs = sorted((s, e, name) for name, s, e in d["modules"])
        bucket = {}
        for text, s, _, tf_op in d["ops"]:
            if tf_op not in innermost:
                innermost[tf_op] = innermost_scope(tf_op, scopes)
            bucket[(trace.op_name(text), s)] = (
                innermost[tf_op] or "unscoped." + trace.op_code(text))
        for op, s, self_ns in trace.self_times([o[:3] for o in d["ops"]]):
            i = bisect.bisect_right(runs, (s, float("inf"))) - 1
            where = runs[i][2] if i >= 0 and runs[i][1] > s else "-"
            by_program[where][bucket[(op, s)]] += self_ns / 1e9 / n

    spans: dict = {}
    for name, s, e, args in events["spans"]:
        sp = spans.setdefault(name, {"count": 0, "s": 0.0,
                                     "idle_s": 0.0 if devices else None,
                                     "args": {}, "calls": []})
        sp["count"] += 1
        sp["s"] += (e - s) / 1e9
        sp["calls"].append(args)
        for k, v in args.items():
            sp["args"][k] = sp["args"].get(k, 0) + v
    if devices:
        busy0 = trace.union((s, e) for _, s, e, _ in devices[0]["ops"])
        for name, own in own_intervals(events["spans"]):
            spans[name]["idle_s"] += (trace.length(trace.subtract(own, busy0))
                                      / 1e9)
    return {"scopes": {p: dict(b) for p, b in by_program.items()},
            "program_spans": spans}


def reduce_dir(trace_dir: str, n_devices: int) -> dict:
    """``reduce`` of the one ``.xplane.pb`` under ``trace_dir``, with the
    program's names."""
    scopes, span_names = program_names()
    return reduce(load(trace.find_xplane(trace_dir), span_names), n_devices,
                  scopes)


def of_view(view) -> dict | None:
    """``reduce_dir`` of the traced run whose per-layer metrics ``view``
    serves (its ``trace_dir``), made once per view; None where the run left
    no trace."""
    if not hasattr(view, "scopes_reduction"):
        try:
            view.scopes_reduction = reduce_dir(view.trace_dir, view.chips)
        except FileNotFoundError:
            view.scopes_reduction = None
    return view.scopes_reduction


def scope_share(view, program: str, scope: str, span: str) -> float | None:
    """Percent of ``program``'s device time whose innermost scope is
    ``scope``, in a traced run that holds the program span ``span``."""
    r = of_view(view)
    module = view.trace["modules"].get(program)
    if not (r and span in r["program_spans"] and module and module["s"]):
        return None
    t = r["scopes"].get(program, {}).get(scope)
    return None if t is None else 100.0 * t / module["s"]
