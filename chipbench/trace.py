"""From the profiler's ``.xplane.pb`` to the numbers the metrics read.

``load`` keeps what the reduction needs, as plain tuples of nanoseconds on
the trace's one clock: per device, its module runs ("XLA Modules") and its
operations ("XLA Ops", "Async XLA Ops"); and the benchmark's own host spans
(``TraceAnnotation``s named ``eval.*`` and ``train.*``). ``reduce`` turns
them into:

- ``window_s``: from the first benchmark span or device operation to the
  last one's end;
- ``busy_s``: the union of operation intervals, averaged over the devices;
- ``modules``: device seconds per program (``prefill``, ``step``, ...),
  averaged over the devices, with how often each ran;
- ``collective_s`` per program: time in collective operations inside that
  program's runs, and ``collective_exposed_s``: the part of it during which
  no other operation ran;
- ``top_ops``: the ten operations that took most time, named
  ``<program>/<HLO op>``, each by its self time (nested operations counted
  once);
- ``idle_gaps``: device 0's idle time within the window, by the benchmark
  span that the host was in meanwhile (``host.other`` when in none).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
SPAN_PREFIXES = ("eval.", "train.")
_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {len(files)}")
    return files[0]


def op_name(text: str) -> str:
    """'%fusion.71 = s32[..] fusion(..), ..' -> 'fusion.71'."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def op_code(text: str) -> str:
    """'%fusion.71 = s32[..]{..} fusion(..)' -> 'fusion'."""
    if " = " not in text:
        return text
    rest = text.split(" = ", 1)[1]
    if rest.startswith("("):              # tuple shape: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    return rest.strip().split("(", 1)[0]


def module_name(text: str) -> str:
    """'jit_prefill(1214243491042212009)' -> 'prefill'."""
    return _MODULE.match(text).group(1)


def is_collective(text: str) -> bool:
    code = op_code(text)
    return any(code.startswith(c) for c in COLLECTIVES)


def load(path: str) -> dict:
    """{"devices": [{"modules": [(name, start, end)], "ops": [(text,
    start, end)], "async_ops": [...]}], "spans": [(name, start, end)]}."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"id": int(plane.name.rsplit(":", 1)[1]), "modules": [],
                   "ops": [], "async_ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops",
                       "Async XLA Ops": "async_ops"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    name = module_name(e.name) if key == "modules" else e.name
                    dev[key].append((name, int(e.start_ns),
                                     int(e.start_ns + e.duration_ns)))
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append((e.name, int(e.start_ns),
                                      int(e.start_ns + e.duration_ns)))
    devices.sort(key=lambda d: d["id"])
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def intersect(a, b) -> list[tuple[int, int]]:
    """Intersection of two unions (each sorted and disjoint)."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a, b) -> list[tuple[int, int]]:
    """a minus b (both unions)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(ops) -> list[tuple[str, int, int]]:
    """(name, start, self time) of each operation: its time less that of
    the operations nested in it (a ``while`` holds its body's operations on
    the same line)."""
    out, stack = [], []          # stack: [name, start, end, child time]
    for t, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= s:
            top = stack.pop()
            out.append((top[0], top[1], top[2] - top[1] - top[3]))
        if stack:
            stack[-1][3] += e - s
        stack.append([op_name(t), s, e, 0])
    out.extend((top[0], top[1], top[2] - top[1] - top[3]) for top in stack)
    return out


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

def reduce(events: dict, n_devices: int) -> dict:
    devices = events["devices"][:n_devices]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    spans = events["spans"]
    starts = [s for _, s, _ in spans] + [s for d in devices
                                          for _, s, _ in d["ops"]]
    ends = [e for _, _, e in spans] + [e for d in devices
                                        for _, _, e in d["ops"]]
    w0, w1 = min(starts), max(ends)
    window = [(w0, w1)]
    n = len(devices)

    busy = None
    busy_total = 0
    modules: dict = defaultdict(lambda: {"s": 0.0, "runs": 0,
                                         "collective_s": 0.0,
                                         "collective_exposed_s": 0.0})
    op_time: dict = defaultdict(float)
    for d in devices:
        busy_d = intersect(union((s, e) for _, s, e in d["ops"]), window)
        busy_total += length(busy_d)
        if busy is None:
            busy = busy_d
        coll = union([(s, e) for t, s, e in d["ops"] + d["async_ops"]
                      if is_collective(t)])
        other = union([(s, e) for t, s, e in d["ops"]
                       if not is_collective(t)])
        exposed = subtract(coll, other)
        runs: dict = defaultdict(list)
        for name, s, e in d["modules"]:
            runs[name].append((s, e))
        for name, intervals in runs.items():
            u = union(intervals)
            m = modules[name]
            m["s"] += length(u) / 1e9 / n
            m["runs"] += len(intervals)
            m["collective_s"] += length(intersect(coll, u)) / 1e9 / n
            m["collective_exposed_s"] += (length(intersect(exposed, u))
                                          / 1e9 / n)
        starts = sorted((s, e, name) for name, s, e in d["modules"])
        for op, s, self_ns in self_times(d["ops"]):
            i = bisect.bisect_right(starts, (s, float("inf"))) - 1
            where = starts[i][2] if i >= 0 and starts[i][1] > s else "-"
            op_time[f"{where}/{op}"] += self_ns / 1e9 / n
    for m in modules.values():
        m["runs"] //= n

    gaps = subtract(window, busy)
    by_span: dict = defaultdict(float)
    per_name: dict = defaultdict(list)
    for name, s, e in spans:
        per_name[name].append((s, e))
    for name, intervals in per_name.items():
        by_span[name] = length(intersect(gaps, union(intervals))) / 1e9
    other = length(subtract(gaps, union((s, e) for _, s, e in spans)))
    if other:
        by_span["host.other"] = other / 1e9
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(by_span.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": (w1 - w0) / 1e9,
            "busy_s": busy_total / n / 1e9,
            "modules": dict(modules),
            "top_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in idle]}


def reduce_dir(trace_dir: str, n_devices: int) -> dict:
    return reduce(load(find_xplane(trace_dir)), n_devices)
