#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine and print its line.

    python3 chipbench/run.py --workload smollm-360m.eval-gen --seed 7 \
        --seconds 30 --trace 0

The cell, its configuration, traffic mix, limits and per-layer metrics are
all found by name from ``BENCHMARK.json`` (see ``common.py``). One process:
set-up (weights from the seed, compilation, warm-up), a measured window of
``--seconds``, then the check of what the window produced against the plain
reference. ``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1``
traces the first seconds of the window and prints its per-layer metrics.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared beside its limit).
The last lines of standard error repeat the checks. Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import common  # noqa: E402


class Tracer:
    """The profiler over the window's first whole work units, at least
    ``common.TRACE_SECONDS`` long, and the work those units did."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        self.active = False
        self.counters = {"prefill": [], "decode": [], "train": []}

    def start(self) -> None:
        import jax
        jax.profiler.start_trace(self.dir)
        self.active, self.t0 = True, time.perf_counter()

    def unit_done(self, block=None, prefill=None, decode=(), train=None):
        if not self.active:
            return
        if prefill is not None:
            self.counters["prefill"].append(prefill)
        if train is not None:
            self.counters["train"].append(train)
        self.counters["decode"].extend(decode)
        if time.perf_counter() - self.t0 >= common.TRACE_SECONDS:
            if block is not None:
                import jax
                jax.block_until_ready(block)
            self.stop()

    def stop(self) -> None:
        if self.active:
            import jax
            jax.profiler.stop_trace()
            self.active = False

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


class Context:
    """What a kind's ``run`` gets: the cell's files, seed, window length,
    devices and tracer, and where it reports the parts of set-up and the
    window's start."""

    def __init__(self, cell, conf, mix, seed, seconds, devices, tracer):
        self.cell, self.conf, self.mix = cell, conf, mix
        self.seed, self.seconds = seed, seconds
        self.devices, self.tracer = devices, tracer
        self.t_window = None
        self.phases = {}
        self.mark("ready")

    def mark(self, name: str) -> None:
        """Seconds from process start to the end of a part of set-up."""
        self.phases[name] = time.perf_counter() - T0

    def window_started(self, t: float) -> None:
        self.t_window = t
        self.phases["window"] = t - T0


def reports(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def cell_metrics(bench: dict, workload: str) -> tuple[list, list]:
    """The end-to-end and per-layer metrics this cell reports."""
    e2e = [m for m in bench["end_to_end"] if reports(m, workload)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


def per_layer(layer: list, trace: dict, tracer: Tracer, conf: dict,
              peaks: dict, chips: int) -> dict:
    """Each per-layer metric's reader; one that finds nothing is left out."""
    import types
    view = types.SimpleNamespace(trace=trace, trace_dir=tracer.dir,
                                 counters=tracer.counters, conf=conf,
                                 peaks=peaks, chips=chips)
    out = {}
    for m in layer:
        value = common.load_module("metrics", m["name"]).read(view)
        if value is not None:
            out[m["name"]] = common.metric(value, m["unit"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = common.manifest()
        cell, _, mix, _ = common.cell_files(bench, args.workload)
        common.load_module("kinds", mix["kind"])
        if not os.path.isdir(os.path.join(common.SRC, "repro")):
            raise common.BenchError(f"no program sources at {common.SRC}")
        common.use_program()
        common.enable_compile_cache()
        devices = common.check_devices(cell["chips"])
        peaks = common.peaks(devices[0].device_kind)
    except common.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      args.trace, devices, peaks)
    print("setup " + " ".join(f"{k} {v:.3f}" for k, v in
                              result.pop("phases").items()), file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr, flush=True)
    print(common.result_line(**result), flush=True)
    return 0


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: int, devices: list, peaks: dict) -> dict:
    """Everything after the look for a chip: set-up, window, check.
    Returns the fields of the result line."""
    cell, conf, mix, limits = common.cell_files(bench, workload)
    kind = common.load_module("kinds", mix["kind"])
    tracer = Tracer() if trace else None
    try:
        ctx = Context(cell, conf, mix, seed, seconds, devices, tracer)
        out = kind.run(ctx)
        e2e, layer = cell_metrics(bench, workload)
        breakdown, device = None, out["device"]
        if tracer is None:
            values = dict(out["end_to_end"], setup_s=ctx.t_window - T0)
            metrics = {m["name"]: common.metric(values[m["name"]], m["unit"])
                       for m in e2e}
        else:
            reduced = common.load_module("", "trace").reduce_dir(
                tracer.dir, len(devices))
            metrics = per_layer(layer, reduced, tracer, conf, peaks,
                                len(devices))
            device = dict(device, busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
            breakdown = {"device_ops": reduced["top_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
    finally:
        if tracer is not None:
            tracer.close()
    ok, checks = common.judge(out["readings"], limits)
    return {"correct": ok and out["attempted"] > 0 and out["failed"] == 0,
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": metrics, "device": device, "checks": checks,
            "breakdown": breakdown, "phases": ctx.phases}


if __name__ == "__main__":
    sys.exit(main())
