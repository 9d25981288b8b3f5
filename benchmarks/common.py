"""Benchmark plumbing: result rows, artifact output, CPU calibration, and
the parallel multi-world runner."""
from __future__ import annotations

import concurrent.futures
import dataclasses
import gc
import heapq
import json
import multiprocessing
import os
import random
import time
from typing import Any, Callable, Optional

ARTIFACTS = os.environ.get("REPRO_BENCH_DIR", "artifacts/bench")

# sequential fallback for the multi-world runner: debugging, or boxes where
# process spawn is more expensive than the parallelism buys back
SEQUENTIAL = os.environ.get("REPRO_BENCH_SEQUENTIAL") == "1"


def _run_world(entry: tuple) -> Any:
    fn, args, kwargs = entry
    return fn(*args, **kwargs)


def run_worlds(worlds: "dict[str, tuple]",
               max_workers: Optional[int] = None) -> dict[str, Any]:
    """Run independent benchmark *worlds* in parallel, one process each.

    ``worlds`` maps a name to ``(fn, args)`` or ``(fn, args, kwargs)`` where
    ``fn`` is a module-level (picklable) callable that builds its own inputs
    from deterministic seeds and returns a picklable result. Returns
    ``{name: result}``.

    The bench suites replay the same trace through several configurations
    (repair-only vs pool vs EASY worlds, baseline vs injected vs parity
    runs); those replays are independent by construction — each world
    regenerates its jobs from a fixed seed — so they can overlap instead of
    dominating CI wall time sequentially. ``events_per_calib`` probe
    worlds may run in here too: each probe interleaves its own calibration
    chunks (see :func:`calibrated_probe`), which is what makes the gated
    ratio robust to contention from sibling worlds — the same property
    that lets it survive noisy shared CI runners. Wall-clock rows, by
    contrast, should be measured *outside* any parallel phase (see
    ``bench_replay``'s headline run).

    Workers are spawned, never forked: a parent that has already touched
    JAX (the checkpoint bench does) may hold the accelerator, and only a
    fresh interpreter is sure not to inherit that. The worlds themselves
    are pure-Python replays and never touch JAX.

    Falls back to in-process sequential execution when
    ``REPRO_BENCH_SEQUENTIAL=1`` or the pool cannot be spawned; if the
    pool breaks mid-run (a worker crashed or was OOM-killed), only the
    worlds that did not complete are re-run inline, so finished results
    are kept and the crash site is visible in the output.
    """
    norm = {name: (w[0], w[1] if len(w) > 1 else (),
                   w[2] if len(w) > 2 else {})
            for name, w in worlds.items()}
    if SEQUENTIAL or len(norm) <= 1:
        return {name: _run_world(w) for name, w in norm.items()}
    workers = max_workers or min(len(norm), os.cpu_count() or 2)
    try:
        with concurrent.futures.ProcessPoolExecutor(
                workers, mp_context=multiprocessing.get_context("spawn")
        ) as pool:
            futs = {name: pool.submit(_run_world, w)
                    for name, w in norm.items()}
            out: dict[str, Any] = {}
            failed: list[str] = []
            for name, f in futs.items():
                try:
                    out[name] = f.result()
                except concurrent.futures.process.BrokenProcessPool:
                    failed.append(name)
    except OSError:
        # constrained sandbox (no fork/spawn): run the worlds inline
        return {name: _run_world(w) for name, w in norm.items()}
    if failed:
        print(f"# run_worlds: process pool broke; rerunning {failed} "
              "inline (completed worlds kept)")
        for name in failed:
            out[name] = _run_world(norm[name])
    return out


def calibration_chunk(n: int = 300_000) -> tuple[int, float]:
    """One fixed seeded heap-push/pop burst (the replay engine's inner-loop
    shape); returns ``(ops, seconds)``. Callers interleave these chunks
    with the workload they are measuring and ratio the *windowed* rates:
    throughput divided by the same-window calibration is roughly
    machine-invariant AND robust to bursty CPU contention, which is what
    lets ``check_regression`` compare a fresh CI run against baselines
    recorded on a different runner class."""
    rng = random.Random(0)
    rand = rng.random
    heappush, heappop = heapq.heappush, heapq.heappop
    h: list = []
    t0 = time.perf_counter()
    for i in range(n):
        heappush(h, (rand(), i))
        if len(h) > 512:
            heappop(h)
    return n, time.perf_counter() - t0


def calibrated_probe(workload: Callable[[], float], rounds: int = 4) -> float:
    """The CI-gate measurement methodology, shared by every
    ``events_per_calib`` metric: run ``workload`` (returns its event/op
    count) ``rounds`` times interleaved with calibration chunks, GC paused
    across the window, and ratio the *windowed* rates — workload events/s
    over same-window calibration ops/s — so runner class and bursty CPU
    contention cancel. Keep all gated benches on this one helper: gates are
    only comparable if their sensitivity to noise is identical."""
    c_ops = c_sec = w_ev = w_sec = 0.0
    gc.collect()
    gc.disable()
    try:
        for _ in range(rounds):
            ops, sec = calibration_chunk()
            c_ops += ops
            c_sec += sec
            t0 = time.perf_counter()
            w_ev += workload()
            w_sec += time.perf_counter() - t0
    finally:
        gc.enable()
    return (w_ev / max(w_sec, 1e-9)) / (c_ops / max(c_sec, 1e-9))


# replint verdict rows stamped into every artifact this process emits (set
# once by benchmarks.run before any bench executes; None = unstamped, e.g.
# a bench module run directly). check_regression refuses fresh artifacts
# whose stamp says the tree had non-baseline lint findings — numbers from
# a dirty tree must never become comparison baselines.
_REPLINT_STAMP: "Optional[dict]" = None


def set_replint_stamp(verdict: dict) -> None:
    global _REPLINT_STAMP
    _REPLINT_STAMP = dict(verdict)


# pallas_cost verdict rows (repro.quality.pallas_cost.verdict) stamped
# alongside the replint stamp: bench numbers recorded while a kernel
# carried RPL2xx resource findings (or while the static cost table
# disagreed with the analytic cost model) must never become baselines.
_PALLAS_COST_STAMP: "Optional[dict]" = None


def set_pallas_cost_stamp(verdict: dict) -> None:
    global _PALLAS_COST_STAMP
    _PALLAS_COST_STAMP = dict(verdict)


# dryrun-artifact provenance (launch.cost_model.dryrun_provenance) stamped
# into the benches that consume artifacts/dryrun/** — check_regression
# compares the fingerprint before comparing any of their metrics, so a
# roofline row is never judged against a baseline built from a different
# cell set (different archs, or calibrated vs raw-HLO records).
_DRYRUN_STAMP: "Optional[dict]" = None
DRYRUN_STAMPED_BENCHES = ("roofline", "moe_comm", "serve")


def set_dryrun_stamp(provenance: dict) -> None:
    global _DRYRUN_STAMP
    _DRYRUN_STAMP = dict(provenance)


@dataclasses.dataclass
class Row:
    bench: str
    metric: str
    value: float
    target: Optional[str] = None       # the paper's figure/claim, as text
    unit: str = ""
    ok: Optional[bool] = None          # within-band verdict when checkable

    def line(self) -> str:
        tgt = self.target or ""
        oks = "" if self.ok is None else ("PASS" if self.ok else "MISS")
        return (f"{self.bench},{self.metric},{self.value:.6g},{self.unit},"
                f"{tgt},{oks}")


def emit(rows: list[Row], name: str) -> None:
    os.makedirs(ARTIFACTS, exist_ok=True)
    if _REPLINT_STAMP is not None:
        rows = rows + [
            Row(name, "replint_clean",
                1.0 if _REPLINT_STAMP.get("clean") else 0.0,
                target="no non-baseline lint findings", unit="bool"),
            Row(name, "replint_findings",
                float(_REPLINT_STAMP.get("findings", 0)), unit="count"),
        ]
    if _PALLAS_COST_STAMP is not None:
        rows = rows + [
            Row(name, "pallas_cost_clean",
                1.0 if _PALLAS_COST_STAMP.get("clean") else 0.0,
                target="no RPL2xx findings + cost-model check holds",
                unit="bool"),
            Row(name, "pallas_cost_findings",
                float(_PALLAS_COST_STAMP.get("n_findings", 0)),
                unit="count"),
        ]
    if _DRYRUN_STAMP is not None and name in DRYRUN_STAMPED_BENCHES:
        # the 32-bit crc fingerprint is exactly representable as a float,
        # so it survives the Row value field and the JSON round-trip
        rows = rows + [
            Row(name, "dryrun_cells",
                float(_DRYRUN_STAMP.get("n_cells", 0)), unit="count"),
            Row(name, "dryrun_calibrated",
                float(_DRYRUN_STAMP.get("n_calibrated", 0)), unit="count"),
            Row(name, "dryrun_fingerprint",
                float(int(_DRYRUN_STAMP.get("fingerprint", "0"), 16)),
                target="cell-set identity for check_regression"),
        ]
    print(f"# --- {name} " + "-" * max(0, 60 - len(name)))
    print("bench,metric,value,unit,paper_target,verdict")
    for r in rows:
        print(r.line())
    with open(os.path.join(ARTIFACTS, f"{name}.json"), "w") as f:
        json.dump([dataclasses.asdict(r) for r in rows], f, indent=1)
