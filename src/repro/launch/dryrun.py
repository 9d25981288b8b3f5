import os
os.environ["JAX_PLATFORMS"] = "cpu"     # CPU dry-run tool, never the chip
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The lines above MUST run before any other import (jax locks its platform
and device count at first init). 512 placeholder host devices let
``make_production_mesh`` build the production meshes: 16x16 (one v5e
pod) and 2x16x16 (two pods).

For every runnable cell this driver:
  1. builds the model + sharding rules,
  2. lowers the right program (train_step / prefill / serve_step),
  3. ``.compile()``s it — sharding mismatches, unsupported collectives and
     shape errors surface here, exactly what the dry-run must prove out,
  4. records memory_analysis / cost_analysis / parsed collective bytes to
     ``artifacts/dryrun/<mesh>/<arch>/<shape>.json`` for §Roofline.

Usage:
  python -m repro.launch.dryrun --arch smollm-360m --shape train_4k --mesh single
  python -m repro.launch.dryrun --mesh both          # the full 40-cell matrix
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax

from repro.config import ParallelConfig, TrainConfig, get_arch
from repro.configs import ASSIGNED
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import SHAPES, ShapeSpec, cell_plan
from repro.models import Model
from repro.serve import compile_prefill, compile_serve_step
from repro.train.train_step import compile_train_step
from repro.utils import human_bytes, logger


def default_parallel(arch: str, mesh) -> ParallelConfig:
    multi_pod = "pod" in mesh.axis_names
    return ParallelConfig(
        zero="zero3_hier" if multi_pod else "zero3",
        shard_model_axes=True, sequence_parallel=True, expert_parallel=True,
        remat="dots", scan_layers=True, moe_impl="gshard")


def lower_cell(arch: str, shape: ShapeSpec, mesh,
               parallel: ParallelConfig | None = None,
               tcfg: TrainConfig | None = None,
               cfg_override=None):
    cfg = cfg_override if cfg_override is not None else get_arch(arch)
    parallel = parallel or default_parallel(arch, mesh)
    model = Model(cfg, parallel, rules=None)
    # rules bound inside train/serve compile via make_rules(mesh, parallel)
    from repro.sharding import make_rules
    model.rules = make_rules(mesh, parallel)
    if shape.kind == "train":
        tcfg = tcfg or TrainConfig(global_batch=shape.global_batch,
                                   seq_len=shape.seq_len)
        lowered, *_ = compile_train_step(model, tcfg, mesh, parallel,
                                         batch_size=shape.global_batch,
                                         seq_len=shape.seq_len,
                                         lower_only=True)
        return lowered
    if shape.kind == "prefill":
        return compile_prefill(model, mesh, parallel,
                               batch=shape.global_batch,
                               seq_len=shape.seq_len)
    return compile_serve_step(model, mesh, parallel,
                              batch=shape.global_batch,
                              kv_len=shape.seq_len)


def _calibrated_costs(arch: str, shape: ShapeSpec, mesh) -> dict:
    """True per-device totals via unrolled layer-delta extrapolation
    (cost_analysis counts scan bodies once — see launch/calibrate.py)."""
    from repro.launch.calibrate import depth_variants, extrapolate
    dv = depth_variants(get_arch(arch))
    par = dataclasses.replace(default_parallel(arch, mesh),
                              scan_layers=False)
    recs = []
    keep = ("flops", "bytes_accessed", "transcendentals")
    for c in (dv.cfg_n1, dv.cfg_n2):
        lowered = lower_cell(arch, shape, mesh, parallel=par, cfg_override=c)
        a = analyze(lowered.compile())
        flat = {k: v for k, v in a["cost"].items() if k in keep}
        for op, b in a["collectives"]["bytes_by_op"].items():
            flat[f"coll_{op}"] = b
        flat["coll_total"] = a["collectives"]["total_bytes_per_device"]
        recs.append(flat)
    out = extrapolate(recs[0], recs[1], dv.k)
    out["calib_k"] = dv.k
    out["calib_n"] = (dv.cfg_n1.num_layers, dv.cfg_n2.num_layers)
    return out


def run_cell(arch: str, shape: ShapeSpec, mesh_name: str, mesh,
             out_dir: str, calibrate: bool = False) -> dict:
    rec: dict = {"arch": arch, "shape": shape.name, "mesh": mesh_name,
                 "kind": shape.kind, "seq_len": shape.seq_len,
                 "global_batch": shape.global_batch,
                 "n_devices": mesh.devices.size}
    t0 = time.time()
    try:
        lowered = lower_cell(arch, shape, mesh)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)
        rec.update(analyze(compiled))
        if calibrate:
            t2 = time.time()
            rec["calibrated"] = _calibrated_costs(arch, shape, mesh)
            rec["calibrate_s"] = round(time.time() - t2, 2)
        rec["status"] = "ok"
        mem = rec.get("memory", {})
        per_dev = (mem.get("argument_size_in_bytes", 0)
                   + mem.get("temp_size_in_bytes", 0))
        logger.info("%-24s %-12s %-7s ok  lower %5.1fs compile %6.1fs "
                    "args+temp/dev %s  flops/dev %.3e  coll/dev %s",
                    arch, shape.name, mesh_name, rec["lower_s"],
                    rec["compile_s"], human_bytes(per_dev),
                    rec.get("cost", {}).get("flops", float("nan")),
                    human_bytes(rec["collectives"]["total_bytes_per_device"]))
    except Exception as e:  # noqa: BLE001 — a failed cell is a result
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        logger.error("%-24s %-12s %-7s FAILED: %s", arch, shape.name,
                     mesh_name, rec["error"])
    path = os.path.join(out_dir, mesh_name, arch)
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, f"{shape.name}.json"), "w") as f:
        json.dump(rec, f, indent=1, default=str)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--calibrate", action="store_true",
                    help="also compile unrolled depth variants for true "
                         "per-device cost totals (single-pod roofline)")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ASSIGNED)
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi", make_production_mesh(multi_pod=True)))

    results = []
    for mesh_name, mesh in meshes:
        for arch in archs:
            cfg = get_arch(arch)
            for shape, verdict in cell_plan(arch, cfg):
                if args.shape and shape.name != args.shape:
                    continue
                if verdict != "run":
                    results.append({"arch": arch, "shape": shape.name,
                                    "mesh": mesh_name, "status": verdict})
                    logger.info("%-24s %-12s %-7s %s", arch, shape.name,
                                mesh_name, verdict)
                    continue
                results.append(run_cell(arch, shape, mesh_name, mesh,
                                        args.out,
                                        calibrate=args.calibrate))
    ok = sum(1 for r in results if r["status"] == "ok")
    skip = sum(1 for r in results if r["status"].startswith("skip"))
    err = sum(1 for r in results if r["status"] == "error")
    logger.info("dry-run done: %d ok, %d skipped, %d failed", ok, skip, err)
    if err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
