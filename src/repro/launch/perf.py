"""§Perf hillclimb driver: lower one cell under a named ParallelConfig
variant and print its calibrated roofline terms.

  PYTHONPATH=src python -m repro.launch.perf --arch gemma3-27b \
      --shape train_4k --variant fsdp2d
"""
import os
# a CPU dry-run tool: 512 placeholder host devices, never the accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

import argparse
import dataclasses
import json

from repro.config import ParallelConfig, get_arch
from repro.launch.calibrate import depth_variants, extrapolate
from repro.launch.dryrun import default_parallel, lower_cell
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.launch.roofline import HBM_BW, ICI_BW, PEAK_FLOPS, \
    model_flops_per_device
from repro.launch.shapes import SHAPES
from repro.utils import human_bytes, logger


def variant_parallel(name: str, base: ParallelConfig, cfg, mesh
                     ) -> ParallelConfig:
    M = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    if name == "baseline":
        return base
    if name == "fsdp2d":          # drop TP/SP; 2-D FSDP + full data-parallel
        return dataclasses.replace(base, shard_model_axes=False,
                                   sequence_parallel=False)
    if name == "fsdp2d_remat_full":
        return dataclasses.replace(base, shard_model_axes=False,
                                   sequence_parallel=False, remat="full")
    if name == "remat_full":
        return dataclasses.replace(base, remat="full")
    if name == "no_sp":           # TP without sequence parallelism
        return dataclasses.replace(base, sequence_parallel=False)
    if name == "ep_align":        # expert-parallel only when E % M == 0
        ep = cfg.moe.num_experts > 0 and cfg.moe.num_experts % M == 0
        return dataclasses.replace(base, expert_parallel=ep)
    if name == "ep_align_fsdp2d":
        ep = cfg.moe.num_experts > 0 and cfg.moe.num_experts % M == 0
        return dataclasses.replace(base, expert_parallel=ep,
                                   shard_model_axes=False,
                                   sequence_parallel=False)
    if name == "zero1":           # params replicated, opt sharded
        return dataclasses.replace(base, zero="zero1")
    if name == "bf16_grads":      # bf16 gradient flow + reductions
        return dataclasses.replace(base, grad_dtype="bfloat16")
    if name == "bf16_grads_mb8":
        return dataclasses.replace(base, grad_dtype="bfloat16")
    if name == "ep_bf16":         # aligned expert sharding + bf16 grads
        ep = cfg.moe.num_experts > 0 and cfg.moe.num_experts % M == 0
        return dataclasses.replace(base, expert_parallel=ep,
                                   grad_dtype="bfloat16")
    if name == "fsdp2d_bf16":     # pure-DP FSDP + bf16 grads
        return dataclasses.replace(base, shard_model_axes=False,
                                   sequence_parallel=False,
                                   grad_dtype="bfloat16")
    if name == "fsdp2d_bf16_noremat":   # + skip recompute (small models)
        return dataclasses.replace(base, shard_model_axes=False,
                                   sequence_parallel=False,
                                   grad_dtype="bfloat16", remat="none")
    raise ValueError(f"unknown variant {name!r}")


def measure(arch: str, shape_name: str, variant: str,
            ssm_overrides: dict | None = None,
            microbatches: int = 1) -> dict:
    from repro.config import TrainConfig
    shape = SHAPES[shape_name]
    if variant.endswith("_tp8"):
        # same 256 chips, deeper data parallelism: TP activation collectives
        # scale with tokens-in-flight per device, param gathers barely move
        mesh = make_mesh((32, 8), ("data", "model"))
        variant_base = variant[:-4]
    else:
        mesh = make_production_mesh()
        variant_base = variant
    cfg = get_arch(arch)
    if ssm_overrides and cfg.ssm is not None:
        cfg = dataclasses.replace(
            cfg, ssm=dataclasses.replace(cfg.ssm, **ssm_overrides))
    par = variant_parallel(variant_base, default_parallel(arch, mesh), cfg,
                           mesh)
    tcfg = TrainConfig(global_batch=shape.global_batch,
                       seq_len=shape.seq_len, microbatches=microbatches)

    # full compile: memory + proof
    lowered = lower_cell(arch, shape, mesh, parallel=par, cfg_override=cfg,
                         tcfg=tcfg)
    full = analyze(lowered.compile())

    # calibrated costs via unrolled depth variants
    dv = depth_variants(cfg)
    par_u = dataclasses.replace(par, scan_layers=False)
    keep = ("flops", "bytes_accessed")
    recs = []
    for c in (dv.cfg_n1, dv.cfg_n2):
        a = analyze(lower_cell(arch, shape, mesh, parallel=par_u,
                               cfg_override=c, tcfg=tcfg).compile())
        flat = {k: v for k, v in a["cost"].items() if k in keep}
        flat["coll_total"] = a["collectives"]["total_bytes_per_device"]
        for op, b in a["collectives"]["bytes_by_op"].items():
            flat[f"coll_{op}"] = b
        recs.append(flat)
    cal = extrapolate(recs[0], recs[1], dv.k)

    compute_s = cal["flops"] / PEAK_FLOPS
    memory_s = cal["bytes_accessed"] / HBM_BW
    coll_s = cal["coll_total"] / ICI_BW
    mf = model_flops_per_device(cfg, shape.kind, shape.seq_len,
                                shape.global_batch, mesh.devices.size)
    out = {
        "arch": arch, "shape": shape_name, "variant": variant,
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": coll_s,
        "dominant": max(("compute", compute_s), ("memory", memory_s),
                        ("collective", coll_s), key=lambda kv: kv[1])[0],
        "roofline_frac": compute_s / max(compute_s, memory_s, coll_s),
        "useful_ratio": mf / max(cal["flops"], 1.0),
        "coll_by_op_gib": {k.replace("coll_", ""): v / 2 ** 30
                           for k, v in cal.items()
                           if k.startswith("coll_") and k != "coll_total"},
        "args_gib": full["memory"].get("argument_size_in_bytes", 0) / 2 ** 30,
        "temp_gib": full["memory"].get("temp_size_in_bytes", 0) / 2 ** 30,
    }
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--ssm-chunk", type=int, default=0)
    ap.add_argument("--ssm-head-block", type=int, default=0)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--out", default="artifacts/perf")
    args = ap.parse_args()
    ov = {}
    if args.ssm_chunk:
        ov["chunk_size"] = args.ssm_chunk
    if args.ssm_head_block:
        ov["head_block"] = args.ssm_head_block
    rec = measure(args.arch, args.shape, args.variant, ov or None,
                  microbatches=args.microbatches)
    os.makedirs(args.out, exist_ok=True)
    tag = f"{args.arch}_{args.shape}_{args.variant}"
    if args.microbatches > 1:
        tag += f"_mb{args.microbatches}"
    if ov:
        tag += "_" + "_".join(f"{k}{v}" for k, v in ov.items())
    with open(os.path.join(args.out, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    logger.info("%s: compute %.3fs memory %.3fs collective %.3fs "
                "dominant=%s frac=%.3f useful=%.3f temp=%.1fGiB",
                tag, rec["compute_s"], rec["memory_s"], rec["collective_s"],
                rec["dominant"], rec["roofline_frac"], rec["useful_ratio"],
                rec["temp_gib"])
    logger.info("collectives: %s",
                {k: round(v, 2) for k, v in rec["coll_by_op_gib"].items()})


if __name__ == "__main__":
    main()
