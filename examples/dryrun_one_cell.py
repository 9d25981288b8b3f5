"""Lower + compile one (arch x shape) cell on the production 16x16 mesh and
print its memory/cost/collective profile — the per-cell core of the
multi-pod dry-run, runnable standalone.

  PYTHONPATH=src python examples/dryrun_one_cell.py [arch] [shape]
"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"     # CPU dry-run tool, never the chip
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

import sys


def main() -> None:
    from repro.launch.dryrun import lower_cell
    from repro.launch.hlo_analysis import analyze
    from repro.launch.mesh import make_production_mesh
    from repro.launch.shapes import SHAPES
    from repro.utils import human_bytes

    arch = sys.argv[1] if len(sys.argv) > 1 else "smollm-360m"
    shape = SHAPES[sys.argv[2] if len(sys.argv) > 2 else "train_4k"]
    mesh = make_production_mesh()
    print(f"lowering {arch} x {shape.name} on mesh {dict(mesh.shape)} ...")
    lowered = lower_cell(arch, shape, mesh)
    compiled = lowered.compile()
    a = analyze(compiled)
    mem, cost, coll = a["memory"], a["cost"], a["collectives"]
    print(f"  args/device : {human_bytes(mem.get('argument_size_in_bytes', 0))}")
    print(f"  temp/device : {human_bytes(mem.get('temp_size_in_bytes', 0))}")
    print(f"  HLO flops   : {cost.get('flops', float('nan')):.3e} "
          f"(scan bodies counted once; see launch/calibrate.py)")
    print(f"  collectives : {coll['counts']}")
    print(f"  coll bytes  : {human_bytes(coll['total_bytes_per_device'])}/device")


if __name__ == "__main__":
    main()
