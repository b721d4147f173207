import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""§Perf hillclimb driver: run one (arch x shape x variant) cell and print
the roofline delta vs the stored baseline.

    python -m repro.launch.perf --arch olmo-1b --shape train_4k \
        --variant remat_none [--out results/perf]

Variants are implemented in repro.launch.steps.VARIANTS; the baseline JSON
is read from results/dryrun (run the sweep first).
"""
import argparse
import json
import pathlib
import sys
import time

import jax

from repro.configs import REGISTRY, get_config
from repro.configs.base import SHAPES
from repro.distributed.hlo_loop_analysis import analyze_hlo
from repro.distributed.roofline import TPU_V5E, roofline
from repro.distributed.hlo_analysis import CollectiveStats
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import VARIANTS, build_jitted_step


def run_variant(arch: str, shape_name: str, variant: str,
                multi_pod: bool = False) -> dict:
    cfg = get_config(arch)
    spec = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    bundle = build_jitted_step(cfg, spec, mesh, variant=variant)
    with jax.set_mesh(mesh):
        compiled = bundle.step.lower(*bundle.example_args).compile()
    mem = compiled.memory_analysis()
    la = analyze_hlo(compiled.as_text())
    peak = None
    if mem is not None:
        peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + max(mem.output_size_in_bytes - mem.alias_size_in_bytes, 0))
    coll = CollectiveStats(
        ops={k: int(v) for k, v in la.collective_ops.items()},
        operand_bytes={},
        wire_bytes={"total": la.collective_wire_bytes})
    rl = roofline(arch, shape_name, "pod16x16", mesh.devices.size,
                  {"flops": la.flops, "bytes accessed": la.bytes_accessed},
                  coll, cfg, spec, TPU_V5E, peak_memory=peak)
    return {"arch": arch, "shape": shape_name, "variant": variant,
            "ok": True, "compile_s": round(time.time() - t0, 1),
            "peak_bytes_per_device": peak, "roofline": rl.as_dict()}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(REGISTRY))
    ap.add_argument("--shape", required=True, choices=sorted(SHAPES))
    ap.add_argument("--variant", required=True, choices=VARIANTS)
    ap.add_argument("--out", default="results/perf")
    ap.add_argument("--baseline-dir", default="results/dryrun")
    args = ap.parse_args()

    rec = run_variant(args.arch, args.shape, args.variant)
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    tag = f"{args.arch}__{args.shape}__{args.variant}"
    (out / f"{tag}.json").write_text(json.dumps(rec, indent=1))

    base_path = (pathlib.Path(args.baseline_dir)
                 / f"{args.arch}__{args.shape}__single.json")
    rl = rec["roofline"]
    line = (f"{tag}: peak {rec['peak_bytes_per_device']/1e9:.2f} GB | "
            f"comp {rl['t_compute']:.4g}s mem {rl['t_memory']:.4g}s "
            f"coll {rl['t_collective']:.4g}s -> {rl['dominant']}")
    if base_path.exists():
        b = json.loads(base_path.read_text())["roofline"]
        for term in ("t_compute", "t_memory", "t_collective"):
            delta = (rl[term] - b[term]) / max(b[term], 1e-12) * 100
            line += f" | {term[2:]} {delta:+.1f}%"
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
