import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# ^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the production mesh ((16,16) single-pod / (2,16,16) multi-pod),
  2. assembles the jitted step with explicit in/out shardings,
  3. ``.lower(**ShapeDtypeStructs).compile()`` — no arrays are allocated,
  4. records ``memory_analysis()`` (proves it fits), ``cost_analysis()``
     (FLOPs/bytes for the roofline), and the collective schedule parsed from
     the optimized HLO,
  5. writes one JSON per cell under ``--out`` for EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""
import argparse
import json
import pathlib
import sys
import time
import traceback

import jax

from repro.configs import REGISTRY, get_config, shapes_for
from repro.configs.base import SHAPES
from repro.distributed.hlo_analysis import (CollectiveStats, collective_bytes,
                                             xla_cost_analysis)
from repro.distributed.hlo_loop_analysis import analyze_hlo
from repro.distributed.roofline import TPU_V5E, roofline
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_jitted_step


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             xla_flags_extra: str = "") -> dict:
    cfg = get_config(arch)
    spec = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    chips = mesh.devices.size

    t0 = time.time()
    bundle = build_jitted_step(cfg, spec, mesh)
    # set_mesh (not `with mesh:`) — activation sharding constraints inside
    # the model read the abstract-mesh context at trace time.
    with jax.set_mesh(mesh):
        lowered = bundle.step.lower(*bundle.example_args)
        compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost_raw = xla_cost_analysis(compiled)
    hlo = compiled.as_text()
    # loop-aware: cost_analysis() charges every while body ONE iteration;
    # analyze_hlo multiplies by known_trip_count (scan-over-layers, flash
    # tiles, microbatches, loss chunks).  Validated in tests/test_hlo_analysis.
    la = analyze_hlo(hlo)
    cost = {"flops": la.flops, "bytes accessed": la.bytes_accessed}
    coll = CollectiveStats(
        ops={k: int(v) for k, v in la.collective_ops.items()},
        operand_bytes={},
        wire_bytes={"loop_aware_total": la.collective_wire_bytes},
    )

    peak = None
    mem_d = {}
    if mem is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            mem_d[k] = getattr(mem, k, None)
        args = mem_d.get("argument_size_in_bytes") or 0
        temp = mem_d.get("temp_size_in_bytes") or 0
        alias = mem_d.get("alias_size_in_bytes") or 0
        out = mem_d.get("output_size_in_bytes") or 0
        # peak live bytes: arguments + temps + non-aliased outputs
        peak = args + temp + max(out - alias, 0)

    rl = roofline(arch, shape_name, mesh_name, chips, cost, coll, cfg, spec,
                  TPU_V5E, peak_memory=peak)

    return {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "chips": chips,
        "kind": bundle.kind,
        "compile_s": round(t_compile, 1),
        "memory_analysis": mem_d,
        "peak_bytes_per_device": peak,
        "fits_hbm": (peak is not None and peak <= TPU_V5E.hbm_bytes),
        "cost_analysis": cost,
        "cost_analysis_raw_xla": {k: cost_raw.get(k) for k in
                                  ("flops", "bytes accessed",
                                   "transcendentals") if k in cost_raw},
        "loops": la.loops,
        "collectives": coll.as_dict(),
        "roofline": rl.as_dict(),
        "sharding_fallbacks": bundle.report.fallbacks,
    }


def cells(arch_filter=None, shape_filter=None):
    for arch, cfg in REGISTRY.items():
        if arch_filter and arch != arch_filter:
            continue
        for spec in shapes_for(cfg):
            if shape_filter and spec.name != shape_filter:
                continue
            yield arch, spec.name


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=sorted(REGISTRY) + [None])
    ap.add_argument("--shape", default=None, choices=sorted(SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = 0
    for arch, shape in cells(args.arch, args.shape):
        for multi in meshes:
            tag = f"{arch}__{shape}__{'multi' if multi else 'single'}"
            path = out / f"{tag}.json"
            if args.skip_existing and path.exists():
                ok = json.loads(path.read_text()).get("ok", False)
                if ok:
                    print(f"[skip] {tag}", flush=True)
                    continue
            print(f"[cell] {tag} ...", flush=True)
            try:
                rec = run_cell(arch, shape, multi)
                rec["ok"] = True
                print(f"  ok: peak={rec['peak_bytes_per_device'] and rec['peak_bytes_per_device']/1e9:.2f} GB"
                      f" dominant={rec['roofline']['dominant']}"
                      f" compile={rec['compile_s']}s", flush=True)
            except Exception as e:  # noqa: BLE001 — record and continue
                failures += 1
                rec = {"arch": arch, "shape": shape,
                       "mesh": "multi" if multi else "single",
                       "ok": False, "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]}
                print(f"  FAIL: {type(e).__name__}: {str(e)[:200]}", flush=True)
            path.write_text(json.dumps(rec, indent=1))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
