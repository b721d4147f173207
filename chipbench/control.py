"""The control of a cell's correctness check: the plain reference at the
precision below the configuration's (4 bits for its 8), put in the
program's place, read by the same comparison that decides ``correct``.

    python chipbench/control.py --workload <name> --images <n> --seeds <s> [<s> ...]

For each seed it makes the cell's weights on the device and the first
``n`` images of the window's stream, as a run does, and prints one JSON
line with the numbers compared.  Each must read above its limit; the
limits in the configuration files were set between these readings and
those of the program's own runs (``PERF.md``).  The benchmark's runs do
not run it.
"""
from __future__ import annotations

import argparse
import itertools
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:] = [p for p in sys.path if pathlib.Path(p or ".").resolve() != HERE]
sys.path.insert(0, str(HERE.parent))

from chipbench.run import (ROOT, cell_parts, compare, family,  # noqa: E402
                           load_bench)


def readings(workload: str, seed: int, images: int, bits: int = 4) -> dict:
    import jax
    import numpy as np

    _, config, _, _, _ = cell_parts(load_bench(), workload)
    fam = family(config)
    params = jax.tree.map(np.asarray, fam.make_params(config, seed))
    answers = [(x, fam.reference_logits(config, params, x, bits=bits))
               for x in itertools.islice(fam.image_stream(config, seed, 1),
                                         images)]
    return compare(fam, config, params, answers)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--images", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    limits = cell_parts(load_bench(), args.workload)[1]["limits"]
    for seed in args.seeds:
        nums = readings(args.workload, seed, args.images)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "device": jax.devices()[0].device_kind,
                          "control": nums, "limits": limits}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
