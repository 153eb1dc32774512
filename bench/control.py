"""The control of the correctness check: the plain reference computed in
bfloat16, the precision below the configurations' float32, put in the
program's place.  It has to come out as not correct.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...]

For each seed it draws the cell's input pool exactly as a run does, puts
the bfloat16 reference's answers where the program's would be, and prints
the numbers the run compares with their limits (one JSON line per seed).
It needs no chip; the benchmark's own runs never run it.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def control(bench, cell: str, seed: int) -> dict:
    import ml_dtypes
    import numpy as np

    from bench.lib.harness import compare
    spec = bench.cell(cell)
    cfg = bench.config(spec["config"])
    traffic = bench.traffic(spec["traffic"])
    rng = np.random.default_rng(seed)
    pool = [cfg.module.inputs(cfg.params, rng)
            for _ in range(int(traffic["pool"]))]
    outputs = [(k, cfg.module.reference(cfg.params, inp,
                                        dtype=ml_dtypes.bfloat16))
               for k, inp in enumerate(pool)]
    worst, bad = compare(cfg, pool, outputs)
    return {"workload": cell, "seed": seed, "correct": bad == 0,
            "max_rel_err": worst, "limit": cfg.limit}


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    from bench.lib.registry import Bench
    b = Bench()
    for s in args.seeds:
        print(json.dumps(control(b, args.workload, s)), flush=True)
