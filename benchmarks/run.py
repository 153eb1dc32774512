"""Benchmark harness - one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only coverage,grain_sweep]

Emits ``name,us_per_call_or_value,derived`` CSV per benchmark:
  coverage        Table II   framework coverage matrix
  endtoend        Table IV   suite wall-time loop vs vector lowering
  grain_sweep     Table V    time vs blocks-per-fetch, both work regimes
  launch_overhead Fig. 11    1000 launches: hazard-only vs sync-always
  reorder         Table VI   GPU-coalesced vs CPU-contiguous access
  roofline        Fig. 9/(g) 3-term roofline per (arch x shape x mesh)
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import (coverage, endtoend, grain_sweep, graph_replay,
                        launch_overhead, reorder, roofline)
from repro.core import compile_cache

# argparse-based benchmarks get an explicit empty argv so they don't
# swallow run.py's own command line
ALL = {
    "coverage": coverage.main,
    "endtoend": endtoend.main,
    "grain_sweep": grain_sweep.main,
    "graph_replay": lambda: graph_replay.main([]),
    "launch_overhead": lambda: launch_overhead.main([]),
    "reorder": reorder.main,
    "roofline": roofline.main,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    picks = args.only.split(",") if args.only else list(ALL)
    failed = []
    for name in picks:
        print(f"\n===== {name} =====")
        t0 = time.time()
        try:
            ALL[name]()
            print(f"bench_{name}_wall,{(time.time()-t0)*1e6:.0f},ok")
        except Exception:
            failed.append(name)
            traceback.print_exc()
            print(f"bench_{name}_wall,{(time.time()-t0)*1e6:.0f},FAILED")
    if failed:
        sys.exit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    compile_cache.use_jax_cache()
    main()
