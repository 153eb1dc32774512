"""Paper Table IV analog: end-to-end suite wall time per lowering (CPU
backend = the paper's non-NVIDIA device).

Columns: loop (paper-faithful CuPBoP), vector (TPU-style vectorized MPMD -
the optimization SVI-C says CPUs are missing).  The vector/loop speedup is
this machine's analogue of the DPC++-vectorization wins on EP/KMeans.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import time_call
from repro.core import cache_clear, compile_cache
from repro.core.cuda_suite import build_suite, run_entry


def main(scale: int = 4):
    suite = build_suite(scale=scale)
    cache_clear()      # benchmark isolation: no precompiled launches
    print("kernel,loop_us,vector_us,speedup")
    geo = []
    for e in suite:
        args = e.make_args(np.random.default_rng(0))
        ts = {}
        for backend in ("loop", "vector"):
            # chain entries time their whole LaunchChain: that IS the
            # workload's end-to-end wall time (launch overheads included).
            # with_reference=False keeps the pure-Python oracle out of the
            # timed region
            fn = lambda e=e, backend=backend: run_entry(
                e, backend, args=args, with_reference=False)
            ts[backend] = time_call(fn, warmup=1, iters=3) * 1e6
        sp = ts["loop"] / ts["vector"]
        geo.append(sp)
        print(f"{e.name},{ts['loop']:.0f},{ts['vector']:.0f},{sp:.2f}")
    gm = float(np.exp(np.mean(np.log(geo))))
    print(f"geomean_speedup,{gm:.2f},vector over loop")


if __name__ == "__main__":
    compile_cache.use_jax_cache()
    main()
