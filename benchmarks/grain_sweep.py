"""Paper Table V analog: execution time vs grain size (blocks per fetch).

Two regimes from the paper:
  * short-block kernels (BS/FIR, ~79-260k inst): aggressive grains win -
    fetch overhead dominates;
  * heavy kernels (GA/AES, >=9M inst): average/fine grains win - utilization
    dominates.

On the CPU backend the "fetch overhead" is the per-fetch loop/dispatch
machinery; the schedule-derived columns (fetches, idle workers) come from
``grain.schedule_trace`` exactly as Fig. 6 draws them.  The heuristic column
shows what ``grain='aggressive'`` would pick.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import time_call
from repro.core import compile_cache
from repro.core import grain as grain_mod
from repro.core.cuda_suite import make_histogram, make_vecadd

POOL = 8
GRAINS = (1, 2, 4, 8, 16, 24, 32)


def bench_kernel(name, kernel, grid, block, args):
    print(f"# {name}: est_block_work={kernel.est_block_work:.0f}")
    times = {}
    cfg = kernel[grid, block]
    for g in GRAINS:
        fn = lambda g=g: cfg.on(grain=g)(args)
        tr = grain_mod.schedule_trace(grid, POOL, g)
        t = time_call(fn, warmup=1, iters=5) * 1e6
        times[g] = t
        print(f"{name}_grain{g},{t:.0f},fetches={tr.n_fetches}"
              f";idle={tr.idle_workers};util={tr.utilization:.2f}")
    best = min(times, key=times.get)
    heur = grain_mod.heuristic_grain(grid, POOL, kernel.est_block_work)
    print(f"{name}_best,{times[best]:.0f},best_grain={best};heuristic={heur}")
    return best, heur


def main():
    rng = np.random.default_rng(0)
    # short-block kernel (BS/FIR regime): tiny per-block work, many blocks
    n = 1 << 15
    block = 32
    vec = make_vecadd(n)
    args = {"a": jnp.asarray(rng.standard_normal(n, dtype=np.float32)),
            "b": jnp.asarray(rng.standard_normal(n, dtype=np.float32)),
            "c": jnp.zeros(n, jnp.float32)}
    bench_kernel("short_vecadd", vec, -(-n // block), block, args)

    # heavy kernel (GA/AES regime): big per-block work
    hn, nbins, hblock, hgrid = 1 << 18, 256, 128, 64
    hist = make_histogram(hn, nbins, hgrid * hblock)
    hargs = {"x": jnp.asarray(rng.integers(0, nbins, hn).astype(np.int32)),
             "hist": jnp.zeros(nbins, jnp.int32)}
    bench_kernel("heavy_hist", hist, hgrid, hblock, hargs)


if __name__ == "__main__":
    compile_cache.use_jax_cache()
    main()
