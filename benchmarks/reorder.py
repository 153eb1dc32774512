"""Paper Table VI / Fig. 10 analog: memory-access reordering.

The HIST kernel in GPU-coalesced order (large per-thread stride, Fig. 10a)
vs CPU/lane-contiguous order (Fig. 10c).  The paper measures LLC misses
(359e9 -> 37290e9 loads without reordering); on the CPU backend the proxy is
wall time of the same kernel under the two access patterns.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import time_call
from repro.core import compile_cache
from repro.core.cuda_suite import make_histogram


def main():
    n, nbins, block, grid = 1 << 20, 256, 128, 32
    tt = grid * block
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, nbins, n).astype(np.int32))
    times = {}
    for backend in ("loop", "vector"):
        for layout in ("coalesced", "contiguous"):
            k = make_histogram(n if backend == "vector" else n // 16,
                               nbins, tt, layout=layout)
            args = {"x": x if backend == "vector" else x[: n // 16],
                    "hist": jnp.zeros(nbins, jnp.int32)}
            fn = lambda k=k, backend=backend, args=args: \
                k[grid, block].on(backend=backend)(args)
            t = time_call(fn, warmup=1, iters=3) * 1e6
            times[(backend, layout)] = t
            print(f"hist_{backend}_{layout},{t:.0f},us "
                  f"(Fig.10{'a' if layout == 'coalesced' else 'c'})")
    # paper's claim holds for the scalar thread loop; the vector lowering
    # INVERTS it - lanes want GPU-coalesced layout (TPU behaves like the GPU)
    lp = times[("loop", "coalesced")] / times[("loop", "contiguous")]
    vc = times[("vector", "contiguous")] / times[("vector", "coalesced")]
    print(f"reorder_loop_speedup,{lp:.2f},contiguous wins under scalar "
          f"threads (paper Table VI)")
    print(f"reorder_vector_speedup,{vc:.2f},coalesced wins under lane "
          f"vectorization (the TPU's vector lowering)")


if __name__ == "__main__":
    compile_cache.use_jax_cache()
    main()
