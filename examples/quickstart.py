"""Quickstart: author a CUDA-style SPMD kernel, launch it like CUDA.

This is the paper's Listing 1/3/4 experience end-to-end with the
CUDA-faithful API surface:

* triple-chevron launches - ``kernel[grid, block](**buffers)`` mirrors
  ``kernel<<<grid, block>>>(...)``, including the optional dyn-shared and
  stream slots;
* ``dim3`` geometry - grids/blocks are ints or up-to-3-tuples, and kernels
  read ``ctx.bid3``/``ctx.tid3`` exactly like ``blockIdx``/``threadIdx``;
* a multi-stream runtime with events (``cudaEventRecord`` /
  ``cudaStreamWaitEvent``) and implicit-barrier hazard tracking.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax.numpy as jnp
import numpy as np

from repro.core import Policy, Runtime, Stream, UnsupportedKernel, backend_names
from repro.core.cuda_suite import (
    make_reverse,
    make_stencil2d,
    make_vecadd,
)

n, block = 1024, 128
grid = -(-n // block)

# --- Listing 1: vecAdd<<<grid, block>>>(a, b, c) ----------------------------
vecadd = make_vecadd(n)
a = np.random.default_rng(0).standard_normal(n, dtype=np.float32)
b = np.random.default_rng(1).standard_normal(n, dtype=np.float32)
for backend in ("loop", "vector", "pallas"):
    try:
        out = vecadd[grid, block].on(backend=backend, grain="aggressive",
                                     pool=4)(
            a=jnp.asarray(a), b=jnp.asarray(b), c=jnp.zeros(n, jnp.float32))
    except UnsupportedKernel as e:     # e.g. Mosaic's refusal on a TPU
        print(f"vecadd[{backend:6s}] unsupported: {e}")
        continue
    ok = np.allclose(np.asarray(out["c"]), a + b)
    print(f"vecadd[{backend:6s}] correct={ok}")
print("registered backends:", backend_names())

# --- dim3: hotspot-style 2-D stencil<<<dim3(gx,gy), dim3(8,8)>>> ------------
h, w = 32, 64
stencil = make_stencil2d(h, w)
x = np.random.default_rng(2).standard_normal((h, w), dtype=np.float32)
out = stencil[(w // 8, h // 8), (8, 8)](
    x=jnp.asarray(x), y=jnp.zeros((h, w), jnp.float32))
p = np.pad(x, 1, mode="edge")
want = 0.2 * (p[1:-1, 1:-1] + p[:-2, 1:-1] + p[2:, 1:-1]
              + p[1:-1, :-2] + p[1:-1, 2:])
print("stencil2d (2-D grid x 2-D block) correct =",
      np.allclose(np.asarray(out["y"]), want, atol=1e-5))

# --- Listing 3: dynamicReverse<<<1, 256, 256*4>>> ---------------------------
rev = make_reverse()
d = np.arange(256, dtype=np.int32)
out = rev[1, 256, 256](d=jnp.asarray(d))   # third slot = dynamic shared
print("dynamicReverse correct =", np.array_equal(np.asarray(out["d"]),
                                                 d[::-1]))

# --- Listing 4: async launches + implicit barrier insertion -----------------
for policy in (Policy.HAZARD_ONLY, Policy.SYNC_ALWAYS):
    s = Stream({"a": jnp.asarray(a), "b": jnp.asarray(b),
                "c": jnp.zeros(n, jnp.float32)}, policy=policy)
    for _ in range(10):
        vecadd[grid, block, None, s]()     # fourth slot = stream
    _ = s.memcpy_d2h("c")      # the RAW hazard: only this must sync
    print(f"stream[{policy.value:12s}] launches=10 "
          f"syncs={s.stats.syncs} (CuPBoP syncs once, HIP-CPU every launch)")

# --- multi-stream pipeline with events --------------------------------------
rt = Runtime({"a": jnp.asarray(a), "b": jnp.asarray(b),
              "c": jnp.zeros(n, jnp.float32)})
compute, copy = rt.stream("compute"), rt.stream("copy")
vecadd[grid, block, None, compute]()
done = rt.event("vecadd_done")
done.record(compute)                       # cudaEventRecord
copy.wait_event(done)                      # cudaStreamWaitEvent
host_c = copy.memcpy_d2h("c")
print("two-stream pipeline correct =", np.allclose(host_c, a + b),
      f"(stats: {rt.stats})")
