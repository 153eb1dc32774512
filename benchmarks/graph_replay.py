"""Graph replay vs eager launches: the cudaGraphLaunch amortization.

Builds a 10-kernel pipeline (a chain of fused-multiply-add steps, each
reading the previous step's output) and times one pass through it two
ways on the **loop** backend:

* **eager** - 10 warm stream launches (each a cache-hit dispatch, but
  still 10 separate JAX dispatches with packing/hazard bookkeeping);
* **graph** - the same pipeline captured once via
  ``stream.begin_capture()``, instantiated, and replayed as a *single*
  jitted dispatch (``GraphExec.launch``).

Also reports the capture/instantiate cost and the graph's topological
structure.  ``--smoke`` shrinks the iteration count for CI; ``--json``
dumps results; ``--check`` asserts graph replay beats 10 eager launches
(the acceptance bar for the graph subsystem).
"""
from __future__ import annotations

import argparse
import json
import time

import jax.numpy as jnp
import numpy as np

from repro.core import Stream, api, compile_cache
from repro.core.kernel import KernelDef

N_STEPS = 10
ITERS = 30
OOB = 1 << 30


def make_step(n: int, src: str, dst: str) -> KernelDef:
    """dst = 0.999 * src + 0.001 (elementwise), CUDA-style SPMD."""

    def stage(ctx, st):
        gid = ctx.bid * ctx.block_dim + ctx.tid
        val = st.glob[src][jnp.minimum(gid, n - 1)] * 0.999 + 0.001
        idx = jnp.where(gid < n, gid, OOB)
        return st.set_glob(**{dst: st.glob[dst].at[idx].set(val,
                                                            mode="drop")})

    return KernelDef(f"step_{src}_to_{dst}", (stage,), writes=(dst,),
                     reads=(src, dst), est_block_work=3e2)


def build_pipeline(n: int):
    """N_STEPS chained kernels over a ring of buffers b0 -> b1 -> ..."""
    kernels = [make_step(n, f"b{i}", f"b{i+1}") for i in range(N_STEPS)]
    bufs = {f"b{i}": jnp.zeros(n, jnp.float32) for i in range(N_STEPS + 1)}
    bufs["b0"] = jnp.asarray(
        np.random.default_rng(0).standard_normal(n, dtype=np.float32))
    return kernels, bufs


def reference(x: np.ndarray) -> np.ndarray:
    for _ in range(N_STEPS):
        x = x * 0.999 + 0.001
    return x


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--json", metavar="PATH")
    ap.add_argument("--check", action="store_true",
                    help="assert graph replay beats eager launches")
    ap.add_argument("--backend", default="loop")
    args = ap.parse_args(argv)

    iters = 10 if args.smoke else ITERS
    n, block = 4096, 128
    grid = -(-n // block)
    kernels, bufs = build_pipeline(n)
    x0 = np.asarray(bufs["b0"])
    api.cache_clear()
    results = {"backend": args.backend, "n_steps": N_STEPS}

    # -- eager: warm every launch specialization, then time the pipeline ----
    s = Stream(dict(bufs))
    def eager_pass():
        for k in kernels:
            k[grid, block, None, s].on(backend=args.backend)()
    eager_pass()
    s.synchronize()
    t0 = time.perf_counter()
    for _ in range(iters):
        eager_pass()
    s.synchronize()
    eager = (time.perf_counter() - t0) / iters
    np.testing.assert_allclose(s.memcpy_d2h(f"b{N_STEPS}"), reference(x0),
                               rtol=1e-5, atol=1e-6)

    # -- graph: capture once, instantiate, replay as one dispatch -----------
    s2 = Stream(dict(bufs))
    t0 = time.perf_counter()
    g = s2.begin_capture()
    for k in kernels:
        k[grid, block, None, s2].on(backend=args.backend)()
    s2.end_capture()
    ex = g.instantiate(s2.buffers)
    capture_s = time.perf_counter() - t0
    ex.launch(s2)                      # first replay pays the XLA compile
    s2.synchronize()
    t0 = time.perf_counter()
    for _ in range(iters):
        ex.launch(s2)
    s2.synchronize()
    graph = (time.perf_counter() - t0) / iters
    np.testing.assert_allclose(s2.memcpy_d2h(f"b{N_STEPS}"), reference(x0),
                               rtol=1e-5, atol=1e-6)

    results.update({
        "eager_us_per_pass": eager * 1e6,
        "graph_us_per_pass": graph * 1e6,
        "graph_speedup": eager / graph,
        "capture_instantiate_us": capture_s * 1e6,
        "levels": len(g.levels()),
        "nodes": len(g.nodes),
    })
    print(g.summary())
    print(f"eager,{eager*1e6:.1f},us per {N_STEPS}-launch pass (warm cache)")
    print(f"graph,{graph*1e6:.1f},us per replay (single dispatch)")
    print(f"graph_speedup,{eager/graph:.2f},eager/graph "
          f"(gate: > 1x on loop backend)")
    print(f"capture_instantiate,{capture_s*1e6:.1f},us one-time")

    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2)
        print(f"json,{args.json},written")
    if args.check:
        assert eager / graph > 1.0, (
            f"graph replay of a {N_STEPS}-launch pipeline must beat "
            f"{N_STEPS} eager launches, got {eager/graph:.2f}x")
        print(f"check,passed,graph {eager/graph:.2f}x faster than eager")
    return results


if __name__ == "__main__":
    compile_cache.use_jax_cache()
    main()
