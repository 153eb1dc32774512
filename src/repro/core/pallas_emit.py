"""Emit a CuPBoP-JAX kernel as a ``pl.pallas_call`` (TPU target).

Mapping:

* CUDA block           -> one iteration of the grain loop inside a grid step;
* task-queue fetch     -> one Pallas grid step (grid = ceil(nBlocks/grain));
* thread axis          -> VPU lanes (vector lowering semantics);
* __shared__ memory    -> functional values inside the kernel body;
* global memory        -> whole-array refs ("gather mode": every buffer is
                          one full-array BlockSpec, and the kernel gathers
                          and scatters inside it);
* written buffers      -> outputs; grid steps run in order, so
                          cross-block accumulation into the output ref is
                          the atomicAdd adaptation.

``interpret=None`` (the default everywhere) resolves from the platform:
the Pallas interpreter off the TPU, a real Mosaic compile on it.  Mosaic
accepts none of the suite kernels in gather mode today: the in-kernel
gathers/scatters of 1-D buffers are refused ("Only 2D gather is
supported"), as are ``dynamic_slice`` and several 2-D index patterns.  The
launch path (:func:`repro.core.api._compile`) compiles non-interpret
entries ahead of time and reports such refusals as
:class:`~repro.core.kernel.UnsupportedKernel`, so coverage probes show the
chip's real row.  On the TPU the ``vector`` lowering is the main path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.dim3 import Dim3
from repro.core.kernel import BlockState, Ctx, KernelDef


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` means: interpret everywhere but on a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def run(kernel: KernelDef, *, grid, block, glob, grain=1, dyn_shared=None,
        interpret: bool | None = None):
    grid, block = Dim3.of(grid), Dim3.of(block)
    n_blocks, block_size = grid.size, block.size
    names = sorted(glob.keys())
    written = [n for n in names if n in set(kernel.writes)]
    read_only = [n for n in names if n not in set(kernel.writes)]
    n_steps = -(-n_blocks // grain)

    def body(*refs):
        in_refs = dict(zip(read_only + written, refs[: len(names)],
                           strict=True))
        out_refs = dict(zip(written, refs[len(names):], strict=True))
        step = pl.program_id(0)

        # first grid step: seed the output buffers from their inputs
        @pl.when(step == 0)
        def _seed():
            for n in written:
                out_refs[n][...] = in_refs[n][...]

        g = {}
        for n in read_only:
            g[n] = in_refs[n][...]
        for n in written:
            g[n] = out_refs[n][...]

        shared0 = kernel.init_shared(dyn_shared)
        ctx_tid = jnp.arange(block_size, dtype=jnp.int32)

        def run_bid(bid, g_):
            ctx = Ctx(bid=bid, tid=ctx_tid, block_dim=block_size,
                      grid_dim=n_blocks, backend="pallas", uses_warp=True,
                      block_dim3=block, grid_dim3=grid)
            st = BlockState(priv={}, shared=shared0, glob=g_)
            for stage in kernel.stages:
                st = stage(ctx, st)
            return st.glob

        def grain_body(i, g_):
            bid = step * grain + i
            return lax.cond(bid < n_blocks, lambda x: run_bid(bid, x),
                            lambda x: x, g_)

        g = lax.fori_loop(0, grain, grain_body, g)
        for n in written:
            out_refs[n][...] = g[n]

    out_shape = [jax.ShapeDtypeStruct(glob[n].shape, glob[n].dtype)
                 for n in written]
    full_spec = lambda a: pl.BlockSpec(a.shape, lambda i: (0,) * a.ndim)
    call = pl.pallas_call(
        body,
        grid=(n_steps,),
        in_specs=[full_spec(glob[n]) for n in read_only + written],
        out_specs=[full_spec(glob[n]) for n in written],
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
    )
    outs = call(*[glob[n] for n in read_only + written])
    new_glob = dict(glob)
    for n, o in zip(written, outs, strict=True):
        new_glob[n] = o
    return new_glob
