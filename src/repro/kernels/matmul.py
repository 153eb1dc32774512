"""Pallas TPU blocked matmul (MXU 128-aligned tiles, f32 VMEM accumulator).

CUDA view: one (mi, ni) output tile is one CUDA block; the k axis is the
fissioned ``__syncthreads`` loop of the classic shared-memory GEMM
(cuda_suite.make_matmul_tiled is the same kernel under the loop lowering);
the accumulator scratch is the demoted register file.  ``grain`` folds
multiple m-tiles into one grid step (coarse-grained fetching).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.pallas_emit import resolve_interpret


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, nk):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "grain",
                                             "interpret"))
def matmul(a, b, *, bm=128, bn=128, bk=128, grain=1, interpret=None):
    """a: [M, K] @ b: [K, N] -> [M, N]."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    bm = min(bm * grain, M)          # grain folds m-tiles per grid step
    bn, bk = min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    nk = K // bk
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(a, b)
