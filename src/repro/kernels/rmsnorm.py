"""Pallas TPU RMSNorm with grain-fetched row blocks.

CUDA view: one block normalizes ``grain`` rows (the paper's aggressive
coarse-grained fetching - rmsnorm is exactly the "few instructions per
block" regime of Table V where bigger grains win); threads are the 128-wide
lane axis across the feature dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.pallas_emit import resolve_interpret


def _kernel(x_ref, s_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)                  # [grain, D]
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * (1.0 + s_ref[...].astype(jnp.float32))
                  ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "grain", "interpret"))
def rmsnorm(x, scale, *, eps=1e-5, grain=8, interpret=None):
    """x: [rows, D]; scale: [D]."""
    rows, D = x.shape
    grain = min(grain, rows)
    while rows % grain:
        grain -= 1
    return pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(rows // grain,),
        in_specs=[
            pl.BlockSpec((grain, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((grain, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, D), x.dtype),
        interpret=resolve_interpret(interpret),
    )(x, scale)
