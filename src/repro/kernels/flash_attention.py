"""Pallas TPU flash attention (causal, GQA) with CuPBoP grain-fetched grid.

CuPBoP mapping (DESIGN.md S2/S3):
* one CUDA block == one (batch, head, q-tile); the Pallas grid is the task
  queue, and ``dimension_semantics`` marks b/h/q tiles parallel ("threads of
  the pool") while the kv axis is 'arbitrary' (sequential on-core - the
  fissioned barrier loop);
* the online-softmax running (m, l, acc) are the thread-block's registers,
  demoted to VMEM scratch across kv steps exactly like registers crossing a
  ``__syncthreads`` are demoted in the loop lowering;
* GQA is expressed through the k/v BlockSpec ``index_map`` (kv head =
  q_head // group) - no materialized repeat;
* shared memory == VMEM tiles selected by BlockSpec.

Tiles default to MXU-aligned (128) and are clamped to the problem size.
Validated against ``ref.flash_attention_ref`` in interpret mode (CPU);
compiles for TPU via Mosaic unchanged.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.pallas_emit import resolve_interpret

NEG_INF = -1e30


def _kernel(qi_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            causal, q_blk, kv_blk, nk, scale):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qi = pl.program_id(2)
    q_start = qi * q_blk
    k_start = ki * kv_blk
    run = True
    if causal:
        # whole kv tile strictly above the diagonal: nothing to do
        run = k_start <= q_start + q_blk - 1

    @pl.when(run)
    def _compute():
        q = qi_ref[0, 0].astype(jnp.float32)           # [q_blk, d]
        k = k_ref[0, 0].astype(jnp.float32)            # [kv_blk, d]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (q_blk, kv_blk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (q_blk, kv_blk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "q_blk", "kv_blk",
                                             "interpret"))
def flash_attention(q, k, v, *, causal=True, q_blk=128, kv_blk=128,
                    interpret=None):
    """q: [B, H, Sq, d]; k/v: [B, Hkv, Skv, d] with H % Hkv == 0."""
    B, H, Sq, d = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    g = H // Hkv
    q_blk = min(q_blk, Sq)
    kv_blk = min(kv_blk, Skv)
    assert Sq % q_blk == 0 and Skv % kv_blk == 0
    nq, nk = Sq // q_blk, Skv // kv_blk
    scale = 1.0 / math.sqrt(d)

    grid = (B, H, nq, nk)
    kernel = functools.partial(_kernel, causal=causal, q_blk=q_blk,
                               kv_blk=kv_blk, nk=nk, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q_blk, d), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, kv_blk, d),
                         lambda b, h, qi, ki: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, kv_blk, d),
                         lambda b, h, qi, ki: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_blk, d),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_blk,), jnp.float32),         # running max
            pltpu.VMEM((q_blk,), jnp.float32),         # running denom
            pltpu.VMEM((q_blk, d), jnp.float32),       # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
