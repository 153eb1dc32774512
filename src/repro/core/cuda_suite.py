"""CUDA-style SPMD kernel suite (the paper's Rodinia/Hetero-Mark stand-ins).

Each entry is a kernel authored in the CuPBoP-JAX IR plus a pure-numpy oracle.
The suite spans the CUDA features whose support differentiates frameworks in
the paper's Table II:

| kernel              | Rodinia counterpart     | features exercised           |
|---------------------|-------------------------|------------------------------|
| vecadd              | Listing 1               | plain SPMD                   |
| reverse             | Listing 3 dynamicReverse| dynamic __shared__, barrier  |
| histogram           | Hetero-Mark HIST        | global atomics, strided access (Fig. 10a) |
| reduce_shared       | Rodinia-style reduction | barrier tree, log2 fission   |
| reduce_warp         | Crystal q11-q13         | warp shuffle (COX nesting)   |
| matmul_tiled        | lud/gemm                | shared tiling, register demotion across many barriers |
| stencil1d           | hotspot                 | halo loads, barrier          |
| softmax_row         | attention primitive     | two barriers                 |
| scan_block          | pathfinder/scan         | Hillis-Steele, 2x log2 stages|
| transpose_tiled     | SVI-C reordering demo   | shared staging, coalescing   |
| pixel_pipeline      | srad extract/compress   | defensive barriers, thread-private shared scratch (fusable) |
| stencil2d           | hotspot                 | 2-D dim3 grid x block, halo  |
| bfs_frontier        | bfs                     | atomicCAS flags, ballot-count, __constant__, launch chain |
| pathfinder          | pathfinder              | row-wavefront DP across launches, halo barrier |
| needle_nw           | nw (Needleman-Wunsch)   | anti-diagonal wavefront across launches |
| backprop_layer      | backprop                | barrier tree + __constant__, owned-slice writes |
| lud_diag            | lud (diagonal step)     | many barriers, in-shared pivoting, owned-slice writes |
| srad_step           | srad                    | stencil + two-phase global reduction chain |
| lavamd              | lavaMD                  | neighbor-list gather into heavy __shared__, register demotion |
| nn                  | nn                      | cane record-file ingest, chained two-level top-k arg-min |
| kmeans              | kmeans                  | convergence chain, device-resident stop, irregular atomicAdd |
| streamcluster       | streamcluster           | dynamic assignment, duplicate atomicAdd + atomicCAS claims |
| hotspot             | hotspot                 | temp/power grid-file ingest, chained 2-D halo stencil |

Rows bfs_frontier through srad_step are the Rodinia-mini expansion:
wavefront kernels iterate via :class:`repro.core.kernel.LaunchChain`
(host-driven inter-launch dependencies), BFS claims nodes with
``atomicCAS`` visited flags and counts its next frontier with
``__syncthreads_count``, and the read-only inputs of bfs/backprop ride in
``__constant__`` space (:class:`repro.core.memory.ConstArray`).  The last
five rows are the coverage sprint toward the paper's 69.6% Rodinia figure:
lavaMD's neighbor-box traversal, nn/hotspot's file-driven input pipelines
(:mod:`repro.core.rodinia_io`), kmeans' iterative-convergence chain with a
device-resident stop predicate, and streamcluster's irregular
atomicAdd/CAS mix.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import memory, rodinia_io
from repro.core.api import launch
from repro.core.kernel import (ChainStats, ChainStep, KernelDef, LaunchChain,
                               expf, logf)

OOB = 1 << 30  # out-of-bounds sentinel for mode="drop" stores


def _gid(ctx):
    return ctx.bid * ctx.block_dim + ctx.tid


# --------------------------------------------------------------------------
# vecadd (paper Listing 1)
# --------------------------------------------------------------------------
def make_vecadd(n: int) -> KernelDef:
    """dtype-agnostic: output dtype follows the input arrays."""
    def stage(ctx, st):
        gid = _gid(ctx)
        val = st.glob["a"][gid] + st.glob["b"][gid]
        idx = jnp.where(gid < n, gid, OOB)
        return st.set_glob(c=st.glob["c"].at[idx].set(val, mode="drop"))

    return KernelDef("vecadd", (stage,), writes=("c",),
                     reads=("a", "b", "c"), est_block_work=3e2)


# --------------------------------------------------------------------------
# reverse (paper Listing 3: extern __shared__, one __syncthreads)
# --------------------------------------------------------------------------
def make_reverse() -> KernelDef:
    def load(ctx, st):
        s = st.shared["s"].at[ctx.tid].set(st.glob["d"][ctx.tid])
        return st.set_shared(s=s)

    def store(ctx, st):
        n = st.shared["s"].shape[0]
        d = st.glob["d"].at[ctx.tid].set(st.shared["s"][n - ctx.tid - 1])
        return st.set_glob(d=d)

    return KernelDef(
        "reverse", (load, store), writes=("d",), reads=("d",),
        shared={"s": ((-1,), jnp.int32)}, est_block_work=2e2,
    )


# --------------------------------------------------------------------------
# histogram (Hetero-Mark HIST; GPU-coalesced stride of Fig. 10a by default)
# --------------------------------------------------------------------------
def make_histogram(n: int, nbins: int, total_threads: int,
                   layout: str = "coalesced") -> KernelDef:
    iters = math.ceil(n / total_threads)

    def stage(ctx, st):
        x, hist = st.glob["x"], st.glob["hist"]
        gid = _gid(ctx)
        for k in range(iters):
            if layout == "coalesced":      # GPU-friendly large stride
                idx = gid + k * total_threads
            else:                          # CPU-friendly contiguous (Fig 10c)
                idx = gid * iters + k
            v = x[jnp.minimum(idx, n - 1)]
            bin_ = jnp.where(idx < n, v, OOB)
            hist = hist.at[bin_].add(1, mode="drop")
        return st.set_glob(hist=hist)

    return KernelDef(f"histogram_{layout}", (stage,), writes=("hist",),
                     reads=("x", "hist"), est_block_work=3e2 * iters)


# --------------------------------------------------------------------------
# reduce_shared: classic barrier-tree block reduction (log2(block) stages)
# --------------------------------------------------------------------------
def make_reduce_shared(n: int, block: int, dtype=jnp.float32) -> KernelDef:
    assert block & (block - 1) == 0, "block must be a power of two"

    def load(ctx, st):
        gid = _gid(ctx)
        v = jnp.where(gid < n, st.glob["x"][jnp.minimum(gid, n - 1)], 0.0)
        return st.set_shared(s=st.shared["s"].at[ctx.tid].set(v))

    def make_level(offset):
        def level(ctx, st):
            s = st.shared["s"]
            partner = s[ctx.tid + offset]
            new = jnp.where(ctx.tid < offset, s[ctx.tid] + partner, s[ctx.tid])
            return st.set_shared(s=s.at[ctx.tid].set(new))
        return level

    def store(ctx, st):
        idx = jnp.where(ctx.tid == 0, ctx.bid, OOB)
        out = st.glob["out"].at[idx].set(st.shared["s"][0], mode="drop")
        return st.set_glob(out=out)

    stages = [load]
    off = block // 2
    while off >= 1:
        stages.append(make_level(off))
        off //= 2
    stages.append(store)
    return KernelDef(
        "reduce_shared", tuple(stages), writes=("out",), reads=("x", "out"),
        shared={"s": ((block,), dtype)}, est_block_work=block * 8.0,
    )


# --------------------------------------------------------------------------
# reduce_warp: shuffle-based reduction (warp-level features; COX/CuPBoP only)
# --------------------------------------------------------------------------
def make_reduce_warp(n: int, block: int, dtype=jnp.float32) -> KernelDef:
    nwarps = block // 32

    def warp_phase(ctx, st):
        gid = _gid(ctx)
        val = jnp.where(gid < n, st.glob["x"][jnp.minimum(gid, n - 1)], 0.0)
        for off in (16, 8, 4, 2, 1):
            val = val + ctx.shfl_xor(val, off)
        idx = jnp.where(ctx.lane == 0, ctx.warp, OOB)
        return st.with_priv({"v": val}).set_shared(
            s=st.shared["s"].at[idx].set(val, mode="drop"))

    def final_phase(ctx, st):
        s = st.shared["s"]
        v = jnp.where(ctx.tid < nwarps, s[jnp.minimum(ctx.tid, nwarps - 1)],
                      0.0)
        for off in (16, 8, 4, 2, 1):
            v = v + ctx.shfl_xor(v, off)
        idx = jnp.where(ctx.tid == 0, ctx.bid, OOB)
        return st.with_priv({}).set_glob(
            out=st.glob["out"].at[idx].set(v, mode="drop"))

    return KernelDef(
        "reduce_warp", (warp_phase, final_phase), writes=("out",),
        reads=("x", "out"),
        shared={"s": ((nwarps,), dtype)}, uses_warp=True,
        est_block_work=block * 4.0,
    )


# --------------------------------------------------------------------------
# matmul_tiled: shared-memory tiled GEMM; acc is a register demoted across
# 2*KT barriers (the hard case for fission correctness)
# --------------------------------------------------------------------------
def make_matmul_tiled(m: int, n: int, k: int, tile: int = 8,
                      dtype=jnp.float32) -> KernelDef:
    assert m % tile == 0 and n % tile == 0 and k % tile == 0
    kt = k // tile
    ntiles_n = n // tile

    def coords(ctx):
        ty, tx = ctx.tid // tile, ctx.tid % tile
        by, bx = ctx.bid // ntiles_n, ctx.bid % ntiles_n
        return ty, tx, by * tile + ty, bx * tile + tx

    def init(ctx, st):
        return st.with_priv({"acc": jnp.zeros(ctx.tid.shape, dtype)})

    def make_load(kk):
        def load(ctx, st):
            ty, tx, row, col = coords(ctx)
            sa = st.shared["sa"].at[ty, tx].set(st.glob["a"][row, kk * tile + tx])
            sb = st.shared["sb"].at[ty, tx].set(st.glob["b"][kk * tile + ty, col])
            return st.set_shared(sa=sa, sb=sb)
        return load

    def compute(ctx, st):
        ty, tx, _, _ = coords(ctx)
        sa, sb = st.shared["sa"], st.shared["sb"]
        # HIGHEST: a CUDA f32 FMA is f32; the TPU default runs bf16 passes
        acc = st.priv["acc"] + jnp.einsum("ti,it->t", sa[ty, :], sb[:, tx],
                                          precision=lax.Precision.HIGHEST)
        return st.with_priv({"acc": acc})

    def store(ctx, st):
        _, _, row, col = coords(ctx)
        c = st.glob["c"].at[row, col].set(st.priv["acc"])
        return st.with_priv({}).set_glob(c=c)

    stages = [init]
    for kk in range(kt):
        stages += [make_load(kk), compute]
    stages.append(store)
    return KernelDef(
        "matmul_tiled", tuple(stages), writes=("c",), reads=("a", "b", "c"),
        shared={"sa": ((tile, tile), dtype),
                "sb": ((tile, tile), dtype)},
        est_block_work=tile * tile * k * 2.0,
    )


# --------------------------------------------------------------------------
# stencil1d (hotspot-like 3-point stencil with shared halo)
# --------------------------------------------------------------------------
def make_stencil1d(n: int, block: int, dtype=jnp.float32) -> KernelDef:
    def load(ctx, st):
        gid = _gid(ctx)
        x = st.glob["x"]
        s = st.shared["s"].at[ctx.tid + 1].set(x[jnp.clip(gid, 0, n - 1)])
        left = x[jnp.clip(gid - 1, 0, n - 1)]
        right = x[jnp.clip(gid + 1, 0, n - 1)]
        s = s.at[jnp.where(ctx.tid == 0, 0, OOB)].set(left, mode="drop")
        s = s.at[jnp.where(ctx.tid == block - 1, block + 1, OOB)].set(
            right, mode="drop")
        return st.set_shared(s=s)

    def compute(ctx, st):
        gid = _gid(ctx)
        s = st.shared["s"]
        val = 0.25 * s[ctx.tid] + 0.5 * s[ctx.tid + 1] + 0.25 * s[ctx.tid + 2]
        idx = jnp.where(gid < n, gid, OOB)
        return st.set_glob(y=st.glob["y"].at[idx].set(val, mode="drop"))

    return KernelDef(
        "stencil1d", (load, compute), writes=("y",), reads=("x", "y"),
        shared={"s": ((block + 2,), dtype)}, est_block_work=block * 6.0,
    )


# --------------------------------------------------------------------------
# stencil2d (hotspot-style 5-point stencil; 2-D grid x 2-D block via dim3)
# --------------------------------------------------------------------------
def make_stencil2d(h: int, w: int, tile_y: int = 8,
                   tile_x: int = 8) -> KernelDef:
    """Rodinia-hotspot-shaped kernel: ``blockIdx``/``threadIdx`` are genuinely
    2-D (read through ``ctx.bid3``/``ctx.tid3``), with a shared halo tile."""

    def load(ctx, st):
        tx, ty, _ = ctx.tid3
        bx, by, _ = ctx.bid3
        row, col = by * tile_y + ty, bx * tile_x + tx
        x = st.glob["x"]
        at = lambda r, c: x[jnp.clip(r, 0, h - 1), jnp.clip(c, 0, w - 1)]
        s = st.shared["s"].at[ty + 1, tx + 1].set(at(row, col))
        # boundary threads fetch the four halo edges
        s = s.at[jnp.where(ty == 0, 0, OOB), tx + 1].set(
            at(row - 1, col), mode="drop")
        s = s.at[jnp.where(ty == tile_y - 1, tile_y + 1, OOB), tx + 1].set(
            at(row + 1, col), mode="drop")
        s = s.at[ty + 1, jnp.where(tx == 0, 0, OOB)].set(
            at(row, col - 1), mode="drop")
        s = s.at[ty + 1, jnp.where(tx == tile_x - 1, tile_x + 1, OOB)].set(
            at(row, col + 1), mode="drop")
        return st.set_shared(s=s)

    def compute(ctx, st):
        tx, ty, _ = ctx.tid3
        bx, by, _ = ctx.bid3
        row, col = by * tile_y + ty, bx * tile_x + tx
        s = st.shared["s"]
        val = 0.2 * (s[ty + 1, tx + 1] + s[ty, tx + 1] + s[ty + 2, tx + 1]
                     + s[ty + 1, tx] + s[ty + 1, tx + 2])
        idx = jnp.where((row < h) & (col < w), row, OOB)
        y = st.glob["y"].at[idx, col].set(val, mode="drop")
        return st.set_glob(y=y)

    return KernelDef(
        "stencil2d", (load, compute), writes=("y",), reads=("x", "y"),
        shared={"s": ((tile_y + 2, tile_x + 2), jnp.float32)},
        est_block_work=tile_y * tile_x * 10.0,
    )


# --------------------------------------------------------------------------
# softmax_row: one block per row, two barriers (max then sum)
# --------------------------------------------------------------------------
def make_softmax_row(block: int, dtype=jnp.float32) -> KernelDef:
    def load(ctx, st):
        v = st.glob["x"][ctx.bid, ctx.tid]
        return st.set_shared(s=st.shared["s"].at[ctx.tid].set(v))

    def exps(ctx, st):
        s = st.shared["s"]
        m = jnp.max(s)                       # every thread reads all of shared
        p = expf(s[ctx.tid] - m)
        return st.set_shared(p=st.shared["p"].at[ctx.tid].set(p))

    def normalize(ctx, st):
        p = st.shared["p"]
        denom = jnp.sum(p)
        y = st.glob["y"].at[ctx.bid, ctx.tid].set(p[ctx.tid] / denom)
        return st.set_glob(y=y)

    return KernelDef(
        "softmax_row", (load, exps, normalize), writes=("y",),
        reads=("x", "y"),
        shared={"s": ((block,), dtype), "p": ((block,), dtype)},
        est_block_work=block * 10.0,
    )


# --------------------------------------------------------------------------
# scan_block: Hillis-Steele inclusive prefix sum (2 stages per level)
# --------------------------------------------------------------------------
def make_scan_block(block: int, dtype=jnp.float32) -> KernelDef:
    assert block & (block - 1) == 0

    def load(ctx, st):
        gid = _gid(ctx)
        return st.set_shared(
            s=st.shared["s"].at[ctx.tid].set(st.glob["x"][gid]))

    def make_read(d):
        def read(ctx, st):
            s = st.shared["s"]
            t = jnp.where(ctx.tid >= d, s[jnp.maximum(ctx.tid - d, 0)], 0.0)
            return st.with_priv({"t": t})
        return read

    def make_write(d):
        def write(ctx, st):
            s = st.shared["s"]
            return st.with_priv({}).set_shared(
                s=s.at[ctx.tid].set(s[ctx.tid] + st.priv["t"]))
        return write

    def store(ctx, st):
        gid = _gid(ctx)
        return st.set_glob(
            y=st.glob["y"].at[gid].set(st.shared["s"][ctx.tid]))

    stages = [load]
    d = 1
    while d < block:
        stages += [make_read(d), make_write(d)]
        d *= 2
    stages.append(store)
    return KernelDef(
        "scan_block", tuple(stages), writes=("y",), reads=("x", "y"),
        shared={"s": ((block,), dtype)},
        est_block_work=block * math.log2(block) * 4.0,
    )


# --------------------------------------------------------------------------
# transpose_tiled: shared-staged transpose (coalescing demo, SVI-C)
# --------------------------------------------------------------------------
def make_transpose_tiled(h: int, w: int, tile: int = 8,
                         dtype=jnp.float32) -> KernelDef:
    assert h % tile == 0 and w % tile == 0
    ntx = w // tile

    def load(ctx, st):
        ty, tx = ctx.tid // tile, ctx.tid % tile
        by, bx = ctx.bid // ntx, ctx.bid % ntx
        t = st.shared["t"].at[ty, tx].set(
            st.glob["x"][by * tile + ty, bx * tile + tx])
        return st.set_shared(t=t)

    def store(ctx, st):
        ty, tx = ctx.tid // tile, ctx.tid % tile
        by, bx = ctx.bid // ntx, ctx.bid % ntx
        y = st.glob["y"].at[bx * tile + ty, by * tile + tx].set(
            st.shared["t"][tx, ty])
        return st.set_glob(y=y)

    return KernelDef(
        "transpose_tiled", (load, store), writes=("y",), reads=("x", "y"),
        shared={"t": ((tile, tile), dtype)},
        est_block_work=tile * tile * 4.0,
    )


# --------------------------------------------------------------------------
# pixel_pipeline: defensive-barrier elementwise pipeline (srad's extract /
# compress stages folded into one kernel).  Naive single-kernel ports keep a
# __syncthreads between the stages even though every thread only ever
# touches its *own* shared scratch cell - the missed-fusion class the
# Polygeist GPU-to-CPU study measures as dominant in translated kernels.
# kernelcheck proves every pair private, so core/optimize.py collapses the
# whole kernel to a single stage (and scalarizes the scratch buffer).
# --------------------------------------------------------------------------
def make_pixel_pipeline(block: int, c0: float = 0.85, c1: float = 0.1,
                        dtype=jnp.float32) -> KernelDef:
    def extract(ctx, st):
        v = st.glob["img"][_gid(ctx)]
        return st.set_shared(
            buf=st.shared["buf"].at[ctx.tid].set(logf(v)))

    def adjust(ctx, st):
        b = st.shared["buf"]
        return st.set_shared(buf=b.at[ctx.tid].set(b[ctx.tid] * c0 + c1))

    def compress(ctx, st):
        out = st.glob["out"].at[_gid(ctx)].set(
            expf(st.shared["buf"][ctx.tid]))
        return st.set_glob(out=out)

    return KernelDef(
        "pixel_pipeline", (extract, adjust, compress), writes=("out",),
        reads=("img", "out"),
        shared={"buf": ((block,), dtype)},
        est_block_work=block * 20.0,
    )


# --------------------------------------------------------------------------
# bfs_frontier (Rodinia bfs): level-synchronous BFS.  Each launch expands the
# current frontier; threads claim unvisited neighbors with an atomicCAS on
# the visited-flag array, winners publish dist/next-frontier, and the block
# counts its wins with __syncthreads_count into a host-readable stop flag.
# --------------------------------------------------------------------------
def make_bfs_frontier(n: int, deg: int) -> KernelDef:
    def expand(ctx, st):
        t = _gid(ctx)
        lvl = st.glob["level"][0]
        in_f = st.glob["frontier"][t] == 1
        visited = st.glob["visited"]
        nxt, dist = st.glob["nxt"], st.glob["dist"]
        edges = st.glob["edges"]
        won_any = jnp.zeros(t.shape, jnp.bool_)
        for k in range(deg):
            nbr = edges[t, k]                        # == n for padding slots
            attempt = in_f & (nbr < n)
            # inactive threads CAS a shared out-of-range slot with a compare
            # value that can never match a 0/1 flag, so they neither write
            # nor shadow a real claimant in the first-occurrence mask
            idx = jnp.where(attempt, nbr, n)
            cmp = jnp.where(attempt, 0, -1)
            visited, old = ctx.atomic_cas(visited, idx, cmp,
                                          jnp.ones_like(idx))
            won = attempt & (old == 0)
            widx = jnp.where(won, nbr, OOB)
            nxt = nxt.at[widx].set(1, mode="drop")
            dist = dist.at[widx].set(lvl + 1, mode="drop")
            won_any = won_any | won
        nwin = ctx.syncthreads_count(won_any)
        active = ctx.atomic_add(st.glob["active"],
                                jnp.where(ctx.tid == 0, 0, OOB), nwin)
        return st.set_glob(visited=visited, nxt=nxt, dist=dist,
                           active=active)

    return KernelDef(
        "bfs_frontier", (expand,),
        writes=("visited", "nxt", "dist", "active"),
        reads=("edges", "frontier", "visited", "nxt", "dist", "active",
               "level"),
        uses_warp=True,
        combines={"visited": "max", "nxt": "max", "dist": "max",
                  "active": "sum"},
        donates=("visited", "nxt", "dist", "active"),
        est_block_work=deg * 64.0,
    )


# --------------------------------------------------------------------------
# pathfinder (Rodinia pathfinder): row-wavefront dynamic programming.  One
# launch per wall row; each block stages the previous row into shared with a
# halo, takes the 3-neighbor min, and adds the current row's weights.  The
# host chain ping-pongs src/dst between launches.
# --------------------------------------------------------------------------
def make_pathfinder(cols: int, block: int, dtype=jnp.int32) -> KernelDef:
    def load(ctx, st):
        col = _gid(ctx)
        src = st.glob["src"]
        s = st.shared["s"].at[ctx.tid + 1].set(
            src[jnp.clip(col, 0, cols - 1)])
        left = src[jnp.clip(col - 1, 0, cols - 1)]
        right = src[jnp.clip(col + 1, 0, cols - 1)]
        s = s.at[jnp.where(ctx.tid == 0, 0, OOB)].set(left, mode="drop")
        s = s.at[jnp.where(ctx.tid == block - 1, block + 1, OOB)].set(
            right, mode="drop")
        return st.set_shared(s=s)

    def compute(ctx, st):
        col = _gid(ctx)
        r = st.glob["row"][0]
        s = st.shared["s"]
        best = jnp.minimum(jnp.minimum(s[ctx.tid], s[ctx.tid + 1]),
                           s[ctx.tid + 2])
        v = st.glob["wall"][r, jnp.clip(col, 0, cols - 1)] + best
        idx = jnp.where(col < cols, col, OOB)
        return st.set_glob(dst=st.glob["dst"].at[idx].set(v, mode="drop"))

    return KernelDef(
        "pathfinder", (load, compute), writes=("dst",),
        reads=("wall", "src", "dst", "row"),
        shared={"s": ((block + 2,), dtype)},
        combines={"dst": "sum"},       # dst re-zeroed per launch: exact
        donates=("dst",),              # ping-pong target: alias, don't copy
        est_block_work=block * 6.0,
    )


# --------------------------------------------------------------------------
# needle_nw (Rodinia nw): Needleman-Wunsch anti-diagonal wavefront.  One
# launch per anti-diagonal; each cell on the diagonal depends only on the
# two previous diagonals, already final in global memory.
# --------------------------------------------------------------------------
def make_needle_nw(n: int, penalty: int = 2) -> KernelDef:
    """dtype-agnostic: score/sim dtype follows the input arrays."""
    def stage(ctx, st):
        t = _gid(ctx)
        d = st.glob["diag"][0]
        lo = jnp.maximum(1, d - n)
        hi = jnp.minimum(n, d - 1)
        valid = t <= hi - lo
        i = jnp.clip(t + lo, 1, n)
        j = jnp.clip(d - i, 1, n)
        score, sim = st.glob["score"], st.glob["sim"]
        dv = score[i - 1, j - 1] + sim[i - 1, j - 1]
        up = score[i - 1, j] - penalty
        lf = score[i, j - 1] - penalty
        v = jnp.maximum(dv, jnp.maximum(up, lf))
        idx = jnp.where(valid, i, OOB)
        return st.set_glob(score=score.at[idx, j].set(v, mode="drop"))

    return KernelDef(
        "needle_nw", (stage,), writes=("score",),
        reads=("score", "sim", "diag"),
        combines={"score": "sum"},     # each cell written once, from zero
        donates=("score",),            # in-place wavefront accumulation
        est_block_work=64.0,
    )


# --------------------------------------------------------------------------
# backprop_layer (Rodinia backprop): forward pass of one layer (barrier-tree
# dot product + sigmoid) fused with the weight-delta update.  Weights,
# inputs, and deltas ride in __constant__ space; each block owns one hidden
# unit, so both outputs are owned-slice ("concat") writes.
# --------------------------------------------------------------------------
def make_backprop_layer(in_n: int, out_n: int, lr: float = 0.3) -> KernelDef:
    assert in_n & (in_n - 1) == 0, "in_n must be a power of two"

    def load(ctx, st):
        j = ctx.bid
        v = st.glob["inp"][ctx.tid] * st.glob["w"][j, ctx.tid]
        return st.set_shared(s=st.shared["s"].at[ctx.tid].set(v))

    def make_level(offset):
        def level(ctx, st):
            s = st.shared["s"]
            partner = s[ctx.tid + offset]
            new = jnp.where(ctx.tid < offset, s[ctx.tid] + partner,
                            s[ctx.tid])
            return st.set_shared(s=s.at[ctx.tid].set(new))
        return level

    def store(ctx, st):
        j = ctx.bid
        total = st.shared["s"][0] + st.glob["bias"][j]
        h = 1.0 / (1.0 + expf(-total))
        idx = jnp.where(ctx.tid == 0, j, OOB)
        hidden = st.glob["hidden"].at[idx].set(h, mode="drop")
        wo = st.glob["w_out"].at[j, ctx.tid].set(
            st.glob["w"][j, ctx.tid]
            + lr * st.glob["delta"][j] * st.glob["inp"][ctx.tid])
        return st.set_glob(hidden=hidden, w_out=wo)

    stages = [load]
    off = in_n // 2
    while off >= 1:
        stages.append(make_level(off))
        off //= 2
    stages.append(store)
    return KernelDef(
        "backprop_layer", tuple(stages), writes=("hidden", "w_out"),
        reads=("inp", "w", "bias", "delta", "hidden", "w_out"),
        shared={"s": ((in_n,), jnp.float32)},
        combines={"hidden": "concat", "w_out": "concat"},
        est_block_work=in_n * 10.0,
    )


# --------------------------------------------------------------------------
# lud_diag (Rodinia lud): the diagonal-block LU step.  Each block factors
# its own b x b tile in shared memory - b-1 barrier-separated elimination
# steps (Doolittle, no pivoting) - then writes L\U back to its owned rows.
# --------------------------------------------------------------------------
def make_lud_diag(ntiles: int, b: int) -> KernelDef:
    def load(ctx, st):
        row = ctx.bid * b + ctx.tid
        return st.set_shared(s=st.shared["s"].at[ctx.tid, :].set(
            st.glob["a"][row, :]))

    def make_step(k):
        def step(ctx, st):
            s = st.shared["s"]
            i = ctx.tid
            m = s[i, k] / s[k, k]
            cols = jnp.arange(b)
            upd = jnp.where(cols[None, :] > k, s[k, :][None, :], 0.0)
            newrow = s[i, :] - m[:, None] * upd
            newrow = newrow.at[:, k].set(m)
            ridx = jnp.where(i > k, i, OOB)
            return st.set_shared(s=s.at[ridx, :].set(newrow, mode="drop"))
        return step

    def store(ctx, st):
        row = ctx.bid * b + ctx.tid
        lu = st.glob["lu"].at[row, :].set(st.shared["s"][ctx.tid, :])
        return st.set_glob(lu=lu)

    stages = [load] + [make_step(k) for k in range(b - 1)] + [store]
    return KernelDef(
        "lud_diag", tuple(stages), writes=("lu",), reads=("a", "lu"),
        shared={"s": ((b, b), jnp.float32)},
        combines={"lu": "concat"},
        est_block_work=b * b * b * 2.0,
    )


# --------------------------------------------------------------------------
# srad_step (Rodinia srad): speckle-reducing anisotropic diffusion.  Each
# iteration is a two-kernel chain: a barrier-tree statistics reduction into
# per-block partials (Rodinia reduces partials on the host; here the update
# kernel folds them), then a 2-D dim3 stencil update with the diffusion
# coefficient derived from the image-wide statistics.
# --------------------------------------------------------------------------
def make_srad_stats(h: int, w: int, block: int) -> KernelDef:
    npix = h * w
    assert block & (block - 1) == 0

    def load(ctx, st):
        gid = _gid(ctx)
        g = jnp.minimum(gid, npix - 1)
        v = jnp.where(gid < npix, st.glob["x"][g // w, g % w], 0.0)
        s1 = st.shared["s1"].at[ctx.tid].set(v)
        s2 = st.shared["s2"].at[ctx.tid].set(v * v)
        return st.set_shared(s1=s1, s2=s2)

    def make_level(offset):
        def level(ctx, st):
            s1, s2 = st.shared["s1"], st.shared["s2"]
            lower = ctx.tid < offset
            n1 = jnp.where(lower, s1[ctx.tid] + s1[ctx.tid + offset],
                           s1[ctx.tid])
            n2 = jnp.where(lower, s2[ctx.tid] + s2[ctx.tid + offset],
                           s2[ctx.tid])
            return st.set_shared(s1=s1.at[ctx.tid].set(n1),
                                 s2=s2.at[ctx.tid].set(n2))
        return level

    def store(ctx, st):
        idx = jnp.where(ctx.tid == 0, ctx.bid, OOB)
        ps = st.glob["psum"].at[idx].set(st.shared["s1"][0], mode="drop")
        pq = st.glob["psq"].at[idx].set(st.shared["s2"][0], mode="drop")
        return st.set_glob(psum=ps, psq=pq)

    stages = [load]
    off = block // 2
    while off >= 1:
        stages.append(make_level(off))
        off //= 2
    stages.append(store)
    return KernelDef(
        "srad_stats", tuple(stages), writes=("psum", "psq"),
        reads=("x", "psum", "psq"),
        shared={"s1": ((block,), jnp.float32),
                "s2": ((block,), jnp.float32)},
        combines={"psum": "sum", "psq": "sum"},
        donates=("psum", "psq"),       # re-zeroed partials: alias freely
        est_block_work=block * 8.0,
    )


def make_srad_update(h: int, w: int, lam: float = 0.2, tile_y: int = 8,
                     tile_x: int = 8) -> KernelDef:
    npix = h * w

    def stage(ctx, st):
        tx, ty, _ = ctx.tid3
        bx, by, _ = ctx.bid3
        r, c = by * tile_y + ty, bx * tile_x + tx
        x = st.glob["x"]
        total = jnp.sum(st.glob["psum"])
        totsq = jnp.sum(st.glob["psq"])
        mean = total / npix
        var = totsq / npix - mean * mean
        q0 = var / (mean * mean)
        rc, cc = jnp.clip(r, 0, h - 1), jnp.clip(c, 0, w - 1)
        at = lambda rr, cx: x[jnp.clip(rr, 0, h - 1), jnp.clip(cx, 0, w - 1)]
        xc = x[rc, cc]
        dN = at(rc - 1, cc) - xc
        dS = at(rc + 1, cc) - xc
        dW = at(rc, cc - 1) - xc
        dE = at(rc, cc + 1) - xc
        g2 = (dN * dN + dS * dS + dW * dW + dE * dE) / (xc * xc)
        ll = (dN + dS + dW + dE) / xc
        num = 0.5 * g2 - 0.0625 * (ll * ll)
        den = (1.0 + 0.25 * ll) * (1.0 + 0.25 * ll)
        q = num / den
        cd = 1.0 / (1.0 + (q - q0) / (q0 * (1.0 + q0)))
        cd = jnp.clip(cd, 0.0, 1.0)
        v = xc + 0.25 * lam * cd * (dN + dS + dW + dE)
        idx = jnp.where((r < h) & (c < w), rc, OOB)
        return st.set_glob(y=st.glob["y"].at[idx, cc].set(v, mode="drop"))

    return KernelDef(
        "srad_update", (stage,), writes=("y",),
        reads=("x", "psum", "psq", "y"),
        combines={"y": "sum"},         # y re-zeroed per launch: exact
        donates=("y",),                # ping-pong target of the x<->y swap
        est_block_work=tile_y * tile_x * 24.0,
    )


# --------------------------------------------------------------------------
# lavaMD (Rodinia lavaMD): per-box particle interactions over a neighbor
# list.  Each block owns one home box; for every neighbor box it stages that
# box's particle positions and charges into shared memory, barriers, and
# accumulates the pairwise potential into a register accumulator that lives
# across 2*nnei barriers (the same register-demotion stress as matmul_tiled,
# but with an indirect neighbor-list gather choosing what to stage).
# --------------------------------------------------------------------------
def make_lavamd(nboxes: int, ppb: int, nnei: int,
                alpha: float = 0.5) -> KernelDef:
    def init(ctx, st):
        return st.with_priv({"acc": jnp.zeros(ctx.tid.shape, jnp.float32)})

    def make_load(k):
        def load(ctx, st):
            nb = st.glob["nbr"][ctx.bid, k]
            base = nb * ppb
            sy = st.shared["sy"].at[ctx.tid].set(
                st.glob["pos"][base + ctx.tid])
            sq = st.shared["sq"].at[ctx.tid].set(
                st.glob["q"][base + ctx.tid])
            return st.set_shared(sy=sy, sq=sq)
        return load

    def compute(ctx, st):
        x = st.glob["pos"][ctx.bid * ppb + ctx.tid]
        sy, sq = st.shared["sy"], st.shared["sq"]
        d = x[:, None] - sy[None, :]
        u = jnp.sum(sq[None, :] * expf(-alpha * d * d), axis=1)
        return st.with_priv({"acc": st.priv["acc"] + u})

    def store(ctx, st):
        f = st.glob["force"].at[ctx.bid * ppb + ctx.tid].set(st.priv["acc"])
        return st.with_priv({}).set_glob(force=f)

    stages = [init]
    for k in range(nnei):
        stages += [make_load(k), compute]
    stages.append(store)
    return KernelDef(
        "lavamd", tuple(stages), writes=("force",),
        reads=("pos", "q", "nbr", "force"),
        shared={"sy": ((ppb,), jnp.float32), "sq": ((ppb,), jnp.float32)},
        combines={"force": "concat"},  # block b owns rows [b*ppb, b*ppb+ppb)
        est_block_work=nnei * ppb * ppb * 6.0,
    )


# --------------------------------------------------------------------------
# nn (Rodinia nn): k-nearest-neighbor search over hurricane records.  The
# records arrive through the cane-file text format (rodinia_io), and each
# of the k output slots is one chain iteration: a two-level barrier-tree
# arg-min (per-block partials, then a single-block final reduction) whose
# winner is appended to the output and masked out of the next pass via the
# `taken` flags.  The (value, index) pairs reduce lexicographically so ties
# break toward the lowest record index, matching np.argmin.
# --------------------------------------------------------------------------
def _nn_argmin_level(off):
    def level(ctx, st):
        sv, si = st.shared["sv"], st.shared["si"]
        v1, i1 = sv[ctx.tid], si[ctx.tid]
        v2, i2 = sv[ctx.tid + off], si[ctx.tid + off]
        take = (ctx.tid < off) & ((v2 < v1) | ((v2 == v1) & (i2 < i1)))
        return st.set_shared(sv=sv.at[ctx.tid].set(jnp.where(take, v2, v1)),
                             si=si.at[ctx.tid].set(jnp.where(take, i2, i1)))
    return level


def make_nn_reduce(n: int, block: int) -> KernelDef:
    assert block & (block - 1) == 0

    def load(ctx, st):
        i = _gid(ctx)
        g = jnp.minimum(i, n - 1)
        tgt = st.glob["target"]
        d = ((st.glob["lat"][g] - tgt[0]) ** 2
             + (st.glob["lng"][g] - tgt[1]) ** 2)
        d = jnp.where((i < n) & (st.glob["taken"][g] == 0), d, jnp.inf)
        sv = st.shared["sv"].at[ctx.tid].set(d)
        si = st.shared["si"].at[ctx.tid].set(g)
        return st.set_shared(sv=sv, si=si)

    def store(ctx, st):
        idx = jnp.where(ctx.tid == 0, ctx.bid, OOB)
        pv = st.glob["pval"].at[idx].set(st.shared["sv"][0], mode="drop")
        pi = st.glob["pidx"].at[idx].set(st.shared["si"][0], mode="drop")
        return st.set_glob(pval=pv, pidx=pi)

    stages = [load]
    off = block // 2
    while off >= 1:
        stages.append(_nn_argmin_level(off))
        off //= 2
    stages.append(store)
    return KernelDef(
        "nn_reduce", tuple(stages), writes=("pval", "pidx"),
        reads=("lat", "lng", "target", "taken", "pval", "pidx"),
        shared={"sv": ((block,), jnp.float32), "si": ((block,), jnp.int32)},
        combines={"pval": "concat", "pidx": "concat"},
        donates=("pval", "pidx"),      # fully rewritten every launch
        est_block_work=block * 8.0,
    )


def make_nn_select(nblocks: int) -> KernelDef:
    assert nblocks & (nblocks - 1) == 0

    def load(ctx, st):
        sv = st.shared["sv"].at[ctx.tid].set(st.glob["pval"][ctx.tid])
        si = st.shared["si"].at[ctx.tid].set(st.glob["pidx"][ctx.tid])
        return st.set_shared(sv=sv, si=si)

    def store(ctx, st):
        step = st.glob["step"][0]
        win_v, win_i = st.shared["sv"][0], st.shared["si"][0]
        oidx = jnp.where(ctx.tid == 0, step, OOB)
        od = st.glob["out_d"].at[oidx].set(win_v, mode="drop")
        oi = st.glob["out_i"].at[oidx].set(win_i, mode="drop")
        tk = st.glob["taken"].at[
            jnp.where(ctx.tid == 0, win_i, OOB)].set(1, mode="drop")
        return st.set_glob(out_d=od, out_i=oi, taken=tk)

    stages = [load]
    off = nblocks // 2
    while off >= 1:
        stages.append(_nn_argmin_level(off))
        off //= 2
    stages.append(store)
    return KernelDef(
        "nn_select", tuple(stages), writes=("out_d", "out_i", "taken"),
        reads=("pval", "pidx", "step", "out_d", "out_i", "taken"),
        shared={"sv": ((nblocks,), jnp.float32),
                "si": ((nblocks,), jnp.int32)},
        # out slots are written once each, from zero; taken flips 0->1
        combines={"out_d": "sum", "out_i": "sum", "taken": "max"},
        est_block_work=nblocks * 6.0,
    )


# --------------------------------------------------------------------------
# kmeans (Rodinia kmeans): Lloyd iterations as a convergence LaunchChain.
# The assign kernel labels every point with its nearest centroid and
# accumulates per-cluster coordinate sums / counts / a moved-points counter
# with atomicAdd (duplicate-heavy irregular scatters); the update kernel
# recomputes centroids from the sums.  The chain's device-resident stop
# predicate polls `changed == 0`; the whole fixed point is bit-stable, so
# overshooting the converged state is an exact no-op on every buffer.
# Coordinates are integer-valued floats, keeping every sum and the final
# centroid division exact across backends and shard merges.
# --------------------------------------------------------------------------
def make_kmeans_assign(n: int, k: int) -> KernelDef:
    def stage(ctx, st):
        i = _gid(ctx)
        g = jnp.minimum(i, n - 1)
        px, py = st.glob["px"][g], st.glob["py"][g]
        cx, cy = st.glob["cx"], st.glob["cy"]
        best = jnp.zeros_like(g)
        bestd = (px - cx[0]) ** 2 + (py - cy[0]) ** 2
        for c in range(1, k):
            dc = (px - cx[c]) ** 2 + (py - cy[c]) ** 2
            closer = dc < bestd          # strict: ties keep the lower c
            best = jnp.where(closer, c, best)
            bestd = jnp.where(closer, dc, bestd)
        valid = i < n
        moved = valid & (st.glob["assign"][g] != best)
        changed = ctx.atomic_add(st.glob["changed"],
                                 jnp.where(moved, 0, OOB), 1)
        assign = st.glob["assign"].at[jnp.where(valid, i, OOB)].set(
            best, mode="drop")
        bidx = jnp.where(valid, best, OOB)
        sumx = ctx.atomic_add(st.glob["sumx"], bidx, px)
        sumy = ctx.atomic_add(st.glob["sumy"], bidx, py)
        count = ctx.atomic_add(st.glob["count"], bidx, 1)
        return st.set_glob(changed=changed, assign=assign, sumx=sumx,
                           sumy=sumy, count=count)

    return KernelDef(
        "kmeans_assign", (stage,),
        writes=("assign", "changed", "sumx", "sumy", "count"),
        reads=("px", "py", "cx", "cy", "assign", "changed", "sumx",
               "sumy", "count"),
        combines={"assign": "concat", "changed": "sum", "sumx": "sum",
                  "sumy": "sum", "count": "sum"},
        donates=("changed", "sumx", "sumy", "count"),  # re-zeroed per iter
        est_block_work=k * 64.0,
    )


def make_kmeans_update(k: int) -> KernelDef:
    def stage(ctx, st):
        c = ctx.bid
        cnt = st.glob["count"][c]
        safe = jnp.maximum(cnt, 1).astype(jnp.float32)
        nx = st.glob["sumx"][c] / safe
        ny = st.glob["sumy"][c] / safe
        empty = cnt == 0                 # empty cluster keeps its centroid
        nx = jnp.where(empty, st.glob["cx"][c], nx)
        ny = jnp.where(empty, st.glob["cy"][c], ny)
        idx = jnp.where(ctx.tid == 0, c, OOB)
        cx = st.glob["cx"].at[idx].set(nx, mode="drop")
        cy = st.glob["cy"].at[idx].set(ny, mode="drop")
        return st.set_glob(cx=cx, cy=cy)

    return KernelDef(
        "kmeans_update", (stage,), writes=("cx", "cy"),
        reads=("sumx", "sumy", "count", "cx", "cy"),
        combines={"cx": "concat", "cy": "concat"},  # block c owns row c
        est_block_work=16.0,
    )


# --------------------------------------------------------------------------
# streamcluster (Rodinia streamcluster pgain): evaluate opening a candidate
# center.  Every point compares its current assignment cost against the
# candidate; switchers accumulate the global gain and their old center's
# per-center savings with duplicate-heavy atomicAdd, and claim the old
# center's dirty flag with atomicCAS (the CAS winner bumps a distinct-dirty
# counter - deduplicated per device, hence nondeterministic under shard).
# --------------------------------------------------------------------------
def make_streamcluster(n: int, k: int) -> KernelDef:
    def stage(ctx, st):
        i = _gid(ctx)
        g = jnp.minimum(i, n - 1)
        valid = i < n
        a = st.glob["assign"][g]
        px, py = st.glob["px"][g], st.glob["py"][g]
        cx, cy = st.glob["cx"], st.glob["cy"]
        dcur = (px - cx[a]) ** 2 + (py - cy[a]) ** 2
        cand = st.glob["cand"]
        dcand = (px - cand[0]) ** 2 + (py - cand[1]) ** 2
        sw = valid & (dcand < dcur)
        save = dcur - dcand
        gain = ctx.atomic_add(st.glob["gain"],
                              jnp.where(sw, 0, OOB), save)
        csave = ctx.atomic_add(st.glob["csave"],
                               jnp.where(sw, a, OOB), save)
        # inactive threads CAS a past-the-end slot with an impossible
        # compare value (the bfs_frontier idiom)
        dirty, old = ctx.atomic_cas(st.glob["dirty"],
                                    jnp.where(sw, a, k),
                                    jnp.where(sw, 0, -1),
                                    jnp.ones_like(a))
        won = sw & (old == 0)
        ndirty = ctx.atomic_add(st.glob["ndirty"],
                                jnp.where(won, 0, OOB), 1)
        switched = st.glob["switched"].at[
            jnp.where(sw, i, OOB)].set(1, mode="drop")
        return st.set_glob(gain=gain, csave=csave, dirty=dirty,
                           ndirty=ndirty, switched=switched)

    return KernelDef(
        "streamcluster", (stage,),
        writes=("gain", "csave", "dirty", "ndirty", "switched"),
        reads=("px", "py", "cx", "cy", "cand", "assign", "gain", "csave",
               "dirty", "ndirty", "switched"),
        combines={"gain": "sum", "csave": "sum", "dirty": "max",
                  "ndirty": "sum", "switched": "sum"},
        donates=("gain", "csave", "dirty", "ndirty", "switched"),
        est_block_work=64.0,
    )


# --------------------------------------------------------------------------
# hotspot (Rodinia hotspot): the real thermal update, promoted from the
# stencil2d skeleton to a chain-driven workload.  The temperature and power
# grids arrive through hotspot's one-value-per-line text files (rodinia_io);
# each iteration stages a haloed temperature tile into shared memory and
# applies the RC thermal step; the chain ping-pongs t <-> t_out across
# `iters` launches with the power grid pinned in __constant__ space.
# --------------------------------------------------------------------------
def make_hotspot(h: int, w: int, tile_y: int = 8, tile_x: int = 8,
                 cap: float = 0.5, rx: float = 0.1, ry: float = 0.1,
                 rz: float = 0.05, amb: float = 80.0) -> KernelDef:
    def load(ctx, st):
        tx, ty, _ = ctx.tid3
        bx, by, _ = ctx.bid3
        row, col = by * tile_y + ty, bx * tile_x + tx
        t = st.glob["t"]
        at = lambda r, c: t[jnp.clip(r, 0, h - 1), jnp.clip(c, 0, w - 1)]
        s = st.shared["s"].at[ty + 1, tx + 1].set(at(row, col))
        s = s.at[jnp.where(ty == 0, 0, OOB), tx + 1].set(
            at(row - 1, col), mode="drop")
        s = s.at[jnp.where(ty == tile_y - 1, tile_y + 1, OOB), tx + 1].set(
            at(row + 1, col), mode="drop")
        s = s.at[ty + 1, jnp.where(tx == 0, 0, OOB)].set(
            at(row, col - 1), mode="drop")
        s = s.at[ty + 1, jnp.where(tx == tile_x - 1, tile_x + 1, OOB)].set(
            at(row, col + 1), mode="drop")
        return st.set_shared(s=s)

    def compute(ctx, st):
        tx, ty, _ = ctx.tid3
        bx, by, _ = ctx.bid3
        row, col = by * tile_y + ty, bx * tile_x + tx
        rc, cc = jnp.clip(row, 0, h - 1), jnp.clip(col, 0, w - 1)
        s = st.shared["s"]
        tc = s[ty + 1, tx + 1]
        p = st.glob["p"][rc, cc]
        v = tc + cap * (
            p
            + ry * (s[ty, tx + 1] + s[ty + 2, tx + 1] - 2.0 * tc)
            + rx * (s[ty + 1, tx] + s[ty + 1, tx + 2] - 2.0 * tc)
            + rz * (amb - tc))
        idx = jnp.where((row < h) & (col < w), row, OOB)
        t_out = st.glob["t_out"].at[idx, cc].set(v, mode="drop")
        return st.set_glob(t_out=t_out)

    return KernelDef(
        "hotspot", (load, compute), writes=("t_out",),
        reads=("t", "p", "t_out"),
        shared={"s": ((tile_y + 2, tile_x + 2), jnp.float32)},
        combines={"t_out": "sum"},     # t_out re-zeroed per launch: exact
        donates=("t_out",),            # ping-pong target of the t<->t_out swap
        est_block_work=tile_y * tile_x * 14.0,
    )


# --------------------------------------------------------------------------
# Suite registry: kernel + launch config + inputs + numpy oracle
# --------------------------------------------------------------------------
@dataclasses.dataclass
class SuiteEntry:
    """One suite workload: kernel(s), launch geometry, inputs, and oracle.

    ``chain`` is set for wavefront workloads driven by a
    :class:`~repro.core.kernel.LaunchChain` (the entry-level
    ``kernel``/``grid``/``block`` then describe the first step, for
    display); ``const`` names buffers bound in ``__constant__`` space at
    launch; ``tol`` is the oracle comparison tolerance;
    ``nondeterministic_shard`` names scratch buffers whose *bit* pattern
    legitimately differs between the shard and single-device backends
    (e.g. a deduplicated-on-one-device win counter) - excluded from
    cross-backend bit comparisons, never from semantic checks;
    ``iteration_state`` names per-iteration chain scratch (stop counters,
    frontier ping-pongs) whose final bits depend on the stop-poll cadence
    - device-resident replays may overshoot a converged stop flag by up
    to ``check_every - 1`` no-op iterations, so these are excluded from
    host-hop-vs-device-resident bit comparisons (the oracle outputs never
    are); ``rodinia`` records the benchmark counterpart for the coverage
    table; ``dim3_free`` marks kernels that read only linearized ids, so
    any ``Dim3`` factorization of the same grid size is equivalent.
    """

    name: str
    features: tuple[str, ...]
    kernel: KernelDef
    grid: int | tuple            # CUDA dim3: int or up-to-3-tuple
    block: int | tuple
    dyn_shared: int | None
    make_args: Callable[[np.random.Generator], dict]
    reference: Callable[[dict], dict]
    chain: LaunchChain | None = None
    const: tuple[str, ...] = ()
    tol: float = 2e-5
    rodinia: str = ""
    dim3_free: bool = True
    nondeterministic_shard: tuple[str, ...] = ()
    iteration_state: tuple[str, ...] = ()


def run_entry(entry: SuiteEntry, backend: str = "loop", *, rng=None,
              args: dict | None = None, grain=1, devices=None, pool=None,
              interpret: bool | None = None, grid=None, block=None,
              with_reference: bool = True, chain_mode: str = "host",
              chain_stats: ChainStats | None = None,
              check_every: int | None = None,
              optimize: bool | None = None):
    """Execute a suite entry end-to-end under one backend.

    The single place that knows how to *drive* an entry: plain entries are
    one launch; chain entries replay their :class:`LaunchChain` with every
    step routed through the same backend/grain/device options; buffers
    named in ``entry.const`` are bound as ``__constant__``
    (:class:`~repro.core.memory.ConstArray`).  Returns ``(out, want)`` -
    the final buffer dict and the numpy oracle's expectation
    (``with_reference=False`` skips the oracle and returns ``want=None``:
    wall-clock benchmarks must not time the pure-Python reference).

    ``chain_mode`` selects the chain replay path (ignored for plain
    entries only if "host"): ``"host"`` is the per-iteration host-hop
    baseline, ``"device"`` the device-resident replay (on-device update
    hooks, stop polled every ``check_every`` iterations), ``"graph"`` the
    graph-captured replay (iterations fused into jitted graph
    dispatches).  ``chain_stats`` collects replay counters.
    """
    if args is None:
        args = entry.make_args(rng if rng is not None
                               else np.random.default_rng(42))
    want = entry.reference(args) if with_reference else None
    bufs = {}
    for k, v in args.items():
        arr = jnp.asarray(v)
        bufs[k] = memory.ConstArray(arr) if k in entry.const else arr
    kw = dict(backend=backend, grain=grain, devices=devices, pool=pool,
              interpret=interpret, optimize=optimize)
    if entry.chain is None:
        if chain_mode != "host":
            raise ValueError(
                f"entry {entry.name}: chain_mode={chain_mode!r} needs a "
                f"LaunchChain entry (this one is a single launch)")
        out = launch(entry.kernel,
                     grid=entry.grid if grid is None else grid,
                     block=entry.block if block is None else block,
                     args=bufs, dyn_shared=entry.dyn_shared, **kw)
    else:
        if grid is not None or block is not None:
            raise ValueError(
                f"entry {entry.name}: geometry overrides are per-step for "
                f"chain entries; rebuild the chain instead")

        def launch_step(step, b):
            return launch(step.kernel, grid=step.grid, block=step.block,
                          args=b, dyn_shared=step.dyn_shared, **kw)

        if chain_mode == "host":
            out = entry.chain.run(launch_step, bufs, stats=chain_stats)
        elif chain_mode == "device":
            out = entry.chain.run_device(launch_step, bufs,
                                         check_every=check_every,
                                         stats=chain_stats)
        elif chain_mode == "graph":
            from repro.core.streams import Stream
            stream = Stream(dict(bufs))
            out = entry.chain.run_graph(stream, check_every=check_every,
                                        stats=chain_stats, **kw)
        else:
            raise ValueError(
                f"unknown chain_mode {chain_mode!r}; "
                f"expected host | device | graph")
    return out, want


def build_suite(scale: int = 1) -> list[SuiteEntry]:
    """scale=1 -> test-sized; larger scales for the wall-clock benchmarks."""
    entries = []
    n = 4096 * scale
    block = 128

    entries.append(SuiteEntry(
        "vecadd", ("spmd",), make_vecadd(n), -(-n // block), block, None,
        lambda r: {"a": r.standard_normal(n, dtype=np.float32),
                   "b": r.standard_normal(n, dtype=np.float32),
                   "c": np.zeros(n, np.float32)},
        lambda a: {"c": a["a"] + a["b"]},
        rodinia="(Listing 1)",
    ))

    rn = 512
    entries.append(SuiteEntry(
        "reverse", ("barrier", "dyn_shared"), make_reverse(), 1, rn, rn,
        lambda r: {"d": r.integers(0, 100, rn).astype(np.int32)},
        lambda a: {"d": a["d"][::-1].copy()},
        rodinia="(Listing 3)",
    ))

    nbins, tt = 64, 16 * block
    hn = 4096 * scale
    entries.append(SuiteEntry(
        "histogram", ("atomic",), make_histogram(hn, nbins, tt), 16, block,
        None,
        lambda r: {"x": r.integers(0, nbins, hn).astype(np.int32),
                   "hist": np.zeros(nbins, np.int32)},
        lambda a: {"hist": np.bincount(a["x"], minlength=nbins)
                   .astype(np.int32)},
        rodinia="Hetero-Mark HIST",
    ))

    rs_n, rs_b = 2048 * scale, 256
    entries.append(SuiteEntry(
        "reduce_shared", ("barrier",), make_reduce_shared(rs_n, rs_b),
        -(-rs_n // rs_b), rs_b, None,
        lambda r: {"x": r.standard_normal(rs_n, dtype=np.float32),
                   "out": np.zeros(-(-rs_n // rs_b), np.float32)},
        lambda a: {"out": a["x"].reshape(-1, rs_b).sum(1)},
        rodinia="srad/kmeans reductions",
    ))

    entries.append(SuiteEntry(
        "reduce_warp", ("warp",), make_reduce_warp(rs_n, rs_b),
        -(-rs_n // rs_b), rs_b, None,
        lambda r: {"x": r.standard_normal(rs_n, dtype=np.float32),
                   "out": np.zeros(-(-rs_n // rs_b), np.float32)},
        lambda a: {"out": a["x"].reshape(-1, rs_b).sum(1)},
        rodinia="Crystal q11-q13",
    ))

    mm = 32 * max(1, scale // 4)
    entries.append(SuiteEntry(
        "matmul_tiled", ("barrier", "demotion"),
        make_matmul_tiled(mm, mm, mm, tile=8), (mm // 8) ** 2, 64, None,
        lambda r: {"a": r.standard_normal((mm, mm), dtype=np.float32),
                   "b": r.standard_normal((mm, mm), dtype=np.float32),
                   "c": np.zeros((mm, mm), np.float32)},
        lambda a: {"c": a["a"] @ a["b"]},
        rodinia="lud/gemm",
    ))

    st_n = 4096 * scale
    entries.append(SuiteEntry(
        "stencil1d", ("barrier",), make_stencil1d(st_n, block),
        -(-st_n // block), block, None,
        lambda r: {"x": r.standard_normal(st_n, dtype=np.float32),
                   "y": np.zeros(st_n, np.float32)},
        lambda a: {"y": (0.25 * a["x"][np.clip(np.arange(st_n) - 1, 0, None)]
                         + 0.5 * a["x"]
                         + 0.25 * a["x"][np.clip(np.arange(st_n) + 1, None,
                                                 st_n - 1)])},
        rodinia="hotspot (1-D)",
    ))

    sh, sw = 32, 64 * scale

    def _stencil2d_ref(a):
        p = np.pad(a["x"], 1, mode="edge")
        return {"y": 0.2 * (p[1:-1, 1:-1] + p[:-2, 1:-1] + p[2:, 1:-1]
                            + p[1:-1, :-2] + p[1:-1, 2:])}

    entries.append(SuiteEntry(
        "stencil2d", ("barrier", "dim3"), make_stencil2d(sh, sw),
        (sw // 8, sh // 8), (8, 8), None,
        lambda r: {"x": r.standard_normal((sh, sw), dtype=np.float32),
                   "y": np.zeros((sh, sw), np.float32)},
        _stencil2d_ref,
        rodinia="hotspot",
        dim3_free=False,
    ))

    rows = 32 * scale
    entries.append(SuiteEntry(
        "softmax_row", ("barrier",), make_softmax_row(block), rows, block,
        None,
        lambda r: {"x": r.standard_normal((rows, block), dtype=np.float32),
                   "y": np.zeros((rows, block), np.float32)},
        lambda a: {"y": (np.exp(a["x"] - a["x"].max(1, keepdims=True))
                         / np.exp(a["x"] - a["x"].max(1, keepdims=True))
                         .sum(1, keepdims=True))},
        rodinia="attention primitive",
    ))

    sc_b = 128
    sc_n = sc_b * 8 * scale
    entries.append(SuiteEntry(
        "scan_block", ("barrier", "demotion"), make_scan_block(sc_b),
        sc_n // sc_b, sc_b, None,
        lambda r: {"x": r.standard_normal(sc_n, dtype=np.float32),
                   "y": np.zeros(sc_n, np.float32)},
        lambda a: {"y": np.cumsum(a["x"].reshape(-1, sc_b), 1).reshape(-1)},
        rodinia="pathfinder/scan",
    ))

    th, tw = 64, 64 * scale
    entries.append(SuiteEntry(
        "transpose_tiled", ("barrier",), make_transpose_tiled(th, tw),
        (th // 8) * (tw // 8), 64, None,
        lambda r: {"x": r.standard_normal((th, tw), dtype=np.float32),
                   "y": np.zeros((tw, th), np.float32)},
        lambda a: {"y": a["x"].T.copy()},
        rodinia="(SVI-C reordering)",
    ))

    pp_n = 4096 * scale
    entries.append(SuiteEntry(
        "pixel_pipeline", ("barrier",), make_pixel_pipeline(block),
        pp_n // block, block, None,
        lambda r: {"img": r.uniform(0.5, 2.0, pp_n).astype(np.float32),
                   "out": np.zeros(pp_n, np.float32)},
        lambda a: {"out": np.exp(np.log(a["img"]) * np.float32(0.85)
                                 + np.float32(0.1))},
        rodinia="srad extract/compress",
    ))

    entries.append(entry_bfs_frontier())
    entries.append(entry_pathfinder(scale))
    entries.append(entry_needle_nw())
    entries.append(entry_backprop_layer())
    entries.append(entry_lud_diag())
    entries.append(entry_srad_step(scale))
    entries.append(entry_lavamd())
    entries.append(entry_nn())
    entries.append(entry_kmeans())
    entries.append(entry_streamcluster())
    entries.append(entry_hotspot())

    return entries


# --------------------------------------------------------------------------
# Rodinia-mini entry builders (exported so the conformance harness can
# rebuild dtype variants of the parameterizable ones)
# --------------------------------------------------------------------------
def entry_bfs_frontier(n: int = 64, deg: int = 4) -> SuiteEntry:
    kernel = make_bfs_frontier(n, deg)
    block, grid = 32, n // 32     # 32-thread blocks: __syncthreads_count

    def margs(r):
        edges = np.full((n, deg), n, np.int32)
        edges[:, 0] = (np.arange(n) + 1) % n      # ring: everything reachable
        for k in range(1, deg):
            edges[:, k] = r.integers(0, n, n)     # random chords
        frontier = np.zeros(n, np.int32)
        frontier[0] = 1
        visited = np.zeros(n, np.int32)
        visited[0] = 1
        dist = np.full(n, -1, np.int32)
        dist[0] = 0
        return {"edges": edges, "frontier": frontier, "visited": visited,
                "dist": dist, "nxt": np.zeros(n, np.int32),
                "active": np.zeros(1, np.int32),
                "level": np.zeros(1, np.int32)}

    def ref(a):
        edges = np.asarray(a["edges"])
        dist = np.full(n, -1, np.int32)
        dist[0] = 0
        frontier = [0]
        while frontier:
            nxtf = []
            for u in frontier:
                for v in edges[u]:
                    if v < n and dist[v] < 0:
                        dist[v] = dist[u] + 1
                        nxtf.append(int(v))
            frontier = nxtf
        return {"dist": dist, "visited": (dist >= 0).astype(np.int32)}

    def prepare(it, bufs):
        if it == 0:
            return {}
        return {"frontier": bufs["nxt"],
                "nxt": jnp.zeros_like(bufs["nxt"]),
                "active": jnp.zeros_like(bufs["active"]),
                "level": jnp.full((1,), it, jnp.int32)}

    def update(bufs):
        # device-resident prepare: the level counter lives on device and
        # increments there - no per-iteration h2d of a fresh host scalar
        return {"frontier": bufs["nxt"],
                "nxt": jnp.zeros_like(bufs["nxt"]),
                "active": jnp.zeros_like(bufs["active"]),
                "level": bufs["level"] + 1}

    chain = LaunchChain(
        steps=(ChainStep(kernel, grid, block, prepare=prepare,
                         update=update),),
        repeat=n,                 # upper bound; stop flag exits early
        stop=lambda bufs: int(np.asarray(bufs["active"])[0]) == 0,
        device_stop=lambda bufs: bufs["active"][0] == 0,
        check_every=4,            # device-resident stop-poll period
    )
    return SuiteEntry(
        "bfs_frontier", ("atomic_cas", "warp", "const", "chain"),
        kernel, grid, block, None, margs, ref,
        chain=chain, const=("edges",), rodinia="bfs",
        dim3_free=False,
        # the win counter dedups per device: shards that independently
        # claim the same node both count it (loop counts it once)
        nondeterministic_shard=("active",),
        # overshooting a converged frontier is a no-op for dist/visited,
        # but leaves the ping-pong scratch at a cadence-dependent state
        iteration_state=("frontier", "nxt", "active", "level"),
    )


def entry_pathfinder(scale: int = 1, dtype=jnp.int32) -> SuiteEntry:
    rows, cols, block = 6, 256 * scale, 64
    kernel = make_pathfinder(cols, block, dtype=dtype)
    grid = cols // block
    npdt = np.dtype(dtype)

    def margs(r):
        # integer-valued weights stay exact under every dtype variant
        wall = r.integers(0, 10, (rows, cols)).astype(npdt)
        return {"wall": wall, "src": wall[0].copy(),
                "dst": np.zeros(cols, npdt),
                "row": np.ones(1, np.int32)}

    def ref(a):
        wall = np.asarray(a["wall"])
        cur = np.asarray(a["src"]).copy()
        idx = np.arange(cols)
        for r in range(1, rows):
            left = cur[np.clip(idx - 1, 0, cols - 1)]
            right = cur[np.clip(idx + 1, 0, cols - 1)]
            cur = wall[r] + np.minimum(np.minimum(left, cur), right)
        return {"dst": cur}

    def prepare(it, bufs):
        upd = {"row": jnp.full((1,), it + 1, jnp.int32),
               "dst": jnp.zeros_like(bufs["dst"])}
        if it:
            upd["src"] = bufs["dst"]
        return upd

    def update(bufs):
        # device-resident ping-pong: src aliases the previous dst, the
        # row counter increments on device
        return {"src": bufs["dst"], "dst": jnp.zeros_like(bufs["dst"]),
                "row": bufs["row"] + 1}

    chain = LaunchChain(
        steps=(ChainStep(kernel, grid, block, prepare=prepare,
                         update=update),),
        repeat=rows - 1,
    )
    return SuiteEntry(
        "pathfinder", ("barrier", "chain"), kernel, grid, block, None,
        margs, ref, chain=chain, rodinia="pathfinder", dim3_free=False,
    )


def entry_needle_nw(n: int = 32, penalty: int = 2,
                    dtype=jnp.int32) -> SuiteEntry:
    block = 16
    grid = n // block
    kernel = make_needle_nw(n, penalty)
    npdt = np.dtype(dtype)

    def margs(r):
        # integer-valued similarity scores stay exact under f32 too
        sim = r.integers(-3, 4, (n, n)).astype(npdt)
        score = np.zeros((n + 1, n + 1), npdt)
        score[0, :] = -penalty * np.arange(n + 1)
        score[:, 0] = -penalty * np.arange(n + 1)
        return {"score": score, "sim": sim, "diag": np.full(1, 2, np.int32)}

    def ref(a):
        sim = np.asarray(a["sim"])
        s = np.asarray(a["score"]).copy()
        for i in range(1, n + 1):
            for j in range(1, n + 1):
                s[i, j] = max(s[i - 1, j - 1] + sim[i - 1, j - 1],
                              s[i - 1, j] - penalty,
                              s[i, j - 1] - penalty)
        return {"score": s}

    chain = LaunchChain(
        steps=(ChainStep(
            kernel, grid, block,
            prepare=lambda it, bufs: {"diag": jnp.full((1,), it + 2,
                                                       jnp.int32)},
            update=lambda bufs: {"diag": bufs["diag"] + 1}),),
        repeat=2 * n - 1,
    )
    return SuiteEntry(
        "needle_nw", ("chain",), kernel, grid, block, None, margs, ref,
        chain=chain, rodinia="nw", dim3_free=False,
    )


def entry_backprop_layer(in_n: int = 64, out_n: int = 16,
                          lr: float = 0.3) -> SuiteEntry:
    kernel = make_backprop_layer(in_n, out_n, lr)

    def margs(r):
        return {"inp": r.standard_normal(in_n, dtype=np.float32),
                "w": r.standard_normal((out_n, in_n),
                                       dtype=np.float32) * 0.5,
                "bias": r.standard_normal(out_n, dtype=np.float32),
                "delta": r.standard_normal(out_n, dtype=np.float32),
                "hidden": np.zeros(out_n, np.float32),
                "w_out": np.zeros((out_n, in_n), np.float32)}

    def ref(a):
        w, inp = np.asarray(a["w"]), np.asarray(a["inp"])
        hidden = 1.0 / (1.0 + np.exp(-(w @ inp + a["bias"])))
        w_out = w + lr * np.asarray(a["delta"])[:, None] * inp[None, :]
        return {"hidden": hidden.astype(np.float32),
                "w_out": w_out.astype(np.float32)}

    return SuiteEntry(
        "backprop_layer", ("barrier", "const"), kernel, out_n, in_n, None,
        margs, ref, const=("inp", "w", "bias", "delta"),
        rodinia="backprop",
    )


def entry_lud_diag(ntiles: int = 8, b: int = 16) -> SuiteEntry:
    kernel = make_lud_diag(ntiles, b)

    def margs(r):
        a = 0.1 * r.standard_normal((ntiles * b, b)).astype(np.float32)
        for t in range(ntiles):                 # diagonally dominant tiles
            a[t * b:(t + 1) * b] += 4.0 * np.eye(b, dtype=np.float32)
        return {"a": a, "lu": np.zeros((ntiles * b, b), np.float32)}

    def ref(a):
        src = np.asarray(a["a"])
        lu = np.zeros_like(src)
        for t in range(ntiles):
            m = src[t * b:(t + 1) * b].copy()
            for k in range(b - 1):
                m[k + 1:, k] = m[k + 1:, k] / m[k, k]
                m[k + 1:, k + 1:] -= np.outer(m[k + 1:, k], m[k, k + 1:])
            lu[t * b:(t + 1) * b] = m
        return {"lu": lu}

    return SuiteEntry(
        "lud_diag", ("barrier",), kernel, ntiles, b, None, margs, ref,
        tol=1e-4, rodinia="lud",
    )


def entry_srad_step(scale: int = 1, iters: int = 2,
                     lam: float = 0.2) -> SuiteEntry:
    h, w, block = 32, 64 * scale, 128
    npix = h * w
    grid1 = npix // block
    stats_k = make_srad_stats(h, w, block)
    update_k = make_srad_update(h, w, lam)

    def margs(r):
        return {"x": np.exp(0.1 * r.standard_normal((h, w))
                            ).astype(np.float32),
                "y": np.zeros((h, w), np.float32),
                "psum": np.zeros(grid1, np.float32),
                "psq": np.zeros(grid1, np.float32)}

    def ref(a):
        x = np.asarray(a["x"]).astype(np.float32).copy()
        for _ in range(iters):
            total = x.sum(dtype=np.float32)
            totsq = (x * x).sum(dtype=np.float32)
            mean = total / npix
            var = totsq / npix - mean * mean
            q0 = var / (mean * mean)
            xp = np.pad(x, 1, mode="edge")
            dn = xp[:-2, 1:-1] - x
            ds = xp[2:, 1:-1] - x
            dw = xp[1:-1, :-2] - x
            de = xp[1:-1, 2:] - x
            g2 = (dn * dn + ds * ds + dw * dw + de * de) / (x * x)
            ll = (dn + ds + dw + de) / x
            num = 0.5 * g2 - 0.0625 * (ll * ll)
            den = (1.0 + 0.25 * ll) * (1.0 + 0.25 * ll)
            q = num / den
            cd = np.clip(1.0 / (1.0 + (q - q0) / (q0 * (1.0 + q0))), 0, 1)
            x = (x + 0.25 * lam * cd * (dn + ds + dw + de)
                 ).astype(np.float32)
        return {"y": x}

    def prep_stats(it, bufs):
        if it == 0:
            return {}
        return {"x": bufs["y"], "y": jnp.zeros_like(bufs["y"]),
                "psum": jnp.zeros_like(bufs["psum"]),
                "psq": jnp.zeros_like(bufs["psq"])}

    def upd_stats(bufs):
        # device-resident x<->y ping-pong + partials re-zero
        return {"x": bufs["y"], "y": jnp.zeros_like(bufs["y"]),
                "psum": jnp.zeros_like(bufs["psum"]),
                "psq": jnp.zeros_like(bufs["psq"])}

    chain = LaunchChain(
        steps=(ChainStep(stats_k, grid1, block, prepare=prep_stats,
                         update=upd_stats),
               ChainStep(update_k, (w // 8, h // 8), (8, 8))),
        repeat=iters,
    )
    return SuiteEntry(
        "srad_step", ("barrier", "dim3", "chain"), stats_k, grid1, block,
        None, margs, ref, chain=chain, tol=1e-4, rodinia="srad",
        dim3_free=False,
    )


def entry_lavamd(nboxes: int = 8, ppb: int = 32, nnei: int = 3,
                 alpha: float = 0.5) -> SuiteEntry:
    kernel = make_lavamd(nboxes, ppb, nnei, alpha)
    n = nboxes * ppb

    def margs(r):
        nbr = np.empty((nboxes, nnei), np.int32)
        nbr[:, 0] = np.arange(nboxes)                    # home box first
        nbr[:, 1] = (np.arange(nboxes) + 1) % nboxes     # ring neighbors
        for k in range(2, nnei):
            nbr[:, k] = r.integers(0, nboxes, nboxes)
        return {"pos": r.uniform(-2.0, 2.0, n).astype(np.float32),
                "q": r.uniform(0.1, 1.0, n).astype(np.float32),
                "nbr": nbr,
                "force": np.zeros(n, np.float32)}

    def ref(a):
        pos = np.asarray(a["pos"], np.float32)
        q = np.asarray(a["q"], np.float32)
        nbr = np.asarray(a["nbr"])
        force = np.zeros(n, np.float32)
        for b in range(nboxes):
            xi = pos[b * ppb:(b + 1) * ppb]
            acc = np.zeros(ppb, np.float32)
            for k in range(nnei):
                nb = int(nbr[b, k])
                y = pos[nb * ppb:(nb + 1) * ppb]
                qq = q[nb * ppb:(nb + 1) * ppb]
                d = xi[:, None] - y[None, :]
                acc = acc + np.sum(qq[None, :] * np.exp(-alpha * d * d),
                                   axis=1, dtype=np.float32)
            force[b * ppb:(b + 1) * ppb] = acc
        return {"force": force}

    return SuiteEntry(
        "lavamd", ("barrier", "demotion", "const"), kernel, nboxes, ppb,
        None, margs, ref, const=("pos", "q", "nbr"), tol=1e-4,
        rodinia="lavaMD",
    )


def entry_nn(n: int = 256, block: int = 64, knn: int = 8) -> SuiteEntry:
    grid = n // block
    reduce_k = make_nn_reduce(n, block)
    select_k = make_nn_select(grid)

    def margs(r):
        lat = r.uniform(0.0, 90.0, n).astype(np.float32)
        lng = r.uniform(0.0, 180.0, n).astype(np.float32)
        # round-trip through the cane record-file format: the parsed
        # arrays are what the kernels AND the oracle both consume
        lat, lng = rodinia_io.parse_records(
            rodinia_io.format_records(lat, lng))
        return {"lat": lat, "lng": lng,
                "target": np.asarray([30.0, 90.0], np.float32),
                "taken": np.zeros(n, np.int32),
                "pval": np.zeros(grid, np.float32),
                "pidx": np.zeros(grid, np.int32),
                "out_d": np.zeros(knn, np.float32),
                "out_i": np.zeros(knn, np.int32),
                "step": np.zeros(1, np.int32)}

    def ref(a):
        lat = np.asarray(a["lat"], np.float32)
        lng = np.asarray(a["lng"], np.float32)
        tgt = np.asarray(a["target"], np.float32)
        work = (lat - tgt[0]) ** 2 + (lng - tgt[1]) ** 2
        taken = np.zeros(n, np.int32)
        out_d = np.zeros(knn, np.float32)
        out_i = np.zeros(knn, np.int32)
        for t in range(knn):
            w = int(np.argmin(work))     # first minimum: lowest index
            out_d[t] = work[w]
            out_i[t] = w
            taken[w] = 1
            work[w] = np.inf
        return {"out_d": out_d, "out_i": out_i, "taken": taken}

    chain = LaunchChain(
        steps=(ChainStep(reduce_k, grid, block),
               ChainStep(select_k, 1, grid,
                         prepare=lambda it, bufs: {
                             "step": jnp.full((1,), it, jnp.int32)},
                         update=lambda bufs: {"step": bufs["step"] + 1})),
        repeat=knn,
    )
    return SuiteEntry(
        "nn", ("barrier", "chain", "const"), reduce_k, grid, block, None,
        margs, ref, chain=chain, const=("lat", "lng", "target"),
        rodinia="nn", dim3_free=False,
    )


def entry_kmeans(n: int = 256, k: int = 4, block: int = 64,
                 repeat: int = 12) -> SuiteEntry:
    grid = n // block
    assign_k = make_kmeans_assign(n, k)
    update_k = make_kmeans_update(k)

    def margs(r):
        centers = np.asarray([[10, 10], [40, 12], [12, 44], [44, 40]],
                             np.float32)[:k]
        which = r.integers(0, k, n)
        px = (centers[which, 0] + r.integers(-4, 5, n)).astype(np.float32)
        py = (centers[which, 1] + r.integers(-4, 5, n)).astype(np.float32)
        return {"px": px, "py": py,
                "cx": px[:k].copy(), "cy": py[:k].copy(),
                "assign": np.zeros(n, np.int32),
                "changed": np.zeros(1, np.int32),
                "sumx": np.zeros(k, np.float32),
                "sumy": np.zeros(k, np.float32),
                "count": np.zeros(k, np.int32)}

    def ref(a):
        px = np.asarray(a["px"], np.float32)
        py = np.asarray(a["py"], np.float32)
        cx = np.asarray(a["cx"], np.float32).copy()
        cy = np.asarray(a["cy"], np.float32).copy()
        assign = np.asarray(a["assign"]).copy()
        sx = np.zeros(k, np.float32)
        sy = np.zeros(k, np.float32)
        cnt = np.zeros(k, np.int32)
        moved = 0
        for _ in range(repeat):
            d = ((px[:, None] - cx[None, :]) ** 2
                 + (py[:, None] - cy[None, :]) ** 2)
            best = np.argmin(d, axis=1).astype(np.int32)
            moved = int((best != assign).sum())
            assign = best
            cnt = np.bincount(best, minlength=k).astype(np.int32)
            sx = np.bincount(best, weights=px,
                             minlength=k).astype(np.float32)
            sy = np.bincount(best, weights=py,
                             minlength=k).astype(np.float32)
            safe = np.maximum(cnt, 1).astype(np.float32)
            cx = np.where(cnt == 0, cx, sx / safe).astype(np.float32)
            cy = np.where(cnt == 0, cy, sy / safe).astype(np.float32)
            if moved == 0:
                break
        return {"assign": assign, "cx": cx, "cy": cy, "count": cnt,
                "sumx": sx, "sumy": sy,
                "changed": np.asarray([moved], np.int32)}

    def prep_assign(it, bufs):
        if it == 0:
            return {}
        return {"changed": jnp.zeros_like(bufs["changed"]),
                "sumx": jnp.zeros_like(bufs["sumx"]),
                "sumy": jnp.zeros_like(bufs["sumy"]),
                "count": jnp.zeros_like(bufs["count"])}

    def upd_assign(bufs):
        # device-resident re-zero of the per-iteration accumulators
        return {"changed": jnp.zeros_like(bufs["changed"]),
                "sumx": jnp.zeros_like(bufs["sumx"]),
                "sumy": jnp.zeros_like(bufs["sumy"]),
                "count": jnp.zeros_like(bufs["count"])}

    chain = LaunchChain(
        steps=(ChainStep(assign_k, grid, block, prepare=prep_assign,
                         update=upd_assign),
               ChainStep(update_k, k, 8)),
        repeat=repeat,                # upper bound; stop flag exits early
        stop=lambda bufs: int(np.asarray(bufs["changed"])[0]) == 0,
        device_stop=lambda bufs: bufs["changed"][0] == 0,
        check_every=3,
    )
    return SuiteEntry(
        "kmeans", ("atomic", "chain"), assign_k, grid, block, None,
        margs, ref, chain=chain, const=("px", "py"), rodinia="kmeans",
        dim3_free=False,
    )


def entry_streamcluster(n: int = 256, k: int = 8,
                        block: int = 64) -> SuiteEntry:
    grid = n // block
    kernel = make_streamcluster(n, k)

    def margs(r):
        return {"px": r.integers(0, 100, n).astype(np.int32),
                "py": r.integers(0, 100, n).astype(np.int32),
                "cx": r.integers(0, 100, k).astype(np.int32),
                "cy": r.integers(0, 100, k).astype(np.int32),
                "cand": r.integers(0, 100, 2).astype(np.int32),
                "assign": r.integers(0, k, n).astype(np.int32),
                "gain": np.zeros(1, np.int32),
                "csave": np.zeros(k, np.int32),
                "dirty": np.zeros(k, np.int32),
                "ndirty": np.zeros(1, np.int32),
                "switched": np.zeros(n, np.int32)}

    def ref(a):
        px = np.asarray(a["px"], np.int64)
        py = np.asarray(a["py"], np.int64)
        cx, cy = np.asarray(a["cx"]), np.asarray(a["cy"])
        assign = np.asarray(a["assign"])
        cand = np.asarray(a["cand"])
        dcur = (px - cx[assign]) ** 2 + (py - cy[assign]) ** 2
        dcand = (px - cand[0]) ** 2 + (py - cand[1]) ** 2
        sw = dcand < dcur
        save = dcur - dcand
        gain = np.asarray([save[sw].sum()], np.int32)
        csave = np.bincount(assign[sw], weights=save[sw].astype(np.float64),
                            minlength=k).astype(np.int32)
        dirty = np.zeros(k, np.int32)
        dirty[np.unique(assign[sw])] = 1
        return {"gain": gain, "csave": csave, "dirty": dirty,
                "switched": sw.astype(np.int32)}

    return SuiteEntry(
        "streamcluster", ("atomic", "atomic_cas"), kernel, grid, block,
        None, margs, ref,
        const=("px", "py", "cx", "cy", "cand", "assign"),
        rodinia="streamcluster",
        # the CAS winner's distinct-dirty counter dedups per device
        nondeterministic_shard=("ndirty",),
    )


def entry_hotspot(h: int = 32, w: int = 64, iters: int = 4,
                  cap: float = 0.5, rx: float = 0.1, ry: float = 0.1,
                  rz: float = 0.05, amb: float = 80.0) -> SuiteEntry:
    kernel = make_hotspot(h, w, cap=cap, rx=rx, ry=ry, rz=rz, amb=amb)

    def margs(r):
        temp = r.uniform(60.0, 100.0, (h, w)).astype(np.float32)
        power = r.uniform(0.0, 1.0, (h, w)).astype(np.float32)
        # round-trip through hotspot's temp_*/power_* file format: the
        # parsed grids are what the kernels AND the oracle both consume
        temp = rodinia_io.parse_grid(rodinia_io.format_grid(temp), h, w)
        power = rodinia_io.parse_grid(rodinia_io.format_grid(power), h, w)
        return {"t": temp, "p": power,
                "t_out": np.zeros((h, w), np.float32)}

    def ref(a):
        t = np.asarray(a["t"], np.float32).copy()
        p = np.asarray(a["p"], np.float32)
        for _ in range(iters):
            tp = np.pad(t, 1, mode="edge")
            north, south = tp[:-2, 1:-1], tp[2:, 1:-1]
            west, east = tp[1:-1, :-2], tp[1:-1, 2:]
            t = (t + cap * (p + ry * (north + south - 2.0 * t)
                            + rx * (west + east - 2.0 * t)
                            + rz * (amb - t))).astype(np.float32)
        return {"t_out": t}

    def prep(it, bufs):
        if it == 0:
            return {}
        return {"t": bufs["t_out"], "t_out": jnp.zeros_like(bufs["t_out"])}

    def upd(bufs):
        # device-resident t <-> t_out ping-pong
        return {"t": bufs["t_out"], "t_out": jnp.zeros_like(bufs["t_out"])}

    chain = LaunchChain(
        steps=(ChainStep(kernel, (w // 8, h // 8), (8, 8), prepare=prep,
                         update=upd),),
        repeat=iters,
    )
    return SuiteEntry(
        "hotspot", ("barrier", "dim3", "chain", "const"), kernel,
        (w // 8, h // 8), (8, 8), None, margs, ref, chain=chain,
        const=("p",), tol=1e-4, rodinia="hotspot", dim3_free=False,
    )
