"""TPU-native SPMD-to-MPMD **vector** lowering (DESIGN.md S2, beyond-paper).

The whole thread block becomes one chunk: the thread axis is carried as the
leading array axis of every private value and maps onto VPU lanes.  Barriers
(stage boundaries) degenerate to program-order sequence points because array
data-flow already serializes stage N before stage N+1 - this is exactly the
vectorized thread loop that the paper's SVI-C identifies as the missing CPU
optimization ("CuPBoP cannot fully utilize the SIMD instructions"); on TPU it
is the *primary* lowering.

Blocks are scheduled one of two ways, chosen at trace time from the kernel:

* **tiled** - when the blocks are independent by the kernel's own
  declarations: every written buffer declares a ``combines`` mode, and a
  trace of one block, with each written buffer replaced by a
  :class:`_WriteLog`, shows each written buffer only *receiving* writes
  (``.at[...].set``/``max``/``min``, or ``add`` on an integer dtype).
  ``run_block`` is vmapped over a tile of block ids - the whole range
  unless the batched per-block state passes ``_TILE_BYTES``, then a loop
  over tiles - every block returns its write log, and each logged write is
  applied as one scatter over all blocks of the tile, blocks past the
  range dropped.  The results are the serial schedule's bits: the writes
  are disjoint or commute exactly.
* **serial** - every other launch (a kernel that reads a buffer it writes,
  old-value atomics, a float add across blocks, undeclared combines): a
  ``fori_loop`` over fetch x grain blocks, one ``lax.cond``-masked block
  at a time, the loop lowering's structure, so the Table-V grain-size
  experiments run identically under both.

:func:`schedules` reports the schedule each traced launch took, and why.
"""
from __future__ import annotations

import contextlib
import math
import threading

import jax
import jax.numpy as jnp
from jax import lax
from jax.extend.core import ClosedJaxpr, Jaxpr, jaxpr_as_fun

from repro.core.dim3 import Dim3
from repro.core.kernel import (
    BlockState,
    Ctx,
    KernelDef,
    block_range_limit,
    check_priv_chunk,
)

# bytes one tile of the tiled schedule may hold, counted as every
# intermediate of the one-block trace times the blocks of the tile (an
# upper bound: the compiler fuses most of them away)
_TILE_BYTES = 2 << 30
_SCATTER_MODES = (None, "drop", "fill", "promise_in_bounds")
# float primitives whose summation order the compiler picks per shape: a
# batch of blocks may round them differently from one block
_REORDERED = ("reduce_sum", "reduce_prod", "reduce", "dot_general",
              "cumsum", "cumprod", "cumlogsumexp", "conv_general_dilated")
_LOG = threading.local()


def _make_ctx(bid, block, grid):
    """``block``/``grid`` are Dim3; the thread axis is their linear size."""
    return Ctx(
        bid=bid,
        tid=jnp.arange(block.size, dtype=jnp.int32),
        block_dim=block.size,
        grid_dim=grid.size,
        backend="vector",
        uses_warp=True,  # warp ops always expressible on the vector axis
        block_dim3=block,
        grid_dim3=grid,
    )


def run_block(kernel: KernelDef, bid, *, block, grid, glob, dyn_shared=None):
    block, grid = Dim3.of(block), Dim3.of(grid)
    shared = kernel.init_shared(dyn_shared)
    st = BlockState(priv={}, shared=shared, glob=glob)
    ctx = _make_ctx(bid, block, grid)
    # barrier-fission optimizer: shared buffers proven dead after a stage
    # leave the carried state (core/optimize.py drop_shared)
    drop = dict(getattr(kernel, "drop_shared", ()) or ())
    for si, stage in enumerate(kernel.stages):
        st = stage(ctx, st)
        check_priv_chunk(st.priv, block.size, kernel.name, si)
        dead = drop.get(si)
        if dead:
            st = st._replace(
                shared={n: v for n, v in st.shared.items()
                        if n not in dead})
    return st.glob


@contextlib.contextmanager
def schedules():
    """Collect, in this thread, the schedule of every :func:`run` traced
    inside the block: ``"tiled"`` or ``"serial: <reason>"``.  Blocks nest:
    each sees what is traced inside it."""
    log: list[str] = []
    stack = _LOG.__dict__.setdefault("stack", [])
    stack.append(log)
    try:
        yield log
    finally:
        stack.pop()


def _note(schedule: str) -> None:
    for log in getattr(_LOG, "stack", ()):
        log.append(schedule)


class _Serial(Exception):
    """The block trace found a use that may couple blocks; says which."""


class _WriteLog:
    """A written buffer during the one-block trace.

    It exposes ``shape``/``dtype``/``ndim`` and receives writes through
    ``.at[idx]``; each write returns a new log holding the old one's
    records plus ``(op, coords, values)``.  Any other use - a read, a
    ``jnp`` function, arithmetic, a ``lax`` control-flow carry - raises
    :class:`_Serial`.
    """

    def __init__(self, name, shape, dtype, records=()):
        self.name, self.shape = name, tuple(shape)
        self.dtype, self.records = jnp.dtype(dtype), records

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def at(self):
        return _At(self)

    def _refuse(self, *_args, **_kw):
        raise _Serial(f"reads written buffer {self.name!r}")

    # JAX probes ``__jax_array__`` with hasattr before every conversion
    __jax_array__ = property(_refuse)
    __array__ = __getitem__ = __iter__ = __len__ = __bool__ = _refuse
    __float__ = __int__ = __index__ = __neg__ = __abs__ = __invert__ = _refuse

    def __getattr__(self, attr):
        if attr.startswith("__"):
            raise AttributeError(attr)
        self._refuse()

    def __repr__(self):
        return f"_WriteLog({self.name!r}, {self.shape}, {self.dtype})"


for _op in ("add", "sub", "mul", "truediv", "floordiv", "mod", "pow",
            "matmul", "and", "or", "xor", "lshift", "rshift"):
    setattr(_WriteLog, f"__{_op}__", _WriteLog._refuse)
    setattr(_WriteLog, f"__r{_op}__", _WriteLog._refuse)
for _op in ("lt", "le", "gt", "ge"):
    setattr(_WriteLog, f"__{_op}__", _WriteLog._refuse)


class _At:
    def __init__(self, buf: _WriteLog):
        self.buf = buf

    def __getitem__(self, idx):
        return _Ref(self.buf, idx)


class _Ref:
    """``buf.at[idx]`` of a :class:`_WriteLog`."""

    def __init__(self, buf: _WriteLog, idx):
        self.buf, self.idx = buf, idx

    def set(self, values, **kw):
        return self._record("set", values, kw)

    def max(self, values, **kw):
        return self._record("max", values, kw)

    def min(self, values, **kw):
        return self._record("min", values, kw)

    def add(self, values, **kw):
        dtype = self.buf.dtype
        if not jnp.issubdtype(dtype, jnp.integer):
            kind = "float" if jnp.issubdtype(dtype, jnp.inexact) else dtype
            raise _Serial(f"{kind} add into {self.buf.name!r}")
        return self._record("add", values, kw)

    def get(self, *_args, **_kw):
        self.buf._refuse()

    def __getattr__(self, op):
        if op.startswith("__"):
            raise AttributeError(op)
        raise _Serial(f"{op} into written buffer {self.buf.name!r}")

    def _record(self, op, values, kw):
        buf = self.buf
        mode = kw.pop("mode", None)
        kw.pop("indices_are_sorted", None)
        kw.pop("unique_indices", None)
        if kw or mode not in _SCATTER_MODES:
            raise _Serial(f"{op} into {buf.name!r} with "
                          f"{kw or {'mode': mode}}")
        if not buf.shape:
            raise _Serial(f"{op} into 0-d buffer {buf.name!r}")
        coords = _coords(buf.shape, self.idx)
        values = jnp.broadcast_to(jnp.asarray(values).astype(buf.dtype),
                                  coords[0].shape)
        return _WriteLog(buf.name, buf.shape, buf.dtype,
                         (*buf.records, (op, coords, values)))


def _coords(shape, idx):
    """Per-axis int32 coordinates of the elements ``x.at[idx]`` addresses,
    each of ``x[idx]``'s shape.  Negative indices wrap once, as JAX's
    indexing does; where the scatter would drop an element, axis 0 reads
    ``shape[0]``, past the end, and the other axes 0."""
    comps = idx if isinstance(idx, tuple) else (idx,)
    simple = len(comps) == len(shape) and not any(
        c is None or c is Ellipsis or isinstance(c, slice) for c in comps)
    if simple:
        comps = [jnp.asarray(c) for c in comps]
        simple = all(jnp.issubdtype(c.dtype, jnp.integer) for c in comps)
    if simple:
        ok, coords = True, []
        for c, d in zip(jnp.broadcast_arrays(*comps), shape, strict=True):
            c = jnp.where(c < 0, c + d, c)
            ok = ok & (c >= 0) & (c < d)
            coords.append(c)
    else:   # slices, newaxis, ellipsis: let JAX's indexing resolve them
        pos = jnp.arange(math.prod(shape), dtype=jnp.int32).reshape(shape)
        pos = pos.at[idx].get(mode="fill", fill_value=-1)
        ok = pos >= 0
        coords = list(jnp.unravel_index(jnp.maximum(pos, 0), shape))
    coords = [jnp.where(ok, c, 0).astype(jnp.int32) for c in coords]
    coords[0] = jnp.where(ok, coords[0], shape[0])
    return coords


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, those of nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, tuple) else (param,):
                if isinstance(sub, ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, Jaxpr):
                    yield from _eqns(sub)


def _trace_block(kernel, block, grid, glob, dyn_shared):
    """Trace one block with every written buffer a :class:`_WriteLog`.

    Returns ``(logged, ops, nbytes)``: ``logged(bid, readonly)`` gives the
    block's writes as ``[(coords, values), ...]`` in program order per
    buffer, ``ops`` their ``(buffer, op)`` pairs, and ``nbytes`` the size
    of every intermediate of the block.  Raises :class:`_Serial`
    where blocks may depend on each other, or where batching them may
    round a float reduction differently.
    """
    undeclared = [n for n in kernel.writes if n not in kernel.combines]
    if undeclared:
        raise _Serial(f"no combines declared for {undeclared}")
    absent = [n for n in kernel.writes if n not in glob]
    if absent:
        raise _Serial(f"written buffers {absent} not bound")
    readonly = {n: v for n, v in glob.items() if n not in kernel.writes}
    ops = []

    def one(bid, ro):
        logs = {n: _WriteLog(n, jnp.shape(glob[n]), jnp.result_type(glob[n]))
                for n in kernel.writes}
        out = run_block(kernel, bid, block=block, grid=grid,
                        glob={**ro, **logs}, dyn_shared=dyn_shared)
        if set(out) != set(glob):
            raise _Serial(f"changes the global buffer set to {sorted(out)}")
        for n in glob:
            if n not in logs:
                if out[n] is not ro[n]:
                    raise _Serial(f"writes undeclared buffer {n!r}")
            elif not (isinstance(out[n], _WriteLog) and out[n].name == n):
                raise _Serial(f"replaces written buffer {n!r}")
        records = [(n, r) for n in kernel.writes for r in out[n].records]
        ops[:] = [(n, op) for n, (op, _, _) in records]
        return [(coords, values) for _, (_, coords, values) in records]

    closed, shape = jax.make_jaxpr(one, return_shape=True)(
        jnp.int32(0), readonly)
    nbytes = 0
    for eqn in _eqns(closed.jaxpr):
        if eqn.primitive.name in _REORDERED and any(
                jnp.issubdtype(v.aval.dtype, jnp.inexact)
                for v in eqn.invars):
            raise _Serial(f"float {eqn.primitive.name} in the block")
        nbytes += sum(math.prod(v.aval.shape) * v.aval.dtype.itemsize
                      for v in eqn.outvars if hasattr(v.aval, "dtype"))
    tree = jax.tree.structure(shape)
    fn = jaxpr_as_fun(closed)

    def logged(bid, ro):
        return jax.tree.unflatten(tree, fn(bid, *jax.tree.leaves(ro)))

    return logged, ops, nbytes


def _run_tiled(logged, ops, nbytes, glob, written, bid_start, count, limit):
    """The tiled schedule over blocks ``[bid_start, bid_start + count)``,
    ids at or past ``limit`` dropped."""
    tile = max(1, min(count, _TILE_BYTES // max(1, nbytes)))
    readonly = {n: v for n, v in glob.items() if n not in written}

    def one_tile(t0, bufs):
        bids = t0 + jnp.arange(tile, dtype=jnp.int32)
        logs = jax.vmap(lambda b: logged(b, readonly))(bids)
        bufs = dict(bufs)
        for (name, op), (coords, values) in zip(ops, logs, strict=True):
            keep = (bids < limit).reshape(tile, *[1] * (values.ndim - 1))
            first = jnp.where(keep, coords[0], bufs[name].shape[0])
            ref = bufs[name].at[(first, *coords[1:])]
            bufs[name] = getattr(ref, op)(values, mode="drop")
        return bufs

    bufs = {n: glob[n] for n in written}
    n_tiles = -(-count // tile)
    if n_tiles == 1:
        bufs = one_tile(bid_start, bufs)
    else:
        bufs = lax.fori_loop(
            0, n_tiles, lambda i, b: one_tile(bid_start + i * tile, b), bufs)
    return {**glob, **bufs}


def _run_serial(kernel: KernelDef, *, grid, block, glob, grain=1,
                dyn_shared=None, bid_start=0, count=None):
    """The serial schedule: a ``fori_loop`` over fetch x grain blocks, each
    block under a ``lax.cond`` that masks ids past the range."""
    grid, block = Dim3.of(grid), Dim3.of(block)
    count = grid.size if count is None else count
    limit = block_range_limit(bid_start, count, grid.size)
    n_fetch = -(-count // grain)

    def run_bid(bid, g):
        return run_block(kernel, bid, block=block, grid=grid, glob=g,
                         dyn_shared=dyn_shared)

    def fetch_body(f, g):
        def grain_body(i, g_):
            bid = bid_start + f * grain + i
            return lax.cond(bid < limit, lambda x: run_bid(bid, x),
                            lambda x: x, g_)
        return lax.fori_loop(0, grain, grain_body, g)

    return lax.fori_loop(0, n_fetch, fetch_body, glob)


def run(kernel: KernelDef, *, grid, block, glob, grain=1, dyn_shared=None,
        bid_start=0, count=None):
    """``bid_start``/``count`` select a block-range view of the grid (same
    contract as :func:`repro.core.lower_loop.run`): blocks keep their
    global linear id, ids past ``grid.size`` are masked.  ``grain`` shapes
    the serial schedule only."""
    grid, block = Dim3.of(grid), Dim3.of(block)
    try:
        logged, ops, nbytes = _trace_block(kernel, block, grid, glob,
                                           dyn_shared)
    except _Serial as why:
        _note(f"serial: {why}")
        return _run_serial(kernel, grid=grid, block=block, glob=glob,
                           grain=grain, dyn_shared=dyn_shared,
                           bid_start=bid_start, count=count)
    _note("tiled")
    count = grid.size if count is None else count
    limit = block_range_limit(bid_start, count, grid.size)
    return _run_tiled(logged, ops, nbytes, glob, tuple(kernel.writes),
                      bid_start, count, limit)
