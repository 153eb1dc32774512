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
  (``.at[...].set``/``max``/``min``, or ``add`` on an integer dtype) or
  read element-wise (``buf[idx]``, ``buf.at[idx].get``) before the block
  first writes it.  A read returns the gather from the buffer's
  launch-entry value, which is the serial schedule's value only where no
  other block writes the element, so such *owned reads* are proven once
  per specialization: the read and write coordinates of every buffer the
  block both reads and writes must depend on the block id and constants
  alone (a forward dependence walk over the one-block jaxpr, nested
  jaxprs taken whole), and, evaluated for every block of the grid, every
  element a block reads must be unwritten in the launch or written by that
  block alone.  ``run_block`` is vmapped over a tile of block ids - the
  whole range unless the batched per-block state passes ``_TILE_BYTES``,
  then a loop over tiles - every block returns its write log, and each
  logged write is applied as one scatter over all blocks of the tile,
  blocks past the range dropped.  The results are the serial schedule's
  bits: the writes are disjoint or commute exactly, and CUDA gives the
  blocks of a launch no order.
* **serial** - every other launch (a whole-buffer use of a written buffer,
  a read after the block's own write, coordinates into a read-and-written
  buffer that depend on buffer data, a read of an element another block
  writes, old-value atomics, a float add across blocks, undeclared
  combines): a ``fori_loop`` over fetch x grain blocks, one
  ``lax.cond``-masked block at a time, the loop lowering's structure, so
  the Table-V grain-size experiments run identically under both.

:func:`schedules` reports the schedule each traced launch took, and why:
``"tiled"``, ``"tiled: owned reads of [<buffers>]"`` or
``"serial: <reason>"``.
"""
from __future__ import annotations

import contextlib
import math
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal, jaxpr_as_fun

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
    inside the block: ``"tiled"``, ``"tiled: owned reads of [...]"`` or
    ``"serial: <reason>"``.  Blocks nest:
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

    It exposes ``shape``/``dtype``/``ndim``, receives writes through
    ``.at[idx]`` and, while it holds no records, element reads through
    ``log[idx]`` and ``.at[idx].get``; each write returns a new log holding
    the old one's records plus ``(op, coords, values)``.  A read gathers
    from ``entry``, the buffer's launch-entry value, and appends the flat
    positions it touched to ``reads``, a list the logs of one trace share.
    Any other use - a read after a write, a ``jnp`` function, arithmetic, a
    ``lax`` control-flow carry, a use inside a nested trace - raises
    :class:`_Serial`.
    """

    def __init__(self, name, shape, dtype, entry, reads, records=()):
        self.name, self.shape = name, tuple(shape)
        self.dtype, self.records = jnp.dtype(dtype), records
        self.entry, self.reads = entry, reads
        self.trace = jax.core.get_opaque_trace_state()

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def at(self):
        return _At(self)

    def _refuse(self, *_args, **_kw):
        raise _Serial(f"reads written buffer {self.name!r}")

    def _check_trace(self):
        if jax.core.get_opaque_trace_state() != self.trace:
            raise _Serial(f"uses written buffer {self.name!r} inside a "
                          f"nested trace")

    def __getitem__(self, idx):
        return self._read(idx, None)

    def _read(self, idx, kw):
        self._check_trace()
        if self.records:
            raise _Serial(f"reads {self.name!r} after writing it")
        self.reads.append((self.name, _read_positions(
            self.shape, idx, (kw or {}).get("mode"))))
        return self.entry[idx] if kw is None else self.entry.at[idx].get(**kw)

    # JAX probes ``__jax_array__`` with hasattr before every conversion
    __jax_array__ = property(_refuse)
    __array__ = __iter__ = __len__ = __bool__ = _refuse
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

    def get(self, **kw):
        return self.buf._read(self.idx, kw)

    def __getattr__(self, op):
        if op.startswith("__"):
            raise AttributeError(op)
        raise _Serial(f"{op} into written buffer {self.buf.name!r}")

    def _record(self, op, values, kw):
        buf = self.buf
        buf._check_trace()
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
        return _WriteLog(buf.name, buf.shape, buf.dtype, buf.entry, buf.reads,
                         (*buf.records, (op, coords, values)))


def _int_components(shape, idx):
    """``idx`` as one integer array per axis of ``shape``, or ``None`` where
    it holds slices, newaxis, ellipsis, floats or too few components."""
    comps = idx if isinstance(idx, tuple) else (idx,)
    if len(comps) != len(shape) or any(
            c is None or c is Ellipsis or isinstance(c, slice) for c in comps):
        return None
    comps = [jnp.asarray(c) for c in comps]
    if not all(jnp.issubdtype(c.dtype, jnp.integer) for c in comps):
        return None
    return jnp.broadcast_arrays(*comps)


def _coords(shape, idx):
    """Per-axis int32 coordinates of the elements ``x.at[idx]`` addresses,
    each of ``x[idx]``'s shape.  Negative indices wrap once, as JAX's
    indexing does; where the scatter would drop an element, axis 0 reads
    ``shape[0]``, past the end, and the other axes 0."""
    comps = _int_components(shape, idx)
    if comps is not None:
        ok, coords = True, []
        for c, d in zip(comps, shape, strict=True):
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


def _read_positions(shape, idx, mode):
    """Flat int32 positions of the elements ``x.at[idx].get(mode=mode)``
    reads, each of its result's shape; negative where it reads none.
    Negative indices wrap once; past the ends the ``fill`` mode reads
    nothing and every other mode reads the clamped element."""
    fill = str(mode).lower().endswith(("fill", "drop"))
    comps = _int_components(shape, idx)
    if comps is None:   # slices, newaxis, ellipsis: JAX's indexing resolves
        pos = jnp.arange(math.prod(shape), dtype=jnp.int32).reshape(shape)
        if fill:
            return pos.at[idx].get(mode="fill", fill_value=-1)
        return pos.at[idx].get(mode="clip")
    ok, flat = True, 0
    for c, d in zip(comps, shape, strict=True):
        c = jnp.where(c < 0, c + d, c).astype(jnp.int32)
        ok = ok & (c >= 0) & (c < d)
        flat = flat * d + jnp.clip(c, 0, d - 1)
    return jnp.where(ok, flat, -1) if fill else flat


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

    Returns ``(logged, ops, nbytes, owned)``: ``logged(bid, readonly,
    entry)`` gives the block's writes as ``[(coords, values), ...]`` in
    program order per buffer, ``ops`` their ``(buffer, op)`` pairs,
    ``nbytes`` the size of every intermediate of the block, and ``owned``
    the written buffers the block reads, whose launch-entry values
    ``entry`` maps (an operand of the block only where ``owned`` is not
    empty).  Raises :class:`_Serial` where blocks may depend on each
    other, or where batching them may round a float reduction differently.
    """
    undeclared = [n for n in kernel.writes if n not in kernel.combines]
    if undeclared:
        raise _Serial(f"no combines declared for {undeclared}")
    absent = [n for n in kernel.writes if n not in glob]
    if absent:
        raise _Serial(f"written buffers {absent} not bound")
    readonly = {n: v for n, v in glob.items() if n not in kernel.writes}
    written = {n: glob[n] for n in kernel.writes}
    ops, reads = [], []

    def one(bid, ro, entry):
        reads.clear()
        logs = {n: _WriteLog(n, jnp.shape(v), jnp.result_type(v), entry[n],
                             reads)
                for n, v in written.items()}
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
        return ([(coords, values) for _, (_, coords, values) in records],
                [pos for _, pos in reads])

    closed, shape = jax.make_jaxpr(one, return_shape=True)(
        jnp.int32(0), readonly, written)
    nbytes = 0
    for eqn in _eqns(closed.jaxpr):
        if eqn.primitive.name in _REORDERED and any(
                jnp.issubdtype(v.aval.dtype, jnp.inexact)
                for v in eqn.invars):
            raise _Serial(f"float {eqn.primitive.name} in the block")
        nbytes += sum(math.prod(v.aval.shape) * v.aval.dtype.itemsize
                      for v in eqn.outvars if hasattr(v.aval, "dtype"))
    # the entry values are operands of the block only where it reads them
    owned = sorted({n for n, _ in reads})
    n_ro = len(jax.tree.leaves(readonly))
    entry_vars = dict(zip(sorted(written), closed.jaxpr.invars[1 + n_ro:],
                          strict=True))
    closed = ClosedJaxpr(closed.jaxpr.replace(invars=[
        *closed.jaxpr.invars[:1 + n_ro], *(entry_vars[n] for n in owned)]),
        closed.consts)
    if owned:
        _prove_owned(closed, grid.size, written, ops, [n for n, _ in reads])
    tree = jax.tree.structure(shape)
    fn = jaxpr_as_fun(closed)

    def logged(bid, ro, entry):
        outs = fn(bid, *jax.tree.leaves(ro), *(entry[n] for n in owned))
        return jax.tree.unflatten(tree, outs)[0]

    return logged, ops, nbytes, owned


def _prove_owned(closed, n_blocks, written, ops, read_names):
    """Raise :class:`_Serial` unless every element a block reads of a
    buffer it writes is unwritten in the launch or written by that block
    alone, over all ``n_blocks`` blocks of the grid.

    ``closed`` is the one-block jaxpr over ``(bid, *buffers)``; its outputs
    are the write records (``ndim`` coordinates and the values each, in
    ``ops`` order), then the flat positions of each read, in
    ``read_names`` order.  The coordinates must depend on the block id and
    constants alone; the equations that compute them are then evaluated
    for every block on the host, so the proof holds whatever traces the
    launch (an outer ``jit`` or ``vmap`` included).
    """
    jaxpr = closed.jaxpr
    both = set(read_names) & {n for n, _ in ops}
    if not both:
        return
    wanted, i = [], 0
    for n, _ in ops:
        ndim = len(jnp.shape(written[n]))
        if n in both:
            wanted.append(("write", n, range(i, i + ndim)))
        i += ndim + 1
    for n in read_names:
        if n in both:
            wanted.append(("read", n, range(i, i + 1)))
        i += 1
    tainted = set(jaxpr.invars[1:])
    for eqn in jaxpr.eqns:           # nested jaxprs count as one equation
        if any(not isinstance(v, Literal) and v in tainted
               for v in eqn.invars):
            tainted.update(eqn.outvars)
    outs = [jaxpr.outvars[j] for _, _, at in wanted for j in at]
    for _, n, at in wanted:
        if any(not isinstance(jaxpr.outvars[j], Literal)
               and jaxpr.outvars[j] in tainted for j in at):
            raise _Serial(f"data-dependent coordinates into written buffer "
                          f"{n!r}")
    # the equations the coordinates need read no buffer: keep only them
    need, eqns = {v for v in outs if not isinstance(v, Literal)}, []
    for eqn in reversed(jaxpr.eqns):
        if need.intersection(eqn.outvars):
            eqns.append(eqn)
            need.update(v for v in eqn.invars if not isinstance(v, Literal))
    sliced = ClosedJaxpr(jaxpr.replace(invars=jaxpr.invars[:1], outvars=outs,
                                       eqns=eqns[::-1]), closed.consts)
    with jax.ensure_compile_time_eval(), jax.default_device(_host()):
        got = jax.jit(jax.vmap(jaxpr_as_fun(sliced)))(
            np.arange(n_blocks, dtype=np.int32))
    got = iter(np.asarray(g) for g in got)
    lo, hi = {}, {}      # per buffer: least and greatest writing block
    for kind, n, at in wanted:
        shape = jnp.shape(written[n])
        cs = [next(got) for _ in at]
        if kind == "write":
            ok = cs[0] < shape[0]
            pos = np.ravel_multi_index([np.where(ok, c, 0) for c in cs],
                                       shape)
            pos, bid = pos[ok], _block_ids(ok)[ok]
            size = math.prod(shape)
            lo.setdefault(n, np.full(size, n_blocks, np.int64))
            hi.setdefault(n, np.full(size, -1, np.int64))
            np.minimum.at(lo[n], pos, bid)
            np.maximum.at(hi[n], pos, bid)
            continue
        ok = cs[0] >= 0
        pos, bid = cs[0][ok], _block_ids(ok)[ok]
        bad = (hi[n][pos] >= 0) & ((lo[n][pos] != bid) | (hi[n][pos] != bid))
        if bad.any():
            j = int(np.argmax(bad))
            p, b = pos[j], bid[j]
            other = lo[n][p] if lo[n][p] != b else hi[n][p]
            raise _Serial(f"block {b} reads {n!r} written by block {other}")


def _block_ids(batched):
    """The block id of every element of an array batched over blocks."""
    return np.broadcast_to(
        np.arange(batched.shape[0]).reshape(-1, *[1] * (batched.ndim - 1)),
        batched.shape)


def _host():
    """The host's CPU device, where the runtime has one."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


def _run_tiled(logged, ops, nbytes, glob, written, owned, bid_start, count,
               limit):
    """The tiled schedule over blocks ``[bid_start, bid_start + count)``,
    ids at or past ``limit`` dropped; every block reads the ``owned``
    buffers' launch-entry values."""
    tile = max(1, min(count, _TILE_BYTES // max(1, nbytes)))
    readonly = {n: v for n, v in glob.items() if n not in written}
    entry = {n: glob[n] for n in owned}

    def one_tile(t0, bufs):
        bids = t0 + jnp.arange(tile, dtype=jnp.int32)
        logs = jax.vmap(lambda b: logged(b, readonly, entry))(bids)
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
        logged, ops, nbytes, owned = _trace_block(kernel, block, grid, glob,
                                                  dyn_shared)
    except _Serial as why:
        _note(f"serial: {why}")
        return _run_serial(kernel, grid=grid, block=block, glob=glob,
                           grain=grain, dyn_shared=dyn_shared,
                           bid_start=bid_start, count=count)
    _note(f"tiled: owned reads of {owned}" if owned else "tiled")
    count = grid.size if count is None else count
    limit = block_range_limit(bid_start, count, grid.size)
    return _run_tiled(logged, ops, nbytes, glob, tuple(kernel.writes), owned,
                      bid_start, count, limit)
