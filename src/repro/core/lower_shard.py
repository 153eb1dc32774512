"""Sharded block scheduler: the launch grid spread across XLA devices.

CuPBoP's core claim is that the CUDA *threadblock* is the unit that maps
onto whatever parallel hardware exists - the paper benchmarks against
hand-written OpenMP/MPI precisely because blocks are what scale across
workers (SIV-A's task queue feeds a whole CPU's worth of them).  The
loop/vector/pallas lowerings in this repo are faithful to the
SPMD-to-MPMD transform but execute the entire grid on one device; this
module is the missing multi-worker half: the paper's worker pool realized
as an XLA device mesh.

The transform is a two-level block schedule:

* **partition** - the grid's linear block ids are split into ``n_dev``
  contiguous ranges (``per = ceil(n_blocks / n_dev)`` each, the tail
  masked), mirroring the static partitioning the paper's *average* grain
  policy produces;
* **per-shard execution** - inside ``shard_map`` over a 1-D device mesh,
  each shard runs its range through an existing single-device lowering
  (``lower_loop`` by default - bit-identical to the ``loop`` backend - or
  ``lower_vector``) via the block-range view (``bid_start``/``count``),
  so ``ctx.bid``/``ctx.bid3`` read globally-correct coordinates;
* **combine** - each written buffer's per-shard partials are merged per
  its ``KernelDef.combines`` declaration: ``psum`` of deltas by default
  (exact for disjoint writes and atomicAdd -
  :func:`repro.core.atomics.combine_partials`), ``pmax``/``pmin`` for
  max/min atomics, or - the zero-communication fast path - ``"concat"``
  for owned-slice writes, where each shard keeps only its own
  leading-axis rows and ``shard_map`` assembles the global buffer from
  the shard-local slices (``out_specs=P(axis)``), no collective at all.

Devices come from the platform: real accelerators, or host devices forced
with ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (how CI and
laptops get a worker pool).  ``devices=`` (``LaunchConfig.on``) caps the
shard count; ``shard_axis=`` names the mesh axis so kernels nested inside
an outer mesh can avoid collisions.
"""
from __future__ import annotations

import warnings

import jax
import numpy as np
from jax import lax
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core import atomics, lower_loop, lower_vector
from repro.core import memory as memory_mod
from repro.core.dim3 import Dim3
from repro.core.kernel import KernelDef, UnsupportedKernel

DEFAULT_AXIS = "blocks"

_INNER = {"loop": lower_loop.run, "vector": lower_vector.run}


def resolve_devices(devices: int | None, n_blocks: int) -> int:
    """Shard count for a launch: requested (or all), capped by the grid."""
    avail = jax.device_count()
    n = avail if devices is None else int(devices)
    if n < 1:
        raise ValueError(f"devices must be >= 1, got {devices!r}")
    if n > avail:
        raise ValueError(
            f"{n} devices requested but only {avail} available; on CPU "
            f"hosts set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} before importing jax")
    return min(n, n_blocks)


def _combine_modes(kernel: KernelDef) -> dict[str, str]:
    modes = {name: kernel.combines.get(name, "sum")
             for name in kernel.writes}
    bad = {n: m for n, m in modes.items()
           if m not in atomics.CROSS_SHARD_COMBINES}
    if bad:
        raise UnsupportedKernel(
            f"kernel {kernel.name}: cross-shard combine mode(s) {bad} not "
            f"in {atomics.CROSS_SHARD_COMBINES}")
    stray = set(kernel.combines) - set(kernel.writes)
    if stray:
        raise UnsupportedKernel(
            f"kernel {kernel.name}: combines declared for non-written "
            f"buffer(s) {sorted(stray)} (writes: {tuple(kernel.writes)})")
    if kernel.combines:
        # A partial declaration is almost certainly a bug: the author
        # thought about cross-shard merging and forgot a buffer, and the
        # implicit "sum" default is exact only for accumulation/zero-init
        # writes.  All-or-nothing: declare every written buffer, or none.
        missing = set(kernel.writes) - set(kernel.combines)
        if missing:
            raise UnsupportedKernel(
                f"kernel {kernel.name}: combines declares "
                f"{sorted(kernel.combines)} but is missing written "
                f"buffer(s) {sorted(missing)}; declare a combine mode for "
                f"every written buffer (use 'sum' for the default) or for "
                f"none")
    return modes


def run(kernel: KernelDef, *, grid, block, glob, grain=1, dyn_shared=None,
        devices: int | None = None, shard_axis: str = DEFAULT_AXIS,
        inner: str = "loop"):
    """Execute the launch with its blocks sharded across XLA devices.

    ``glob`` must hold raw arrays: the tracked-buffer wrappers
    (:class:`~repro.core.memory.DeviceBuffer`, ``ConstArray``) are
    unwrapped - with liveness/const checks and donation bookkeeping - on
    the shared :mod:`repro.core.api` launch path.  A wrapper reaching
    ``shard_map`` directly would die in an opaque pytree error, so catch
    it here with the actual fix.  Donated buffers are safe under every
    combine mode: XLA's input-output aliasing preserves the pre-launch
    value the ``"sum"`` combine reads (``g + psum(out - g)``), copying
    only when lifetimes overlap.
    """
    bad = [n for n, v in glob.items()
           if isinstance(v, (memory_mod.ConstArray,
                             memory_mod.DeviceBuffer))]
    if bad:
        raise TypeError(
            f"shard backend received wrapped buffer object(s) {sorted(bad)}"
            f"; launch through repro.core.api (kernel[grid, block](...) or "
            f"launch(...)) so handles are liveness-checked and unwrapped")
    grid, block = Dim3.of(grid), Dim3.of(block)
    inner_run = _INNER[inner]
    modes = _combine_modes(kernel)
    n_blocks = grid.size
    n_dev = resolve_devices(devices, n_blocks)
    if n_dev == 1:       # single worker: the inner lowering verbatim
        return inner_run(kernel, grid=grid, block=block, glob=glob,
                         grain=grain, dyn_shared=dyn_shared)
    per = -(-n_blocks // n_dev)
    mesh = Mesh(np.array(jax.devices()[:n_dev]), (shard_axis,))

    # "concat" (owned-slice) needs equal shard ranges and a leading axis
    # that rows-per-block divides; otherwise degrade to "sum" - correct
    # for accumulation and zero-initialized buffers, but a float
    # overwrite of large prior values rounds through in + (out - in), so
    # the degrade is warned, not silent.
    rows_per_block: dict[str, int] = {}
    for name, mode in list(modes.items()):
        if mode != "concat":
            continue
        rows = np.shape(glob[name])[0] if np.ndim(glob[name]) else 0
        if n_blocks % n_dev == 0 and rows and rows % n_blocks == 0:
            rows_per_block[name] = rows // n_blocks
        else:
            warnings.warn(
                f"kernel {kernel.name}: buffer {name!r} declared "
                f"combines='concat' but grid {n_blocks} / devices {n_dev} "
                f"/ rows {rows} do not divide evenly; falling back to "
                f"'sum' (exact only for accumulation or zero-initialized "
                f"buffers - pad the grid or match the device count for "
                f"owned-slice combining)", stacklevel=2)
            modes[name] = "sum"

    def shard_fn(g):
        start = lax.axis_index(shard_axis) * per
        out = inner_run(kernel, grid=grid, block=block, glob=g,
                        grain=grain, dyn_shared=dyn_shared,
                        bid_start=start, count=per)
        merged = dict(g)
        for name in kernel.writes:
            if modes[name] == "concat":        # keep only the owned rows
                rpb = rows_per_block[name]
                merged[name] = lax.dynamic_slice_in_dim(
                    out[name], start * rpb, per * rpb, 0)
            else:
                merged[name] = atomics.combine_partials(
                    modes[name], g[name], out[name], shard_axis)
        return merged

    # Every buffer goes in replicated (each shard sees the full heap, as
    # every CuPBoP worker sees all of host memory).  Outputs are
    # replicated too - the combine collectives leave identical values on
    # every device - except owned-slice buffers, which come back sharded
    # along the axis and reassemble positionally.
    out_specs = {name: P(shard_axis) if modes.get(name) == "concat" else P()
                 for name in glob}
    # check_vma off: the combine collectives leave replicated values that
    # the static varying-axes check cannot prove replicated
    sharded = jax.shard_map(shard_fn, mesh=mesh, in_specs=(P(),),
                            out_specs=out_specs, check_vma=False)
    return sharded(glob)
