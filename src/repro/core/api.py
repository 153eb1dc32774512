"""Kernel-launch API: ``kernel[<<<grid, block, dyn_shared, stream>>>](args)``.

Launch configurations are JIT-specialized per (kernel, backend, grid, block,
grain, shapes) - the same choice POCL makes ("replaces these variables with
actual values during the kernel launch... makes MPMD kernels easy to
optimize", paper SVII-A.1); the compiled-launch cache plays the role of
CuPBoP's once-per-program thread pool: one expensive setup, then cheap
launches.

Two equivalent entry points:

* triple-chevron (CUDA-shaped): ``kernel[grid, block](**buffers)`` where
  ``grid``/``block`` are ints or up-to-3-tuples (``dim3``), with optional
  ``dyn_shared`` and ``stream`` slots - ``kernel[(gx, gy), (bx, by), shmem,
  stream]`` mirrors ``kernel<<<dim3(gx,gy), dim3(bx,by), shmem, stream>>>``;
* keyword (legacy): ``launch(kernel, grid=..., block=..., args=...)`` - a
  thin shim over the same path.

Backends come from the open registry in :mod:`repro.core.backends`; the
compiled-launch cache is weak-keyed on the kernel so entries die with their
``KernelDef`` (and ``cache_clear()`` resets it for benchmarks).  The cache
is two-level: a bounded in-memory LRU of :class:`CompiledKernel` entries
(warm launches skip trace+lower entirely) over an optional on-disk artifact
store (:mod:`repro.core.compile_cache` - the ``cudaModuleLoad`` analogue,
enabled via ``CUPBOP_CACHE_DIR`` or :func:`enable_disk_cache`).

Tracing: each launch is a ``cupbop.launch`` span (``cupbop.launch_batch``
for stacked batches) holding ``cupbop.compile`` around the miss path and
``cupbop.dispatch`` around the call of the compiled entry.  The spans are
``jax.profiler.TraceAnnotation``s, recorded only while a profiler session
is on, on the same clock as the device trace.  Every device program is
named for its kernel (XLA module ``jit_<kernel>__<backend>``), and the
kernel body runs under ``jax.named_scope(<kernel>)``.  :class:`CacheStats`
times the miss path, first dispatches and warm launches at all times.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import os
import re
import time
import traceback
import weakref
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import backends as backends_mod
from repro.core import compile_cache
from repro.core import grain as grain_mod
from repro.core import memory as memory_mod
from repro.core import lower_vector, packing, pallas_emit
from repro.core.backends import backend_names, get_backend, register_backend
from repro.core.dim3 import Dim3
from repro.core.kernel import CompiledKernel, KernelDef, UnsupportedKernel

__all__ = [
    "BACKENDS", "CacheStats", "LaunchConfig", "cache_clear", "cache_resize",
    "cache_size", "cache_stats", "compiled", "coverage",
    "disable_disk_cache", "enable_disk_cache", "launch", "launch_batch",
    "register_backend", "supported",
]

# The compiled-launch cache lives ON each kernel (a private dict attached to
# the KernelDef), so entries die exactly when their kernel does - the seed
# keyed a global dict on id(kernel), which can collide after a KernelDef is
# garbage-collected.  A WeakKeyDictionary would not fix that: the cached
# jitted fn closes over the kernel, and weak-key mappings hold values
# strongly, so the value->key edge would pin every entry forever.  Attached
# to the kernel, kernel -> cache -> jitted fn -> kernel is a pure cycle the
# GC collects.  The WeakSet only enumerates kernels for cache_clear();
# the LRU order ring holds (weakref, key) pairs so eviction never extends
# a kernel's lifetime, and entries of dead kernels are pruned lazily.
_CACHE_ATTR = "_launch_cache"
_CACHED_KERNELS: "weakref.WeakSet[KernelDef]" = weakref.WeakSet()
_LRU: "collections.OrderedDict[tuple, None]" = collections.OrderedDict()
_MAX_ENTRIES = max(1, int(os.environ.get("CUPBOP_CACHE_SIZE", "256")))
_DISK: "compile_cache.DiskCache | None" = compile_cache.from_env()


@dataclasses.dataclass
class CacheStats:
    """Counters for the compiled-launch cache (reset by ``cache_clear``).

    ``hits``/``misses`` count in-memory lookups; ``disk_hits`` are misses
    served by deserializing an on-disk artifact instead of re-tracing;
    ``disk_stores`` count artifacts persisted; ``evictions`` count LRU
    drops after the cache exceeded its bound.

    Host seconds, from ``time.perf_counter``: ``trace_s`` is the miss path
    (the ``eval_shape`` trace, the Mosaic compile for ``pallas``, any
    disk-artifact load or store); ``first_calls``/``first_call_s`` count
    and time the first dispatch of each new specialization, a graph
    replay's included (XLA lowering plus the backend compile or the
    persistent-cache fetch); ``warm_launches``/``warm_launch_s`` count and
    time whole launches of a specialization already dispatched before -
    the steady-state launch cost, sanitize and optimize passes included
    when they are on.

    ``vector_tiled``/``vector_serial`` count traced specializations (plain
    and batched entries; shard backends with a vector inner lowering
    included) by the block schedule :mod:`repro.core.lower_vector` chose;
    the entry's ``schedule`` says why a serial one stayed serial.
    ``tiled_launches``/``serial_launches`` count launches (warm and cold,
    a stacked batch once) of entries with a vector block schedule, by
    that schedule.
    """

    hits: int = 0
    misses: int = 0
    evictions: int = 0
    disk_hits: int = 0
    disk_stores: int = 0
    trace_s: float = 0.0
    first_calls: int = 0
    first_call_s: float = 0.0
    warm_launches: int = 0
    warm_launch_s: float = 0.0
    vector_tiled: int = 0
    vector_serial: int = 0
    tiled_launches: int = 0
    serial_launches: int = 0


_STATS = CacheStats()
_span = jax.profiler.TraceAnnotation


def __getattr__(name: str):
    if name == "BACKENDS":  # legacy frozen tuple, now a registry snapshot
        return backend_names()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def _kernel_cache(kernel: KernelDef) -> dict:
    cache = getattr(kernel, _CACHE_ATTR, None)
    if cache is None:
        cache = {}
        object.__setattr__(kernel, _CACHE_ATTR, cache)  # frozen dataclass
        _CACHED_KERNELS.add(kernel)
    return cache


def _lru_touch(kernel: KernelDef, key: tuple) -> None:
    _LRU.move_to_end((weakref.ref(kernel), key))


def _evict_to_bound() -> None:
    while len(_LRU) > _MAX_ENTRIES:
        (ref, old_key), _ = _LRU.popitem(last=False)
        owner = ref()
        if owner is None:          # kernel already died; stale order entry
            continue
        if getattr(owner, _CACHE_ATTR, {}).pop(old_key, None) is not None:
            _STATS.evictions += 1


def _lru_insert(kernel: KernelDef, key: tuple) -> None:
    _LRU[(weakref.ref(kernel), key)] = None
    _evict_to_bound()


def cache_clear() -> None:
    """Drop all compiled launches and reset stats (benchmark isolation)."""
    for k in list(_CACHED_KERNELS):
        getattr(k, _CACHE_ATTR, {}).clear()
    _LRU.clear()
    global _STATS
    _STATS = CacheStats()


def cache_size() -> int:
    return sum(len(getattr(k, _CACHE_ATTR, {})) for k in _CACHED_KERNELS)


def cache_stats() -> CacheStats:
    """A snapshot of the cache counters."""
    return dataclasses.replace(_STATS)


def cache_resize(max_entries: int) -> None:
    """Re-bound the LRU (evicting down if needed); benchmarks use 1-2."""
    global _MAX_ENTRIES
    if max_entries < 1:
        raise ValueError(f"cache bound must be >= 1, got {max_entries}")
    _MAX_ENTRIES = max_entries
    _evict_to_bound()


def enable_disk_cache(path: str) -> "compile_cache.DiskCache":
    """Persist compile artifacts under ``path`` (cudaModuleLoad analogue)."""
    global _DISK
    _DISK = compile_cache.DiskCache(path)
    return _DISK


def disable_disk_cache() -> None:
    global _DISK
    _DISK = None


def device_opts(backend_entry, devices, shard_axis) -> dict:
    """Extra builder kwargs for multi-device backends.

    Only backends tagged ``multi_device`` receive ``devices``/
    ``shard_axis`` - single-device builders (including third-party ones
    registered before the tag existed) keep the plain uniform signature.
    """
    if backend_entry.supports("multi_device"):
        return {"devices": devices, "shard_axis": shard_axis}
    return {}


def program_name(*parts: str) -> str:
    """``parts`` joined by ``__`` with every character outside
    ``[A-Za-z0-9_]`` replaced by ``_``: the name a device program carries
    (its XLA module reads ``jit_<name>``)."""
    return re.sub(r"[^A-Za-z0-9_]", "_", "__".join(parts))


def _body(kernel: KernelDef, backend: str, grid: Dim3, block: Dim3,
          grain: int, dyn_shared, treedef, interpret: bool, **extra):
    """The kernel over packed leaves, named ``<kernel>__<backend>``."""
    entry = get_backend(backend)
    scope = program_name(kernel.name)

    def fn(*leaves):
        glob = packing.unpack(leaves, treedef)  # kernel prologue (SIII-C.2)
        with jax.named_scope(scope):
            return entry.run(kernel, grid=grid, block=block, glob=glob,
                             grain=grain, dyn_shared=dyn_shared,
                             interpret=interpret, **extra)

    fn.__name__ = fn.__qualname__ = program_name(kernel.name, backend)
    return fn


def _build(kernel: KernelDef, backend: str, grid: Dim3, block: Dim3,
           grain: int, dyn_shared, treedef, interpret: bool,
           devices, shard_axis, donate_idx: tuple[int, ...] = ()):
    fn = _body(kernel, backend, grid, block, grain, dyn_shared, treedef,
               interpret, **device_opts(get_backend(backend), devices,
                                        shard_axis))
    # leaves of declared-donated, handle-bound buffers hand their storage
    # to XLA: the input array is consumed (deleted) and may alias the
    # output buffer - safe because the caller's only path to it is the
    # DeviceBuffer handle, which rebind_outputs points at the output
    return jax.jit(fn, donate_argnums=donate_idx)


def _resolve_grain(kernel: KernelDef, grain, pool, n_blocks: int) -> int:
    if isinstance(grain, str):
        pool = pool or jax.device_count()
        if grain == "average":
            grain = grain_mod.average_grain(n_blocks, pool)
        elif grain == "aggressive":
            grain = grain_mod.heuristic_grain(n_blocks, pool,
                                              kernel.est_block_work)
        else:
            raise ValueError(f"unknown grain policy {grain!r}")
    return max(1, min(int(grain), n_blocks))


def _compile(kernel: KernelDef, backend: str, grid: Dim3, block: Dim3,
             grain: int, dyn_shared, interpret: bool, treedef, leaves,
             shapes, key: tuple, devices, shard_axis,
             donate_idx: tuple[int, ...] = ()) -> CompiledKernel:
    """Cache-miss path: disk artifact if available, else trace+lower."""
    akey = None
    if _DISK is not None:
        akey = compile_cache.artifact_key(
            kernel.fingerprint(), backend, grid, block, grain, dyn_shared,
            interpret, treedef, shapes, devices=devices,
            shard_axis=shard_axis, donate_idx=donate_idx)
        loaded = _DISK.load(akey)
        if loaded is not None:
            # deserialized artifacts dispatch without donation (jax.export
            # does not carry aliasing); handle re-binding still applies, so
            # semantics match - only the storage reuse is lost
            _STATS.disk_hits += 1

            def fn(*leaves):
                return loaded(*leaves)

            fn.__name__ = fn.__qualname__ = program_name(kernel.name, backend)
            return CompiledKernel(kernel=kernel, backend=backend, grid=grid,
                                  block=block, key=key, fn=jax.jit(fn),
                                  source="disk")
    fn = _build(kernel, backend, grid, block, grain, dyn_shared, treedef,
                interpret, devices, shard_axis, donate_idx)
    # surface UnsupportedKernel eagerly (coverage probes rely on this)
    jax.eval_shape(fn, *leaves)
    if backend == "pallas" and not interpret:
        mosaic_compile(fn, leaves)
    if _DISK is not None and _DISK.store(akey, fn, leaves):
        _STATS.disk_stores += 1
    return CompiledKernel(kernel=kernel, backend=backend, grid=grid,
                          block=block, key=key, fn=fn, source="trace")


def mosaic_compile(fn, leaves) -> None:
    """Compile a non-interpret ``pallas`` entry ahead of time.

    Mosaic refuses kernels at lowering, after ``jax.eval_shape`` has
    passed, so without this a refused kernel would read as supported and
    then crash at dispatch.  ``leaves`` may be arrays or
    ``ShapeDtypeStruct``s (with the target device's sharding).  With the
    persistent compilation cache on, the dispatch that follows reuses
    this compile.
    """
    try:
        fn.lower(*leaves).compile()
    except Exception as e:  # noqa: BLE001 - every refusal means unsupported
        msg = str(e).strip().splitlines()[0] if str(e).strip() else ""
        if not msg:         # bare asserts inside the Mosaic lowering
            frame = traceback.extract_tb(e.__traceback__)[-1]
            msg = f"at {os.path.basename(frame.filename)}:{frame.lineno}"
        raise UnsupportedKernel(
            f"pallas/Mosaic: {type(e).__name__}: {msg}") from e


def _entry_for(kernel: KernelDef, grid: Dim3, block: Dim3, args: dict,
               backend: str, grain, dyn_shared, interpret: bool | None,
               pool, devices=None,
               shard_axis: str = "blocks") -> tuple[CompiledKernel, tuple]:
    """Resolve the launch specialization: memory hit, disk hit, or compile."""
    grain = _resolve_grain(kernel, grain, pool, grid.size)
    # resolved before keying, so the cache holds what actually ran
    interpret = pallas_emit.resolve_interpret(interpret)
    # single-device backends ignore the device options, so normalize them
    # out of the key - launch(backend="loop", devices=4) must share the
    # specialization (and disk artifact) of the plain launch
    opts = device_opts(get_backend(backend), devices, shard_axis)
    devices = opts.get("devices")
    shard_axis = opts.get("shard_axis", "blocks")
    # handle liveness + CONST-space enforcement: reject freed DeviceBuffer
    # and written-ConstArray bindings, unwrap the rest (honored here so
    # every backend obeys); donation applies only to declared buffers the
    # caller bound by live handle (memory.donated_names)
    donated = set(memory_mod.donated_names(kernel, args))
    args = memory_mod.resolve_launch_args(kernel, args)
    leaves, treedef = packing.pack(args)  # host prologue (SIII-C.2)
    donate_idx = _donate_leaf_indices(args, donated)
    shapes = tuple((l.shape, jnp.asarray(l).dtype.name) for l in leaves)
    key = (backend, grid, block, grain, dyn_shared, interpret, treedef,
           shapes, devices, shard_axis, donate_idx)
    per_kernel = _kernel_cache(kernel)
    entry = per_kernel.get(key)
    if entry is not None:
        _STATS.hits += 1
        _lru_touch(kernel, key)
        return entry, leaves
    _STATS.misses += 1
    with _miss_path() as traced:
        entry = _compile(kernel, backend, grid, block, grain, dyn_shared,
                         interpret, treedef, leaves, shapes, key, devices,
                         shard_axis, donate_idx)
    _keep_schedule(entry, traced)
    per_kernel[key] = entry
    _lru_insert(kernel, key)
    return entry, leaves


@contextlib.contextmanager
def _miss_path():
    """The ``cupbop.compile`` span; its host seconds go to
    :attr:`CacheStats.trace_s`.  Yields the block schedules of the vector
    lowerings traced inside it."""
    t0 = time.perf_counter()
    try:
        with _span("cupbop.compile"), lower_vector.schedules() as traced:
            yield traced
    finally:
        _STATS.trace_s += time.perf_counter() - t0


def _keep_schedule(entry: CompiledKernel, traced: list[str]) -> None:
    """Record a new entry's vector block schedule on it, and count it."""
    if not traced:
        return
    entry.schedule = next((s for s in traced if s != "tiled"), "tiled")
    if entry.schedule == "tiled":
        _STATS.vector_tiled += 1
    else:
        _STATS.vector_serial += 1


def _count_launch(entry: CompiledKernel) -> None:
    """Count a launch of ``entry`` by its vector block schedule."""
    if entry.schedule == "tiled":
        _STATS.tiled_launches += 1
    elif entry.schedule is not None:
        _STATS.serial_launches += 1


def count_first_dispatch(t0: float) -> None:
    """Count the first dispatch of a new specialization (a launch entry,
    or a graph replay), begun at ``time.perf_counter()`` ``t0``, in
    :class:`CacheStats` ``first_calls``/``first_call_s``."""
    _STATS.first_calls += 1
    _STATS.first_call_s += time.perf_counter() - t0


def _dispatch(entry: CompiledKernel, leaves) -> tuple[Any, bool]:
    """Call a compiled entry under ``cupbop.dispatch``; the first call of
    a specialization is counted with :func:`count_first_dispatch`.
    Returns the outputs and whether this was that first call."""
    first = entry.hits == 0
    t0 = time.perf_counter()
    with _span("cupbop.dispatch"):
        out = entry(*leaves)
    if first:
        count_first_dispatch(t0)
    return out, first


def _donate_leaf_indices(resolved_args: dict, donated: set) -> tuple:
    """Leaf positions of donated buffers in the packed ``void**`` tuple."""
    if not donated:
        return ()
    idx, pos = [], 0
    for name in sorted(resolved_args):   # tree_flatten's dict-key order
        n_leaves = len(jax.tree_util.tree_leaves(resolved_args[name]))
        if name in donated:
            idx.extend(range(pos, pos + n_leaves))
        pos += n_leaves
    return tuple(idx)


def _sanitize_enabled(sanitize) -> bool:
    """Explicit ``sanitize=`` wins; otherwise the CUPBOP_SANITIZE env var."""
    if sanitize is not None:
        return bool(sanitize)
    return os.environ.get("CUPBOP_SANITIZE", "0") not in ("", "0")


def _optimize_enabled(optimize) -> bool:
    """Explicit ``optimize=`` wins; otherwise the CUPBOP_OPTIMIZE env var."""
    if optimize is not None:
        return bool(optimize)
    return os.environ.get("CUPBOP_OPTIMIZE", "0") not in ("", "0")


def _launch(kernel: KernelDef, grid: Dim3, block: Dim3, args: dict,
            backend: str, grain, dyn_shared, interpret: bool | None,
            pool, devices=None, shard_axis: str = "blocks",
            sanitize: bool | None = None,
            optimize: bool | None = None) -> dict:
    t0 = time.perf_counter()
    with _span("cupbop.launch", kernel=kernel.name, backend=backend):
        if _sanitize_enabled(sanitize):
            # kernelcheck gate: races / declaration drift / donation
            # hazards fail the launch before any compiled entry runs.
            # Clean verdicts are memoized on the kernel, so chains
            # re-check for free.  Runs on the BASE kernel (before any
            # optimize rewrite) so finding stage indices match the
            # author's source.
            from repro.core import analyze as analyze_mod
            analyze_mod.sanitize_launch(kernel, grid=grid, block=block,
                                        args=args, dyn_shared=dyn_shared)
        if _optimize_enabled(optimize):
            # barrier-fission optimizer: swap in the verdict-backed
            # derived kernel (memoized per geometry+shapes).  The derived
            # kernel has its own fingerprint domain, so both compile-cache
            # tiers keep optimized and unoptimized specializations apart.
            from repro.core import optimize as optimize_mod
            kernel = optimize_mod.optimize_launch(kernel, grid=grid,
                                                  block=block, args=args,
                                                  dyn_shared=dyn_shared)
        entry, leaves = _entry_for(kernel, grid, block, args, backend,
                                   grain, dyn_shared, interpret, pool,
                                   devices, shard_axis)
        out, first = _dispatch(entry, leaves)
        _count_launch(entry)
        # donated handle-bound buffers come back as the SAME handle,
        # re-bound to the kernel's output (the CUDA in-place view);
        # everything else is a plain functional result
        out = memory_mod.rebind_outputs(kernel, args, out)
    if not first:
        _STATS.warm_launches += 1
        _STATS.warm_launch_s += time.perf_counter() - t0
    return out


def compiled(kernel: KernelDef, *, grid, block, args: dict,
             backend: str = "vector", grain: int | str = 1,
             dyn_shared: int | None = None, interpret: bool | None = None,
             pool: int | None = None, devices: int | None = None,
             shard_axis: str = "blocks",
             optimize: bool | None = None) -> CompiledKernel:
    """Compile (or fetch) the launch specialization without running it.

    The ``cudaModuleGetFunction`` analogue: pre-warm a specialization
    (e.g. at service startup, before traffic) or inspect its provenance -
    callers get the same :class:`CompiledKernel` a warm ``launch`` would
    dispatch through, with ``source`` telling whether it came from trace,
    memory, or a disk artifact.  ``optimize=True`` pre-warms the
    barrier-fission-optimized specialization instead (its own fingerprint,
    so it never collides with the base kernel's cache entries).
    """
    grid, block = Dim3.of(grid), Dim3.of(block)
    if _optimize_enabled(optimize):
        from repro.core import optimize as optimize_mod
        kernel = optimize_mod.optimize_launch(kernel, grid=grid,
                                              block=block, args=args,
                                              dyn_shared=dyn_shared)
    entry, _ = _entry_for(kernel, grid, block, args,
                          backend, grain, dyn_shared, interpret, pool,
                          devices, shard_axis)
    return entry


@dataclasses.dataclass(frozen=True)
class LaunchConfig:
    """A kernel bound to its ``<<<grid, block, dyn_shared, stream>>>``.

    Calling it launches: buffers go in as keyword arguments (or one
    positional dict) and the updated buffer dict comes back.  Execution
    options that CUDA keeps out of the chevrons (backend, grain, interpret,
    and for multi-device backends the shard count/axis) are set with
    :meth:`on`, which returns a re-bound config::

        out = kernel[(gx, gy), (bx, by)].on(backend="pallas")(x=x, y=y)
        out = kernel[grid, block].on(backend="shard", devices=4)(x=x)

    When a ``stream`` occupies the fourth chevron slot the launch is routed
    through ``stream.launch`` (async, hazard-tracked) and returns the
    stream; otherwise it is a synchronous ``api`` launch returning the
    updated buffers.
    """

    kernel: KernelDef
    grid: Dim3
    block: Dim3
    dyn_shared: int | None = None
    stream: Any = None
    backend: str = "vector"
    grain: int | str = 1
    interpret: bool | None = None
    pool: int | None = None
    devices: int | None = None
    shard_axis: str = "blocks"
    sanitize: bool | None = None
    optimize: bool | None = None

    @classmethod
    def from_chevron(cls, kernel: KernelDef, config: tuple) -> "LaunchConfig":
        grid, block, *rest = config
        dyn_shared = rest[0] if len(rest) >= 1 else None
        stream = rest[1] if len(rest) >= 2 else None
        if dyn_shared is not None and not isinstance(dyn_shared, int):
            raise TypeError(
                f"kernel {kernel.name}: third chevron slot (dyn_shared) must "
                f"be an int or None, got {dyn_shared!r}")
        return cls(kernel=kernel, grid=Dim3.of(grid), block=Dim3.of(block),
                   dyn_shared=dyn_shared, stream=stream)

    def on(self, **overrides) -> "LaunchConfig":
        """Re-bind execution options: backend, grain, interpret, pool,
        devices (shard count for multi-device backends; None = all
        available), shard_axis (mesh axis name)."""
        allowed = {"backend", "grain", "interpret", "pool", "devices",
                   "shard_axis", "sanitize", "optimize"}
        bad = set(overrides) - allowed
        if bad:
            raise TypeError(f"LaunchConfig.on() got unexpected options "
                            f"{sorted(bad)}; allowed: {sorted(allowed)}")
        return dataclasses.replace(self, **overrides)

    def __call__(self, args: dict | None = None, /, **buffers):
        merged = {**(args or {}), **buffers}
        if self.stream is not None:
            self.stream.launch(
                self.kernel, grid=self.grid, block=self.block,
                backend=self.backend, grain=self.grain,
                dyn_shared=self.dyn_shared,
                args=merged or None,
                interpret=self.interpret, pool=self.pool,
                devices=self.devices, shard_axis=self.shard_axis,
                optimize=self.optimize)
            return self.stream
        return _launch(self.kernel, self.grid, self.block, merged,
                       self.backend, self.grain, self.dyn_shared,
                       self.interpret, self.pool, self.devices,
                       self.shard_axis, self.sanitize, self.optimize)


def launch(kernel: KernelDef, *, grid, block, args: dict,
           backend: str = "vector", grain: int | str = 1,
           dyn_shared: int | None = None, interpret: bool | None = None,
           pool: int | None = None, devices: int | None = None,
           shard_axis: str = "blocks",
           sanitize: bool | None = None,
           optimize: bool | None = None) -> dict:
    """Launch ``kernel`` over ``grid`` blocks of ``block`` threads.

    Legacy keyword shim over the :class:`LaunchConfig` path; ``grid`` and
    ``block`` accept ints or up-to-3-tuples (CUDA ``dim3``).  ``args`` maps
    global-buffer names to arrays; returns the dict with the kernel's
    written buffers replaced.  ``grain`` may be an int, "average", or
    "aggressive" (paper SIV-A heuristics; ``pool`` = worker count).
    ``devices``/``shard_axis`` reach multi-device backends (``shard``)
    only; single-device backends ignore them.  ``sanitize=True`` (or
    ``CUPBOP_SANITIZE=1``) runs :mod:`repro.core.analyze` kernelcheck on
    the launch first and raises ``SanitizerError`` on findings.
    ``optimize=True`` (or ``CUPBOP_OPTIMIZE=1``) applies the
    :mod:`repro.core.optimize` barrier-fission pass first - bit-identical
    results from a verdict-backed kernel with fewer stages.
    """
    return _launch(kernel, Dim3.of(grid), Dim3.of(block), args, backend,
                   grain, dyn_shared, interpret, pool, devices, shard_axis,
                   sanitize, optimize)


def _build_batch(kernel: KernelDef, backend: str, grid: Dim3, block: Dim3,
                 grain: int, dyn_shared, treedef, interpret: bool):
    """Jitted entry running N stacked launches of one specialization.

    The inner fn is the same per-launch body :func:`_build` jits; here
    it is ``vmap``-ed over a leading request axis instead, so N compatible
    launches become ONE dispatch (named ``<kernel>__<backend>__batch``).
    Stacking and row-indexing are pure data movement and the lowerings are
    rank-polymorphic jnp programs, so each row is bit-identical to the
    independent launch it replaces.
    """
    one = _body(kernel, backend, grid, block, grain, dyn_shared, treedef,
                interpret)
    one.__name__ = one.__qualname__ = program_name(kernel.name, backend,
                                                   "batch")
    return jax.jit(jax.vmap(one))


def launch_batch(kernel: KernelDef, *, grid, block, args_list: list[dict],
                 backend: str = "vector", grain: int | str = 1,
                 dyn_shared: int | None = None, interpret: bool | None = None,
                 pool: int | None = None,
                 sanitize: bool | None = None,
                 optimize: bool | None = None) -> list[dict]:
    """Run N compatible launches of ``kernel`` as one stacked dispatch.

    The serving tier's batcher: every dict in ``args_list`` must bind the
    same buffer structure (treedef and leaf shapes/dtypes) - request
    ``i``'s leaves become row ``i`` of a stacked leading axis, one
    ``jit(vmap(...))`` entry runs all rows, and the outputs are unstacked
    back into one result dict per request.  Batched entries live in the
    same LRU/:class:`CacheStats` as plain launches (keyed with a
    ``("batch", n)`` component), so a warm batch of a hot specialization
    is a cache hit like any other.

    Semantics vs :func:`launch`, per request: handle liveness and
    const-space enforcement are identical (``resolve_launch_args`` runs on
    each request) and donated handles re-bind to their row's output; the
    only loss is XLA storage donation itself (rows are stacked into fresh
    arrays, so there is no input storage to alias).  Multi-device backends
    raise :class:`UnsupportedKernel` - stacked batching is single-device
    (batch across requests XOR shard across devices; a service dispatches
    sharded traffic request-at-a-time).
    """
    if not args_list:
        raise ValueError("launch_batch: args_list must be non-empty")
    grid, block = Dim3.of(grid), Dim3.of(block)
    with _span("cupbop.launch_batch", kernel=kernel.name, n=len(args_list)):
        if _sanitize_enabled(sanitize):
            from repro.core import analyze as analyze_mod
            analyze_mod.sanitize_launch(kernel, grid=grid, block=block,
                                        args=args_list[0],
                                        dyn_shared=dyn_shared)
        if _optimize_enabled(optimize):
            from repro.core import optimize as optimize_mod
            kernel = optimize_mod.optimize_launch(kernel, grid=grid,
                                                  block=block,
                                                  args=args_list[0],
                                                  dyn_shared=dyn_shared)
        if len(args_list) == 1:
            # a batch of one is a plain launch (donation and disk tier
            # apply); passes run above, so suppress the env-var defaults
            return [_launch(kernel, grid, block, args_list[0], backend, grain,
                            dyn_shared, interpret, pool,
                            sanitize=False, optimize=False)]
        if get_backend(backend).supports("multi_device"):
            raise UnsupportedKernel(
                f"launch_batch: backend {backend!r} shards blocks across "
                f"devices; stacked request batching is single-device "
                f"only - dispatch these requests independently")
        grain = _resolve_grain(kernel, grain, pool, grid.size)
        interpret = pallas_emit.resolve_interpret(interpret)
        packed, treedef0, shapes0 = [], None, None
        for i, a in enumerate(args_list):
            leaves, treedef = packing.pack(
                memory_mod.resolve_launch_args(kernel, a))
            shapes = tuple((l.shape, jnp.asarray(l).dtype.name)
                           for l in leaves)
            if i == 0:
                treedef0, shapes0 = treedef, shapes
            elif (treedef, shapes) != (treedef0, shapes0):
                raise ValueError(
                    f"launch_batch: request {i} does not match the batch "
                    f"specialization (buffer structure or leaf shapes/dtypes "
                    f"differ from request 0); only compatible launches stack")
            packed.append(leaves)
        n = len(packed)
        stacked = tuple(jnp.stack([p[j] for p in packed])
                        for j in range(len(packed[0])))
        key = ("batch", n, backend, grid, block, grain, dyn_shared, interpret,
               treedef0, shapes0)
        per_kernel = _kernel_cache(kernel)
        entry = per_kernel.get(key)
        if entry is not None:
            _STATS.hits += 1
            _lru_touch(kernel, key)
        else:
            _STATS.misses += 1
            with _miss_path() as traced:
                fn = _build_batch(kernel, backend, grid, block, grain,
                                  dyn_shared, treedef0, interpret)
                # surface UnsupportedKernel eagerly, as the single-launch path
                jax.eval_shape(fn, *stacked)
                if backend == "pallas" and not interpret:
                    mosaic_compile(fn, stacked)
            entry = CompiledKernel(kernel=kernel, backend=backend, grid=grid,
                                   block=block, key=key, fn=fn, source="trace")
            _keep_schedule(entry, traced)
            per_kernel[key] = entry
            _lru_insert(kernel, key)
        out, _ = _dispatch(entry, stacked)
        _count_launch(entry)
        return [memory_mod.rebind_outputs(
                    kernel, a, {name: v[i] for name, v in out.items()})
                for i, a in enumerate(args_list)]


def supported(kernel: KernelDef, backend: str, *, grid=4, block=64,
              args=None, dyn_shared=None) -> bool:
    """Coverage probe: can ``backend`` express ``kernel``? (Table II cell).

    ``backend`` must name a registered backend - unknown names raise
    ``UnknownBackend`` rather than reading as "unsupported".
    """
    get_backend(backend)  # raise eagerly on unknown names
    try:
        if args is None:
            raise ValueError("supported() needs representative args")
        launch(kernel, grid=grid, block=block, args=args, backend=backend,
               dyn_shared=dyn_shared)
        return True
    except UnsupportedKernel:
        return False


def coverage(kernel: KernelDef, *, grid=4, block=64, args=None,
             dyn_shared=None) -> dict[str, bool]:
    """One Table-II row: ``supported()`` across every registered backend."""
    return {
        name: supported(kernel, name, grid=grid, block=block, args=args,
                        dyn_shared=dyn_shared)
        for name in backends_mod.backend_names()
    }
