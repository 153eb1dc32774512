"""Kernel IR for CuPBoP-JAX.

A CUDA-style SPMD kernel is represented *post-frontend* as a ``KernelDef``:
an ordered tuple of **stages** separated by implicit ``__syncthreads()``
barriers (the paper's loop-fission points, CuPBoP SIII-B.3), a declaration of
__shared__ memory (SIII-B.1), and the set of global buffers the kernel writes
(used by the stream runtime's implicit-barrier dependence analysis, SIII-C.1).

Stage functions are written against a ``Ctx`` + ``BlockState`` and must be
lowering-agnostic: the same stage body executes under

* ``lower="loop"``   - the paper-faithful MCUDA/COX/CuPBoP loop lowering
                       (explicit loop over thread chunks, register demotion
                       across barriers, warp x lane nesting);
* ``lower="vector"`` - the TPU-native lowering (thread axis vectorized onto
                       VPU lanes, pure jnp);
* ``lower="pallas"`` - vector semantics emitted inside ``pl.pallas_call``
                       with grain-size block fetching (SIV-A).

The contract that makes this possible: every thread-private value ("register")
carries a leading *thread-chunk* axis. Under the loop lowering the chunk is 1
(or 32 when warp-level functions are used - the paper's two-level nesting);
under vector/pallas it is the whole block. Authors index shared/global arrays
with ``arr[idx]`` / ``arr.at[idx].set(v)`` which is shape-polymorphic in the
chunk size.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
from typing import Any, Callable, Mapping, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import memory
from repro.core.dim3 import Dim3

WARP_SIZE = 32

# host spans of chain replay (recorded only inside a profiler session)
_span = jax.profiler.TraceAnnotation


def expf(x):
    """CUDA ``expf``, accurate to an ulp or two.

    XLA's default TPU ``exp`` and ``log`` are fast approximations
    (measured on a TPU v5e over [-1, 1] and [0.5, 2]: ``exp`` 58 ulp,
    ``log`` about 4000 ulp near 1); the HIGHEST accuracy mode measured
    1.1 and 2.5 ulp.  Other backends give the same bits either way.
    """
    return lax.exp(x, accuracy=lax.AccuracyMode.HIGHEST)


def logf(x):
    """CUDA ``logf``; see :func:`expf`."""
    return lax.log(x, accuracy=lax.AccuracyMode.HIGHEST)


class UnsupportedKernel(Exception):
    """Raised when a lowering cannot express a kernel feature.

    This is the analogue of an 'unsupport' cell in the paper's Table II."""


class BlockState(NamedTuple):
    """Functional view of one CUDA block's memory during a stage.

    priv   : pytree of thread-private values; every leaf has leading axis
             = thread-chunk size.  Values that live across a barrier are
             demoted to ``[block_size, ...]`` arrays by the loop lowering
             (CuPBoP register demotion).
    shared : dict name -> array, the block's __shared__ memory (SIII-B.1).
    glob   : dict name -> array, global-memory buffers (heap/HBM).
    """

    priv: Any
    shared: dict
    glob: dict

    def with_priv(self, priv: Any) -> "BlockState":
        return self._replace(priv=priv)

    def set_shared(self, **kv: Any) -> "BlockState":
        return self._replace(shared={**self.shared, **kv})

    def set_glob(self, **kv: Any) -> "BlockState":
        return self._replace(glob={**self.glob, **kv})


@dataclasses.dataclass
class Ctx:
    """Per-stage execution context: CUDA special registers + warp intrinsics.

    ``bid``/``tid`` play the role of the paper's runtime-assigned variables
    (block_index / thread id, SIII-B.2): they are *not* hardware registers on
    the target, so CuPBoP materializes them explicitly - here they are traced
    values fed by the lowering.

    ``bid``/``tid`` stay *linearized* (every lowering iterates linear ids),
    while ``bid3``/``tid3`` recover CUDA's ``blockIdx``/``threadIdx`` triples
    from the ``Dim3`` launch geometry with x-fastest ordering, so 2-D/3-D
    kernels (hotspot/srad-style stencils) read their coordinates exactly as
    the CUDA source does.
    """

    bid: Any                 # scalar int32 block id (linearized)
    tid: Any                 # [chunk] int32 thread ids within the block
    block_dim: int           # python int (POCL-style JIT specialization)
    grid_dim: Any            # int or traced scalar
    backend: str             # 'loop' | 'vector' | 'pallas'
    uses_warp: bool = False
    block_dim3: Dim3 | None = None   # CUDA blockDim (defaults to 1-D)
    grid_dim3: Dim3 | None = None    # CUDA gridDim (defaults to 1-D)

    def __post_init__(self):
        if self.block_dim3 is None:
            self.block_dim3 = Dim3(int(self.block_dim))
        if self.grid_dim3 is None and isinstance(self.grid_dim, int):
            self.grid_dim3 = Dim3(int(self.grid_dim))
        # a traced grid_dim with no declared Dim3 geometry leaves
        # grid_dim3 == None; bid3 raises instead of silently flattening
        # (every lowering passes grid_dim3 explicitly, so this only
        # affects hand-constructed Ctx objects)

    @property
    def tid3(self):
        """``threadIdx`` as an ``(x, y, z)`` triple of [chunk] arrays."""
        return self.block_dim3.coords(self.tid)

    @property
    def bid3(self):
        """``blockIdx`` as an ``(x, y, z)`` triple of scalars."""
        if self.grid_dim3 is None:
            raise UnsupportedKernel(
                "blockIdx read under a traced grid extent with no Dim3 "
                "geometry: blockIdx.y/z would silently flatten to 0. "
                "Pass grid_dim3= when constructing Ctx (the lowerings do)."
            )
        return self.grid_dim3.coords(self.bid)

    @property
    def lane(self):
        return self.tid % WARP_SIZE

    @property
    def warp(self):
        return self.tid // WARP_SIZE

    # ---- warp-level functions (CuPBoP supports these via two-level loops;
    #      DPC++/HIP-CPU coverage gaps in Table II come from their absence) --
    def shfl(self, val, src_lane):
        from repro.core import warp as _warp
        return _warp.shfl(val, src_lane)

    def shfl_up(self, val, delta):
        from repro.core import warp as _warp
        return _warp.shfl_up(val, delta)

    def shfl_down(self, val, delta):
        from repro.core import warp as _warp
        return _warp.shfl_down(val, delta)

    def shfl_xor(self, val, mask):
        from repro.core import warp as _warp
        return _warp.shfl_xor(val, mask)

    def vote_all(self, pred):
        from repro.core import warp as _warp
        return _warp.vote_all(pred)

    def vote_any(self, pred):
        from repro.core import warp as _warp
        return _warp.vote_any(pred)

    def ballot(self, pred):
        from repro.core import warp as _warp
        return _warp.ballot(pred)

    def warp_reduce(self, val, op="add"):
        from repro.core import warp as _warp
        return _warp.reduce(val, op)

    def syncthreads_count(self, pred):
        """``__syncthreads_count``: block-wide count of true predicates.

        Requires the thread chunk to span the whole block (always true
        under vector/pallas; under the loop lowering only for 32-thread
        blocks in warp mode - the classic blockDim==warpSize idiom)."""
        from repro.core import warp as _warp
        return _warp.syncthreads_count(pred, self.block_dim)

    # ---- atomics (TPU adaptation: deterministic scatter / grid-serial) -----
    def atomic_add(self, arr, idx, val):
        from repro.core import atomics as _atomics
        return _atomics.atomic_add(arr, idx, val)

    def atomic_max(self, arr, idx, val):
        from repro.core import atomics as _atomics
        return _atomics.atomic_max(arr, idx, val)

    def atomic_min(self, arr, idx, val):
        from repro.core import atomics as _atomics
        return _atomics.atomic_min(arr, idx, val)

    def atomic_cas(self, arr, idx, cmp, val):
        from repro.core import atomics as _atomics
        return _atomics.atomic_cas(arr, idx, cmp, val)

    def atomic_exch(self, arr, idx, val):
        from repro.core import atomics as _atomics
        return _atomics.atomic_exch(arr, idx, val)

    def atomic_cas_first(self, arr, idx, cmp, val):
        from repro.core import atomics as _atomics
        return _atomics.atomic_cas_first(arr, idx, cmp, val)


Stage = Callable[[Ctx, BlockState], BlockState]


@dataclasses.dataclass(frozen=True, eq=False)  # eq=False: hash by identity
class KernelDef:
    """A CUDA kernel after barrier fission.

    ``stages`` are the code regions between consecutive ``__syncthreads()``
    (Fig. 4 of the paper: Loop1 / Loop2).  ``shared`` declares __shared__
    arrays; a dimension of ``-1`` is the paper's *extern* dynamic shared
    memory, resolved by the ``dyn_shared`` launch parameter (Listing 3).
    ``writes`` names the global buffers this kernel mutates - consumed by the
    stream runtime for implicit-barrier insertion (Listing 4).
    ``reads`` optionally names the global buffers the kernel consumes (the
    analogue of ``const __restrict__`` annotations): graph capture uses it
    to build precise dependence edges; ``None`` means "may read anything"
    and degrades to conservative whole-heap ordering.
    ``est_block_work`` is the per-block instruction estimate used by the
    aggressive-grain heuristic (Table V '# inst' column).
    ``combines`` declares, per written buffer, how the *shard* backend
    merges per-shard partial results across devices (see
    :mod:`repro.core.atomics`): ``"sum"`` (the default - exact for
    cross-block ``atomicAdd`` accumulation and disjoint writes into
    zero-initialized buffers; float overwrites of large prior values
    round), ``"max"``/``"min"`` (cross-block ``atomicMax``/``atomicMin``),
    or ``"concat"`` (owned-slice writes, zero communication and always
    exact).
    ``donates`` names written buffers whose *input storage* a launch may
    consume (``cudaMalloc``'d memory the kernel overwrites in place, CUDA's
    default view): when such a buffer is bound to a live
    :class:`~repro.core.memory.DeviceBuffer`, the input is donated to XLA
    and the handle re-binds to the output, so ping-pong chains alias
    instead of copy.  Must be a subset of ``writes`` - donation aliasing a
    buffer the kernel also reads is only legal because it was declared -
    and is hashed into the fingerprint (donation changes the compiled
    launch ABI).

    Subscripting a kernel is the triple-chevron launch syntax::

        kernel[grid, block](**buffers)                     # <<<g, b>>>
        kernel[(gx, gy), (bx, by)](**buffers)              # dim3 grids
        kernel[grid, block, shmem](**buffers)              # <<<g, b, s>>>
        kernel[grid, block, shmem, stream](**buffers)      # <<<g, b, s, st>>>

    returning a bound :class:`~repro.core.api.LaunchConfig`.
    """

    name: str
    stages: Sequence[Stage]
    writes: Sequence[str]
    shared: Mapping[str, tuple[tuple[int, ...], Any]] = dataclasses.field(
        default_factory=dict
    )
    reads: Sequence[str] | None = None
    uses_warp: bool = False
    est_block_work: float = 1e6
    combines: Mapping[str, str] = dataclasses.field(default_factory=dict)
    donates: Sequence[str] = ()

    def __post_init__(self):
        stray = set(self.donates) - set(self.writes)
        if stray:
            raise ValueError(
                f"kernel {self.name}: donates {sorted(stray)} not in writes "
                f"{tuple(self.writes)}; only written buffers can consume "
                f"their input storage")
        # combines declarations are validated at definition time so a typo
        # fails where it was written, not launches later inside lower_shard
        from repro.core import atomics  # lazy: atomics is import-light
        unwritten = set(self.combines) - set(self.writes)
        if unwritten:
            raise ValueError(
                f"kernel {self.name}: combines for {sorted(unwritten)} not "
                f"in writes {tuple(self.writes)}; cross-shard merges apply "
                f"to written buffers only")
        bad = {n: m for n, m in self.combines.items()
               if m not in atomics.CROSS_SHARD_COMBINES}
        if bad:
            raise ValueError(
                f"kernel {self.name}: unknown combine mode(s) {bad}; "
                f"supported: {atomics.CROSS_SHARD_COMBINES}")

    def __getitem__(self, config):
        """``kernel[grid, block(, dyn_shared(, stream))]`` -> LaunchConfig."""
        from repro.core.api import LaunchConfig  # lazy: api imports kernel

        if not isinstance(config, tuple) or not 2 <= len(config) <= 4:
            raise TypeError(
                f"kernel {self.name}: launch config must be "
                f"[grid, block(, dyn_shared(, stream))]; got {config!r}"
            )
        return LaunchConfig.from_chevron(self, config)

    def resolved_shared(self, dyn_shared: int | None):
        out = {}
        for name, (shape, dtype) in self.shared.items():
            if any(d == -1 for d in shape):
                if dyn_shared is None:
                    raise ValueError(
                        f"kernel {self.name}: shared array {name} is extern "
                        f"(dynamic); pass dyn_shared= at launch"
                    )
                shape = tuple(dyn_shared if d == -1 else d for d in shape)
            out[name] = (tuple(int(d) for d in shape), dtype)
        return out

    def init_shared(self, dyn_shared: int | None):
        return {
            name: jnp.zeros(shape, dtype)
            for name, (shape, dtype) in self.resolved_shared(dyn_shared).items()
        }

    def fingerprint(self) -> str:
        """Content hash of the kernel, stable across processes.

        Keys the on-disk compile cache (the role ``cudaModuleLoad`` plays in
        CuPBoP's Fig. 3 library replacement): two ``KernelDef``s built from
        the same factory with the same parameters hash equal, while editing a
        stage body, the shared spec, or the read/write sets invalidates every
        cached artifact.  Stage closures are hashed by bytecode plus captured
        cell values (factory parameters like tile sizes live in cells).
        """
        h = hashlib.sha256()
        h.update(repr((self.name, tuple(self.writes),
                       None if self.reads is None else tuple(self.reads),
                       tuple(sorted((n, (tuple(s), jnp.dtype(d).name))
                                    for n, (s, d) in self.shared.items())),
                       self.uses_warp,
                       tuple(sorted(self.combines.items())),
                       tuple(self.donates))).encode())
        for stage in self.stages:
            _hash_callable(h, stage, depth=0)
        return h.hexdigest()


@dataclasses.dataclass(frozen=True)
class ChainStep:
    """One launch of a :class:`LaunchChain`.

    ``prepare`` runs host-side *before* the launch and returns a dict of
    buffer overrides merged into the heap - the analogue of the host code
    between CUDA launches (bump the iteration scalar, ping-pong swap the
    src/dst pointers, re-zero a per-iteration accumulator).  It receives
    ``(iteration, buffers)`` and must not mutate ``buffers``.

    ``read`` names buffers the host copies back after the launch, each
    with the number of leading elements copied - Rodinia srad's
    ``cudaMemcpy`` of the reduced sums is ``{"sums": 1, "sums2": 1}``.
    The host-hop replay makes the copy, and the ``prepare`` of the next
    step sees each such buffer as its host copy (a NumPy array of those
    elements) in place of the device buffer; the heap keeps the device
    buffer.

    ``update`` is the *device-resident* form of the same hook: a pure,
    traceable function of the buffer dict alone (``bufs -> overrides``,
    jnp ops only, no iteration number - per-iteration scalars live in
    small device buffers the update increments, e.g. ``level + 1``).
    Because it needs no host values it runs without any host round-trip
    and captures into a graph as an update node; where the host path
    reads buffers back, ``update`` computes from the device buffers what
    ``prepare`` computes from the host copies.  The device-resident
    contract: ``update`` is applied before every launch *except iteration
    0*, whose ``prepare`` must therefore be an identity (all the suite
    chains already satisfy this - their ``prepare(0, ...)`` re-states the
    initial buffer values) - unless an earlier step of the iteration
    reads buffers back: the step right after such a read computes from
    it in every iteration, the first included.
    """

    kernel: "KernelDef"
    grid: Any
    block: Any
    dyn_shared: int | None = None
    prepare: Callable[[int, dict], dict] | None = None
    update: Callable[[dict], dict] | None = None
    read: Mapping[str, int] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class ChainStats:
    """Replay counters for :class:`LaunchChain` runs.

    ``host_syncs`` counts host round-trips forced by the chain driver
    (stop-flag reads - the traffic the device-resident mode amortizes);
    ``host_reads`` counts the copies of :attr:`ChainStep.read` buffers
    back to the host (one per step that declares any, per iteration, in
    host-hop replay only); ``graph_replays`` counts fused graph dispatches
    in graph mode; ``runs`` counts whole chain runs.  A caller's
    ``stats`` counts its own runs; :func:`chain_totals` counts every run
    of the process.
    """

    iterations: int = 0
    launches: int = 0
    host_syncs: int = 0
    host_reads: int = 0
    graph_replays: int = 0
    runs: int = 0

    @property
    def syncs_per_iteration(self) -> float:
        return self.host_syncs / max(1, self.iterations)

    def add(self, other: "ChainStats") -> None:
        for f in dataclasses.fields(self):
            setattr(self, f.name,
                    getattr(self, f.name) + getattr(other, f.name))


_TOTALS = ChainStats()


def chain_totals() -> ChainStats:
    """Counters of every :class:`LaunchChain` run of this process, as
    ``api.cache_stats()`` keeps the launch cache's (a copy)."""
    return dataclasses.replace(_TOTALS)


@contextlib.contextmanager
def _tally(stats: ChainStats | None):
    """Count one chain run: yields its own :class:`ChainStats`, added to
    ``stats`` (where given) and to the process totals when it ends."""
    run = ChainStats(runs=1)
    try:
        yield run
    finally:
        _TOTALS.add(run)
        if stats is not None:
            stats.add(run)


@dataclasses.dataclass(frozen=True)
class LaunchChain:
    """Inter-launch dependency idiom for iterative wavefront kernels.

    Rodinia's wavefront codes (pathfinder, needle, bfs, srad) re-launch
    one or more kernels from a host loop, each launch consuming the
    previous launch's writes - the dependency lives *between* launches,
    not between stages of one kernel.  A ``LaunchChain`` makes that idiom
    declarative: ``steps`` run in order, the whole sequence ``repeat``
    times, with ``stop(buffers)`` checked host-side between iterations
    (the analogue of Rodinia BFS reading back its ``stop`` flag).
    ``before`` and ``after`` are launched once, ahead of the first
    iteration and behind the last (srad's ``extract`` and ``compress``),
    with no hooks.  Steps of one iteration may differ in geometry (srad's
    reduction passes), and a step may read buffers back to the host
    (:attr:`ChainStep.read`).

    The chain is backend-agnostic: the caller supplies ``launch_step``,
    which runs one :class:`ChainStep` under whatever backend/grain/device
    options the caller chose, so the same chain sweeps identically under
    loop/vector/pallas/shard lowerings (how the conformance harness
    replays wavefront kernels per backend).  Kernels stay constant across
    iterations - per-iteration values travel through small device buffers
    set by ``prepare`` - so every launch after the first hits the
    compiled-launch cache.

    Three replay modes, all bit-identical on the oracle outputs:

    * :meth:`run` - the host-hop baseline: host ``prepare`` hooks, the
      steps' ``read`` buffers copied back and the stop flag read back
      **every** iteration (host syncs per iteration, the traffic
      Polygeist-style GPU-to-CPU work shows dominating translated-kernel
      runtime);
    * :meth:`run_device` - device-resident: ``update`` hooks keep the
      inter-launch state on device, nothing is read back, and the stop
      flag (``device_stop``, a device predicate) is polled only every
      ``check_every`` iterations, so host syncs drop to O(1/k);
    * :meth:`run_graph` - device-resident *and* graph-captured: the
      iteration body is captured once into a
      :class:`~repro.core.graphs.Graph` and replayed as fused jitted
      dispatches (one dispatch for the whole chain when there is no stop
      flag).

    Each host-driven iteration is a ``cupbop.chain.iteration`` span, each
    stop-flag read ``cupbop.chain.stop``, each copy of read buffers
    ``cupbop.chain.read`` and each graph replay ``cupbop.graph.replay``
    (profiler spans; :class:`ChainStats` counts).

    Stop-flag chains replayed in k-batched modes may overshoot
    convergence by up to ``check_every - 1`` iterations; such chains must
    be no-ops once converged (Rodinia BFS is: an empty frontier claims
    nothing), and per-iteration scratch like the frontier ping-pong is
    declared in ``SuiteEntry.iteration_state`` so conformance compares
    only cadence-independent buffers.
    """

    steps: Sequence[ChainStep]
    repeat: int = 1
    stop: Callable[[dict], bool] | None = None
    device_stop: Callable[[dict], Any] | None = None
    check_every: int = 1
    before: Sequence[ChainStep] = ()
    after: Sequence[ChainStep] = ()

    @property
    def all_steps(self) -> tuple[ChainStep, ...]:
        """Every step in launch order: ``before``, ``steps``, ``after``."""
        return (*self.before, *self.steps, *self.after)

    def _has_stop(self) -> bool:
        return self.stop is not None or self.device_stop is not None

    def _require_device_resident(self):
        for step in self.steps:
            if step.update is None and step.prepare is not None:
                raise UnsupportedKernel(
                    f"chain step {step.kernel.name}: host-side prepare hook "
                    f"without a device update; graph capture needs on-device "
                    f"inter-launch state (declare ChainStep.update)")

    def _updates(self, it: int) -> list[bool]:
        """Per step, whether its ``update`` applies in iteration ``it``:
        after the first iteration always, in it only after a read."""
        out, read = [], False
        for step in self.steps:
            out.append(step.update is not None and (it > 0 or read))
            read = read or bool(step.read)
        return out

    def _stopped(self, bufs: dict) -> bool:
        """Read the stop predicate back to the host (THE host sync)."""
        with _span("cupbop.chain.stop"):
            if self.device_stop is not None:
                raw = {n: memory.unwrap(v) for n, v in bufs.items()}
                return bool(np.asarray(self.device_stop(raw)))
            if self.stop is not None:
                return bool(self.stop(bufs))
            return False

    @staticmethod
    def _read(step: ChainStep, bufs: dict) -> dict:
        """Copy the leading elements of ``step.read``'s buffers back to
        the host (the ``cudaMemcpy`` of Rodinia's host loop)."""
        with _span("cupbop.chain.read"):
            return jax.device_get({
                n: memory.unwrap(bufs[n]).reshape(-1)[:count]
                for n, count in step.read.items()})

    def _apply_update(self, step: ChainStep, bufs: dict) -> dict:
        raw = {n: memory.unwrap(v) for n, v in bufs.items()}
        return {**bufs, **step.update(raw)}

    def _launch_once(self, steps, launch_step, bufs: dict,
                     run: ChainStats) -> dict:
        for step in steps:
            bufs = {**bufs, **launch_step(step, bufs)}
            run.launches += 1
        return bufs

    def run(self, launch_step: Callable[[ChainStep, dict], dict],
            bufs: dict, stats: ChainStats | None = None) -> dict:
        """Host-hop replay: host prepare hooks and reads, stop checked per
        iteration."""
        with _tally(stats) as run:
            bufs = self._launch_once(self.before, launch_step, bufs, run)
            host: dict = {}
            for it in range(self.repeat):
                if it and self._has_stop():
                    run.host_syncs += 1
                    if self._stopped(bufs):
                        break
                with _span("cupbop.chain.iteration", it=it):
                    for step in self.steps:
                        if step.prepare is not None:
                            bufs = {**bufs,
                                    **step.prepare(it, {**bufs, **host})}
                        bufs = {**bufs, **launch_step(step, bufs)}
                        run.launches += 1
                        host = {}
                        if step.read:
                            host = self._read(step, bufs)
                            run.host_reads += 1
                run.iterations += 1
            return self._launch_once(self.after, launch_step, bufs, run)

    def run_device(self, launch_step: Callable[[ChainStep, dict], dict],
                   bufs: dict, *, check_every: int | None = None,
                   stats: ChainStats | None = None) -> dict:
        """Device-resident replay: on-device updates, stop polled 1-in-k.

        Steps with an ``update`` hook never call their host ``prepare``;
        steps with only a legacy ``prepare`` still work (but reintroduce
        the host hop they encode).  Nothing is read back.
        """
        k = max(1, self.check_every if check_every is None else check_every)
        with _tally(stats) as run:
            bufs = self._launch_once(self.before, launch_step, bufs, run)
            for it in range(self.repeat):
                if it and self._has_stop() and it % k == 0:
                    run.host_syncs += 1
                    if self._stopped(bufs):
                        break
                with _span("cupbop.chain.iteration", it=it):
                    for step, upd in zip(self.steps, self._updates(it),
                                         strict=True):
                        if step.update is not None:
                            if upd:
                                bufs = self._apply_update(step, bufs)
                        elif step.prepare is not None:
                            bufs = {**bufs, **step.prepare(it, bufs)}
                        bufs = {**bufs, **launch_step(step, bufs)}
                        run.launches += 1
                run.iterations += 1
            return self._launch_once(self.after, launch_step, bufs, run)

    def run_graph(self, stream, *, check_every: int | None = None,
                  stats: ChainStats | None = None, **launch_kw) -> dict:
        """Graph-captured device-resident replay.

        ``before`` and iteration 0 launch eagerly (iteration 0's prepare
        is identity by the device-resident contract, or its update follows
        a read and applies); the remaining iterations are captured
        *once* as a graph unit - ``update`` hooks become update nodes,
        launches kernel nodes - and replayed; ``after`` launches eagerly.
        Without a stop flag the unit is all ``repeat - 1`` remaining
        iterations: the whole chain collapses to one fused jitted
        dispatch.  With a stop flag the unit is ``check_every``
        iterations and the predicate is polled once per replay.

        ``stream`` supplies the capture surface and the heap;
        ``launch_kw`` (backend/grain/devices/...) reaches every captured
        launch.  Steps with a host ``prepare`` but no device ``update``
        cannot be captured and raise :class:`UnsupportedKernel`.
        """
        self._require_device_resident()
        with _tally(stats) as run:
            self._stream_steps(stream, self.before, (), **launch_kw)
            self._stream_steps(stream, self.steps, self._updates(0),
                               **launch_kw)
            run.iterations += 1
            run.launches += len(self.before) + len(self.steps)
            if self.repeat > 1:
                self._replay(stream, run, check_every, **launch_kw)
            self._stream_steps(stream, self.after, (), **launch_kw)
            run.launches += len(self.after)
            return dict(stream.buffers)

    def _stream_steps(self, stream, steps, updates, **launch_kw) -> None:
        """Launch ``steps`` on ``stream``, each after its device update
        where ``updates`` says so."""
        for i, step in enumerate(steps):
            if i < len(updates) and updates[i]:
                stream.device_update(step.update)
            stream.launch(step.kernel, grid=step.grid, block=step.block,
                          dyn_shared=step.dyn_shared, **launch_kw)

    def _replay(self, stream, run: ChainStats, check_every: int | None,
                **launch_kw) -> None:
        """Iterations 1 .. ``repeat - 1`` of :meth:`run_graph`."""
        k = max(1, self.check_every if check_every is None else check_every)
        unit = min(k, self.repeat - 1) if self._has_stop() \
            else self.repeat - 1
        ex = self.capture_unit(stream, unit, **launch_kw)
        done = 1
        while done < self.repeat:
            if done > 1 and self._has_stop():
                run.host_syncs += 1
                if self._stopped(stream.buffers):
                    break
            remaining = self.repeat - done
            if remaining < unit:
                # tail shorter than the captured unit: run it eagerly so
                # the chain never exceeds its repeat bound (a replay would
                # overshoot by unit - remaining real iterations, diverging
                # from run()/run_device() on any non-converged chain)
                for _ in range(remaining):
                    self._stream_steps(stream, self.steps, self._updates(1),
                                       **launch_kw)
                run.iterations += remaining
                run.launches += remaining * len(self.steps)
                break
            with _span("cupbop.graph.replay"):
                ex.launch(stream)
            done += unit
            run.iterations += unit
            run.launches += unit * len(self.steps)
            run.graph_replays += 1

    def capture_unit(self, stream, iterations: int, **launch_kw):
        """Capture ``iterations`` chain iterations into one reusable
        :class:`~repro.core.graphs.GraphExec` (cudaGraphInstantiate for a
        chain unit).

        Each captured iteration is [device update; launch] per step, so a
        replay advances the heap by ``iterations`` chain iterations -
        replay it in a loop for steady-state serving, as :meth:`run_graph`
        and the membench benchmark do.  Requires every per-iteration hook
        to be device-resident (``ChainStep.update``).
        """
        self._require_device_resident()
        graph = stream.begin_capture()
        for _ in range(iterations):
            self._stream_steps(stream, self.steps, self._updates(1),
                               **launch_kw)
        stream.end_capture()
        return graph.instantiate(stream.buffers)


def _hash_callable(h, fn: Callable, depth: int) -> None:
    code = getattr(fn, "__code__", None)
    if code is None or depth > 4:    # builtins / pathological nesting
        h.update(repr(fn).encode())
        return
    h.update(code.co_code)
    h.update(repr([c for c in code.co_consts
                   if not hasattr(c, "co_code")]).encode())
    for const in code.co_consts:     # nested lambdas/defs inside the stage
        if hasattr(const, "co_code"):
            h.update(const.co_code)
    for cell in fn.__closure__ or ():
        try:
            v = cell.cell_contents
        except ValueError:           # empty cell
            continue
        if callable(v):
            _hash_callable(h, v, depth + 1)
        elif hasattr(v, "dtype") and hasattr(v, "shape"):
            # arrays: repr truncates past ~1000 elements, which would let
            # two kernels with different captured weights collide
            arr = jax.device_get(v)
            h.update(repr((arr.shape, arr.dtype.name)).encode())
            h.update(arr.tobytes())
        else:
            h.update(repr(v).encode())


@dataclasses.dataclass
class CompiledKernel:
    """A launch specialization after trace+lower: CuPBoP's ``CUmodule``.

    One entry per (kernel, backend, geometry, arg-shape) key in the compile
    cache; ``fn`` is the jitted callable over packed leaves (the ``void**``
    ABI of :mod:`repro.core.packing`).  ``source`` records how the entry was
    produced - ``"trace"`` (cold trace+lower) or ``"disk"`` (deserialized
    artifact, the ``cudaModuleLoad`` path) - and ``hits`` counts warm
    launches served by this entry.  ``schedule`` is the block schedule the
    ``vector`` lowering traced for it (:mod:`repro.core.lower_vector`):
    ``"tiled"``, or ``"serial: <reason>"``; ``None`` where no vector
    lowering was traced (other backends, disk artifacts).
    """

    kernel: KernelDef
    backend: str
    grid: Dim3
    block: Dim3
    key: tuple
    fn: Callable
    source: str = "trace"
    hits: int = 0
    schedule: str | None = None

    def __call__(self, *leaves):
        self.hits += 1
        return self.fn(*leaves)


def block_range_limit(bid_start, count: int, n_blocks: int):
    """Exclusive upper block-id bound for a block-range view.

    ``min(bid_start + count, n_blocks)`` for python ints and traced
    scalars alike.  Grain fetch loops round ``count`` up to a grain
    multiple, and under the shard backend the rounded tail slots belong
    to the *next* shard's range - both lowerings must mask against this
    limit, not just against the grid size.
    """
    if isinstance(bid_start, int):
        return min(bid_start + count, n_blocks)
    return jnp.minimum(bid_start + count, n_blocks)


def check_priv_chunk(priv: Any, chunk: int, kernel_name: str, stage_idx: int):
    """Enforce the thread-chunk leading-axis contract on priv leaves."""
    for leaf in jax.tree_util.tree_leaves(priv):
        shape = getattr(leaf, "shape", ())
        if len(shape) == 0 or shape[0] != chunk:
            raise UnsupportedKernel(
                f"kernel {kernel_name} stage {stage_idx}: thread-private leaf "
                f"has shape {shape}, expected leading thread-chunk axis "
                f"{chunk}. Broadcast scalars with jnp.full((chunk,), v)."
            )
