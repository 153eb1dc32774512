"""Stream runtime: async launches, events, implicit barriers (paper SIII-C.1).

CuPBoP keeps kernel launches asynchronous (the host thread pushes a task and
continues) and inserts a barrier *only* when a later host operation reads or
writes a buffer a pending kernel writes (Listing 4).  HIP-CPU, by contrast,
synchronizes before every memcpy - the paper measures this as a 30 % average
slowdown (SV-B.2, FIR).

JAX dispatch is already asynchronous, so the "task queue" here tracks
*pending writers per buffer* and the barrier is ``block_until_ready``:

* ``Policy.HAZARD_ONLY``  - CuPBoP: sync iff a RAW/WAW hazard exists;
* ``Policy.SYNC_ALWAYS``  - HIP-CPU baseline: sync after every launch.

``Stream.stats`` counts launches/syncs for the Fig. 11 benchmark.

Beyond the single-stream seed, a :class:`Runtime` hosts *multiple named
streams over one buffer heap* plus CUDA-shaped :class:`Event` objects::

    rt = Runtime({"x": x, "y": y, "tmp": t})
    s0, s1 = rt.stream("compute"), rt.stream("copy")
    producer[grid, block, None, s0]()           # <<<g, b, 0, s0>>>
    ev = rt.event("produced")
    ev.record(s0)                               # cudaEventRecord
    s1.wait_event(ev)                           # cudaStreamWaitEvent
    consumer[grid, block, None, s1]()
    rt.synchronize()                            # cudaDeviceSynchronize

Cross-stream hazards are tracked on the shared heap: a launch (or memcpy)
touching a buffer whose in-flight writer lives on *another* stream inserts
a barrier there first - the implicit-barrier analysis of Listing 4 extended
stream-to-stream.

Streams also support CUDA-Graphs-style capture
(:mod:`repro.core.graphs`)::

    g = s.begin_capture()                       # cudaStreamBeginCapture
    kernel[grid, block, None, s]()              # recorded, not executed
    s.end_capture()                             # cudaStreamEndCapture
    ex = g.instantiate()                        # cudaGraphInstantiate
    ex.launch(s)                                # cudaGraphLaunch

While capturing, launches/memcpy_h2d/event record+wait become DAG nodes;
host-visible operations (``memcpy_d2h``, ``synchronize``, ``malloc``) raise
``GraphError`` - the cudaErrorStreamCaptureUnsupported rule.
"""
from __future__ import annotations

import dataclasses
import enum
import itertools
import threading
import time
from typing import Any

import jax
import numpy as np

from repro.core import api
from repro.core import graphs as graphs_mod
from repro.core import memory as memory_mod
from repro.core.dim3 import Dim3
from repro.core.kernel import KernelDef


class Policy(enum.Enum):
    HAZARD_ONLY = "hazard_only"    # CuPBoP
    SYNC_ALWAYS = "sync_always"    # HIP-CPU baseline


@dataclasses.dataclass
class StreamStats:
    launches: int = 0
    syncs: int = 0
    barriers_inserted: int = 0
    graph_launches: int = 0

    def __iadd__(self, other: "StreamStats") -> "StreamStats":
        self.launches += other.launches
        self.syncs += other.syncs
        self.barriers_inserted += other.barriers_inserted
        self.graph_launches += other.graph_launches
        return self


class Event:
    """A CUDA event: a fence over the work a stream had in flight at record.

    ``record`` captures the recording stream's pending buffers (the array
    values themselves - later heap updates don't move the fence) and starts
    a watcher thread that stamps the completion time the moment the fenced
    work finishes - so ``elapsed`` measures when the *device* work
    completed (cudaEventElapsedTime), not when the host got around to
    calling ``synchronize``.
    """

    def __init__(self, name: str = "event"):
        self.name = name
        self._fence: dict[str, Any] = {}
        self._stream: "Stream | None" = None
        self._recorded = False
        self._time: float | None = None
        self._watcher: threading.Thread | None = None
        self._error: Exception | None = None
        self._gen = 0              # guards against stale watcher threads
        self._capture = None       # (Graph, node idx) when captured

    def record(self, stream: "Stream") -> "Event":
        """Snapshot ``stream``'s in-flight writes (cudaEventRecord)."""
        if stream._capture is not None:
            stream._capture.add_event_record(stream, self)
            return self
        self._capture = None       # eager re-record supersedes a capture
        self._fence = {n: stream.buffers[n] for n in stream._pending}
        self._stream = stream
        self._recorded = True
        self._time = None          # re-record resets completion
        self._gen += 1
        self._watcher = threading.Thread(
            target=self._watch, args=(self._gen, tuple(self._fence.values())),
            daemon=True)
        self._watcher.start()
        return self

    def _watch(self, gen: int, fence: tuple):
        err = None
        try:
            for a in fence:
                jax.block_until_ready(a)
        except Exception as e:     # fenced work failed; surface on sync
            err = e
        if self._gen == gen:       # a re-record supersedes this watcher
            self._time = time.perf_counter()
            self._error = err

    def query(self) -> bool:
        """True iff all fenced work has finished (cudaEventQuery)."""
        if not self._recorded:
            return False
        return self._time is not None or \
            all(_is_ready(a) for a in self._fence.values())

    def synchronize(self) -> "Event":
        """Block until the fenced work completes (cudaEventSynchronize)."""
        if not self._recorded:
            raise RuntimeError(f"event {self.name!r} was never recorded")
        self._watcher.join()
        if self._error is not None:
            raise RuntimeError(
                f"event {self.name!r}: fenced work failed") from self._error
        return self

    def elapsed(self, later: "Event") -> float:
        """Milliseconds between this event's completion and ``later``'s
        (cudaEventElapsedTime; both events must have been recorded).

        Raises ``RuntimeError`` - never returns garbage or ``None`` - when
        either record point is missing: an event that was never recorded
        (cudaErrorInvalidResourceHandle), one captured into a graph (its
        record executes only at replay, which takes no wall-clock stamp),
        or one whose completion stamp was superseded by a re-record while
        the watcher was in flight.
        """
        for role, e in (("start", self), ("end", later)):
            if e._capture is not None:
                raise RuntimeError(
                    f"cannot compute elapsed time: {role} event {e.name!r} "
                    f"was captured into a graph, not recorded eagerly")
            if not e._recorded:
                raise RuntimeError(
                    f"cannot compute elapsed time: {role} event {e.name!r} "
                    f"has not been recorded (cudaEventRecord first)")
        self.synchronize()
        later.synchronize()
        if self._time is None or later._time is None:
            which = self.name if self._time is None else later.name
            raise RuntimeError(
                f"cannot compute elapsed time: event {which!r} has no "
                f"completion stamp (a re-record superseded the watcher "
                f"before it finished; synchronize the new record instead)")
        return (later._time - self._time) * 1e3


def _is_ready(a) -> bool:
    try:
        return bool(a.is_ready())
    except AttributeError:
        jax.block_until_ready(a)
        return True


class Stream:
    """A CUDA stream over named global buffers.

    Standalone (the seed API) it owns a private heap; created through a
    :class:`Runtime` it shares the runtime's heap and participates in
    cross-stream hazard tracking.
    """

    def __init__(self, buffers: dict[str, Any] | None = None,
                 policy: Policy = Policy.HAZARD_ONLY,
                 *, name: str = "stream0",
                 runtime: "Runtime | None" = None):
        self.name = name
        self.runtime = runtime
        if runtime is not None:
            self.buffers = runtime.buffers      # shared heap (same object)
            if buffers:
                self.buffers.update(buffers)
        else:
            self.buffers = dict(buffers or {})
        self.policy = policy
        self._pending: set[str] = set()   # buffers with an in-flight writer
        self._capture: "graphs_mod.Graph | None" = None
        self.stats = StreamStats()

    # -- graph capture (cudaStreamBeginCapture / cudaStreamEndCapture) -------
    def begin_capture(self, graph: "graphs_mod.Graph | None" = None):
        """Start recording this stream's work into a graph.

        Subsequent launches, ``memcpy_h2d`` and event record/wait calls
        become DAG nodes instead of executing.  Pass an existing ``graph``
        to capture several streams into one DAG (or use
        ``Runtime.begin_capture``).
        """
        if self._capture is not None:
            raise graphs_mod.GraphError(
                f"stream {self.name!r} is already capturing")
        g = graph if graph is not None else graphs_mod.Graph()
        g._attach(self)
        self._capture = g
        return g

    def end_capture(self) -> "graphs_mod.Graph":
        """Stop capturing and return the graph (cudaStreamEndCapture)."""
        if self._capture is None:
            raise graphs_mod.GraphError(
                f"stream {self.name!r} is not capturing")
        g = self._capture
        self._capture = None
        g._detach(self)
        return g

    def _forbid_capture(self, op: str):
        if self._capture is not None:
            raise graphs_mod.GraphError(
                f"{op} on capturing stream {self.name!r}: host-visible "
                f"operations are not capturable "
                f"(cudaErrorStreamCaptureUnsupported)")

    # -- memory management (Fig. 3 library replacement) ----------------------
    def malloc(self, name: str, shape, dtype):
        self._forbid_capture("malloc")
        import jax.numpy as jnp
        self.buffers[name] = jnp.zeros(shape, dtype)
        return name

    def _forbid_const_dst(self, op: str, name: str):
        if isinstance(self.buffers.get(name), memory_mod.ConstArray):
            raise memory_mod.UnsupportedSpace(
                f"{op} into heap buffer {name!r}: it is __constant__ "
                f"(ConstArray); constant memory is read-only on device")

    def memcpy_h2d(self, name: str, host: np.ndarray):
        self._forbid_const_dst("memcpy_h2d", name)
        if self._capture is not None:
            self._capture.add_h2d(self, name, np.asarray(host))
            return
        # host->device write: must order after pending writers of `name`
        self._barrier_if_hazard({name})
        self.buffers[name] = jax.device_put(np.asarray(host))

    def memcpy_d2d(self, dst: str, src):
        """cudaMemcpyDeviceToDevice onto the named heap (capturable).

        ``src`` is another heap name, or a device array / tracked handle
        whose value lands on the heap.  Named-to-named copies capture as
        graph ``d2d`` nodes; array-source copies capture like an h2d node
        with a device-resident payload.  An existing destination must
        match the source's geometry (CUDA's byte-count rule).
        """
        self._forbid_const_dst("memcpy_d2d", dst)

        def check_against_heap(val):
            # CUDA's byte-count rule, enforced at enqueue time on BOTH the
            # eager and capture paths - a mismatched captured copy must
            # fail here like its eager twin, not as an opaque shape error
            # deep inside the jitted replay
            have = self.buffers.get(dst)
            if have is not None:
                cur = memory_mod.unwrap(have, "memcpy_d2d")
                memory_mod._check_geometry("d2d", cur.shape, cur.dtype,
                                           val.shape, val.dtype)

        if isinstance(src, str):
            if self._capture is not None:
                if src in self.buffers:
                    check_against_heap(
                        memory_mod.unwrap(self.buffers[src], "memcpy_d2d"))
                self._capture.add_d2d(self, dst, src)  # validates the source
                return
            if src not in self.buffers:
                raise KeyError(
                    f"stream {self.name!r}: no source buffer {src!r} on the "
                    f"heap; malloc/memcpy_h2d first (typo'd name?)")
            self._barrier_if_hazard({dst, src})
            val = memory_mod.unwrap(self.buffers[src], "memcpy_d2d")
        else:
            val = memory_mod.unwrap(src, "memcpy_d2d")
            if self._capture is not None:
                check_against_heap(val)
                self._capture.add_h2d(self, dst, val)
                return
            self._barrier_if_hazard({dst})
        check_against_heap(val)
        self.buffers[dst] = val
        self._mark_pending((dst,))

    def memcpy_d2h(self, name: str) -> np.ndarray:
        self._forbid_capture("memcpy_d2h")
        self._barrier_if_hazard({name})
        return np.asarray(jax.device_get(
            memory_mod.unwrap(self.buffers[name], "memcpy_d2h")))

    def device_update(self, fn, writes: tuple | None = None) -> tuple:
        """Apply an on-device heap update: ``fn(buffers) -> overrides``.

        The device-resident analogue of host code between chained CUDA
        launches: ``fn`` must be a pure, traceable function of the heap
        (jnp ops only).  Eagerly it enqueues lazily - no host sync;
        during capture it becomes a graph *update node* replayed inside
        the fused dispatch.  ``writes`` names the updated buffers and is
        inferred abstractly (``jax.eval_shape``) when omitted.  Returns
        the written names.
        """
        raw = {n: memory_mod.unwrap(v, "device_update")
               for n, v in self.buffers.items()}
        if writes is None:
            spec = {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
                    for n, v in raw.items()}
            writes = tuple(sorted(jax.eval_shape(fn, spec)))
        for name in writes:
            self._forbid_const_dst("device_update", name)
        if self._capture is not None:
            self._capture.add_update(self, fn, writes)
            return writes
        self._wait_foreign_writers(set(self.buffers))
        self.buffers.update(fn(raw))
        self._mark_pending(writes)
        return writes

    # -- kernel launch (async; Fig. 5) ---------------------------------------
    def launch(self, kernel: KernelDef, *, grid, block,
               backend: str = "vector", grain: int | str = 1,
               dyn_shared: int | None = None,
               args: dict[str, Any] | None = None,
               interpret: bool | None = None, pool: int | None = None,
               devices: int | None = None, shard_axis: str = "blocks",
               optimize: bool | None = None):
        """Async launch over the stream's heap.

        The kernel always sees the full heap (device memory); a non-None
        value in ``args`` is written to the heap first (an implicit
        ``memcpy_h2d``, with the usual hazard ordering), so
        ``kernel[g, b, None, s](a=x)`` computes on ``x`` and the heap's
        other buffers - not on whatever the heap last held for ``a``.

        ``args`` values may be tracked :class:`~repro.core.memory
        .DeviceBuffer` handles: they are liveness-checked, their arrays
        land on the heap, and handles bound to buffers the kernel
        declares in ``donates`` are re-bound to the launch's output (the
        CUDA in-place view) - the heap itself always holds raw arrays, so
        hazard fences and event snapshots never see a stale handle.

        Deliberately, stream launches do NOT donate storage to XLA (the
        direct ``api.launch`` path does): an :class:`Event` recorded on
        this stream fences the heap's *array snapshots*, and donating a
        previously-written buffer would delete an array a live fence
        still watches, poisoning ``event.synchronize()``.  Handle
        re-binding is preserved; only the storage-aliasing optimization
        is confined to the direct path.
        """
        grid, block = Dim3.of(grid), Dim3.of(block)
        handles = {n: v for n, v in (args or {}).items()
                   if isinstance(v, memory_mod.DeviceBuffer)}
        if args:
            args = {n: (memory_mod.unwrap(v, "launch") if n in handles
                        else v)
                    for n, v in args.items()}
        if self._capture is not None:
            known = set(self.buffers) | self._capture.written()
            missing = [n for n in (args or {}) if n not in known]
            if missing:
                raise KeyError(
                    f"stream {self.name!r}: no buffer(s) {missing} on the "
                    f"heap; malloc/memcpy_h2d first (typo'd name?)")
            for n, v in (args or {}).items():
                if v is not None:       # arg update = captured h2d node
                    self._capture.add_h2d(self, n,
                                          memory_mod.unwrap(v, "launch"))
            self._capture.add_kernel(
                self, kernel, grid=grid, block=block, backend=backend,
                grain=grain, dyn_shared=dyn_shared, interpret=interpret,
                pool=pool, devices=devices, shard_axis=shard_axis,
                optimize=optimize)
            return
        if args:
            missing = [n for n in args if n not in self.buffers]
            if missing:
                raise KeyError(
                    f"stream {self.name!r}: no buffer(s) {missing} on the "
                    f"heap; malloc/memcpy_h2d first (typo'd name?)")
            updates = {n: v for n, v in args.items() if v is not None}
            if updates:
                self._barrier_if_hazard(set(updates))
                self.buffers.update(updates)
        buf_args = dict(self.buffers)
        # order after in-flight writers of touched buffers on OTHER streams
        self._wait_foreign_writers(set(buf_args) | set(kernel.writes))
        new = api.launch(kernel, grid=grid, block=block, args=buf_args,
                         backend=backend, grain=grain, dyn_shared=dyn_shared,
                         interpret=interpret, pool=pool, devices=devices,
                         shard_axis=shard_axis, optimize=optimize)
        self.buffers.update({n: new[n] for n in kernel.writes})
        memory_mod.rebind_outputs(kernel, handles,
                                  {n: new[n] for n in kernel.writes
                                   if n in handles})
        self._mark_pending(kernel.writes)
        self.stats.launches += 1
        if self.policy is Policy.SYNC_ALWAYS:
            self.synchronize()

    # -- events ---------------------------------------------------------------
    def record(self, event: Event | None = None) -> Event:
        """Record ``event`` on this stream (cudaEventRecord); creates one
        when called bare."""
        return (event or Event()).record(self)

    def wait_event(self, event: Event):
        """cudaStreamWaitEvent: order this stream after ``event``.

        With JAX's dataflow ordering the wait is a hazard edge, not a hard
        stall: it only blocks (and only counts a barrier) when the fenced
        work is still in flight on the recording stream.  The fence is the
        *snapshot taken at record time* - work launched on the source
        stream after the record is not waited on (and stays pending there).

        During capture the wait becomes a DAG edge from the event's record
        node (which must belong to the same graph).
        """
        if self._capture is not None:
            self._capture.add_event_wait(self, event)
            return
        if event._capture is not None:
            raise graphs_mod.GraphError(
                f"stream {self.name!r} cannot eagerly wait on event "
                f"{event.name!r}: it was captured into a graph and only "
                f"fires at replay")
        if not event._recorded:
            raise RuntimeError(
                f"stream {self.name!r} cannot wait on unrecorded event "
                f"{event.name!r}")
        src = event._stream
        if src is None or src is self:
            return  # same-stream wait: program order already serializes
        # pending buffers whose in-flight writer IS the recorded snapshot
        fenced = {n for n, a in event._fence.items()
                  if n in src._pending and src.buffers.get(n) is a}
        superseded = [a for n, a in event._fence.items() if n not in fenced]
        if fenced:
            self.stats.barriers_inserted += 1
            src._sync_buffers(fenced)
        for a in superseded:
            # a later launch re-wrote the buffer: wait on the snapshot
            # itself without clearing the newer writer's pending state
            jax.block_until_ready(a)

    # -- synchronization ------------------------------------------------------
    def _mark_pending(self, names):
        self._pending.update(names)
        if self.runtime is not None:
            for n in names:
                self.runtime._writers[n] = self

    def _wait_foreign_writers(self, touched: set[str]):
        """Cross-stream implicit barrier (Listing 4, stream-to-stream)."""
        if self.runtime is None:
            return
        by_owner: dict[Stream, set[str]] = {}
        for n in touched:
            owner = self.runtime._writers.get(n)
            if owner is not None and owner is not self and n in owner._pending:
                by_owner.setdefault(owner, set()).add(n)
        for owner, names in by_owner.items():
            self.stats.barriers_inserted += 1
            owner._sync_buffers(names)

    def _barrier_if_hazard(self, touched: set[str]):
        self._wait_foreign_writers(touched)
        if self.policy is Policy.SYNC_ALWAYS:
            self.synchronize()
            return
        hazard = touched & self._pending
        if hazard:
            self.stats.barriers_inserted += 1
            self._sync_buffers(hazard)

    def _sync_buffers(self, names):
        for n in names:
            jax.block_until_ready(self.buffers[n])
        self._pending -= set(names)
        if self.runtime is not None:
            for n in names:
                if self.runtime._writers.get(n) is self:
                    del self.runtime._writers[n]
        self.stats.syncs += 1

    def synchronize(self):
        """cudaStreamSynchronize: no-op when nothing is in flight (the seed
        blocked on every buffer and counted a sync even with an empty
        pending set, skewing the Fig. 11 launch/sync ratios)."""
        self._forbid_capture("synchronize")
        if not self._pending:
            return
        self._sync_buffers(set(self._pending))


class Runtime:
    """A device context: one buffer heap, many named streams, events.

    The CUDA-shaped entry point for multi-stream programs; single-stream
    code can keep using a bare :class:`Stream`.
    """

    def __init__(self, buffers: dict[str, Any] | None = None,
                 policy: Policy = Policy.HAZARD_ONLY):
        self.policy = policy
        self.buffers: dict[str, Any] = dict(buffers or {})
        self._writers: dict[str, Stream] = {}   # buffer -> in-flight writer
        self._streams: dict[str, Stream] = {}
        self._event_ids = itertools.count()
        self._capture: "graphs_mod.Graph | None" = None

    # -- streams --------------------------------------------------------------
    def stream(self, name: str = "default") -> Stream:
        """Get-or-create the named stream (cudaStreamCreate).

        A stream created during ``begin_capture`` joins the capture, so
        multi-stream pipelines can be recorded without pre-declaring every
        stream.
        """
        if name not in self._streams:
            s = Stream(policy=self.policy, name=name, runtime=self)
            if self._capture is not None:
                s.begin_capture(self._capture)
            self._streams[name] = s
        return self._streams[name]

    # -- graph capture (device-wide: every stream records into one DAG) ------
    def begin_capture(self) -> "graphs_mod.Graph":
        """Capture all of this runtime's streams into one graph."""
        if self._capture is not None:
            raise graphs_mod.GraphError("runtime is already capturing")
        busy = [s.name for s in self._streams.values()
                if s._capture is not None]
        if busy:    # check first: a partial attach would half-capture
            raise graphs_mod.GraphError(
                f"runtime cannot begin capture: stream(s) {busy} are "
                f"already capturing independently")
        g = graphs_mod.Graph()
        for s in self._streams.values():
            s.begin_capture(g)
        self._capture = g
        return g

    def end_capture(self) -> "graphs_mod.Graph":
        """End the device-wide capture and return the graph."""
        if self._capture is None:
            raise graphs_mod.GraphError("runtime is not capturing")
        g = self._capture
        self._capture = None
        for s in self._streams.values():
            if s._capture is g:
                s.end_capture()
        return g

    @property
    def streams(self) -> tuple[Stream, ...]:
        return tuple(self._streams.values())

    @property
    def default(self) -> Stream:
        return self.stream("default")

    # -- events ---------------------------------------------------------------
    def event(self, name: str | None = None) -> Event:
        """cudaEventCreate."""
        return Event(name or f"event{next(self._event_ids)}")

    # -- memory (default-stream semantics, as in CUDA's NULL stream) ----------
    def malloc(self, name: str, shape, dtype):
        return self.default.malloc(name, shape, dtype)

    def memcpy_h2d(self, name: str, host: np.ndarray):
        self.default.memcpy_h2d(name, host)

    def memcpy_d2d(self, dst: str, src):
        self.default.memcpy_d2d(dst, src)

    def memcpy_d2h(self, name: str) -> np.ndarray:
        return self.default.memcpy_d2h(name)

    def device_update(self, fn, writes: tuple | None = None) -> tuple:
        return self.default.device_update(fn, writes)

    # -- synchronization ------------------------------------------------------
    def synchronize(self):
        """cudaDeviceSynchronize: drain every stream."""
        for s in self._streams.values():
            s.synchronize()

    @property
    def stats(self) -> StreamStats:
        """Aggregate launch/sync/barrier counts across all streams."""
        total = StreamStats()
        for s in self._streams.values():
            total += s.stats
        return total
