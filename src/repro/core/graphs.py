"""CUDA Graphs analogue: capture a launch DAG once, replay as one dispatch.

CUDA amortizes per-launch overhead by recording a stream's work into a graph
(``cudaStreamBeginCapture`` .. ``cudaStreamEndCapture``), instantiating it
(``cudaGraphInstantiate``), and replaying the whole DAG with a single
``cudaGraphLaunch``.  Polygeist/MOCCI makes the same point for CPU targets:
once per-launch work is amortized, transpiled CUDA closes the gap with
native code.  Here the capture records kernel launches, h2d memcpys, and
event record/wait edges into a :class:`Graph`; :meth:`Graph.instantiate`
topologically levels the DAG and traces every node into **one** jitted
replay function, so an N-launch pipeline becomes a single JAX dispatch.

Dependence edges come from the same hazard model the eager stream runtime
uses (paper Listing 4, extended stream-to-stream):

* program order within each captured stream (CUDA stream semantics);
* RAW/WAW/WAR over global buffers - a kernel's write set is its declared
  ``KernelDef.writes``; its read set is ``KernelDef.reads`` when declared,
  else conservatively the whole heap at capture time;
* explicit ``event.record(s0)`` / ``s1.wait_event(event)`` pairs captured
  on streams of the same graph (``cudaStreamWaitEvent`` inside capture).

Nodes in the same topological level have no path between them; the fused
trace preserves only true dataflow, so XLA is free to schedule them in
parallel - the "batching" of independent nodes.

The replay's device program is named for the kernels it holds
(``jit_<kernel>__<backend>[__...]__graph``), and each kernel node runs
under ``jax.named_scope(<kernel>)``, so fused kernels stay attributable
op by op in a device trace.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Sequence

import jax
import numpy as np

from repro.core import api
from repro.core import memory as memory_mod
from repro.core.backends import get_backend
from repro.core.dim3 import Dim3
from repro.core.kernel import KernelDef


class GraphError(RuntimeError):
    """Invalid capture or replay (the cudaErrorStreamCapture* family)."""


@dataclasses.dataclass
class GraphNode:
    """One captured operation.

    ``kind`` is ``"kernel"`` | ``"h2d"`` | ``"d2d"`` | ``"update"`` |
    ``"event_record"`` | ``"event_wait"``; event nodes carry ordering
    only and execute nothing at replay.  ``deps`` are indices of nodes
    that must precede this one (always smaller than ``idx``, so node
    order is already topological).  ``d2d`` copies heap buffer ``src``
    onto ``buffer``; ``update`` applies the pure on-device heap function
    ``fn`` (a captured :meth:`Stream.device_update`) inside the fused
    replay.
    """

    idx: int
    kind: str
    stream: str
    deps: tuple[int, ...]
    label: str
    # kernel fields
    kernel: KernelDef | None = None
    grid: Dim3 | None = None
    block: Dim3 | None = None
    backend: str = "vector"
    grain: int = 1
    dyn_shared: int | None = None
    interpret: bool | None = None
    devices: int | None = None
    shard_axis: str = "blocks"
    reads: tuple[str, ...] = ()
    writes: tuple[str, ...] = ()
    # h2d / d2d fields
    buffer: str | None = None
    host: Any = None
    src: str | None = None
    # update fields
    fn: Callable | None = None


class Graph:
    """A captured DAG of launches/memcpys/events (a ``cudaGraph_t``)."""

    def __init__(self):
        self.nodes: list[GraphNode] = []
        self._last_writer: dict[str, int] = {}
        self._readers: dict[str, set[int]] = {}
        self._stream_tail: dict[str, int] = {}
        self._streams: list[Any] = []          # attached capturing streams

    # -- capture plumbing (called by Stream/Runtime) -------------------------
    def _attach(self, stream) -> None:
        if stream not in self._streams:
            self._streams.append(stream)

    def _detach(self, stream) -> None:
        if stream in self._streams:
            self._streams.remove(stream)

    def _ordered_deps(self, stream_name: str, reads, writes) -> set[int]:
        deps: set[int] = set()
        tail = self._stream_tail.get(stream_name)
        if tail is not None:                   # stream program order
            deps.add(tail)
        for b in reads:                        # RAW
            if b in self._last_writer:
                deps.add(self._last_writer[b])
        for b in writes:                       # WAW + WAR
            if b in self._last_writer:
                deps.add(self._last_writer[b])
            deps.update(self._readers.get(b, ()))
        return deps

    def _commit(self, node: GraphNode) -> GraphNode:
        self.nodes.append(node)
        for b in node.writes:
            self._last_writer[b] = node.idx
            self._readers[b] = set()
        for b in node.reads:
            self._readers.setdefault(b, set()).add(node.idx)
        self._stream_tail[node.stream] = node.idx
        return node

    def written(self) -> set[str]:
        """Buffers any node writes (kernel writes + h2d targets)."""
        return {b for n in self.nodes for b in n.writes}

    def touched(self) -> set[str]:
        return self.written() | {b for n in self.nodes for b in n.reads}

    def add_kernel(self, stream, kernel: KernelDef, *, grid, block,
                   backend: str = "vector", grain=1,
                   dyn_shared: int | None = None, interpret: bool | None = None,
                   pool: int | None = None, devices: int | None = None,
                   shard_axis: str = "blocks",
                   optimize: bool | None = None) -> GraphNode:
        grid, block = Dim3.of(grid), Dim3.of(block)
        if api._optimize_enabled(optimize):
            # barrier-fission happens at CAPTURE time: the node stores the
            # derived kernel, so every replay runs the fused stages.  The
            # analysis needs concrete buffer values; a kernel whose inputs
            # are first produced inside the graph (not yet on the heap) is
            # captured unoptimized rather than analyzed on garbage.
            needed = set(kernel.writes) | set(
                kernel.reads if kernel.reads is not None
                else stream.buffers)
            if needed <= set(stream.buffers):
                from repro.core import optimize as optimize_mod
                kernel = optimize_mod.optimize_launch(
                    kernel, grid=grid, block=block,
                    args={n: stream.buffers[n] for n in sorted(needed)},
                    dyn_shared=dyn_shared)
        heap_names = set(stream.buffers) | self.written()
        if kernel.reads is not None:
            missing = set(kernel.reads) - heap_names
            if missing:
                raise GraphError(
                    f"capture on stream {stream.name!r}: kernel "
                    f"{kernel.name} reads {sorted(missing)} which exist "
                    f"neither on the heap nor earlier in the graph")
            reads = tuple(kernel.reads)
        else:                   # undeclared reads: order after everything
            reads = tuple(sorted(heap_names))
        writes = tuple(kernel.writes)
        grain = api._resolve_grain(kernel, grain, pool, grid.size)
        idx = len(self.nodes)
        node = GraphNode(
            idx=idx, kind="kernel", stream=stream.name,
            deps=tuple(sorted(self._ordered_deps(stream.name, reads,
                                                 writes))),
            label=f"{kernel.name}[{tuple(grid)},{tuple(block)}]@{backend}",
            kernel=kernel, grid=grid, block=block, backend=backend,
            grain=grain, dyn_shared=dyn_shared, interpret=interpret,
            devices=devices, shard_axis=shard_axis,
            reads=reads, writes=writes)
        return self._commit(node)

    def add_h2d(self, stream, buffer: str, host) -> GraphNode:
        idx = len(self.nodes)
        node = GraphNode(
            idx=idx, kind="h2d", stream=stream.name,
            deps=tuple(sorted(self._ordered_deps(stream.name, (),
                                                 (buffer,)))),
            label=f"h2d:{buffer}", buffer=buffer, host=host,
            writes=(buffer,))
        return self._commit(node)

    def add_d2d(self, stream, dst: str, src: str) -> GraphNode:
        """Capture a device-to-device copy between named heap buffers."""
        known = set(stream.buffers) | self.written()
        if src not in known:
            raise GraphError(
                f"capture on stream {stream.name!r}: d2d source {src!r} "
                f"exists neither on the heap nor earlier in the graph")
        idx = len(self.nodes)
        node = GraphNode(
            idx=idx, kind="d2d", stream=stream.name,
            deps=tuple(sorted(self._ordered_deps(stream.name, (src,),
                                                 (dst,)))),
            label=f"d2d:{src}->{dst}", buffer=dst, src=src,
            reads=(src,), writes=(dst,))
        return self._commit(node)

    def add_update(self, stream, fn, writes: tuple) -> GraphNode:
        """Capture an on-device heap update (Stream.device_update).

        The update reads the whole heap (its signature is the full buffer
        dict), so it orders conservatively after every prior writer.
        """
        heap_names = tuple(sorted(set(stream.buffers) | self.written()))
        idx = len(self.nodes)
        node = GraphNode(
            idx=idx, kind="update", stream=stream.name,
            deps=tuple(sorted(self._ordered_deps(stream.name, heap_names,
                                                 tuple(writes)))),
            label=f"update:{','.join(writes)}", fn=fn,
            reads=heap_names, writes=tuple(writes))
        return self._commit(node)

    def add_event_record(self, stream, event) -> GraphNode:
        idx = len(self.nodes)
        node = GraphNode(
            idx=idx, kind="event_record", stream=stream.name,
            deps=tuple(sorted(self._ordered_deps(stream.name, (), ()))),
            label=f"record:{event.name}")
        event._capture = (self, idx)
        return self._commit(node)

    def add_event_wait(self, stream, event) -> GraphNode:
        cap = getattr(event, "_capture", None)
        if cap is None or cap[0] is not self:
            raise GraphError(
                f"stream {stream.name!r} cannot wait on event "
                f"{event.name!r}: it was not recorded during this capture "
                f"(record it on a stream captured into the same graph)")
        deps = self._ordered_deps(stream.name, (), ()) | {cap[1]}
        idx = len(self.nodes)
        node = GraphNode(idx=idx, kind="event_wait", stream=stream.name,
                         deps=tuple(sorted(deps)),
                         label=f"wait:{event.name}")
        return self._commit(node)

    # -- structure -----------------------------------------------------------
    def levels(self) -> list[list[int]]:
        """Topological levels: nodes in one level are mutually independent."""
        depth: dict[int, int] = {}
        out: list[list[int]] = []
        for n in self.nodes:
            d = 1 + max((depth[i] for i in n.deps), default=-1)
            depth[n.idx] = d
            while len(out) <= d:
                out.append([])
            out[d].append(n.idx)
        return out

    def summary(self) -> str:
        lines = [f"graph: {len(self.nodes)} nodes, "
                 f"{len(self.levels())} levels"]
        for lvl, idxs in enumerate(self.levels()):
            labels = ", ".join(self.nodes[i].label for i in idxs)
            lines.append(f"  level {lvl}: {labels}")
        return "\n".join(lines)

    def instantiate(self, buffers: dict | None = None) -> "GraphExec":
        """Compile the DAG into a single-dispatch executable
        (``cudaGraphInstantiate``).  With ``buffers`` the replay is
        shape-validated eagerly; otherwise validation happens on first
        launch."""
        if self._streams:
            raise GraphError(
                "instantiate() during capture: call end_capture() first "
                f"(streams still capturing: "
                f"{[s.name for s in self._streams]})")
        ex = GraphExec(self)
        if buffers is not None:
            ex.validate(buffers)
        return ex


class GraphExec:
    """An instantiated graph: one jitted replay over the buffer heap.

    ``replay(buffers)`` is the pure-functional core: heap dict in, updated
    written-buffer dict out, all captured nodes executed inside a single
    jitted call.  ``launch(stream)`` is ``cudaGraphLaunch``: it orders the
    replay after in-flight foreign writers of touched buffers (the eager
    runtime's hazard rule), dispatches once, and marks the written buffers
    pending on the stream.
    """

    def __init__(self, graph: Graph):
        self.graph = graph
        self.written = tuple(sorted(graph.written()))
        self.launches = 0
        # heap inputs: every touched buffer that is not first produced
        # inside the graph itself
        produced: set[str] = set()
        needed: set[str] = set()
        for n in graph.nodes:
            needed.update(b for b in n.reads if b not in produced)
            needed.update(b for b in n.writes
                          if n.kind == "kernel" and b not in produced)
            produced.update(n.writes)
        self.inputs = tuple(sorted(needed))
        self._host = [n.host for n in graph.nodes if n.kind == "h2d"]

        def replay(heap: dict, host: Sequence):
            return self._replay(heap, host)

        kernels = dict.fromkeys(api.program_name(n.kernel.name, n.backend)
                                for n in graph.nodes if n.kind == "kernel")
        replay.__name__ = replay.__qualname__ = api.program_name(
            *kernels, "graph")
        self._jit = jax.jit(replay)

    def _replay(self, heap: dict, host: Sequence):
        glob = dict(heap)
        hi = 0
        for node in self.graph.nodes:
            if node.kind == "kernel":
                entry = get_backend(node.backend)
                with jax.named_scope(api.program_name(node.kernel.name)):
                    out = entry.run(node.kernel, grid=node.grid,
                                    block=node.block, glob=dict(glob),
                                    grain=node.grain,
                                    dyn_shared=node.dyn_shared,
                                    interpret=node.interpret,
                                    **api.device_opts(entry, node.devices,
                                                      node.shard_axis))
                for b in node.writes:
                    glob[b] = out[b]
            elif node.kind == "h2d":
                glob[node.buffer] = host[hi]
                hi += 1
            elif node.kind == "d2d":
                glob[node.buffer] = glob[node.src]
            elif node.kind == "update":
                upd = node.fn(dict(glob))
                for b in node.writes:
                    glob[b] = upd[b]
            # event nodes: ordering only, nothing to execute
        return {b: glob[b] for b in self.written}

    def _heap_inputs(self, buffers: dict) -> dict:
        missing = [b for b in self.inputs if b not in buffers]
        if missing:
            raise GraphError(
                f"graph replay needs buffer(s) {missing} on the heap")
        # ConstArray/DeviceBuffer heap entries unwrap (liveness-checked)
        # here: the jitted replay traces over raw arrays only
        return {b: memory_mod.unwrap(buffers[b], "graph replay")
                for b in self.inputs}

    def validate(self, buffers: dict) -> None:
        """Abstractly trace the replay to surface shape/support errors."""
        import jax.numpy as jnp
        heap = self._heap_inputs(buffers)
        jax.eval_shape(self._replay, heap,
                       tuple(jnp.asarray(h) for h in self._host))

    def update_h2d(self, buffer: str, host) -> None:
        """Swap a captured memcpy's source (cudaGraphExecMemcpyNodeSetParams
        analogue): same shape/dtype, no re-instantiation needed."""
        h2d_nodes = [n for n in self.graph.nodes if n.kind == "h2d"]
        matches = [i for i, n in enumerate(h2d_nodes) if n.buffer == buffer]
        if not matches:
            raise GraphError(
                f"no captured h2d node writes buffer {buffer!r}")
        if len(matches) > 1:
            raise GraphError(
                f"{len(matches)} captured h2d nodes write buffer "
                f"{buffer!r}; per-node updates of multi-copy graphs are "
                f"not supported - re-capture instead")
        i = matches[0]
        old, new = np.asarray(self._host[i]), np.asarray(host)
        if old.shape != new.shape or old.dtype != new.dtype:
            raise GraphError(
                f"update_h2d({buffer!r}): replacement must match the "
                f"captured copy ({old.shape}, {old.dtype.name}), got "
                f"({new.shape}, {new.dtype.name})")
        self._host[i] = host

    def replay(self, buffers: dict) -> dict:
        """Run the whole DAG as one dispatch; returns written buffers.
        The first replay compiles, and is counted in
        :func:`repro.core.api.cache_stats` as a first dispatch."""
        first = self.launches == 0
        self.launches += 1
        t0 = time.perf_counter()
        out = self._jit(self._heap_inputs(buffers), tuple(self._host))
        if first:
            api.count_first_dispatch(t0)
        return out

    def launch(self, target) -> Any:
        """``cudaGraphLaunch``: replay onto a stream's (or runtime's
        default-stream's) heap, honoring cross-stream hazards."""
        stream = target.default if hasattr(target, "default") else target
        if getattr(stream, "_capture", None) is not None:
            raise GraphError(
                f"stream {stream.name!r} is capturing; graph launch inside "
                f"a capture is not supported")
        stream._wait_foreign_writers(self.graph.touched())
        out = self.replay(stream.buffers)
        stream.buffers.update(out)
        stream._mark_pending(self.written)
        stream.stats.graph_launches += 1
        return stream
