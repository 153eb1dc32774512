"""Reduce a JAX profiler trace to busy time, idle gaps and op self times.

:func:`record` runs a block under ``jax.profiler``; :func:`load` reads the
``.xplane.pb`` it wrote with ``jax.profiler.ProfileData``, once the
measurement is over (reading millions of events takes tens of seconds).
It keeps, per device plane, the intervals in which a program ran (busy
time), the op events of the first device plane (the breakdown), and the
benchmark's own host spans (names starting
``bench.``, written with ``jax.profiler.TraceAnnotation``).  The traced
window is the ``bench.window`` span.  Everything after that is interval
arithmetic on ``(name, start_ns, end_ns)`` tuples, kept free of the
profiler so that tests can check it on hand-made intervals.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import itertools
import os
import tempfile
from typing import Callable

WINDOW = "bench.window"
MODULES = "XLA Modules"
OPS = "XLA Ops"

#: op events read per line: XLA:TPU writes every op of every while-loop
#: iteration (2.3 M for one hotspot job), so the breakdown reads a sample
MAX_OPS = 1_000_000


def is_tpu_plane(name: str) -> bool:
    return name.startswith("/device:TPU:")


def is_busy_line(name: str) -> bool:
    """XLA:TPU writes each program run on ``XLA Modules``."""
    return name == MODULES


def is_op_line(name: str) -> bool:
    """Each op of a program (every while-loop iteration included) is on
    ``XLA Ops``."""
    return name == OPS


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[..] fusion(..)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class Trace:
    """Busy intervals per device plane, op events of the first device
    plane per line, host spans, and the window."""

    busy: dict               # plane -> [(start_ns, end_ns)] of programs
    ops: dict                # line -> [(op, start_ns, end_ns)], 1st plane
    host: list               # [(span, start_ns, end_ns)], ``bench.*`` only
    window: tuple            # (start_ns, end_ns) of ``bench.window``

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> dict:
        """Per device, seconds of the window in which a program ran."""
        lo, hi = self.window
        return {d: busy_ns(iv, lo, hi) / 1e9 for d, iv in self.busy.items()}

    def mean_busy_s(self) -> float:
        busy = self.busy_s()
        return sum(busy.values()) / len(busy) if busy else 0.0


def load(path: str, device_plane: Callable[[str], bool] = is_tpu_plane,
         busy_line: Callable[[str], bool] = is_busy_line,
         op_line: Callable[[str], bool] = is_op_line) -> Trace:
    """Read the ``.xplane.pb`` under ``path`` (a profiler log directory)."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {path}, found "
                           f"{len(files)}")
    data = ProfileData.from_file(files[0])
    planes = [p.name for p in data.planes if device_plane(p.name)]
    busy, ops, host = {p: [] for p in planes}, {}, []
    names: dict = {}
    for plane in data.planes:
        device = plane.name in busy
        for line in plane.lines:
            is_busy = device and busy_line(line.name)
            is_ops = device and op_line(line.name)
            if is_busy:
                busy[plane.name] += [(ev.start_ns, ev.end_ns)
                                     for ev in line.events]
            if is_ops and plane.name == min(planes):
                evs = []
                for ev in itertools.islice(line.events, MAX_OPS):
                    n = ev.name
                    if n not in names:
                        names[n] = op_name(n)
                    evs.append((names[n], ev.start_ns, ev.end_ns))
                ops[line.name] = evs
            if not (is_busy or is_ops):
                host.extend((ev.name, ev.start_ns, ev.end_ns)
                            for ev in line.events
                            if ev.name.startswith("bench."))
    windows = [(s, e) for name, s, e in host if name == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW} span in the trace, found "
                           f"{len(windows)}")
    return Trace(busy, ops, host, windows[0])


@contextlib.contextmanager
def record(into: list):
    """Trace the block as the window; append the profiler's log directory
    (a fresh one under ``TMPDIR``) to ``into``.  Read it with :func:`load`
    after the measurement, and remove it."""
    import jax
    d = tempfile.mkdtemp(prefix="bench-trace-")
    into.append(d)
    jax.profiler.start_trace(d)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()


def union(intervals) -> list:
    """Merge ``(start, end)`` or ``(name, start, end)`` intervals into
    disjoint, sorted ``(start, end)`` pairs."""
    spans = sorted((iv[-2], iv[-1]) for iv in intervals if iv[-1] > iv[-2])
    out = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(spans, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in spans if e > lo and s < hi]


def busy_ns(intervals, lo, hi) -> float:
    """Length of the union of ``intervals`` inside ``[lo, hi]``."""
    return float(sum(e - s for s, e in clip(union(intervals), lo, hi)))


def idle_pct(busy: float, window: float) -> float:
    return 100.0 * (1.0 - busy / window)


def gaps(intervals, lo, hi) -> list:
    """The idle ``(start, end)`` stretches of ``[lo, hi]``, longest first."""
    out, cur = [], lo
    for s, e in clip(union(intervals), lo, hi):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return sorted(out, key=lambda g: g[0] - g[1])


def self_ns(events) -> dict:
    """Self time in ns per op name: its duration less that of the ops
    nested directly inside it on the same line (a while loop's self time
    is its own control between the ops of its body)."""
    tot: dict = {}
    stack: list = []                   # [name, end, child_ns, dur_ns]

    def close(frame):
        name, _, child, dur = frame
        tot[name] = tot.get(name, 0) + max(0, dur - child)

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        if e <= s:
            continue
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] += e - s
        stack.append([name, e, 0, e - s])
    while stack:
        close(stack.pop())
    return tot


def what_host_did(gap, host) -> str:
    """The host span that overlaps an idle gap most, or ``host.other``."""
    best, label = 0, "host.other"
    for name, s, e in host:
        if name == WINDOW:
            continue
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap > best:
            best, label = overlap, name
    return label


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The ``breakdown`` of a result line, from the first device plane:
    the ops with the most self time (in the sample read), and the longest
    idle gaps by what the host was doing in them (seconds)."""
    if not tr.busy:
        return {"device_ops": [], "idle_gaps": []}
    lo, hi = tr.window
    per_op: dict = {}
    for evs in tr.ops.values():
        inside = [ev for ev in evs if ev[1] < hi and ev[2] > lo]
        for name, ns in self_ns(inside).items():
            per_op[name] = per_op.get(name, 0) + ns
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle = gaps(tr.busy[min(tr.busy)], lo, hi)[:top]
    return {"device_ops": [[n, ns / 1e9] for n, ns in top_ops if ns > 0],
            "idle_gaps": [[what_host_did(g, tr.host), (g[1] - g[0]) / 1e9]
                          for g in idle]}
