"""The one general load generator: closed-loop application jobs.

A traffic mix is a JSON file of parameters under ``bench/traffic/``:
``{"kind": "job", "pool": P, "trace_s": T}`` runs whole application jobs
back to back (closed loop, one at a time).  The window closes at the end of
the job in flight when ``--seconds`` have passed, so it always holds whole
jobs.  ``pool`` is the number of distinct inputs drawn from the seed that
the jobs take in turn; a ``--trace 1`` run traces only the jobs of the
first ``trace_s`` seconds of its window, since the profiler keeps an event
for every iteration of a while loop and a whole window would overflow it.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable


@dataclasses.dataclass
class JobWindow:
    """A closed-loop window: ``jobs`` whole jobs from ``start`` to ``end``."""

    start: float
    end: float
    jobs: int

    @property
    def seconds(self) -> float:
        return self.end - self.start


def run_jobs(job: Callable[[int], None], seconds: float,
             clock: Callable[[], float] = time.perf_counter) -> JobWindow:
    """Run ``job(0)``, ``job(1)``, ... until ``seconds`` have passed; the
    job in flight then is finished, and the window ends with it."""
    start = now = clock()
    jobs = 0
    while not jobs or now - start < seconds:
        job(jobs)
        jobs += 1
        now = clock()
    return JobWindow(start, now, jobs)
