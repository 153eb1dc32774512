"""The generator: a closed-loop window closes at a job boundary."""
from __future__ import annotations

from bench.lib.traffic import run_jobs


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_window_ends_with_the_job_in_flight():
    clock = FakeClock()
    durations = [0.7, 0.7, 0.7, 0.7, 0.7]

    def job(i):
        clock.t += durations[i]

    win = run_jobs(job, 2.0, clock=clock)
    # 2.0 s runs out during the third job, which is finished
    assert win.jobs == 3
    assert abs(win.seconds - 2.1) < 1e-9
    assert abs(win.seconds / win.jobs - 0.7) < 1e-9


def test_window_holds_at_least_one_job():
    clock = FakeClock()
    win = run_jobs(lambda i: setattr(clock, "t", clock.t + 5.0), 1.0,
                   clock=clock)
    assert win.jobs == 1 and win.seconds == 5.0
