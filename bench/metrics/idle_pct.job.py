"""Device idle share of a job window: 1 - union of device op intervals over
the traced window, averaged over the cell's devices (device trace)."""
from bench.lib.trace import idle_pct


def read(run):
    if run.kind != "job" or run.trace is None or not run.trace.busy:
        return None
    return idle_pct(run.trace.mean_busy_s(), run.trace.window_s)
