"""Host seconds in the launch cache's miss path over the process: the
trace, the Mosaic compile for ``pallas``, any disk-artifact load or store
(program counter: ``api.cache_stats().trace_s``).  All in set-up when
the run's note ``compile_in_window_s`` is 0.  Nothing where the program
keeps no such counter, or nothing missed."""


def read(run):
    from repro.core import api
    stats = api.cache_stats()
    if not stats.misses or not hasattr(stats, "trace_s"):
        return None
    return stats.trace_s
