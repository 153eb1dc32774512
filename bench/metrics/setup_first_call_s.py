"""Host seconds in the first dispatch of each new launch specialization
over the process: XLA lowering plus the backend compile or the
persistent-cache fetch (program counter: ``api.cache_stats()``
``first_call_s``).  Nothing where the program keeps no such counter."""


def read(run):
    from repro.core import api
    stats = api.cache_stats()
    if not getattr(stats, "first_calls", 0):
        return None
    return stats.first_call_s
