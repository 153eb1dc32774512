"""Device-to-host reads the chain replay makes per job: copies of the
buffers a chain step declares to read back, over the chain runs of the
process (program counter: ``kernel.chain_totals()`` ``host_reads`` over
``runs``).  Nothing where the program keeps no such counter."""


def read(run):
    try:
        from repro.core.kernel import chain_totals
    except ImportError:
        return None
    totals = chain_totals()
    if not totals.runs:
        return None
    return totals.host_reads / totals.runs
