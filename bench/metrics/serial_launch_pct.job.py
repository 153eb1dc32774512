"""Share of the process's launches with a vector block schedule that ran
the serial one (program counter: ``api.cache_stats()``
``serial_launches`` over ``serial_launches + tiled_launches``).  Nothing
where the program keeps no such counter."""


def read(run):
    from repro.core import api
    stats = api.cache_stats()
    serial = getattr(stats, "serial_launches", None)
    tiled = getattr(stats, "tiled_launches", None)
    if serial is None or tiled is None or not serial + tiled:
        return None
    return 100.0 * serial / (serial + tiled)
