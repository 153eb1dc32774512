"""Seconds of set-up in JAX's trace, lower and backend-compile duration
events (program counter: JAX's monitoring events)."""


def read(run):
    return run.setup_compile_s
