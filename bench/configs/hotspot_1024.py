"""Rodinia hotspot: the program's entry, seeded inputs, plain reference, work.

The reference is the benchmark's own copy of the explicit thermal update
(Rodinia hotspot's ``compute_tran_temp`` with one step per launch), so
that no change to the program's suite can move the yardstick.
"""
from __future__ import annotations

import numpy as np

OUTPUT = "t_out"


def entry(p: dict):
    """The program's hotspot chain at these sizes (its public builder)."""
    from repro.core.cuda_suite import entry_hotspot
    return entry_hotspot(p["rows"], p["cols"], p["iterations"],
                         **p["coefficients"])


def inputs(p: dict, rng: np.random.Generator) -> dict:
    shape = (p["rows"], p["cols"])
    return {"t": rng.uniform(60.0, 100.0, shape).astype(np.float32),
            "p": rng.uniform(0.0, 1.0, shape).astype(np.float32),
            "t_out": np.zeros(shape, np.float32)}


def reference(p: dict, inp: dict, dtype=np.float32) -> np.ndarray:
    """``iterations`` explicit steps with edge-clamped neighbours, every
    operation rounded to ``dtype``."""
    k = {name: np.asarray(v, dtype) for name, v in p["coefficients"].items()}
    two = np.asarray(2.0, dtype)
    t = np.asarray(inp["t"]).astype(dtype)
    pw = np.asarray(inp["p"]).astype(dtype)
    for _ in range(p["iterations"]):
        tp = np.pad(t, 1, mode="edge")
        north, south = tp[:-2, 1:-1], tp[2:, 1:-1]
        west, east = tp[1:-1, :-2], tp[1:-1, 2:]
        t = t + k["cap"] * (pw + k["ry"] * (north + south - two * t)
                            + k["rx"] * (west + east - two * t)
                            + k["rz"] * (k["amb"] - t))
    return t.astype(np.float32)


def work(p: dict) -> tuple[float, float]:
    """Least operations and bytes of one job, from the application's shape.

    Per cell and step: 14 floating-point operations (``2t`` once, three
    per neighbour pair term, two for the ambient term, three to sum the
    four terms, one for ``cap`` and one to add to ``t``); the step reads
    ``t`` and the power grid and writes the new ``t`` once each, 4 bytes
    an element.
    """
    cells = p["rows"] * p["cols"] * p["iterations"]
    return 14.0 * cells, 3.0 * 4 * cells
