"""Rodinia srad_v1: the program's entry, seeded inputs, plain reference, work.

The reference is the benchmark's own copy of srad_v1's equations (Rodinia
3.1 ``cuda/srad/srad_v1``: extract, per iteration the image statistics,
srad and srad2, then compress), so that no change to the program's suite
can move the yardstick.  The image is column-major, as Rodinia keeps it:
pixel ``ei`` is row ``ei % rows``, column ``ei // rows``.
"""
from __future__ import annotations

import numpy as np

OUTPUT = "I"


def entry(p: dict):
    """The program's srad_v1 chain at these sizes (its public entry)."""
    from repro.core.cuda_suite import SRAD_THREADS, entry_srad_v1
    if p["threads"] != SRAD_THREADS:
        raise ValueError(f"threads {p['threads']}: the program's srad_v1 "
                         f"runs {SRAD_THREADS} a block")
    return entry_srad_v1(p["rows"], p["cols"], p["iterations"], p["lambda"])


def inputs(p: dict, rng: np.random.Generator) -> dict:
    """An 8-bit image from ``rng``, Rodinia's clamped neighbour index
    tables, and the zeroed device buffers the kernels write."""
    rows, cols = p["rows"], p["cols"]
    ne = rows * cols
    i = np.arange(rows, dtype=np.int32)
    j = np.arange(cols, dtype=np.int32)
    zero = np.zeros(ne, np.float32)
    return {"I": rng.integers(0, 256, ne).astype(np.float32),
            "iN": np.maximum(i - 1, 0), "iS": np.minimum(i + 1, rows - 1),
            "jW": np.maximum(j - 1, 0), "jE": np.minimum(j + 1, cols - 1),
            "sums": zero, "sums2": zero.copy(), "dN": zero.copy(),
            "dS": zero.copy(), "dW": zero.copy(), "dE": zero.copy(),
            "c": zero.copy(), "q0sqr": np.zeros(1, np.float32)}


def reference(p: dict, inp: dict, dtype=np.float32) -> np.ndarray:
    """``iterations`` srad_v1 steps, every operation rounded to ``dtype``
    but the image sums, which are exact in float64 and then rounded."""
    rows, cols = p["rows"], p["cols"]
    ne = rows * cols

    def k(x):
        return np.asarray(x, dtype)

    img = np.asarray(inp["I"]).reshape(cols, rows).astype(dtype)  # [col, row]
    img = np.exp(img / k(255))
    for _ in range(p["iterations"]):
        total = k(img.astype(np.float64).sum())
        total2 = k((img * img).astype(np.float64).sum())
        mean = total / k(ne)
        mean2 = mean * mean
        q0 = (total2 / k(ne) - mean2) / mean2
        pad = np.pad(img, 1, mode="edge")
        dn, ds = pad[1:-1, :-2] - img, pad[1:-1, 2:] - img
        dw, de = pad[:-2, 1:-1] - img, pad[2:, 1:-1] - img
        g2 = (dn * dn + ds * ds + dw * dw + de * de) / (img * img)
        lap = (dn + ds + dw + de) / img
        num = k(0.5) * g2 - k(0.0625) * (lap * lap)
        den = k(1) + k(0.25) * lap
        qsqr = num / (den * den)
        den = (qsqr - q0) / (q0 * (k(1) + q0))
        c = k(1) / (k(1) + den)
        c = np.where(c < 0, k(0), np.where(c > 1, k(1), c))
        cpad = np.pad(c, 1, mode="edge")
        d = c * dn + cpad[1:-1, 2:] * ds + c * dw + cpad[2:, 1:-1] * de
        img = img + k(0.25 * p["lambda"]) * d
    return (np.log(img) * k(255)).reshape(-1).astype(np.float32)


def work(p: dict) -> tuple[float, float]:
    """Least operations and bytes of one job, from the application's shape.

    Per pixel and iteration, 43 floating-point operations: the statistics
    3 (add the pixel, square it, add the square); the four differences 4;
    the squared gradient 9 (four squares, three adds, the pixel's square,
    one divide); the Laplacian 4 (three adds, one divide); the numerator 4
    (halve, square, sixteenth, subtract); the denominator 2; ``qsqr`` 2
    (square, divide); the coefficient 6 (subtract ``q0sqr``, divide by the
    image's ``q0sqr (1 + q0sqr)``, add one, invert, two clamps); the
    divergence 7 (four products, three adds); the update 2.  Per pixel
    and job, 4 more: extract (divide, exp) and compress (log, multiply).
    Each iteration reads the image and writes it once, 4 bytes a pixel
    each way; the statistics of an iteration can be summed as the
    iteration before writes the image, and extract and compress fold into
    the first and last iteration.
    """
    ne = p["rows"] * p["cols"]
    return 43.0 * ne * p["iterations"] + 4.0 * ne, 8.0 * ne * p["iterations"]
