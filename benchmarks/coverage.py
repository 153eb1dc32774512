"""Paper Table II analog: kernel-suite coverage per framework/lowering.

Frameworks modeled (SVII-A):
  naive        - MCUDA-without-fission (single-stage kernels only)
  loop_nowarp  - DPC++/HIP-CPU class (barriers ok, no warp intrinsics)
  loop         - CuPBoP/COX loop lowering (full)
  vector       - CuPBoP-JAX TPU vector lowering (full)
  pallas       - CuPBoP-JAX Pallas emission (full)

The paper's headline: CuPBoP 69.6% vs 56.6% on Rodinia; Crystal 100% vs 0/76.9
(warp shuffle + atomicCAS gaps).  Our suite reproduces the *ordering* with the
same feature-driven gaps, and :func:`percentages` publishes the paper-style
coverage percentage (correct kernels / suite size, per framework) that the
README table and the CI coverage gate consume.
"""
from __future__ import annotations

import numpy as np

from repro.core import UnsupportedKernel, backend_names, compile_cache
from repro.core.cuda_suite import build_suite, run_entry

#: the paper's Table II Rodinia coverage: CuPBoP vs the best prior
#: CUDA-on-CPU translator (DPC++).  Our percentages are over the suite's
#: kernels, not the full Rodinia set, so the *ordering* is the claim.
PAPER_CUPBOP_PCT = 69.6
PAPER_PRIOR_PCT = 56.6


def frameworks() -> tuple[str, ...]:
    """Columns come from the live backend registry, not a frozen tuple."""
    return backend_names()


def percentages(table: dict) -> dict[str, float]:
    """Paper-style coverage percentage per framework.

    ``correct`` cells count toward coverage; ``unsupport`` *and*
    ``incorrect`` cells count against it (a wrong answer is no more
    coverage than a refusal).  Keys follow the table's rows, so a table
    from :func:`run` yields one percentage per registered backend.
    """
    if not table:
        return {fw: 0.0 for fw in frameworks()}
    fws = next(iter(table.values()))[0].keys()
    return {fw: 100.0 * sum(row[fw] == "correct"
                            for row, _ in table.values()) / len(table)
            for fw in fws}


def run() -> dict:
    suite = build_suite(scale=1)
    table = {}
    for e in suite:
        row = {}
        for fw in frameworks():
            try:
                # run_entry drives chain entries (wavefront kernels) through
                # their full LaunchChain, so "correct" means the whole
                # Rodinia-style workload agreed, not just one launch
                out, want = run_entry(e, fw, rng=np.random.default_rng(0))
                tol = max(e.tol, 2e-5)
                ok = all(np.allclose(np.asarray(out[k]), v, rtol=tol,
                                     atol=tol) for k, v in want.items())
                row[fw] = "correct" if ok else "incorrect"
            except UnsupportedKernel:
                row[fw] = "unsupport"
        table[e.name] = (row, e.features)
    return table


def main():
    table = run()
    names = sorted(table)
    fws = frameworks()
    print("kernel," + ",".join(fws) + ",features")
    for n in names:
        row, feats = table[n]
        print(n + "," + ",".join(row[f] for f in fws)
              + "," + "|".join(feats))
    print()
    pct = percentages(table)
    for fw in fws:
        print(f"coverage_{fw},{pct[fw]:.1f},%")
    cov = {fw: sum(table[n][0][fw] == "correct" for n in names)
           for fw in fws}
    assert cov["naive"] < cov["loop_nowarp"] < cov["loop"] == cov["vector"], \
        "paper's coverage ordering must reproduce"
    print("paper_ordering,1,naive<nowarp<cupbop (Table II reproduced)")
    print(f"paper_figures,CuPBoP {PAPER_CUPBOP_PCT}% vs prior "
          f"{PAPER_PRIOR_PCT}% on Rodinia; here loop/vector reach "
          f"{pct['loop']:.1f}% vs loop_nowarp {pct['loop_nowarp']:.1f}% "
          f"vs naive {pct['naive']:.1f}%")


if __name__ == "__main__":
    compile_cache.use_jax_cache()
    main()
