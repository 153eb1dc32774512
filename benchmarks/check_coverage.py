"""Coverage gate: fail CI when any backend's suite coverage regresses.

Runs the Table-II coverage sweep (``benchmarks/coverage.py``) and compares
each backend's number of correct kernels *and* its paper-style coverage
percentage (the figure published next to the paper's 69.6%/56.6%) against
the committed baseline in ``benchmarks/coverage_baseline.json``.  Any drop
fails the gate; gains (e.g. a new backend adding a row per kernel) are
reported with a hint to refresh the baseline via ``--update`` - regenerate
it, never hand-edit.  The percentage check matters independently of the
raw counts: growing the suite by five kernels while supporting none of
them keeps every count flat but dilutes the percentage, which is exactly
the regression the paper's headline figure would catch.

``--disable KERNEL`` artificially marks one suite kernel unsupported on
every backend before comparing - CI uses this to prove the gate actually
trips (a gate that cannot fail gates nothing).  ``--json PATH`` writes the
measured counts/percentages as a machine-readable artifact for CI upload.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import coverage as coverage_bench

from repro.core import compile_cache

BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "coverage_baseline.json")


def current_counts(disable: str | None = None) -> tuple[dict, dict, int]:
    table = coverage_bench.run()
    if disable is not None:
        if disable not in table:
            raise SystemExit(
                f"--disable {disable!r}: no such suite kernel; "
                f"have {sorted(table)}")
        row, feats = table[disable]
        table[disable] = ({fw: "unsupport" for fw in row}, feats)
    counts = {fw: sum(table[k][0][fw] == "correct" for k in table)
              for fw in coverage_bench.frameworks()}
    pct = coverage_bench.percentages(table)
    return counts, {fw: round(pct[fw], 1) for fw in counts}, len(table)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--update", "--write", action="store_true",
                    dest="write",
                    help="regenerate the baseline from the current suite "
                         "(instead of hand-editing it)")
    ap.add_argument("--disable", metavar="KERNEL",
                    help="artificially disable one kernel (gate self-test)")
    ap.add_argument("--baseline", default=BASELINE)
    ap.add_argument("--json", metavar="PATH",
                    help="write the measured counts/percentages here "
                         "(CI artifact)")
    args = ap.parse_args(argv)

    counts, percent, n_kernels = current_counts(args.disable)

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"n_kernels": n_kernels, "backends": counts,
                       "percent": percent}, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"coverage artifact written: {args.json}")

    if args.write:
        with open(args.baseline, "w") as f:
            json.dump({"n_kernels": n_kernels, "backends": counts,
                       "percent": percent}, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"baseline written: {args.baseline}")
        return 0

    try:
        with open(args.baseline) as f:
            base = json.load(f)
    except FileNotFoundError:
        print(f"FAIL: no baseline at {args.baseline}; commit one with "
              f"--write", file=sys.stderr)
        return 2

    failed = False
    base_pct = base.get("percent", {})
    for fw, want in sorted(base["backends"].items()):
        got = counts.get(fw)
        if got is None:
            print(f"FAIL {fw}: backend disappeared from the registry "
                  f"(baseline: {want}/{base['n_kernels']})",
                  file=sys.stderr)
            failed = True
        elif got < want:
            print(f"FAIL {fw}: {got}/{n_kernels} correct, baseline "
                  f"{want}/{base['n_kernels']}", file=sys.stderr)
            failed = True
        elif fw in base_pct and percent[fw] < base_pct[fw]:
            # counts held but the published percentage regressed - the
            # suite grew faster than this backend's support
            print(f"FAIL {fw}: coverage {percent[fw]}% below baseline "
                  f"{base_pct[fw]}%", file=sys.stderr)
            failed = True
        elif got > want:
            print(f"PASS {fw}: {got}/{n_kernels} correct "
                  f"({percent[fw]}%; baseline {want}; refresh with "
                  f"--write)")
        else:
            print(f"PASS {fw}: {got}/{n_kernels} correct ({percent[fw]}%)")
    for fw in sorted(set(counts) - set(base["backends"])):
        print(f"NOTE {fw}: new backend ({counts[fw]}/{n_kernels} correct), "
              f"not in baseline")

    if n_kernels < base["n_kernels"]:
        print(f"FAIL: suite shrank to {n_kernels} kernels "
              f"(baseline {base['n_kernels']})", file=sys.stderr)
        failed = True

    if failed:
        print("coverage gate: FAILED", file=sys.stderr)
        return 1
    print("coverage gate: passed")
    return 0


if __name__ == "__main__":
    compile_cache.use_jax_cache()
    sys.exit(main())
