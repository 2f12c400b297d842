"""Benchmark driver: one module per paper table/figure plus the serving-
system benchmarks.  ``python -m benchmarks.run [--quick] [--only NAME]``
prints one CSV-ish line per measurement (prefix identifies the table).

The benchmark set, its execution order, and the one-line description each
``--help`` and ``docs/benchmarks.md`` show all come from ONE place:
``benchmarks.registry.BENCHMARKS`` (the docs CI job asserts the
descriptions appear verbatim in the methodology page, so code and docs
cannot drift).  Methodology — what each line means and which paper
figure/table it reproduces — lives in docs/benchmarks.md.
"""
from __future__ import annotations

import argparse
import importlib
import time
import traceback

from benchmarks.registry import BENCHMARKS, describe
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(
        formatter_class=argparse.RawDescriptionHelpFormatter,
        epilog="benchmarks (run in this order; see docs/benchmarks.md):\n"
               + describe())
    ap.add_argument("--quick", action="store_true",
                    help="reduced sizes/steps (CI mode)")
    ap.add_argument("--only", default=None, choices=sorted(BENCHMARKS),
                    help="run a single benchmark by registry name")
    args = ap.parse_args()

    names = [args.only] if args.only else list(BENCHMARKS)
    failures = 0
    for name in names:
        mod = importlib.import_module(BENCHMARKS[name][0])
        t0 = time.time()
        print(f"== {name} ==", flush=True)
        try:
            mod.main(quick=args.quick)
            print(f"== {name} done in {time.time() - t0:.1f}s ==", flush=True)
        except Exception:
            failures += 1
            print(f"== {name} FAILED ==")
            traceback.print_exc()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
