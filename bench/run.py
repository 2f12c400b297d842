#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (from a profiler trace of the window).  The last line
of stdout is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (with ``busy_s``/``window_s`` when traced),
``breakdown`` when traced, and last ``checks``: every number compared
with the reference beside its limit (also the last lines of stderr).
The run needs the chips the cell asks for; without them it exits
non-zero and prints no result.

Two options no check uses: ``--control bfloat16`` serves in that dtype
(the comparison's control, which must come out not correct), and
``--sweep r1,r2,...`` serves the cell's open-loop mix at each offered
rate in turn after one set-up and prints one line per rate (how the
rates in ``bench/traffic`` were found).  ``--keep-trace FILE`` writes
the first 200 ms of a traced window as a JSON extract.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the compilation cache lives in the checkout, at a fixed path, and keeps
# every entry (a size cap makes JAX evict, and one entry left without its
# access-time file then fails every later write)
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--sweep", default=None)
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)

    from bench import harness

    bm, cell, config, mix = harness.load_cell(args.workload)
    log = lambda s: print(s, file=sys.stderr, flush=True)  # noqa: E731
    rates = [float(r) for r in args.sweep.split(",")] if args.sweep else None
    for out in harness.run_cell(
            config, mix,
            harness.metrics_of(bm, args.workload, args.trace), args.seed,
            args.seconds, bool(args.trace), T_START,
            chips=int(cell["chips"]), control=args.control,
            keep_trace=args.keep_trace, sweep=rates, log=log):
        for name, c in out.get("checks", {}).items():
            log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
