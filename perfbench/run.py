"""Run one benchmark cell and print its result line.

    python3 perfbench/run.py --workload dialogpt-medium.sessions \
        --seed 1234 --seconds 51 --trace 0

From the root of a checkout that holds the program (``src/``).  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace
0``, its per-layer metrics with ``--trace 1``), ``device`` and, last,
``checks``: each number compared for ``correct`` with its limit.  The line
before it is a diagnostic: where set-up went, compilations inside the
window, how late the generator ran and the requests of the window.  The
checks are also the last lines of standard error.

A run that finds no TPU, or fewer chips than the cell asks for, exits 2
and prints no result.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    from harness import cell
    try:
        out = cell.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_proc=T_PROC)
    except cell.NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    res = out["result"]
    for k, c in res["checks"].items():
        print(f"check {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print("diagnostics " + json.dumps(out["diagnostics"]))
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
