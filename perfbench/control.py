"""Readings for the limit of ``max_logit_gap``: the program's and the
control's, seed by seed, in one process.

    python3 perfbench/control.py --workload dialogpt-medium.sessions \
        --seconds 51 --seeds 101 102 103

Each seed is one run of the cell as ``run.py`` makes it (same set-up,
load and window).  After the window the compared requests are judged
twice by the same ``check.judge`` that decides ``correct``: as served
(the program's verdict) and with the program's tokens replaced by those
that the reference computed in fp8 puts first at each served position
(the control's verdict, which has to come out false).  The limit lies
between the largest program reading and the smallest control reading.
The benchmark's own runs do not run this.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from harness import cell, check
    rows = []
    for seed in args.seeds:
        out = cell.run(args.workload, seed, args.seconds, False,
                       t_proc=time.perf_counter())
        ref, params, model = out["reference"], out["params"], out["model"]
        control = check.gaps(ref, params, model, out["compared"],
                             quant="fp8")
        res = out["result"]
        tokens = res["checks"]["tokens_compared"]["value"]
        _, ctl_correct = check.judge(float(control.max()), res["failed"],
                                     tokens, out["limits"])
        row = {"seed": seed,
               "program": {"correct": res["correct"],
                           "max_logit_gap":
                           res["checks"]["max_logit_gap"]["value"]},
               "control": {"correct": ctl_correct,
                           "max_logit_gap": float(control.max())},
               "tokens_compared": tokens,
               "limit": out["limits"]["max_logit_gap"]}
        print(json.dumps(row), flush=True)
        rows.append(row)
        del out, ref, params
        gc.collect()
    print(json.dumps({
        "workload": args.workload,
        "program_max": max(r["program"]["max_logit_gap"] for r in rows),
        "control_min": min(r["control"]["max_logit_gap"] for r in rows),
        "program_correct_all": all(r["program"]["correct"] for r in rows),
        "control_correct_any": any(r["control"]["correct"] for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
