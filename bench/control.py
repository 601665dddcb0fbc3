"""The control of ``correct``, and the readings its limit is set from, on
the chip.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 30

For each seed, in one process: one run of the cell as ``bench/run.py``
makes it (weights from the seed, the window, the sample of finished
sessions), judged by the run's own check with the control in the
program's place: at every served position, the token that the int8
reference ranks first.  That run has to read ``correct`` false.  On the
same sample the served tokens are judged too (the program's reading).
Prints one JSON line per seed (the control's ``correct`` and checks, and
both readings: the mean gap, the widest and the share above 0) and, last,
for each number the largest program reading and the smallest control
reading.  Exits non-zero where a control run reads ``correct`` true.  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys

from run import ROOT, load_cell, run_cell


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import check
    bench, cell, conf, traffic = load_cell(ROOT, args.workload)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        got = {}

        def program(ctx):
            got["control"] = ctx.gaps
            got["program"] = check.served_gaps(
                ctx.ref, ctx.conf, ctx.weights, ctx.sample, ctx.max_len)

        out = run_cell(bench, cell, conf, traffic, seed=seed,
                       seconds=args.seconds, trace=False,
                       chips=cell["chips"], control=True, also=program)
        row = {"seed": seed, "control_correct": out["correct"], **got,
               "checks": out["checks"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    for k in ("mean", "widest", "off_best"):
        print(json.dumps({"workload": args.workload, "number": k,
                          "lower": max(r["program"][k] for r in rows),
                          "upper": min(r["control"][k] for r in rows)}))
    return 1 if any(r["control_correct"] for r in rows) else 0


if __name__ == "__main__":
    sys.exit(main())
