"""An open mix's knee: the highest rate the system sustains without a
growing backlog, found once by a sweep on the chip.

    python3 bench/sweep.py --workload <name> --rates 1.5,2,2.5 --seeds 1,2

For each rate and seed, one run of the cell as ``bench/run.py`` makes it,
in this one process, with the mix's ``sessions_per_s`` replaced and a
smaller sample judged (``--check``).  Prints one JSON line per run:
requests due, finished and still waiting at the close, preempted requests
resumed, the first-token tails, the longest tick, how late the generator
offered, and the 90th percentile of the queue wait in each half of the
window (a wait that grows from the first half to the second is a growing
backlog).
"""
from __future__ import annotations

import argparse
import json
import sys

from run import ROOT, load_cell, run_cell


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seeds", default="1")
    p.add_argument("--seconds", type=float, default=51.0)
    p.add_argument("--check", type=int, default=4,
                   help="sessions judged against the reference per run")
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import driver
    bench, cell, conf, traffic = load_cell(ROOT, args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        for seed in (int(s) for s in args.seeds.split(",")):
            mix = dict(traffic, sessions_per_s=rate,
                       check_sessions=args.check)
            got = {}

            def inspect(ctx):
                w = ctx.window
                mid = (w.t0 + w.t1) / 2
                due = [t for t in w.turns if w.t0 <= t.due <= w.t1]
                half = lambda a, b: driver.percentile(
                    [((t.placed if t.placed and t.placed <= w.t1 else w.t1)
                      - t.due) * 1e3 for t in due if a <= t.due < b], 90)
                first = driver.first_token_ms(w, "fresh")
                got.update(
                    due=len(due), finished=sum(t.done for t in due),
                    waiting=sum(t.placed is None for t in w.turns),
                    resumed=sum(map(len, w.rec["resume"])),
                    ticks=w.ticks, tokens=driver.window_tokens(w),
                    longest_tick_s=w.longest_tick,
                    late_max_s=max(w.late, default=None),
                    wait_p90_first_half_ms=half(w.t0, mid),
                    wait_p90_second_half_ms=half(mid, w.t1 + 1),
                    ttft_p50_ms=driver.percentile(first, 50),
                    ttft_p90_ms=driver.percentile(first, 90),
                    itl_p95_ms=driver.percentile(
                        driver.token_gaps_ms(w), 95))

            out = run_cell(bench, cell, conf, mix, seed=seed,
                           seconds=args.seconds, trace=False,
                           chips=cell["chips"], also=inspect)
            print(json.dumps({"rate": rate, "seed": seed, **got,
                              "correct": out["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
