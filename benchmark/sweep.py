"""Find a serving cell's knee once: ``python3 -m benchmark.sweep --workload
<name> --rates 2,3,4,5,6 --seconds 30 [--seed 1]``.  One replica, warmed
once, then one pre-roll + window + drain per rate with the cell's own
traffic mix and only ``rate_per_s`` changed.  Prints one table row per rate;
the knee is the highest rate at which the backlog does not grow over the
window (``backlog_at_end`` stays near rate x a request's time, no arrival
fires late), no request fails and at least nine requests in ten meet both
of the mix's ``limits`` (``share_meeting_limits``).  ``--callers`` overrides
the mix's count of caller processes, so that a sweep above the present rate
is not capped by callers sized for it.  Not part of a run of the benchmark:
the rate a cell offers is the number in its traffic file."""

from __future__ import annotations

import argparse
import json
import sys

from .lib.manifest import Cell
from .runners.common import compact, say
from .runners.serve import Replica, say_failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--callers", type=int, default=None)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    args = ap.parse_args(argv)
    cell = Cell(args.manifest, args.workload)
    base = dict(cell.traffic)
    if args.callers:
        base["callers"] = args.callers
    with Replica(cell, args.seed) as rep:
        pool = rep.callers(base, args.seed)
        try:
            rep.warm(base, pool)
            rows = []
            for rate in (float(r) for r in args.rates.split(",")):
                traffic = dict(base, rate_per_s=rate)
                win = rep.window(traffic, args.seconds, args.seed, False,
                                 pool)
                roll = win["roll"]
                if roll["failed"]:
                    say_failed(rep, win, args.seconds)
                row = {"rate_per_s": rate, **{k: roll[k] for k in (
                    "attempted", "completed", "failed", "backlog_at_end",
                    "ttft_p50_ms", "ttft_p95_ms", "tpot_p50_ms",
                    "tpot_p95_ms", "share_meeting_limits",
                    "serve_out_tokens_per_s", "lateness_p95_ms",
                    "host_stalls")
                    if k in roll}}
                rows.append(row)
                say("sweep row: " + compact(row))
        finally:
            pool.close()
    print(json.dumps({"workload": args.workload, "seconds": args.seconds,
                      "device": rep.dev, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
