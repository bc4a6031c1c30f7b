"""Find a serving cell's knee once: ``python3 -m benchmark.sweep --workload
<name> --rates 2,3,4,5,6 --seconds 30 [--seed 1]``.  One replica, warmed
once, then one pre-roll + window + drain per rate with the cell's own
traffic mix and only ``rate_per_s`` changed.  Prints one table row per rate;
the knee is the highest rate at which the backlog does not grow over the
window and no request fails.  Not part of a run of the benchmark: the rate
a cell offers is the number in its traffic file."""

from __future__ import annotations

import argparse
import json
import sys

from .lib.manifest import Cell
from .runners.common import compact, say
from .runners.serve import Replica


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    args = ap.parse_args(argv)
    cell = Cell(args.manifest, args.workload)
    with Replica(cell, args.seed) as rep:
        pool = rep.callers(cell.traffic, args.seed)
        try:
            rep.warm(cell.traffic, pool)
            rows = []
            for rate in (float(r) for r in args.rates.split(",")):
                traffic = dict(cell.traffic, rate_per_s=rate)
                roll = rep.window(traffic, args.seconds, args.seed, False,
                                  pool)["roll"]
                row = {"rate_per_s": rate, **{k: roll[k] for k in (
                    "attempted", "completed", "failed", "backlog_at_end",
                    "ttft_p50_ms", "ttft_p95_ms", "tpot_p50_ms",
                    "tpot_p95_ms", "serve_out_tokens_per_s",
                    "lateness_p95_ms")}}
                rows.append(row)
                say("sweep row: " + compact(row))
        finally:
            pool.close()
    print(json.dumps({"workload": args.workload, "seconds": args.seconds,
                      "device": rep.dev, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
