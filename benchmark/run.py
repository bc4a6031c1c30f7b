"""One run of one cell: ``python3 -m benchmark.run --workload <name> --seed
<n> --seconds <s> --trace <0|1> [--manifest BENCHMARK.json] [--dump FILE]``.

Loads, warms up, measures for ``--seconds``, and prints one JSON object as
the last line of standard output.  Everything about the cell comes from
files found by name (see ``lib/manifest.py`` and ``README.md``); the
configuration's ``kind`` names the runner module under ``runners/``.  A run
that cannot give a result (wrong devices, a missing file, a worker that did
not stop) prints its reason on stderr, exits non-zero and prints no result
line.  ``BENCH_RUN`` in the environment is the driver's and is ignored.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()      # before the heavy imports

import argparse                          # noqa: E402
import importlib                         # noqa: E402
import sys                               # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    ap.add_argument("--dump", default=None, metavar="FILE",
                    help="also write the window's roll-up and raw samples "
                         "(requests or steps) there as JSON, for analysis")
    args = ap.parse_args(argv)

    from .lib.manifest import Cell, ManifestError
    from .runners.common import CellFailed
    try:
        cell = Cell(args.manifest, args.workload)
        kind = cell.config.get("kind", "")
        if not kind.isidentifier():
            raise ManifestError(f"{cell.config_path}: kind {kind!r}")
        try:
            runner = importlib.import_module(f"{__package__}.runners.{kind}")
        except ImportError as e:
            raise ManifestError(f"no runner for kind {kind!r}: {e}") from e
        runner.run(cell, args.seed, args.seconds, bool(args.trace),
                   T_PROCESS_START, args.dump)
    except (CellFailed, ManifestError) as e:
        print(f"benchmark.run: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
