"""What both runners share: the clock, the information lines, the result
line and the failure that prints none."""

from __future__ import annotations

import json
import sys
import time


class CellFailed(Exception):
    """The run cannot give a result: reason on stderr, non-zero exit, no
    result line."""


def say(msg: str):
    """An information line: everything above the last line is information."""
    print(f"[bench +{time.monotonic() - say.t0:7.2f}s] {msg}", flush=True)


say.t0 = time.monotonic()


def compact(obj, digits: int = 4):
    """JSON for an information line, floats shortened."""
    def r(x):
        if isinstance(x, float):
            return round(x, digits)
        if isinstance(x, dict):
            return {k: r(v) for k, v in x.items()}
        if isinstance(x, (list, tuple)):
            return [r(v) for v in x]
        return x
    return json.dumps(r(obj), sort_keys=True)


def dump(path, **what):
    """``--dump``: the raw material of the roll-up, for analysis offline."""
    if path:
        with open(path, "w") as f:
            json.dump(what, f)


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, breakdown=None):
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
