"""What both runners share: the clock, the information lines, the result
line and the failure that prints none."""

from __future__ import annotations

import json
import sys
import threading
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


class StallClock:
    """A thread of the harness's own process that sleeps ``tick_s`` at a
    time and keeps every wake-up that came ``late_s`` or more after it was
    due, on the host's monotonic clock.  The machine stops every process at
    once now and then, for a tenth of a second or for some seconds (PERF.md
    section 6): nothing in a run's numbers says so, and a window that held
    such a stop reads a tail many times its level.  This says it, beside the
    numbers; it changes none of them."""

    def __init__(self, tick_s: float = 0.005, late_s: float = 0.03):
        self.tick_s, self.late_s = tick_s, late_s
        self.stalls = []              # (when the wake-up came, seconds late)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="bench-stall",
                                        daemon=True)

    def _run(self):
        last = time.monotonic()
        while not self._stop.wait(self.tick_s):
            now = time.monotonic()
            late = now - last - self.tick_s
            if late >= self.late_s:
                self.stalls.append((now, late))
            last = now

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()

    def between(self, t0: float, t1: float) -> dict:
        """The stops that ended in ``[t0, t1)`` of the monotonic clock:
        their count, the longest and their sum, in milliseconds."""
        late = [s for t, s in list(self.stalls) if t0 <= t < t1]
        return {"n": len(late), "longest_ms": max(late, default=0.0) * 1e3,
                "total_ms": sum(late) * 1e3}


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, breakdown=None, compared=None,
                host_stalls=None):
    """The last line of standard output.  ``compared`` holds each number
    that decided ``correct`` beside its limit (``{name: {"value", "limit"}}``):
    it is also the run's last lines on standard error, and the last key of
    the line.  ``host_stalls`` (``StallClock.between`` over the window) is a
    key the driver does not read."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if host_stalls is not None:
        out["host_stalls"] = host_stalls
    out["compared"] = compared or {}
    sys.stdout.flush()
    for name, c in out["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
