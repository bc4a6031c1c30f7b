"""Reduction of a ``jax.profiler`` trace to what the per-layer metrics read.

``load_xplane`` turns an ``.xplane.pb`` into a neutral structure (planes ->
lines -> ``(name, start_ns, duration_ns)`` events) with nothing but JAX;
everything after it is interval arithmetic on that structure, which the
tests drive with small synthetic traces.

What a v5e trace looks like (PR 24, looked at by hand): every chip is one
plane ``/device:TPU:<n>``.  Its ``XLA Modules`` line holds one event per
executed program, named ``jit_<function>(<fingerprint>)``.  Its ``XLA Ops``
line holds one event per executed HLO operation, named by the operation's
whole HLO text (``%fusion.12 = bf16[...] fusion(...)``); a ``while`` or
``conditional`` encloses the events of its body, so durations nest and only
*self* time may be summed.  ``Async XLA Ops`` holds the spans of
asynchronous operations from their ``-start`` to their ``-done`` (copies,
slices, collectives), which overlap what the core runs meanwhile.  A Pallas
kernel is a ``custom-call`` with ``custom_call_target="tpu_custom_call"``
under whatever name JAX gave the enclosing primitive (``%closed_call.6``).
Host threads are lines of the ``/host:CPU`` plane; of those only the
benchmark's own ``TraceAnnotation`` spans (named ``bench:...``) are kept.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[int, int]

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
PALLAS = 'custom_call_target="tpu_custom_call"'
#: how a table row names a Pallas kernel, whatever its instruction is called
PALLAS_TAG = " [pallas]"
SPAN_PREFIX = "bench:"
#: HLO operations that move data between chips
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|async-collective)")


def find_xplane(trace_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def load_xplane(path: str) -> List[dict]:
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                      for ev in line.events
                      if device or ev.name.startswith(SPAN_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


# ------------------------------------------------------ interval arithmetic

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(merged: List[Interval]) -> int:
    return sum(b - a for a, b in merged)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The part of merged ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def _iv(events) -> List[Interval]:
    return [(s, s + d) for _n, s, d in events]


def op_name(text: str) -> str:
    """The instruction's name out of an event's HLO text (``%fusion.12 =
    ...`` -> ``fusion.12``), with ``PALLAS_TAG`` on a Pallas kernel; a name
    that is not HLO text is returned as it is."""
    m = re.match(r"%(\S+) = ", text)
    name = m.group(1) if m else text
    return name + PALLAS_TAG if PALLAS in text else name


def base_name(name: str) -> str:
    """A name without its instance number: ``fusion.123`` -> ``fusion``,
    ``jit_step(4099...)`` -> ``jit_step``, the tag of a kernel kept."""
    tag = PALLAS_TAG if name.endswith(PALLAS_TAG) else ""
    name = name[:len(name) - len(tag)] if tag else name
    name = re.sub(r"\(\d+\)$", "", name)
    return re.sub(r"[.\-_]\d+$", "", name) + tag


def self_times(events) -> List[Tuple[str, int, int, int, bool]]:
    """``(name, start, duration, self_ns, leaf)`` for the events of one
    line, where an event may enclose later ones (a loop and its body)."""
    out, stack = [], []           # stack of indexes into out
    for name, s, d in sorted((e for e in events if e[2] > 0),
                             key=lambda e: (e[1], -e[2])):
        while stack and s >= out[stack[-1]][1] + out[stack[-1]][2]:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            out[stack[-1]] = (parent[0], parent[1], parent[2],
                              parent[3] - d, False)
        out.append((name, s, d, d, True))
        stack.append(len(out) - 1)
    return out


# ---------------------------------------------------------------- reduction

def _line(plane: dict, name: str) -> list:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def _host_spans(planes: List[dict]) -> List[Tuple[str, int, int]]:
    return sorted(((n, s, s + d) for p in planes
                   if not DEVICE_PLANE.match(p["name"])
                   for ln in p["lines"] for n, s, d in ln["events"]),
                  key=lambda e: e[1])


def _label_gap(lo: int, hi: int, spans, prev: str, nxt: str) -> str:
    best, best_ov = None, 0
    for n, s, e in spans:
        if s >= hi:
            break
        ov = min(hi, e) - max(lo, s)
        # the innermost (shortest) span that covers most of the gap wins
        if ov > 0.5 * (hi - lo) and (best is None or e - s < best_ov):
            best, best_ov = n, e - s
    if best is not None:
        return best[len(SPAN_PREFIX):]
    return f"{prev} -> {nxt}"


def summarize(planes: List[dict], window_s: float, top: int = 100) -> dict:
    """What the readers get: per-chip busy, collective and exposed
    collective seconds, self time by operation and time by program (mean
    over the chips), and the idle time by what surrounded it."""
    devs = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    spans = _host_spans(planes)
    per_dev, ops, mods, gaps = [], {}, {}, {}
    for p in devs:
        mod_ev = _line(p, MODULES_LINE)
        op_ev = [(op_name(n), s, d) for n, s, d in _line(p, OPS_LINE)]
        async_ev = [(op_name(n), s, d) for n, s, d in _line(p, ASYNC_LINE)]
        timed = self_times(op_ev)
        leaves = [(n, s, d) for n, s, d, _self, leaf in timed if leaf]
        busy = union(_iv(leaves or mod_ev))
        # a collective is exposed while the core runs nothing else: its
        # synchronous form and the wait of its ``-done`` sit on the ops
        # line, the span of its asynchronous form on the async line
        coll = union(_iv(e for e in leaves + async_ev
                         if COLLECTIVE.match(e[0])))
        other = union(_iv(e for e in leaves if not COLLECTIVE.match(e[0])))
        per_dev.append({
            "plane": p["name"], "busy_s": total(busy) / 1e9,
            "collective_s": total(coll) / 1e9,
            "collective_exposed_s": total(subtract(coll, other)) / 1e9,
            "ops": len(op_ev), "programs": len(mod_ev),
            "lines": {ln["name"]: len(ln["events"]) for ln in p["lines"]}})
        for n, _s, _d, self_ns, _leaf in timed:
            row = ops.setdefault(base_name(n), [0, 0])
            row[0] += self_ns
            row[1] += 1
        for n, _s, d in mod_ev:
            row = mods.setdefault(base_name(n), [0, 0])
            row[0] += d
            row[1] += 1
        # idle between programs, by what ran before and after (or by the
        # benchmark's own host span over it); idle inside programs as one row
        mod_sorted = sorted(mod_ev, key=lambda e: e[1])
        mod_iv = union(_iv(mod_sorted))
        if mod_iv and op_ev:
            row = gaps.setdefault("inside programs, between operations",
                                  [0, 0])
            row[0] += total(subtract(mod_iv, busy))
            row[1] += 1
        for (n0, s0, d0), (n1, s1, _d1) in zip(mod_sorted, mod_sorted[1:]):
            if s1 > s0 + d0:
                label = _label_gap(s0 + d0, s1, spans, base_name(n0),
                                   base_name(n1))
                row = gaps.setdefault(label, [0, 0])
                row[0] += s1 - (s0 + d0)
                row[1] += 1
    n = max(len(devs), 1)
    # the host's clock brackets the span from inside (after start_trace
    # returned, before stop_trace was called); the device's events may reach
    # a little beyond both ends, and busy time is never more than the span
    edges = [(s, s + d) for p in devs for ln in p["lines"]
             if ln["name"] in (OPS_LINE, MODULES_LINE)
             for _n, s, d in ln["events"]]
    if edges:
        window_s = max(window_s, (max(e[1] for e in edges)
                                  - min(e[0] for e in edges)) / 1e9)

    def table(d):
        rows = sorted(d.items(), key=lambda kv: -kv[1][0])[:top]
        return [[k, v[0] / 1e9 / n, v[1] / n] for k, v in rows]

    return {
        "window_s": window_s,
        "devices": per_dev,
        "busy_s": sum(d["busy_s"] for d in per_dev) / n,
        "collective_s": sum(d["collective_s"] for d in per_dev) / n,
        "collective_exposed_s":
            sum(d["collective_exposed_s"] for d in per_dev) / n,
        "ops": table(ops), "programs": table(mods), "idle": table(gaps),
    }


def seconds_matching(rows: List[list], pattern: str) -> Tuple[float, float]:
    """(seconds, count) summed over the rows of a summary table whose name
    matches ``pattern``."""
    rx = re.compile(pattern)
    hit = [r for r in rows if rx.search(r[0])]
    return sum(r[1] for r in hit), sum(r[2] for r in hit)


def breakdown(summary: dict) -> dict:
    """The result line's ``breakdown``: at most ten rows each."""
    return {"device_ops": [[r[0], r[1]] for r in summary["ops"][:10]],
            "idle_gaps": [[r[0], r[1]] for r in summary["idle"][:10]]}


def describe(path: str, per_line: int = 12) -> str:
    """A trace by hand: every plane and line with its number of events and,
    for the device planes, the names that took most time with the first
    event's stats.  ``python3 -m benchmark.lib.trace <file.xplane.pb>``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  line {line.name!r}: {len(events)} events")
            if not DEVICE_PLANE.match(plane.name):
                names = sorted({e.name for e in events
                                if e.name.startswith(SPAN_PREFIX)})
                if names:
                    out.append(f"    spans: {names}")
                continue
            agg: Dict[str, list] = {}
            for e in events:
                row = agg.setdefault(base_name(op_name(e.name)), [0, 0, e])
                row[0] += e.duration_ns
                row[1] += 1
            for name, (ns, n, first) in sorted(
                    agg.items(), key=lambda kv: -kv[1][0])[:per_line]:
                stats = {k: (str(v)[:80]) for k, v in first.stats}
                out.append(f"    {name}: {ns / 1e6:.3f} ms in {n} events; "
                           f"first={first.name[:300]!r} stats={stats}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    print(describe(sys.argv[1]))
