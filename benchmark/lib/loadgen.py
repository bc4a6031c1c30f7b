"""The one general traffic generator.  A traffic mix is a data file of
parameters; this module turns it and ``--seed`` into a schedule of requests
and drives them against a ``fire`` function.  Adapted from
``ray_tpu/serve/loadgen.py`` (seeded arrivals, heavy-tailed lengths, prefix
pools, timing from the scheduled arrival), which later PRs may change; the
yardstick may not move with the program.

Every seed gives the same work: the multiset of request shapes and of
inter-arrival gaps is drawn from the mix's own ``shape_seed``; ``--seed``
draws the token values and, unless the mix says ``"order": "fixed"``, the
order.  So two seeds never differ by how much work they hold.

Traffic file keys (all lengths in tokens):

* ``loop``: ``"open"`` (arrivals on a schedule, whatever the system
  answers) or ``"closed"`` (``clients`` callers, each sending its next
  request when the last completes).
* open: ``rate_per_s``; optional ``burst`` ``{"start_s", "end_s", "mult"}``
  (rate times ``mult`` inside the interval).
* ``prompt`` / ``output``: ``{"dist": "lognormal", "median", "sigma", "lo",
  "hi"}``, ``{"dist": "uniform", "lo", "hi"}`` or ``{"dist": "fixed",
  "value"}``.
* ``prefix``: ``{"pool", "len"}``: each prompt starts with one of ``pool``
  shared prefixes of ``len`` tokens (0 / absent: nothing shared).
* ``preroll_s``: the same traffic runs this long before the window, as
  set-up, so that the window starts in steady state.
* ``order``: ``"seeded"`` (default: ``--seed`` orders the shapes and gaps)
  or ``"fixed"`` (one schedule for every seed; the seed still draws the
  token values and the weights).
* ``callers``: open loop only, the number of caller processes, which caps
  the requests outstanding at once (a closed loop has ``clients`` of them).
* ``temperature``, ``drain_grace_s``, ``request_timeout_s``, ``shape_seed``.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import queue
import random
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor, wait
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

MAX_SEED = (1 << 31) - 1


def fold_seed(seed: int) -> int:
    """``--seed`` may need more than 32 signed bits; fold it into the range
    every generator here (and JAX's PRNGKey) takes."""
    return int(seed) % MAX_SEED


# ------------------------------------------------------------------ shapes

def draw_len(rng: random.Random, spec: dict) -> int:
    dist = spec["dist"]
    if dist == "fixed":
        return int(spec["value"])
    if dist == "uniform":
        return rng.randint(int(spec["lo"]), int(spec["hi"]))
    if dist == "lognormal":
        n = int(round(spec["median"] * math.exp(
            rng.gauss(0.0, spec["sigma"]))))
        return max(int(spec["lo"]), min(int(spec["hi"]), n))
    raise ValueError(f"unknown length distribution {dist!r}")


def shapes(traffic: dict, n: int, salt: int) -> List[Tuple[int, int, int]]:
    """``n`` request shapes ``(prompt_len, output_len, prefix_index)``: the
    mix's fixed multiset, the same for every ``--seed``.  ``prompt_len``
    counts the shared prefix."""
    rng = random.Random(int(traffic.get("shape_seed", 0)) * 1_000_003 + salt)
    pre = traffic.get("prefix") or {}
    pool, plen = int(pre.get("pool", 0)), int(pre.get("len", 0))
    out = []
    for _ in range(n):
        tail = draw_len(rng, traffic["prompt"])
        idx = rng.randrange(pool) if pool and plen else -1
        out.append((tail + (plen if idx >= 0 else 0),
                    draw_len(rng, traffic["output"]), idx))
    return out


def _gaps(rate: float, n: int, span_s: float, traffic: dict,
          salt: int) -> List[float]:
    """``n`` exponential inter-arrival gaps at ``rate``, scaled so that the
    arrivals they add up to end just inside ``span_s``."""
    rng = random.Random(int(traffic.get("shape_seed", 0)) * 7_368_787 + salt)
    gaps = [rng.expovariate(rate) for _ in range(n)]
    total = sum(gaps)
    scale = (span_s - 0.5 / rate) / total if total > 0 else 1.0
    return [g * scale for g in gaps]


def _arrivals(rate: float, span_s: float, traffic: dict, order: random.Random,
              salt: int) -> List[float]:
    n = int(round(rate * span_s))
    gaps = _gaps(rate, n, span_s, traffic, salt) if n else []
    order.shuffle(gaps)
    out, t = [], 0.0
    for g in gaps:
        t += g
        out.append(t)
    return out


@dataclasses.dataclass
class Planned:
    """One request of a schedule.  ``t_sched`` is relative to the window's
    first instant (negative: pre-roll); None in a closed loop."""
    t_sched: Optional[float]
    prompt_len: int
    output_len: int
    prefix_index: int
    index: int


def _plan(times: List[Optional[float]], shp, order: random.Random,
          first_index: int) -> List[Planned]:
    shp = list(shp)
    order.shuffle(shp)
    return [Planned(t, p, o, x, first_index + i)
            for i, (t, (p, o, x)) in enumerate(zip(times, shp))]


def _order(traffic: dict, seed: int) -> random.Random:
    """The generator that orders the mix's shapes and gaps: from ``--seed``,
    or with ``"order": "fixed"`` from the mix's own ``shape_seed``, so that
    every seed replays one schedule and only the token values (and the
    weights) differ.  An open loop near its knee wants the latter: which
    long request falls next to the window's edge moved its metrics by 10 to
    30% between seeds, and by 0.1% between two runs of one seed (PR 24)."""
    if traffic.get("order", "seeded") == "fixed":
        seed = int(traffic.get("shape_seed", 0))
    return random.Random(fold_seed(seed) * 2_654_435_761 % (1 << 61))


def open_schedule(traffic: dict, seconds: float, seed: int) -> List[Planned]:
    """Pre-roll and window arrivals of an open loop, sorted by time."""
    order = _order(traffic, seed)
    rate = float(traffic["rate_per_s"])
    pre_s = float(traffic.get("preroll_s", 0.0))
    pre = [t - pre_s for t in _arrivals(rate, pre_s, traffic, order, 1)] \
        if pre_s > 0 else []
    win = _arrivals(rate, seconds, traffic, order, 2)
    burst = traffic.get("burst")
    if burst:
        b0, b1 = float(burst["start_s"]), min(float(burst["end_s"]), seconds)
        extra = rate * max(float(burst["mult"]) - 1.0, 0.0)
        if extra > 0 and b1 > b0:
            win = sorted(win + [b0 + t for t in _arrivals(
                extra, b1 - b0, traffic, order, 3)])
    plan = _plan(pre, shapes(traffic, len(pre), 1), order, 0)
    plan += _plan(win, shapes(traffic, len(win), 2), order, len(pre))
    return plan


def closed_schedule(traffic: dict, per_client: int,
                    seed: int) -> List[List[Planned]]:
    """For each of ``clients`` callers, the requests it sends in turn."""
    order = _order(traffic, seed)
    clients = int(traffic["clients"])
    plan = _plan([None] * (clients * per_client),
                 shapes(traffic, clients * per_client, 4), order, 0)
    return [plan[c::clients] for c in range(clients)]


class Payloads:
    """Token values of a request: a pure function of (seed, index), so that
    they do not depend on how the firing threads interleave."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.seed = fold_seed(seed)
        self.vocab = vocab
        self.temperature = float(traffic.get("temperature", 0.0))
        pre = traffic.get("prefix") or {}
        self.prefix_len = int(pre.get("len", 0))

    def _tokens(self, key: int, n: int) -> List[int]:
        rng = np.random.default_rng([self.seed, key])
        return rng.integers(1, self.vocab, size=n).tolist()

    def make(self, req: Planned) -> dict:
        head: List[int] = []
        if req.prefix_index >= 0:
            head = self._tokens(1_000_000_007 + req.prefix_index,
                                self.prefix_len)
        return {"tokens": head + self._tokens(
            req.index, req.prompt_len - len(head)),
            "max_tokens": req.output_len,
            "temperature": self.temperature}


# ------------------------------------------------------------------ capture

@dataclasses.dataclass
class Sample:
    """One request as its caller saw it.  Times are seconds on the host's
    monotonic clock relative to the window's first instant; ``t_start`` is
    the scheduled arrival (open loop) or the send (closed loop), and every
    latency is taken from it."""
    t_start: float
    t_fired: float
    token_times: List[float]
    expected_tokens: int
    prompt_len: int
    t_end: float
    error: str = ""

    @property
    def complete(self) -> bool:
        return not self.error and len(self.token_times) == \
            self.expected_tokens


class ClientPool:
    """``n`` caller processes (``lib/client.py``), each streaming one
    request at a time.  ``fire(req, t_start)`` hands the request to an idle
    caller and blocks until its sample is back; with every caller busy it
    waits, and the sample's ``t_fired`` shows by how much."""

    def __init__(self, n: int, address: str, deployment: str, traffic: dict,
                 vocab: int, seed: int, timeout_s: float):
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env = dict(os.environ)
        env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
        self.procs = [subprocess.Popen(
            [sys.executable, "-m", "benchmark.lib.client", address,
             deployment, json.dumps(traffic), str(vocab), str(seed),
             str(timeout_s)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=env, cwd=root) for _ in range(n)]
        self.idle: "queue.Queue[subprocess.Popen]" = queue.Queue()
        self.epoch = 0.0

    def wait_ready(self, timeout_s: float = 120.0):
        """Every caller has attached to the cluster and holds a handle."""
        deadline = time.monotonic() + timeout_s
        done = []

        def one(p):
            line = p.stdout.readline()
            done.append(bool(line) and json.loads(line).get("ready"))

        threads = [threading.Thread(target=one, args=(p,))
                   for p in self.procs]
        for t in threads:
            t.start()
        for t in threads:
            t.join(max(0.0, deadline - time.monotonic()))
        if len(done) != len(self.procs) or not all(done):
            self.close()
            raise RuntimeError(f"{len(self.procs) - sum(map(bool, done))} of "
                               f"{len(self.procs)} caller processes did not "
                               "attach to the cluster")
        for p in self.procs:
            self.idle.put(p)

    @staticmethod
    def _ask(p: subprocess.Popen, cmd: dict) -> dict:
        """One command to a caller and its one line of answer."""
        p.stdin.write(json.dumps(cmd) + "\n")
        p.stdin.flush()
        line = p.stdout.readline()
        if not line:
            raise RuntimeError(f"caller process {p.pid} ended mid-request")
        return json.loads(line)

    def warm(self, at_once: int = 16):
        """One small request from every caller through the ingress the mix
        names (``lib/client.py`` says why), ``at_once`` at a time."""
        def one(p):
            r = self._ask(p, {"warm": True})
            if not r["warmed"]:
                raise RuntimeError(f"caller process {p.pid} could not send "
                                   f"its warm-up request: {r['err']}")
        with ThreadPoolExecutor(max_workers=at_once) as pool:
            list(pool.map(one, self.procs))

    def fire(self, req: Planned, t_start: Optional[float]) -> Sample:
        p = self.idle.get()
        r = self._ask(p, {
            "i": req.index, "p": req.prompt_len, "o": req.output_len,
            "x": req.prefix_index, "t": t_start, "epoch": self.epoch})
        self.idle.put(p)
        return Sample(r["t_start"], r["t_fired"], r["times"], req.output_len,
                      req.prompt_len, r["t_end"], r["err"])

    def close(self, timeout_s: float = 20.0):
        """End every caller and wait until each has ended."""
        for p in self.procs:
            try:
                p.stdin.close()
            except OSError:
                pass
        deadline = time.monotonic() + timeout_s
        for p in self.procs:
            try:
                p.wait(max(0.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()      # mid-request past the drain deadline
                p.wait()


class LoadRun:
    """Drives one schedule and collects the samples.  ``stop_at`` (relative
    to the epoch) ends the sending; requests in flight are awaited until
    ``stop_at + drain_grace_s`` and abandoned, as failed, after it."""

    def __init__(self, fire: Callable[[Planned, float], Sample], epoch: float,
                 stop_at: float, drain_grace_s: float,
                 max_outstanding: int = 256):
        self.fire, self.epoch = fire, epoch
        self.stop_at, self.drain_grace_s = stop_at, drain_grace_s
        self.samples: List[Sample] = []
        self.unfinished: List[Tuple[Planned, float]] = []
        self._lock = threading.Lock()
        self._pool = ThreadPoolExecutor(max_workers=max_outstanding,
                                        thread_name_prefix="loadgen")
        self._inflight: Dict[int, Tuple[Planned, float]] = {}
        self.nothing_more = threading.Event()
        self.running = True           # until the drain has ended

    def _now(self) -> float:
        return time.monotonic() - self.epoch

    def _one(self, req: Planned, t_start: Optional[float]):
        with self._lock:
            self._inflight[req.index] = (
                req, self._now() if t_start is None else t_start)
        s = self.fire(req, t_start)
        with self._lock:
            self._inflight.pop(req.index, None)
            self.samples.append(s)

    def _finish(self, futures):
        """Wait for the requests in flight: to the drain's deadline, or until
        ``nothing_more`` is set (the runner sets it once the system holds no
        request any more: what is still open then will not end, and waiting
        out the grace only makes the run longer; it counts as failed either
        way)."""
        deadline = self.epoch + self.stop_at + self.drain_grace_s
        for f in futures:
            while not f.done():
                left = deadline - time.monotonic()
                if left <= 0 or self.nothing_more.is_set():
                    break
                wait([f], timeout=min(left, 0.25))
            if not f.done():
                break
        with self._lock:
            self.unfinished = list(self._inflight.values())
        self.running = False
        self._pool.shutdown(wait=False, cancel_futures=True)

    def run_open(self, plan: List[Planned]):
        futures = []
        for req in plan:
            delay = self.epoch + req.t_sched - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            futures.append(self._pool.submit(self._one, req, req.t_sched))
        self._finish(futures)

    def run_closed(self, per_client: List[List[Planned]], start_at: float):
        """Caller ``c`` of ``n`` sends its first request at ``start_at * (1
        - c / n)`` (``start_at`` is negative: the pre-roll), so that the
        callers' phases are spread over the pre-roll and do not complete
        in waves (PR 24: 32 callers started together gave two or three
        waves a window, and which wave crossed its edge moved the rate by
        a tenth)."""
        n = len(per_client)

        def client(c: int, reqs: List[Planned]):
            delay = self.epoch + start_at * (1 - c / n) - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            for req in reqs:
                if self._now() >= self.stop_at:
                    return
                self._one(req, None)
            raise RuntimeError("a closed-loop client ran out of planned "
                               "requests before the window ended")

        futures = [self._pool.submit(client, c, reqs)
                   for c, reqs in enumerate(per_client)]
        # every client stops sending at stop_at; then the drain grace runs
        time.sleep(max(0.0, self.epoch + self.stop_at - time.monotonic()))
        self._finish(futures)
        for f in futures:
            if f.done() and f.exception() is not None:
                raise f.exception()
