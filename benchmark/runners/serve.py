"""Runner of a ``kind: serve`` configuration.  This parent never imports
JAX: the replica's worker holds the chip.  It deploys the benchmark's
deployment class through ``serve.run``, warms the shapes the cell's traffic
reaches, runs the pre-roll and the window from ``lib/loadgen``, and asks the
replica for everything only the chip's owner can say."""

from __future__ import annotations

import dataclasses
import os
import sys
import threading
import time

from ..lib import loadgen, manifest, rollup
from ..lib.peaks import peaks_for
from .common import (CellFailed, StallClock, compact, dump, result_line,
                     say)

#: the traced span inside the window, unless the traffic file says otherwise
TRACE_START_SHARE, TRACE_SECONDS_MAX, TRACE_SHARE_MAX = 0.4, 6.0, 0.25
#: the drain ends early after this many looks, this far apart, at an engine
#: that holds no request and has made no token since the last look
IDLE_POLLS, IDLE_POLL_S = 5, 1.0


def _wait_exited(pids, timeout_s: float = 60.0):
    def alive(pid):
        try:
            with open(f"/proc/{pid}/stat") as f:
                return f.read().rsplit(")", 1)[1].split()[0] != "Z"
        except OSError:
            return False
    t0 = time.monotonic()
    while any(alive(p) for p in pids):
        if time.monotonic() - t0 > timeout_s:
            raise CellFailed(f"worker processes {pids} still alive "
                             f"{timeout_s:.0f}s after shutdown")
        time.sleep(0.05)


def warm_lengths(traffic: dict, dep: dict) -> list:
    """One prompt length for each prefill bucket the mix's prompts reach."""
    pre = traffic.get("prefix") or {}
    shared = int(pre.get("len", 0)) if pre.get("pool") else 0
    spec = traffic["prompt"]
    lo = (spec["value"] if spec["dist"] == "fixed" else spec["lo"]) + shared
    hi = (spec["value"] if spec["dist"] == "fixed" else spec["hi"]) + shared
    out, below = [], 0
    for b in sorted(dep["buckets"]):
        if lo <= b and hi > below:           # some length in (below, b]
            out.append(min(b, hi, dep["max_len"] - 2))
        below = b
    return out


class Replica:
    """One deployed replica of the cell's configuration and what the parent
    may ask of it.  ``with Replica(cell, seed) as r`` deploys and warms;
    leaving the block tears everything down and waits for the workers."""

    def __init__(self, cell: manifest.Cell, seed: int):
        self.cell, self.seed = cell, seed
        self.want = cell.config.get("platform", "tpu")
        self.pids = []
        self.gave_up = False      # a drain ended on an idle engine

    def __enter__(self):
        cell, want = self.cell, self.want
        from ray_tpu.core.common import detect_node_resources
        from ray_tpu.utils.compile_cache import (cache_entries,
                                                 place_compile_cache)
        chips = int(detect_node_resources().get("TPU", 0))
        if want == "tpu" and chips != cell.chips:
            raise CellFailed(
                f"cell {cell.name!r} needs {cell.chips} TPU chip(s); this "
                f"host shows {chips} (device nodes, no JAX)")
        cache_dir = place_compile_cache()      # before any worker starts
        say(f"compile cache {cache_dir}: {cache_entries(cache_dir)} entries")

        import ray_tpu
        from ray_tpu import serve
        from ray_tpu.core import api

        from ..serve_app import BenchLLMServer

        # deployment settings that are not traffic come from the
        # configuration file's ``serve`` block, so that what the file
        # states is what runs
        dep_cfg = cell.config["serve"]
        self.session = ray_tpu.init(
            _system_config=dict(dep_cfg.get("system_config", {})))
        try:
            t0 = time.monotonic()
            dep = serve.deployment(
                BenchLLMServer, name="bench-llm",
                ray_actor_options=({"num_tpus": chips} if want == "tpu"
                                   else {}),
                **dep_cfg.get("deployment_options", {}))
            self.h = h = serve.run(
                dep.bind(config_path=cell.config_path,
                         model_path=cell.model_path, seed=self.seed,
                         chips=cell.chips), timeout_s=900.0)
            self.pids = [w.pid
                         for w in api._state.node_agent.workers.values()]
            self.dev = dev = h.device_info.remote().result(timeout_s=60)
            say(f"replica HEALTHY after {time.monotonic() - t0:.1f}s on "
                f"{dev['platform']} ({dev['kind']} x{dev['count']}), "
                f"{compact(dev['timings'])}")
            if dev["platform"] != want or dev["count"] != cell.chips:
                raise CellFailed(f"replica runs on {dev}, the cell needs "
                                 f"{cell.chips} x {want}")
            if "jax" in sys.modules:
                raise CellFailed("the serve parent imported jax")
        except BaseException:
            self.__exit__(None, None, None)
            raise
        return self

    def __exit__(self, *exc):
        import ray_tpu
        from ray_tpu import serve
        serve.shutdown()
        ray_tpu.shutdown()
        _wait_exited(self.pids)
        say(f"torn down; {len(self.pids)} worker processes gone")

    def call(self, method: str, *args, timeout_s: float = 600.0):
        return getattr(self.h, method).remote(*args).result(
            timeout_s=timeout_s)

    def callers(self, traffic: dict, seed: int) -> "loadgen.ClientPool":
        """The caller processes of one traffic mix, started: they attach to
        the cluster while ``warm`` compiles, which waits for them."""
        from ..lib import client
        ingress = traffic.get("ingress", "native_generator")
        if ingress not in client.INGRESS:
            raise CellFailed(f"traffic names ingress {ingress!r}; "
                             f"lib/client.py has {sorted(client.INGRESS)}")
        n = int(traffic["clients"] if traffic["loop"] == "closed"
                else traffic.get("callers", 48))
        return loadgen.ClientPool(
            n, os.environ["RAYTPU_GCS_ADDRESS"], "bench-llm", traffic,
            self.cell.config["vocab_size"], seed,
            float(traffic.get("request_timeout_s", 300.0)))

    def warm(self, traffic: dict, pool: "loadgen.ClientPool"):
        """The programs the mix's prompts reach, then one request from every
        caller through the whole path (router, replica, ingress)."""
        lens = warm_lengths(traffic, self.cell.config["serve"])
        say(f"warmed prompt lengths {lens}: "
            + compact(self.call("warm", lens, timeout_s=1200)))
        t0 = time.monotonic()
        pool.wait_ready()
        t1 = time.monotonic()
        pool.warm()
        say(f"{len(pool.procs)} caller processes attached {t1 - t0:.1f}s "
            f"after that and sent their first request in "
            f"{time.monotonic() - t1:.1f}s")

    def window(self, traffic: dict, seconds: float, seed: int, trace: bool,
               pool: "loadgen.ClientPool", on_epoch=None) -> dict:
        """Pre-roll, window and drain of one traffic mix; returns the
        roll-up with the samples and the replica's marks."""
        grace = float(traffic.get("drain_grace_s", 30.0))
        pre_s = float(traffic.get("preroll_s", 0.0))
        if traffic["loop"] == "open":
            plan = loadgen.open_schedule(traffic, seconds, seed)
        elif traffic["loop"] == "closed":
            plan = loadgen.closed_schedule(
                traffic, int(traffic.get("requests_per_client", 64)), seed)
        else:
            raise CellFailed(f"serve runner: unknown loop {traffic['loop']!r}")

        epoch = time.monotonic() + pre_s + 0.25
        pool.epoch = epoch
        load = loadgen.LoadRun(pool.fire, epoch, stop_at=seconds,
                               drain_grace_s=grace,
                               max_outstanding=len(pool.procs))
        marks = {}

        def at_window_edges():
            time.sleep(max(0.0, epoch - time.monotonic()))
            marks["compile0"] = self.call("compile_state", timeout_s=60)
            marks["stats0"] = self.call("stats", timeout_s=60)
            if trace:
                tr = traffic.get("trace") or {}
                start = float(tr.get("start_s", TRACE_START_SHARE * seconds))
                length = float(tr.get("seconds", min(
                    TRACE_SECONDS_MAX, TRACE_SHARE_MAX * seconds)))
                time.sleep(max(0.0, epoch + start - time.monotonic()))
                self.call("trace_start", os.path.join(
                    self.session["session_dir"], "bench_trace"),
                    timeout_s=120)
                time.sleep(length)
                self.call("trace_stop", timeout_s=300)
            time.sleep(max(0.0, epoch + seconds - time.monotonic()))
            marks["stats1"] = self.call("stats", timeout_s=60)
            marks["compile1"] = self.call("compile_state", timeout_s=60)
            # the drain: once the engine has held no request for IDLE_POLLS
            # looks in a row, a stream still open will not end (PERF.md, the
            # stream that never ends): stop waiting out the grace for it
            idle, made = 0, None
            while load.running and idle < IDLE_POLLS:
                time.sleep(IDLE_POLL_S)
                now = self.call("stats", timeout_s=60)
                quiet = now.get("active") == 0 and now.get("tokens_out") == made
                made = now.get("tokens_out")
                idle = idle + 1 if quiet else 0
            if load.running:
                say(f"the engine has held no request for "
                    f"{IDLE_POLLS * IDLE_POLL_S:.0f}s: the drain ends")
                load.nothing_more.set()
                self.gave_up = True

        edge = threading.Thread(target=at_window_edges, name="bench-edges")
        edge.start()
        if on_epoch:
            on_epoch(epoch)
        with StallClock() as clock:
            if traffic["loop"] == "open":
                load.run_open(plan)
            else:
                load.run_closed(plan, start_at=-pre_s)
            edge.join()
        roll = rollup.serve_window(load.samples, load.unfinished, seconds,
                                   seconds + grace)
        # what the machine did to the run, beside what the run read
        roll["host_stalls_preroll"] = clock.between(epoch - pre_s, epoch)
        roll["host_stalls"] = clock.between(epoch, epoch + seconds)
        limits = traffic.get("limits")
        if limits:
            roll["share_meeting_limits"] = rollup.share_meeting(
                load.samples, seconds, limits["ttft_ms"] / 1e3,
                limits["tpot_ms"] / 1e3)
        return {"roll": roll, "samples": load.samples, "epoch": epoch,
                "unfinished": load.unfinished, **marks}


def run(cell: manifest.Cell, seed: int, seconds: float, trace: bool,
        t_process_start: float, dump_path=None):
    with Replica(cell, seed) as rep:
        pool = rep.callers(cell.traffic, seed)
        try:
            result = _measure(rep, pool, cell, seed, seconds, trace,
                              t_process_start, dump_path)
        finally:
            # a caller still mid-request is killed here; at once where the
            # drain has shown that its stream will not end
            pool.close(2.0 if rep.gave_up else 20.0)
    result_line(*result)      # the last line, after the teardown's


def say_failed(rep: Replica, win: dict, seconds: float):
    """What each failed request looked like to its caller, and whether the
    engine still holds anything once the drain has ended: a request the
    engine has finished and its caller never saw end is the program's
    delivery path's (PERF.md, the stream that never ends)."""
    for s in win["samples"]:
        if 0.0 <= s.t_start < seconds and not s.complete:
            say(f"failed request: scheduled {s.t_start:.3f} prompt "
                f"{s.prompt_len} got {len(s.token_times)} of "
                f"{s.expected_tokens} tokens, last at "
                f"{(s.token_times or [float('nan')])[-1]:.3f}, ended "
                f"{s.t_end:.3f}: {s.error or 'short'}")
    for req, t in win["unfinished"]:
        say(f"failed request: scheduled {t:.3f} prompt {req.prompt_len} "
            f"asked {req.output_len} tokens: no end by the drain's deadline")
    now = rep.call("stats", timeout_s=60)
    say("engine after the drain: " + compact(
        {k: now.get(k) for k in ("active", "free_slots", "tokens_out",
                                 "delivered_tokens", "admitted_requests",
                                 "first_tokens")}))


def _measure(rep: Replica, pool, cell: manifest.Cell, seed: int,
             seconds: float, trace: bool, t_process_start: float, dump_path):
    traffic = cell.traffic
    rep.warm(traffic, pool)
    setup = {}

    def on_epoch(epoch):
        setup["s"] = epoch - t_process_start
        say(f"set-up done; pre-roll {float(traffic.get('preroll_s', 0)):.0f}s"
            f" then window {seconds:.0f}s ({traffic['loop']} loop); "
            f"setup_s={setup['s']:.2f}")

    win = rep.window(traffic, seconds, seed, trace, pool, on_epoch)
    roll = win["roll"]
    roll["setup_s"] = setup["s"]
    say("window: " + compact(roll))
    dump(dump_path, cell=cell.name, seed=seed, seconds=seconds, roll=roll,
         samples=[dataclasses.asdict(s) for s in win["samples"]],
         unfinished=[[dataclasses.asdict(r), t]
                     for r, t in win["unfinished"]],
         stats0=win["stats0"], stats1=win["stats1"])
    if roll["failed"]:
        say_failed(rep, win, seconds)
    say("engine over the window: " + compact(
        {k: win["stats1"][k] - win["stats0"][k]
         for k in ("steps", "tokens_out", "admit_batches")}))

    ref = rep.call("check_reference")
    say("reference check: " + compact(ref, 6))
    mem = rep.call("memory")
    say("memory: " + compact(mem))
    no_compile = win["compile0"] == win["compile1"]
    if not no_compile:
        say(f"COMPILED INSIDE THE WINDOW: {win['compile0']} -> "
            f"{win['compile1']}")
    correct = bool(ref["ok"] and no_compile)
    chk = cell.config["serve"]["check"]
    compared = {
        "max_abs_diff": {"value": ref["max_abs_diff"],
                         "limit": chk["tol_max_abs"]},
        "rms_diff": {"value": ref["rms_diff"], "limit": chk["tol_rms"]},
        "compiled_in_window": {"value": int(not no_compile), "limit": 0}}
    dev = rep.dev
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": mem["memory_peak_bytes"]}
    breakdown = None
    if trace:
        from ..lib import trace as trace_lib
        tr = rep.call("trace_summary")
        summary = tr["summary"]
        say("trace: " + compact({k: summary[k] for k in (
            "window_s", "busy_s", "devices", "programs")}))
        say("trace ops: " + compact(summary["ops"][:15]))
        say("trace idle: " + compact(summary["idle"][:10]))
        if rep.want == "tpu" and not summary["busy_s"] > 0:
            raise CellFailed("the traced span holds no device operation")
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        breakdown = trace_lib.breakdown(summary)
        epoch = win["epoch"]
        ctx = {
            "cell": cell.entry, "config": cell.config, "traffic": traffic,
            "seconds": seconds, "roll": roll, "samples": win["samples"],
            "trace": summary, "model": cell.model,
            "span": {"t0": tr["t0"] - epoch, "t1": tr["t1"] - epoch,
                     "stats0": tr["stats0"], "stats1": tr["stats1"]},
            "stats0": win["stats0"], "stats1": win["stats1"],
            "device": device,
            "peaks": peaks_for(dev["kind"]) if rep.want == "tpu" else None,
        }
        values = {m["name"]: cell.reader(m["name"])(ctx)
                  for m in cell.metrics("per_layer")}
        metrics = manifest.metric_line(values, cell.metrics("per_layer"))
    else:
        metrics = manifest.metric_line(roll, cell.metrics("end_to_end"))
    return (correct, roll["attempted"], roll["failed"], metrics, device,
            breakdown, compared, roll["host_stalls"])
