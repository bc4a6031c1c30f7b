"""LLM serving benchmark: dense vs paged KV cache through the Serve stack.

BASELINE.json's second north-star metric is "Serve req/s + p50 TTFT" for a
continuous-batching LLM deployment (config #4).  This drives the real stack:
HTTP-less handle path -> router -> replica actor -> LLMEngine (slot-scheduled
continuous batching, bucketed prefill, single compiled decode step) on the
local accelerator, THREE times over the same long-prompt mix:

  1. dense  — slots x max_len KV rows (the r2 configuration)
  2. paged  — block-table KV pages (models/paged_decode.py)
  3. paged + shared-prefix workload — every prompt shares a long common
     prefix, so prefill hits the refcounted prefix cache

Prints ONE JSON line.  vs_baseline = paged req/s / dense req/s on the same
mix (>= 1.0 means paging pays for itself; the reference has no LLM server to
compare against, SURVEY §2.7).

One process owns a chip: only the replica touches JAX, and it asks the
scheduler for the host's ``TPU`` resource.  This parent never imports JAX; it
finds the chips by their device nodes (``detect_node_resources``) and takes
the platform from the replica's own ``stats()``.  No chip, or a replica that
came up on anything but a TPU, is an error (exit code non-zero).  ``--preset
tiny`` is the CPU rehearsal of the control flow, not a measurement.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import threading
import time

from ray_tpu.utils.compile_cache import place_compile_cache

#: Schema contract for one configuration's per-request breakdown — the
#: full serving picture (open item #2) captured in one run.  Guarded by
#: tests/test_serve_observability.py::test_bench_llm_breakdown_schema so a
#: refactor cannot silently drop a field between chip windows.
REQUEST_KEYS = frozenset({
    "req_per_s", "n_requests", "decode_tok_per_s",
    "p50_ttft_ms", "p95_ttft_ms", "p99_ttft_ms",
    "p50_tpot_ms", "p95_tpot_ms",
})
#: engine-side breakdown keys (LLMEngine.breakdown(), via LLMServer.stats)
ENGINE_KEYS = frozenset({
    "admit_batches",
})


def request_rollup(samples, wall_s: float) -> dict:
    """Per-request metrics rollup: ``samples`` is a list of
    ``(ttft_s, latency_s, n_tokens)`` tuples; returns the REQUEST_KEYS
    dict.  Pure — the schema-guard test drives it with synthetic
    samples.  TPOT = (latency - ttft) / (n_tokens - 1): steady-state
    decode pace after the first token."""
    n = len(samples)
    if not n:
        raise ValueError("no request samples")
    ttfts = sorted(s[0] for s in samples)
    tpots = sorted((lat - ttft) / (nt - 1)
                   for ttft, lat, nt in samples if nt > 1)

    def pct(xs, q):
        return xs[min(len(xs) - 1, int(len(xs) * q))] if xs else None

    rnd = lambda v: None if v is None else round(v * 1000, 2)  # noqa: E731
    return {
        "req_per_s": round(n / wall_s, 2),
        "n_requests": n,
        "decode_tok_per_s": round(sum(s[2] for s in samples) / wall_s, 1),
        "p50_ttft_ms": rnd(pct(ttfts, 0.50)),
        "p95_ttft_ms": rnd(pct(ttfts, 0.95)),
        "p99_ttft_ms": rnd(pct(ttfts, 0.99)),
        "p50_tpot_ms": rnd(pct(tpots, 0.50)),
        "p95_tpot_ms": rnd(pct(tpots, 0.95)),
    }


class PhaseAborted(RuntimeError):
    """One configuration failed to become servable; carries the
    controller's view of why (per-replica states) so the checkpoint
    records a diagnosable reason instead of a bare timeout."""

    def __init__(self, msg: str, detail: dict):
        super().__init__(msg)
        self.detail = detail


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--preset", default="llama-1b")
    p.add_argument("--clients", type=int, default=8)
    p.add_argument("--requests", type=int, default=32)
    p.add_argument("--prompt-len", type=int, default=256,
                   help="max prompt length in the mix (min is 1/4 of this)")
    p.add_argument("--max-tokens", type=int, default=64)
    p.add_argument("--num-slots", type=int, default=16)
    p.add_argument("--max-len", type=int, default=1024)
    p.add_argument("--storm", action="store_true",
                   help="add an open-loop arrival-spike phase (paged "
                        "config, serve/loadgen burst schedule + "
                        "heavy-tailed prompt lengths): how TTFT behaves "
                        "through a burst at fixed chip capacity")
    p.add_argument("--storm-rate", type=float, default=2.0,
                   help="storm base arrivals/s (spike is 4x)")
    p.add_argument("--deploy-timeout", type=float, default=300.0,
                   help="seconds to wait for a configuration's replica to "
                        "go HEALTHY before aborting that phase (the old "
                        "blind 900 s wait is gone: we poll serve.status() "
                        "and record the stuck replica's state instead)")
    p.add_argument("--fresh", action="store_true",
                   help="ignore an existing BENCH_LLM_partial.json instead "
                        "of resuming from its checkpointed phases")
    args = p.parse_args()

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core.common import detect_node_resources
    from ray_tpu.serve.llm import llm_deployment

    rehearsal = args.preset == "tiny"
    n_chips = int(detect_node_resources().get("TPU", 0))
    if not n_chips and not rehearsal:
        sys.exit("bench_llm.py: no TPU: this host shows no chip device node "
                 "(/dev/accel<N> or /dev/vfio/<N>) and TPU_VISIBLE_CHIPS is "
                 "unset; --preset tiny rehearses the control flow on the CPU")
    place_compile_cache()  # before any worker starts: they inherit it

    rng = random.Random(0)
    buckets = (args.prompt_len // 4, args.prompt_len // 2, args.prompt_len)

    def mixed_prompt():
        """Long-prompt mix: lengths spread across all prefill buckets."""
        n = rng.randint(args.prompt_len // 4, args.prompt_len)
        return [rng.randint(1, 1000) for _ in range(n)]

    _prefix = [rng.randint(1, 1000) for _ in range(args.prompt_len - 32)]

    def prefix_prompt():
        """Shared-prefix workload: identical long prefix + short unique tail
        (multi-turn / system-prompt shape; hits the paged prefix cache)."""
        return _prefix + [rng.randint(1, 1000) for _ in range(32)]

    def drive(handle, make_prompt):
        """Run the client fleet; returns the REQUEST_KEYS breakdown."""
        samples = []  # (ttft_s, latency_s, n_tokens) per request
        lock = threading.Lock()
        reqs_per_client = args.requests // args.clients

        def client():
            for _ in range(reqs_per_client):
                t0 = time.monotonic()
                first, n = None, 0
                for _tok in handle.stream({"tokens": make_prompt(),
                                           "max_tokens": args.max_tokens}):
                    if first is None:
                        first = time.monotonic() - t0
                    n += 1
                with lock:
                    samples.append((first, time.monotonic() - t0, n))

        t0 = time.time()
        threads = [threading.Thread(target=client)
                   for _ in range(args.clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return request_rollup(samples, time.time() - t0)

    def drive_storm(handle):
        """Open-loop burst phase (serve/loadgen): arrivals fire on a
        seeded schedule regardless of completion pace, so queueing delay
        shows in TTFT instead of slowing the client.  Heavy-tailed
        prompt/decode lengths stress the prefill buckets + paged KV the
        way production traffic would."""
        from ray_tpu.serve import loadgen

        srng = random.Random(1)
        warm_s, spike_s, cool_s = 5.0, 10.0, 5.0
        total = warm_s + spike_s + cool_s
        arrivals = loadgen.burst_arrivals(
            args.storm_rate, 4.0, warm_s, warm_s + spike_s, total, srng)

        def payload(idx: int):
            return loadgen.llm_payload(
                1, idx, prompt_median=args.prompt_len // 2,
                prompt_lo=args.prompt_len // 4, prompt_hi=args.prompt_len,
                decode_median=args.max_tokens // 2,
                decode_hi=args.max_tokens)

        runner = loadgen.StormRunner(
            loadgen.stream_fire(handle, payload, timeout_s=600.0),
            max_outstanding=256)
        t0 = time.time()
        storm_samples = runner.run(arrivals)
        wall = time.time() - t0
        ok = [s.rollup_tuple() for s in storm_samples if s.ok]
        out = request_rollup(ok, wall) if ok else {"n_requests": 0}
        out["n_errors"] = sum(1 for s in storm_samples if not s.ok)
        out["arrivals"] = loadgen.arrival_rate_series(arrivals)
        out["ttft_p95_series"] = loadgen.windowed_p95_series(storm_samples)
        return out

    def wait_servable(name: str, timeout_s: float):
        """Poll serve.status() until ``name`` is HEALTHY.  The old path
        blocked 900 s inside serve.run with zero visibility — when a
        replica hung in STARTING the whole run
        burned its budget and reported nothing.  On timeout, raise with
        the controller's per-replica states so the checkpoint says WHY."""
        deadline = time.monotonic() + timeout_s
        last: dict = {}
        while time.monotonic() < deadline:
            try:
                last = serve.status().get(name, {})
            except Exception as e:  # noqa: BLE001 — controller booting
                last = {"error": repr(e)}
            if last.get("status") == "HEALTHY":
                return
            time.sleep(2.0)
        pending = [{"name": r.get("name"), "state": r.get("state")}
                   for r in last.get("replicas", [])]
        raise PhaseAborted(
            f"{name} not HEALTHY after {timeout_s:.0f}s",
            {"status": last.get("status"), "replicas": pending,
             **({"error": last["error"]} if "error" in last else {})})

    def run_serve(paged: bool, make_prompt, label: str,
                  storm: bool = False, extra_engine: dict | None = None):
        """One full cluster lifecycle per configuration: the TPU is held
        exclusively by the replica process, so the next configuration's
        replica can only initialize after a complete teardown."""
        print(f"# {label}: deploying…", flush=True)
        ray_tpu.init(num_cpus=8)
        try:
            dep = llm_deployment(
                args.preset, num_slots=args.num_slots, max_len=args.max_len,
                max_concurrent_queries=256, health_check_timeout_s=600.0,
                ray_actor_options=({"num_tpus": n_chips} if n_chips else {}),
                engine_kwargs={"buckets": buckets, "warmup_buckets": True,
                               "paged": paged, **(extra_engine or {})})
            h = serve.run(dep, timeout_s=args.deploy_timeout,
                          _blocking=False)
            wait_servable(f"llm-{args.preset}", args.deploy_timeout)
            list(h.stream({"tokens": make_prompt(), "max_tokens": 4}))
            res = drive_storm(h) if storm else drive(h, make_prompt)
            # engine-side serving picture: batch occupancy/padding waste,
            # KV page utilization, prefix-cache hit rate (LLMServer.stats
            # -> LLMEngine.breakdown), and where the replica ran
            res["engine"] = h.stats.remote().result(timeout_s=60)
            if res["engine"]["platform"] != "tpu" and not rehearsal:
                sys.exit(f"bench_llm.py: no TPU: the replica ran on "
                         f"platform={res['engine']['platform']} "
                         f"kind={res['engine']['device_kind']}")
            return res
        finally:
            try:
                serve.shutdown()
            except Exception:
                pass
            # returns once the replica's process has exited (NodeAgent.stop
            # waits for its workers), which is when the chip is free again
            ray_tpu.shutdown()

    # Resume from the checkpoint file: a re-run after a run that died
    # replays only the missing phases (each phase persists its numbers the
    # moment it completes).  --fresh starts over.
    partial = {}
    if not args.fresh and os.path.exists("BENCH_LLM_partial.json"):
        try:
            with open("BENCH_LLM_partial.json") as f:
                partial = json.load(f)
            done = [k for k, v in partial.items()
                    if not (isinstance(v, dict) and "aborted" in v)]
            if done:
                print(f"# resuming: phases {done} checkpointed, skipping",
                      flush=True)
        except Exception:  # noqa: BLE001 — corrupt checkpoint: start over
            partial = {}

    def phase(key, *a, **kw):
        """Run one configuration and persist its numbers IMMEDIATELY — a
        later phase that dies must not lose earlier results.  A
        checkpointed phase is not run again on resume; an aborted one (deploy
        never went HEALTHY) records its reason, re-runs next time, and
        makes this run exit non-zero."""
        cached = partial.get(key)
        if isinstance(cached, dict) and "aborted" not in cached:
            print(f"# {key}: checkpointed, skipping", flush=True)
            return cached
        try:
            res = run_serve(*a, **kw)
        except PhaseAborted as e:
            res = {"aborted": str(e), **e.detail}
        partial[key] = res
        print(f"# {key}: {json.dumps(res)}", flush=True)
        with open("BENCH_LLM_partial.json", "w") as f:
            json.dump(partial, f, indent=1)
        return res

    def ok(res):
        return isinstance(res, dict) and "aborted" not in res \
            and "req_per_s" in res

    try:
        dense = phase("dense", False, mixed_prompt, "dense")
        paged = phase("paged", True, mixed_prompt, "paged")
        prefix = phase("paged_prefix", True, prefix_prompt, "paged+prefix")
        # speculative decoding under the same continuous-batching paged
        # config: 1-layer draft, verify-window target step (the PR-19
        # serving path; acceptance + rollback stats land in res["engine"])
        spec = phase("paged_spec", True, mixed_prompt, "paged+spec",
                     extra_engine={"spec_decode_enabled": True, "spec_k": 4,
                                   "spec_draft_layers": 1})
        storm = None
        if args.storm:
            # checkpointed like every phase
            storm = phase("storm", True, mixed_prompt, "storm", True)
        out = {
            "metric": "serve_llm_req_per_s",
            "value": paged.get("req_per_s"),
            "unit": "req/s",
            "dense": dense,
            "paged": paged,
            "paged_prefix_hit": prefix,
            "paged_spec": spec,
            **({"storm": storm} if storm is not None else {}),
            "model": args.preset,
            "clients": args.clients, "requests": args.requests,
            "prompt_mix": [args.prompt_len // 4, args.prompt_len],
            "max_tokens": args.max_tokens,
            "num_slots": args.num_slots, "max_len": args.max_len,
        }
        if ok(dense) and ok(paged):
            # paging must at least match dense on the same long-prompt mix
            out["vs_baseline"] = round(
                paged["req_per_s"] / max(dense["req_per_s"], 1e-9), 3)
        if ok(paged) and ok(spec):
            out["spec_vs_paged"] = round(
                spec["decode_tok_per_s"]
                / max(paged["decode_tok_per_s"], 1e-9), 3)
        print(json.dumps(out))
        aborted = [k for k, v in partial.items()
                   if isinstance(v, dict) and "aborted" in v]
        if aborted:
            sys.exit(f"bench_llm.py: phases aborted: {aborted}")
    finally:
        if ray_tpu.is_initialized():
            ray_tpu.shutdown()


if __name__ == "__main__":
    main()
