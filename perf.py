"""Core microbenchmarks — the ``ray_perf.py`` equivalent.

Reference harness: ``python/ray/_private/ray_perf.py``; reference numbers:
BASELINE.md "Core microbenchmarks" (v2.6.3 release log, m4.16xlarge-class,
64 cores).  This box is 1 core, so absolute numbers are not comparable 1:1 —
the table tracks round-over-round movement of the pure-Python substrate and
flags order-of-magnitude regressions vs the reference envelope.

Run: ``python perf.py [--out PERF.json]`` — prints one JSON object with every
metric, and a ``vs_baseline`` per metric where BASELINE.md has a row.
"""

from __future__ import annotations

import argparse
import collections
import json
import time


from ray_tpu.util.procmem import rss_mb as _rss_mb


BASELINE = {
    "tasks_sync": 1329.0,
    "tasks_async": 10940.0,
    "actor_calls_sync_1_1": 2528.0,
    "actor_calls_async_1_1": 8233.0,
    "actor_calls_async_n_n": 32688.0,
    "async_actor_calls_sync_1_1": 1520.0,
    "async_actor_calls_async_1_1": 2683.0,
    "get_small": 6144.0,
    "put_gbps": 18.4,
    "wait_1k_refs": 5.1,
    "pg_create_remove": 983.0,
    "serve_noop_req_s": 630.0,
}


_REPS = 3  # per-metric repetitions inside one suite pass (see --reps)


def timeit(fn, n: int, warmup: int = 1) -> list:
    """Per-rep ops/s samples of fn() called n times (fn may batch internally).

    Repeating the timed region _REPS times per suite pass is what stabilizes
    the headline multipliers: single-shot samples on this 1-core box swing
    +/-40% (e.g. PERF_r05 get_small IQR 52k on a 94k median), and the
    aggregator needs several samples per metric to quote a meaningful
    median + IQR + min."""
    for _ in range(warmup):
        fn()
    samples = []
    for _ in range(max(_REPS, 1)):
        t0 = time.perf_counter()
        fn()
        dt = time.perf_counter() - t0
        samples.append(n / dt)
    return samples


def run_suite(S: float, with_serve: bool) -> dict:
    """One full pass over the microbench suite on a fresh cluster.
    Every metric maps to a LIST of per-rep ops/s samples."""
    import numpy as np

    import ray_tpu

    # explicit store size: the put benchmark must measure shm write
    # throughput, not LRU spill-to-disk (which the default capacity triggers
    # at 8x64MB)
    ray_tpu.init(num_cpus=8, object_store_memory=2 << 30)
    results = {}

    @ray_tpu.remote
    def noop(_x=None):
        return None

    @ray_tpu.remote
    class Counter:
        def ping(self):
            return None

    @ray_tpu.remote
    class AsyncCounter:
        async def ping(self):
            return None

    try:
        # warm the worker pool
        ray_tpu.get([noop.remote() for _ in range(8)])

        n = int(200 * S)
        results["tasks_sync"] = timeit(
            lambda: [ray_tpu.get(noop.remote()) for _ in range(n)], n)

        n = int(1000 * S)
        results["tasks_async"] = timeit(
            lambda: ray_tpu.get([noop.remote() for _ in range(n)]), n)

        # submit_burst: 1k no-arg tasks submitted back-to-back, then one
        # batched get — end-to-end ops/s PLUS percentiles of the bare
        # .remote() submission call (the user-thread cost the fast path's
        # spec-template cache and submit coalescing shave).
        nb = int(1000 * S)
        results["submit_burst_submit_us_p50"] = []
        results["submit_burst_submit_us_p99"] = []
        burst_calls = [0]

        def burst():
            burst_calls[0] += 1
            t_sub = []
            refs = []
            for _ in range(nb):
                s0 = time.perf_counter()
                refs.append(noop.remote())
                t_sub.append(time.perf_counter() - s0)
            ray_tpu.get(refs)
            if burst_calls[0] == 1:
                return  # timeit()'s warmup pass: cold-path latencies
                # (lease acquisition, spec-cache fill) must not skew the
                # warm percentiles — ops/s already excludes warmup
            t_sub.sort()
            results["submit_burst_submit_us_p50"].append(
                t_sub[len(t_sub) // 2] * 1e6)
            results["submit_burst_submit_us_p99"].append(
                t_sub[min(len(t_sub) - 1, int(len(t_sub) * 0.99))] * 1e6)

        results["submit_burst"] = timeit(burst, nb)

        # submit_churn: sustained WINDOW-deep submit/drain steady state —
        # every completion admits the next submission, so this measures
        # the pipeline the admission gate enforces at production depths
        # (ops/s, bare-submit latency percentiles, and the RSS the steady
        # state retains), not a one-shot burst.
        nc = int(4000 * S)
        window = 1000
        results["submit_churn_submit_us_p50"] = []
        results["submit_churn_submit_us_p99"] = []
        results["submit_churn_rss_delta_mb"] = []
        churn_calls = [0]

        def churn():
            churn_calls[0] += 1
            rss0 = _rss_mb()
            t_sub = []
            dq = collections.deque()
            for _ in range(nc):
                s0 = time.perf_counter()
                dq.append(noop.remote())
                t_sub.append(time.perf_counter() - s0)
                if len(dq) >= window:
                    ray_tpu.get(dq.popleft())
            ray_tpu.get(list(dq))
            if churn_calls[0] == 1:
                return  # warmup pass: exclude cold-path latencies
            t_sub.sort()
            results["submit_churn_submit_us_p50"].append(
                t_sub[len(t_sub) // 2] * 1e6)
            results["submit_churn_submit_us_p99"].append(
                t_sub[min(len(t_sub) - 1, int(len(t_sub) * 0.99))] * 1e6)
            results["submit_churn_rss_delta_mb"].append(
                max(0.0, _rss_mb() - rss0))

        results["submit_churn"] = timeit(churn, nc)

        a = Counter.remote()
        ray_tpu.get(a.ping.remote())
        n = int(300 * S)
        results["actor_calls_sync_1_1"] = timeit(
            lambda: [ray_tpu.get(a.ping.remote()) for _ in range(n)], n)

        n = int(2000 * S)
        results["actor_calls_async_1_1"] = timeit(
            lambda: ray_tpu.get([a.ping.remote() for _ in range(n)]), n)

        actors = [Counter.remote() for _ in range(4)]
        ray_tpu.get([x.ping.remote() for x in actors])
        n = int(2000 * S)
        results["actor_calls_async_n_n"] = timeit(
            lambda: ray_tpu.get([actors[i % 4].ping.remote()
                                 for i in range(n)]), n)

        aa = AsyncCounter.remote()
        ray_tpu.get(aa.ping.remote())
        n = int(300 * S)
        results["async_actor_calls_sync_1_1"] = timeit(
            lambda: [ray_tpu.get(aa.ping.remote()) for _ in range(n)], n)
        n = int(2000 * S)
        results["async_actor_calls_async_1_1"] = timeit(
            lambda: ray_tpu.get([aa.ping.remote() for _ in range(n)]), n)

        small = ray_tpu.put(np.zeros(16))
        n = int(2000 * S)
        results["get_small"] = timeit(
            lambda: [ray_tpu.get(small) for _ in range(n)], n)

        big = np.zeros(64 * 1024 * 1024, np.uint8)  # 64 MB
        n = max(int(8 * S), 2)

        def put_big():
            for _ in range(n):
                ray_tpu.put(big)

        results["put_gbps"] = [ops * big.nbytes / 1e9
                               for ops in timeit(put_big, n)]

        refs = [noop.remote() for _ in range(1000)]
        ray_tpu.get(refs)
        n = max(int(20 * S), 5)
        results["wait_1k_refs"] = timeit(
            lambda: [ray_tpu.wait(refs, num_returns=1000, timeout=10)
                     for _ in range(n)], n)

        n = max(int(20 * S), 5)

        def pg_cycle():
            for _ in range(n):
                pg = ray_tpu.placement_group([{"CPU": 1}])
                pg.ready(timeout=30)
                ray_tpu.remove_placement_group(pg)

        results["pg_create_remove"] = timeit(pg_cycle, n)

        if with_serve:
            # free the microbench actors' CPUs for the serve replicas
            for actor in [a, aa, *actors]:
                ray_tpu.kill(actor)
            from ray_tpu import serve

            @serve.deployment(num_replicas=2)
            def snoop(_x=None):
                return b"ok"

            h = serve.run(snoop)
            for _ in range(20):
                h.remote().result()
            n = int(300 * S)
            results["serve_noop_req_s"] = timeit(
                lambda: [h.remote().result() for _ in range(n)], n)
            serve.shutdown()
    finally:
        ray_tpu.shutdown()
    return results


#: the "off" arm of the fast-path A/B: result inlining, spec template
#: caching, and lease pipelining all disabled — results route through the
#: shm store (worker-side store_create + caller-side fetch per result) and
#: every submission re-encodes its full spec, isolating exactly what the
#: submission fast path buys on this box in this run.
FASTPATH_OFF = {"inline_result_max_bytes": 0,
                "spec_cache_enabled": False,
                "lease_pipeline_window": 0}


def _measure_submission(S: float, system_config: dict | None) -> dict:
    """One fresh-cluster measurement of the submission-plane metrics only
    (the A/B arms; full-suite metrics stay with run_suite)."""
    import ray_tpu
    ray_tpu.init(num_cpus=8, object_store_memory=2 << 30,
                 _system_config=system_config or None)
    out = {}

    @ray_tpu.remote
    def noop(_x=None):
        return None

    @ray_tpu.remote
    class Counter:
        def ping(self):
            return None

    try:
        ray_tpu.get([noop.remote() for _ in range(8)])
        n = int(1000 * S)
        out["tasks_async"] = max(timeit(
            lambda: ray_tpu.get([noop.remote() for _ in range(n)]), n))
        a = Counter.remote()
        ray_tpu.get(a.ping.remote())
        n = int(300 * S)
        out["actor_calls_sync_1_1"] = max(timeit(
            lambda: [ray_tpu.get(a.ping.remote()) for _ in range(n)], n))
    finally:
        ray_tpu.shutdown()
    return out


def _measure_serve_reqs(S: float, system_config: dict | None) -> dict:
    """One fresh-cluster serve request-throughput measurement (the
    serve-observability A/B arms): a 2-replica noop deployment driven via
    the handle path, sequential (latency-bound) and pipelined."""
    import ray_tpu
    from ray_tpu import serve
    ray_tpu.init(num_cpus=8, _system_config=system_config or None)
    out = {}
    try:
        @serve.deployment(num_replicas=2, max_concurrent_queries=64)
        def snoop(_x=None):
            return b"ok"

        h = serve.run(snoop)
        for _ in range(20):
            h.remote().result()
        n = int(300 * S)
        out["serve_noop_req_s"] = max(timeit(
            lambda: [h.remote().result() for _ in range(n)], n))
        n = int(600 * S)

        def pipelined():
            rs = [h.remote() for _ in range(n)]
            for r in rs:
                r.result()

        out["serve_pipelined_req_s"] = max(timeit(pipelined, n))
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
    return out


def run_ab_serve_metrics(S: float, pairs: int) -> dict:
    """Interleaved same-box A/B: serve_metrics_enabled on vs off — the
    serve observability plane's request-throughput overhead (the ISSUE-6
    acceptance gate: <= 5%)."""
    on_runs, off_runs = [], []
    off_cfg = {"serve_metrics_enabled": False}
    for i in range(pairs):
        on_runs.append(_measure_serve_reqs(S, None))
        off_runs.append(_measure_serve_reqs(S, dict(off_cfg)))
        print(f"# serve ab pair {i + 1}/{pairs}: on={on_runs[-1]} "
              f"off={off_runs[-1]}", flush=True)
    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    ratio = {k: round(med([r[k] for r in on_runs])
                      / max(med([r[k] for r in off_runs]), 1e-9), 3)
             for k in on_runs[0]}
    return {"pairs_on": on_runs, "pairs_off": off_runs,
            "off_config": off_cfg, "ratio_on_off": ratio}


def _measure_specroute(S: float, on: bool) -> dict:
    """One fresh-cluster LLM serving measurement for the speculative +
    cache-routed A/B (PR-19 gate): 2 replicas of a compute-bound CPU toy
    model behind the real handle -> router -> replica -> engine path.

    ON arm: speculative decoding (1-layer draft, verify-window target
    step) + prefix-cache-aware routing.  OFF arm: dense decode + pure
    power-of-two-choices.  Both arms serve the SAME damped checkpoint and
    the SAME seeded shared-prefix traffic — the decode/routing planes are
    the only delta.  The model is deliberately deeper/wider than the
    'tiny' preset: speculation pays when layer compute dominates the
    per-step fixed cost (embed + lm_head + dispatch), which is also the
    regime real targets live in; on a toy-tiny config the fixed cost
    swamps the drafted layers and speculation measures slower."""
    import os
    import queue
    import threading
    import time as _time

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve import loadgen

    sys_cfg = None if on else {"serve_prefix_routing_enabled": False}
    ray_tpu.init(num_cpus=8, _system_config=sys_cfg)
    out = {"arm": "spec+routed" if on else "dense+p2c"}
    try:
        @serve.deployment(name="specbench", num_replicas=2,
                          max_concurrent_queries=64,
                          health_check_timeout_s=600.0)
        class SpecBench:
            """LLM replica over a damped checkpoint (speculative.py's
            honest-about-itself benchmark trick: tail layers' output
            projections scaled so target ~= draft + small residual while
            the target still pays full depth)."""

            def __init__(self, spec: bool):
                import jax
                import jax.numpy as jnp
                from ray_tpu.models import speculative as specmod
                from ray_tpu.models import transformer
                from ray_tpu.models.config import TransformerConfig
                from ray_tpu.serve.llm import LLMEngine
                cfg = TransformerConfig(
                    vocab_size=512, num_layers=8, hidden_size=256,
                    num_heads=8, num_kv_heads=4, mlp_size=1024,
                    max_seq_len=512)
                params = transformer.init_params(
                    jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
                params = specmod.damp_block_outputs(params, 0.02,
                                                    from_layer=1)
                kw = dict(paged=True, page_size=16, buckets=(64, 128),
                          warmup_buckets=True, steps_per_dispatch=12)
                if spec:
                    kw.update(spec_decode_enabled=True, spec_k=6,
                              spec_draft_layers=1)
                self.engine = LLMEngine(cfg, params, num_slots=16,
                                        max_len=512, **kw)

            async def __call__(self, request):
                import asyncio
                from ray_tpu.serve.llm import _FLUSH  # noqa: F401
                body = (request.json() if hasattr(request, "json")
                        else request)
                req = self.engine.submit(
                    body["tokens"],
                    max_tokens=int(body.get("max_tokens", 32)))
                loop = asyncio.get_event_loop()
                while True:
                    item = await loop.run_in_executor(None, req.out.get)
                    if not isinstance(item, int):
                        if isinstance(item, BaseException):
                            raise item
                        return
                    yield item

            def stats(self) -> dict:
                return self.engine.breakdown()

            def prefix_digest(self):
                from ray_tpu.core.config import get_config
                cap = int(getattr(get_config(),
                                  "serve_prefix_digest_max", 32))
                return self.engine.prefix_digest(cap)

        h = serve.run(SpecBench.bind(spec=on), timeout_s=600)
        n = max(12, int(24 * S))
        payloads = [loadgen.llm_payload(
            1234, i, prompt_median=64, prompt_lo=48, prompt_hi=96,
            decode_median=24, decode_lo=16, decode_hi=32, vocab=500,
            prefix_pool=6, prefix_len=64) for i in range(n)]
        # warm both replicas' decode/spec programs before timing
        for _ in range(4):
            sum(1 for _ in h.stream({"tokens": payloads[0]["tokens"][:],
                                     "max_tokens": 4}))
        work: queue.Queue = queue.Queue()
        for pl in payloads:
            work.put(pl)
        counts = []
        lock = threading.Lock()

        def client():
            while True:
                try:
                    pl = work.get_nowait()
                except queue.Empty:
                    return
                ntok = sum(1 for _ in h.stream(dict(pl), timeout_s=600.0))
                with lock:
                    counts.append(ntok)

        t0 = _time.monotonic()
        threads = [threading.Thread(target=client) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = _time.monotonic() - t0
        out["tok_s"] = round(sum(counts) / wall, 2)
        out["n_requests"] = len(counts)
        out["wall_s"] = round(wall, 2)
        # per-replica engine stats: spec acceptance + prefix hit rate
        from ray_tpu.serve.router import get_router
        router = get_router()
        router._refresh(force=True)
        spec_tot = {"tokens": 0, "drafted": 0, "accepted": 0, "rounds": 0}
        lookups = hits = 0
        for rep in list(router._table.get("specbench", [])):
            try:
                st = ray_tpu.get(router._replica_handle(rep)
                                 .handle_request.remote((), {}, "stats"),
                                 timeout=60)
            except Exception:  # noqa: BLE001 — stats are additive
                continue
            sp = st.get("spec")
            if sp:
                for k in spec_tot:
                    spec_tot[k] += int(sp.get(k, 0))
            pc = st.get("prefix_cache") or {}
            lookups += int(pc.get("lookups", 0))
            hits += int(pc.get("hits", 0))
        if spec_tot["drafted"]:
            out["spec_acceptance"] = round(
                spec_tot["accepted"] / spec_tot["drafted"], 4)
            out["spec_tokens_per_round"] = round(
                spec_tot["tokens"] / max(spec_tot["rounds"], 1), 2)
        out["prefix_hit_rate"] = (round(hits / lookups, 4)
                                  if lookups else None)
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
    return out


def run_ab_specroute(S: float, pairs: int) -> dict:
    """Interleaved same-box A/B: speculative decode + prefix-cache-aware
    routing ON vs dense decode + load-only p2c (the PR-19 acceptance
    gate: spec+routed decode tokens/s >= 1.3x the dense arm on the same
    damped CPU model + seeded shared-prefix traffic)."""
    on_runs, off_runs = [], []
    for i in range(pairs):
        on_runs.append(_measure_specroute(S, True))
        off_runs.append(_measure_specroute(S, False))
        print(f"# specroute ab pair {i + 1}/{pairs}: on={on_runs[-1]} "
              f"off={off_runs[-1]}", flush=True)
    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    ratio = round(med([r["tok_s"] for r in on_runs])
                  / max(med([r["tok_s"] for r in off_runs]), 1e-9), 3)
    return {"pairs_on": on_runs, "pairs_off": off_runs,
            "ratio_on_off": {"tok_s": ratio},
            "gate": {"min_ratio": 1.3, "passed": ratio >= 1.3}}


def _measure_autoscale_reqs(S: float, slo_policy: bool) -> dict:
    """One fresh-cluster serve request-throughput measurement for the
    autoscaler A/B: a steady 2-replica noop deployment — the ON arm runs
    the policy="slo" control loop (targets high enough that steady load
    never trips a scale event: the measured cost is the per-reconcile
    signal rollup + policy tick, not replica churn), the OFF arm pins
    num_replicas=2 with no autoscaling at all."""
    import ray_tpu
    from ray_tpu import serve
    ray_tpu.init(num_cpus=8)
    out = {}
    try:
        opts = dict(max_concurrent_queries=64)
        if slo_policy:
            opts["autoscaling_config"] = dict(
                policy="slo", min_replicas=2, max_replicas=4,
                target_ongoing_requests=1000.0, ttft_p95_target_ms=60_000.0,
                upscale_delay_s=3.0, downscale_delay_s=30.0)
        else:
            opts["num_replicas"] = 2

        @serve.deployment(**opts)
        def anoop(_x=None):
            return b"ok"

        h = serve.run(anoop)
        for _ in range(20):
            h.remote().result()
        n = int(300 * S)
        out["serve_noop_req_s"] = max(timeit(
            lambda: [h.remote().result() for _ in range(n)], n))
        n = int(600 * S)

        def pipelined():
            rs = [h.remote() for _ in range(n)]
            for r in rs:
                r.result()

        out["serve_pipelined_req_s"] = max(timeit(pipelined, n))
        # the A/B is only valid if the policy held steady: a scale event
        # mid-measurement would be measuring replica churn, not overhead
        if slo_policy:
            reps = serve.status()["anoop"]["replicas"]
            out["replicas_end"] = len(
                [r for r in reps if r["state"] == "RUNNING"])
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
    return out


def run_ab_autoscale(S: float, pairs: int) -> dict:
    """Interleaved same-box A/B: SLO autoscaler policy on vs no
    autoscaling, over a steady noop deployment (the ISSUE-15 acceptance
    gate: <= 5% request-throughput overhead for the control loop)."""
    on_runs, off_runs = [], []
    for i in range(pairs):
        on_runs.append(_measure_autoscale_reqs(S, True))
        off_runs.append(_measure_autoscale_reqs(S, False))
        print(f"# autoscale ab pair {i + 1}/{pairs}: on={on_runs[-1]} "
              f"off={off_runs[-1]}", flush=True)
    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    keys = [k for k in on_runs[0] if k in off_runs[0]]
    ratio = {k: round(med([r[k] for r in on_runs])
                      / max(med([r[k] for r in off_runs]), 1e-9), 3)
             for k in keys}
    return {"pairs_on": on_runs, "pairs_off": off_runs,
            "off_config": "num_replicas=2, autoscaling=None",
            "ratio_on_off": ratio}


#: the "off" arm of the train-observability A/B: the kill switch sheds the
#: step/stage histograms, MFU/goodput gauges, memory sampling AND the
#: per-step trace spans — isolating exactly what train_metrics_enabled
#: costs a tight report-every-step CPU loop.
TRAIN_OBS_OFF = {"train_metrics_enabled": False}


def _measure_train_obs(S: float, system_config: dict | None) -> dict:
    """One fresh-cluster measurement of a small CPU train loop's
    steps/s (the train-observability A/B arms): a 1-worker
    DataParallelTrainer whose loop stamps the data_wait/step_compute
    phases and reports EVERY step — the densest instrumentation pattern
    a real loop would use."""
    import tempfile

    import ray_tpu
    ray_tpu.init(num_cpus=4, _system_config=system_config or None)
    out = {}
    try:
        from ray_tpu.train import (DataParallelTrainer, RunConfig,
                                   ScalingConfig)
        steps = max(int(200 * S), 20)

        def loop(config):
            import time as _t

            from ray_tpu import train
            obs = train.get_context().observability()
            obs.set_model(flops_per_token=1e3, tokens_per_step=1024,
                          peak_flops=1e12)
            n = config["steps"]
            t0 = _t.perf_counter()
            for i in range(n):
                with obs.phase("data_wait"):
                    pass
                with obs.phase("step_compute"):
                    pass
                train.report(
                    {"step": i,
                     "steps_per_s": n / max(_t.perf_counter() - t0, 1e-9)})

        trainer = DataParallelTrainer(
            train_loop_per_worker=loop,
            train_loop_config={"steps": steps},
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name="ab-train-obs",
                                 storage_path=tempfile.mkdtemp()))
        result = trainer.fit()
        out["train_steps_per_s"] = result.metrics["steps_per_s"]
    finally:
        ray_tpu.shutdown()
    return out


def run_ab_train_obs(S: float, pairs: int) -> dict:
    """Interleaved same-box A/B: train_metrics_enabled on vs off — the
    train observability plane's per-step overhead (the ISSUE-10
    acceptance gate: <= 5%)."""
    on_runs, off_runs = [], []
    for i in range(pairs):
        on_runs.append(_measure_train_obs(S, None))
        off_runs.append(_measure_train_obs(S, dict(TRAIN_OBS_OFF)))
        print(f"# train-obs ab pair {i + 1}/{pairs}: on={on_runs[-1]} "
              f"off={off_runs[-1]}", flush=True)
    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    ratio = {k: round(med([r[k] for r in on_runs])
                      / max(med([r[k] for r in off_runs]), 1e-9), 3)
             for k in on_runs[0]}
    return {"pairs_on": on_runs, "pairs_off": off_runs,
            "off_config": TRAIN_OBS_OFF, "ratio_on_off": ratio}


def _measure_elastic(S: float, mode: str) -> dict:
    """One fresh-cluster run of a fixed training workload (epochs x 100 ms
    of "compute", checkpoint every epoch) under a seeded mid-run
    preemption, for the elastic-vs-restart A/B arms:

    - ``elastic``:  ScalingConfig(min_workers=1) — the drain notice
      resizes the group 2 -> 1 in place, then back up when the
      replacement node lands;
    - ``restart``:  rigid world size + FailureConfig retries — the same
      preemption kills the run, which restarts from the latest
      checkpoint once the replacement node can host the full group;
    - ``baseline``: same cluster and workload, no chaos (the undisturbed
      goodput yardstick).

    The chaos schedule (seed, after_s, notice_s) and the 2 s
    replacement-node lag are identical for elastic and restart, so the
    measured gap is exactly the recovery-path cost."""
    import tempfile
    import threading

    import ray_tpu
    from ray_tpu.core.cluster import Cluster
    from ray_tpu.core.rpc import run_async

    epochs = max(int(240 * S), 30)
    sleep_s = 0.1
    cluster = Cluster(initialize_head=False)
    out = {}
    try:
        n1 = cluster.add_node(num_cpus=4)
        n2 = cluster.add_node(num_cpus=4)
        cluster.wait_for_nodes(2)
        info = cluster.connect_driver()
        from ray_tpu.core.core_worker import global_worker
        from ray_tpu.train import (Checkpoint, DataParallelTrainer,
                                   FailureConfig, RunConfig, ScalingConfig)
        # info["node_id"] is None when joining an existing cluster: identify
        # the driver by its attached agent's address instead
        victim = n2 if n1.address == global_worker().agent_address else n1
        if mode != "baseline":
            spec = {"seed": 23, "kills": [
                {"kind": "preempt_node", "after_s": 3.0, "notice_s": 2.0,
                 "node": victim.node_id[:8]}]}
            run_async(global_worker().gcs.call("chaos_set", spec=spec))

            def _replace():  # the spot market delivers a replacement node
                deadline = time.monotonic() + 120
                while (victim.proc.poll() is None
                       and time.monotonic() < deadline):
                    time.sleep(0.1)
                time.sleep(2.0)  # provisioning lag, identical for both arms
                cluster.add_node(num_cpus=4)

            threading.Thread(target=_replace, daemon=True).start()

        def loop(config):
            import json as _json
            import os as _os
            import tempfile as _tmp
            import time as _t

            from ray_tpu import train
            from ray_tpu.train import Checkpoint as _Ckpt
            rank0 = train.get_context().get_world_rank() == 0
            start = 0
            ckpt = train.get_checkpoint()
            if ckpt:
                with open(_os.path.join(ckpt.path, "e.json")) as f:
                    start = _json.load(f)["epoch"] + 1
            for e in range(start, config["epochs"]):
                _t.sleep(config["sleep_s"])
                ck = None
                if rank0:
                    d = _tmp.mkdtemp()
                    with open(_os.path.join(d, "e.json"), "w") as f:
                        _json.dump({"epoch": e}, f)
                    ck = _Ckpt(d)
                train.report({"epoch": e}, checkpoint=ck)

        scaling = ScalingConfig(
            num_workers=2, resources_per_worker={"CPU": 3.0},
            min_workers=1 if mode == "elastic" else None)
        failures = FailureConfig(max_failures=5 if mode == "restart" else 0)
        trainer = DataParallelTrainer(
            train_loop_per_worker=loop,
            train_loop_config={"epochs": epochs, "sleep_s": sleep_s},
            scaling_config=scaling,
            run_config=RunConfig(name=f"ab-elastic-{mode}",
                                 storage_path=tempfile.mkdtemp(),
                                 failure_config=failures))
        t0 = time.perf_counter()
        result = trainer.fit()
        wall = time.perf_counter() - t0
        assert result.error is None, f"{mode} arm failed: {result.error!r}"
        assert result.metrics["epoch"] == epochs - 1
        out["wall_s"] = round(wall, 3)
        # the workload's intrinsic productive time over actual wall clock:
        # one comparable goodput number for all three arms
        out["goodput"] = round(epochs * sleep_s / wall, 4)
        out["resizes"] = result.num_resizes
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        cluster.shutdown()
    return out


def run_ab_elastic(S: float, pairs: int) -> dict:
    """Elastic resize vs restart-from-checkpoint on the SAME seeded chaos
    schedule, plus an undisturbed baseline (the ISSUE-18 acceptance
    gates: elastic goodput >= 80% of undisturbed; resize strictly
    cheaper than restart)."""
    arms = {"elastic": [], "restart": [], "baseline": []}
    for i in range(pairs):
        for mode in ("elastic", "restart", "baseline"):
            arms[mode].append(_measure_elastic(S, mode))
        print(f"# elastic ab pair {i + 1}/{pairs}: "
              f"elastic={arms['elastic'][-1]} "
              f"restart={arms['restart'][-1]} "
              f"baseline={arms['baseline'][-1]}", flush=True)
    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    g = {m: med([r["goodput"] for r in arms[m]]) for m in arms}
    w = {m: med([r["wall_s"] for r in arms[m]]) for m in arms}
    return {"pairs": arms,
            "goodput": {m: round(v, 4) for m, v in g.items()},
            "wall_s": {m: round(v, 3) for m, v in w.items()},
            "elastic_vs_baseline_goodput": round(
                g["elastic"] / max(g["baseline"], 1e-9), 3),
            "elastic_vs_restart_wall": round(
                w["elastic"] / max(w["restart"], 1e-9), 3)}


#: the "off" arm of the scheduler-observability A/B: the kill switch sheds
#: loop busy-fraction sampling, per-GCS-handler busy attribution, the
#: owner serialize/flush histograms and the backpressure counters —
#: isolating what sched_metrics_enabled costs the submission hot path.
SCHED_OBS_OFF = {"sched_metrics_enabled": False}


def _measure_sched_obs(S: float, system_config: dict | None) -> dict:
    """One fresh-cluster measurement of the sched-observability A/B arms:
    tasks_async (the owner-loop-bound path the saturation metrics watch)
    plus submit_burst ops/s and bare-submit p99."""
    import ray_tpu
    ray_tpu.init(num_cpus=8, object_store_memory=2 << 30,
                 _system_config=system_config or None)
    out = {}

    @ray_tpu.remote
    def noop(_x=None):
        return None

    try:
        ray_tpu.get([noop.remote() for _ in range(8)])
        n = int(1000 * S)
        out["tasks_async"] = max(timeit(
            lambda: ray_tpu.get([noop.remote() for _ in range(n)]), n))
        nb = int(1000 * S)
        sub_p99 = []
        calls = [0]

        def burst():
            calls[0] += 1
            t_sub = []
            refs = []
            for _ in range(nb):
                s0 = time.perf_counter()
                refs.append(noop.remote())
                t_sub.append(time.perf_counter() - s0)
            ray_tpu.get(refs)
            if calls[0] == 1:
                return  # warmup pass
            t_sub.sort()
            sub_p99.append(
                t_sub[min(len(t_sub) - 1, int(len(t_sub) * 0.99))] * 1e6)

        out["submit_burst"] = max(timeit(burst, nb))
        out["submit_burst_submit_us_p99"] = (
            sorted(sub_p99)[len(sub_p99) // 2] if sub_p99 else None)
    finally:
        ray_tpu.shutdown()
    return out


def run_ab_sched_obs(S: float, pairs: int) -> dict:
    """Interleaved same-box A/B: sched_metrics_enabled on vs off over
    tasks_async + submit_burst (the ISSUE-11 acceptance gate: <= 5%
    overhead)."""
    on_runs, off_runs = [], []
    for i in range(pairs):
        on_runs.append(_measure_sched_obs(S, None))
        off_runs.append(_measure_sched_obs(S, dict(SCHED_OBS_OFF)))
        print(f"# sched ab pair {i + 1}/{pairs}: on={on_runs[-1]} "
              f"off={off_runs[-1]}", flush=True)
    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    ratio = {k: round(med([r[k] for r in on_runs])
                      / max(med([r[k] for r in off_runs]), 1e-9), 3)
             for k in ("tasks_async", "submit_burst")}
    return {"pairs_on": on_runs, "pairs_off": off_runs,
            "off_config": SCHED_OBS_OFF, "ratio_on_off": ratio}


#: both arms of the health-plane A/B run the detectors' tick cadences
#: HOT (2 Hz health check + scrape, dashboard head up) so the on-arm pays
#: every cost the plane can impose; the off-arm differs by ONE switch.
HEALTH_AB_BASE = {"health_check_period_s": 0.5,
                  "metrics_scrape_period_s": 0.5}
HEALTH_OFF = {"health_metrics_enabled": False}


def _measure_health(S: float, system_config: dict | None) -> dict:
    """One fresh-cluster measurement of the health-plane A/B arms:
    submit_churn (window-deep submit/drain — the owner/GCS loops the
    GCS-side rules watch) + serve_noop req/s (the loop the head-side
    SLO rules watch), with the dashboard head running so the scrape-loop
    detector is actually on the clock."""
    import collections
    import ray_tpu
    from ray_tpu import serve
    cfg = dict(HEALTH_AB_BASE)
    cfg.update(system_config or {})
    ray_tpu.init(num_cpus=8, _system_config=cfg)
    out = {}
    try:
        from ray_tpu.dashboard import head as dash_head
        dash_head.start_dashboard()

        @ray_tpu.remote
        def noop(_x=None):
            return None

        ray_tpu.get([noop.remote() for _ in range(8)])
        nc = int(2000 * S)
        window = 500

        def churn():
            dq = collections.deque()
            for _ in range(nc):
                dq.append(noop.remote())
                if len(dq) >= window:
                    ray_tpu.get(dq.popleft())
            ray_tpu.get(list(dq))

        out["submit_churn"] = max(timeit(churn, nc))

        @serve.deployment(num_replicas=2, max_concurrent_queries=64)
        def snoop(_x=None):
            return b"ok"

        h = serve.run(snoop)
        for _ in range(20):
            h.remote().result()
        n = int(300 * S)
        out["serve_noop_req_s"] = max(timeit(
            lambda: [h.remote().result() for _ in range(n)], n))
        serve.shutdown()
        dash_head.stop_dashboard()
    finally:
        ray_tpu.shutdown()
    return out


def run_ab_health(S: float, pairs: int) -> dict:
    """Interleaved same-box A/B: health_metrics_enabled on vs off over
    submit_churn + serve_noop with hot detector cadences (the ISSUE-17
    acceptance gate: <= 5% overhead; off restores zero series)."""
    on_runs, off_runs = [], []
    for i in range(pairs):
        on_runs.append(_measure_health(S, None))
        off_runs.append(_measure_health(S, dict(HEALTH_OFF)))
        print(f"# health ab pair {i + 1}/{pairs}: on={on_runs[-1]} "
              f"off={off_runs[-1]}", flush=True)
    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    ratio = {k: round(med([r[k] for r in on_runs])
                      / max(med([r[k] for r in off_runs]), 1e-9), 3)
             for k in on_runs[0]}
    return {"pairs_on": on_runs, "pairs_off": off_runs,
            "off_config": HEALTH_OFF, "base_config": HEALTH_AB_BASE,
            "ratio_on_off": ratio}


#: the "off" arm of the object-observability A/B: the object plane's one
#: kill switch — no raytpu_object_*/raytpu_mem_* series, no flight-recorder
#: events, no copy-ledger accounting, no transfer-ring writes.
OBJECT_OBS_OFF = {"object_metrics_enabled": False}


def _measure_object_obs(S: float, system_config: dict | None) -> dict:
    """One fresh-cluster measurement of the object-plane A/B arms: put
    GB/s (the instrumented 1-copy path), same-host large get ops/s (the
    instrumented 0-copy path), and an 8-way large-arg fan-out (every
    worker fetches the same plasma object — the broadcast-shaped path)."""
    import numpy as np

    import ray_tpu
    ray_tpu.init(num_cpus=8, object_store_memory=2 << 30,
                 _system_config=system_config or None)
    out = {}
    try:
        big = np.zeros(64 * 1024 * 1024, np.uint8)  # 64 MB
        n = max(int(8 * S), 2)

        def put_big():
            for _ in range(n):
                ray_tpu.put(big)

        out["put_gbps"] = max(ops * big.nbytes / 1e9
                              for ops in timeit(put_big, n))

        ref = ray_tpu.put(big)
        ng = max(int(40 * S), 5)
        out["get_big"] = max(timeit(
            lambda: [ray_tpu.get(ref) for _ in range(ng)], ng))

        @ray_tpu.remote
        def touch(obj):
            return int(obj[0])

        ray_tpu.get([touch.remote(ref) for _ in range(8)])  # warmup
        nb = max(int(6 * S), 2)

        def fanout():
            for _ in range(nb):
                ray_tpu.get([touch.remote(ref) for _ in range(8)])

        out["arg_fanout_8"] = max(ops * 8 for ops in timeit(fanout, nb))
    finally:
        ray_tpu.shutdown()
    return out


def run_ab_object_obs(S: float, pairs: int) -> dict:
    """Interleaved same-box A/B: object_metrics_enabled on vs off over
    put/get/fan-out (the ISSUE-12 acceptance gate: <= 5% overhead)."""
    on_runs, off_runs = [], []
    for i in range(pairs):
        on_runs.append(_measure_object_obs(S, None))
        off_runs.append(_measure_object_obs(S, dict(OBJECT_OBS_OFF)))
        print(f"# object ab pair {i + 1}/{pairs}: on={on_runs[-1]} "
              f"off={off_runs[-1]}", flush=True)
    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    ratio = {k: round(med([r[k] for r in on_runs])
                      / max(med([r[k] for r in off_runs]), 1e-9), 3)
             for k in on_runs[0]}
    return {"pairs_on": on_runs, "pairs_off": off_runs,
            "off_config": OBJECT_OBS_OFF, "ratio_on_off": ratio}


#: the "off" arm of the zero-copy-put + wire-rate-transfer A/B: the exact
#: pre-PR data plane — classic serialize-then-copy put (one write_into
#: memcpy), one socket per (puller, source) pair, fixed chunk size (no
#: adaptive growth).
ZCPUT_OFF = {"zero_copy_put_enabled": False,
             "transfer_sockets_per_source": 1,
             "object_transfer_chunk_bytes": 8 * 1024 * 1024,
             "object_transfer_chunk_max": 0}


def run_ab_zcput(S: float, pairs: int) -> dict:
    """Interleaved same-box A/B: zero-copy put + multi-socket adaptive
    transfer ON vs the prior 1-copy/fixed-chunk plane (the ISSUE-14
    gates: put_gbps >= 1.5x with the ledger showing put/copies=0, and the
    off arm's put_gbps/get_big within the <=5% regression envelope of
    PERF_r13)."""
    on_runs, off_runs = [], []
    for i in range(pairs):
        on_runs.append(_measure_object_obs(S, None))
        off_runs.append(_measure_object_obs(S, dict(ZCPUT_OFF)))
        print(f"# zcput ab pair {i + 1}/{pairs}: on={on_runs[-1]} "
              f"off={off_runs[-1]}", flush=True)
    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    ratio = {k: round(med([r[k] for r in on_runs])
                      / max(med([r[k] for r in off_runs]), 1e-9), 3)
             for k in on_runs[0]}
    return {"pairs_on": on_runs, "pairs_off": off_runs,
            "off_config": ZCPUT_OFF, "ratio_on_off": ratio}


#: the "off" arm of the batched-submission A/B: one task per push RPC, one
#: lease per request RPC, one actor call per batch — the unbatched
#: submission plane the scale-envelope work replaced.
SUBMIT_BATCH_OFF = {"submit_batching_enabled": False}


def run_ab_submit_batching(S: float, pairs: int) -> dict:
    """Interleaved same-box A/B: batched submission on vs off (the ISSUE-7
    acceptance gate: >= 1.5x tasks_async)."""
    on_runs, off_runs = [], []
    for i in range(pairs):
        on_runs.append(_measure_submission(S, None))
        off_runs.append(_measure_submission(S, dict(SUBMIT_BATCH_OFF)))
        print(f"# submit ab pair {i + 1}/{pairs}: on={on_runs[-1]} "
              f"off={off_runs[-1]}", flush=True)
    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    ratio = {k: round(med([r[k] for r in on_runs])
                      / max(med([r[k] for r in off_runs]), 1e-9), 3)
             for k in on_runs[0]}
    return {"pairs_on": on_runs, "pairs_off": off_runs,
            "off_config": SUBMIT_BATCH_OFF, "ratio_on_off": ratio}


#: the "off" arm of the horizontal-control-plane A/B (PR-13): the PRE-PR
#: submission/completion plane — per-result push frames, per-ref get
#: waits, 16-task push batches, one GCS process (gcs_table_shards=1), one
#: connection, no shard processes, no serialization pool, no lanes.
CPSHARD_OFF = {
    "completion_batching_enabled": False,
    "max_tasks_in_flight_per_worker": 16,
    "gcs_table_shards": 1,
    "gcs_shard_processes": 0,
    "gcs_client_connections": 1,
    "agent_client_connections": 1,
    "owner_serialize_threads": 0,
    "control_plane_io_lanes": False,
}

#: the "on" arm: the shipped defaults (completion batching, 64-task push
#: batches) plus 4 GCS shard processes fronted by the router and 2
#: parallel GCS connections.  Worker-connection lanes and the owner
#: serialization pool ship OFF by default: measured net-negative for
#: these workloads on a GIL interpreter (see ARCHITECTURE.md
#: "Horizontal control plane"), they exist for free-threaded builds and
#: multi-driver topologies.
CPSHARD_ON = {
    "gcs_shard_processes": 4,
    "gcs_client_connections": 2,
}


def _measure_cpshard(S: float, system_config: dict | None) -> dict:
    """One fresh-cluster measurement of the control-plane A/B metrics:
    tasks_async + pg_create_remove (the acceptance gates), a 50k-task
    drain (the scale proxy), and the fast paths that must NOT regress
    (get_small, put_gbps)."""
    import numpy as np

    import ray_tpu
    ray_tpu.init(num_cpus=8, object_store_memory=2 << 30,
                 _system_config=system_config or None)
    out = {}

    @ray_tpu.remote
    def noop(_x=None):
        return None

    try:
        ray_tpu.get([noop.remote() for _ in range(8)])
        n = int(1000 * S)
        out["tasks_async"] = max(timeit(
            lambda: ray_tpu.get([noop.remote() for _ in range(n)]), n))

        n = max(int(20 * S), 5)

        def pg_cycle():
            for _ in range(n):
                pg = ray_tpu.placement_group([{"CPU": 1}])
                pg.ready(timeout=30)
                ray_tpu.remove_placement_group(pg)

        out["pg_create_remove"] = max(timeit(pg_cycle, n))

        nd = int(50_000 * S)
        t0 = time.perf_counter()
        refs = [noop.remote() for _ in range(nd)]
        for i in range(0, nd, 10_000):
            ray_tpu.get(refs[i:i + 10_000], timeout=900)
        out["drain_tasks_per_s"] = round(nd / (time.perf_counter() - t0), 1)

        small = ray_tpu.put(np.zeros(16))
        n = int(2000 * S)
        out["get_small"] = max(timeit(
            lambda: [ray_tpu.get(small) for _ in range(n)], n))

        big = np.zeros(64 * 1024 * 1024, np.uint8)
        n = max(int(8 * S), 2)

        def put_big():
            for _ in range(n):
                ray_tpu.put(big)

        out["put_gbps"] = max(ops * big.nbytes / 1e9
                              for ops in timeit(put_big, n))
    finally:
        ray_tpu.shutdown()
    return out


def run_ab_cpshard(S: float, pairs: int) -> dict:
    """Interleaved same-box A/B: the horizontal control plane (GCS shard
    processes + completion batching + bigger push batches) vs the pre-PR
    single-process, single-lane plane (the ISSUE-13 acceptance gate)."""
    on_runs, off_runs = [], []
    for i in range(pairs):
        on_runs.append(_measure_cpshard(S, dict(CPSHARD_ON)))
        off_runs.append(_measure_cpshard(S, dict(CPSHARD_OFF)))
        print(f"# cpshard ab pair {i + 1}/{pairs}: on={on_runs[-1]} "
              f"off={off_runs[-1]}", flush=True)
    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    ratio = {k: round(med([r[k] for r in on_runs])
                      / max(med([r[k] for r in off_runs]), 1e-9), 3)
             for k in on_runs[0]}
    return {"pairs_on": on_runs, "pairs_off": off_runs,
            "on_config": CPSHARD_ON, "off_config": CPSHARD_OFF,
            "ratio_on_off": ratio,
            "vs_baseline_on": {
                k: round(med([r[k] for r in on_runs]) / BASELINE[k], 3)
                for k in on_runs[0] if k in BASELINE}}


#: the "off" arm of the native-submission-plane A/B: the exact pre-PR
#: owner hot loop — per-call TaskSpec ctor (no templates, no free-list
#: recycling), per-spec wire tuples (no packed frames / C encoder), full
#: 3-events-per-task trails, per-ref refcount locking restored via the
#: scalar paths' semantics (batch helpers remain but the knobs gate the
#: allocation/encode/event savings the tentpole added).
SUBMIT_PLANE_OFF = {"submit_plane_native_enabled": False,
                    "task_event_sample_n": 0,
                    "spec_freelist_max": 0}


def run_ab_submitplane(S: float, pairs: int) -> dict:
    """Interleaved same-box A/B: the native submission plane (slotted/
    pooled specs + packed C-encoded frames + sampled events) on vs off
    (the ISSUE-16 acceptance gate: >= 1.5x tasks_async)."""
    on_cfg = {"task_event_sample_n": 8}
    on_runs, off_runs = [], []
    for i in range(pairs):
        on_runs.append(_measure_submission(S, dict(on_cfg)))
        off_runs.append(_measure_submission(S, dict(SUBMIT_PLANE_OFF)))
        print(f"# submitplane ab pair {i + 1}/{pairs}: on={on_runs[-1]} "
              f"off={off_runs[-1]}", flush=True)
    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    ratio = {k: round(med([r[k] for r in on_runs])
                      / max(med([r[k] for r in off_runs]), 1e-9), 3)
             for k in on_runs[0]}
    return {"pairs_on": on_runs, "pairs_off": off_runs,
            "on_config": on_cfg, "off_config": SUBMIT_PLANE_OFF,
            "ratio_on_off": ratio}


def _chipspeed_jax():
    """Import jax for the chip-speed A/B: CPU backend, 8 forced host
    devices so the dp=4 collectives in parallel/zero.py are real (must
    run before the first jax import in this process)."""
    import os
    import sys
    if "jax" not in sys.modules:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    return jax


def _measure_chipspeed(S: float, arm: str, steps: int) -> dict:
    """One fresh-jit run of the tiny-config dp=4 CPU train loop for one
    knob combination (``arm``: '+'-joined subset of quant/zero, or 'off';
    the tiny config's head_dim 16 cannot tile the splash kernel, and an
    explicit ``attention_impl="splash"`` raises there since PR 22).  Fixed seed and fixed batch schedule so arms are comparable
    numerically, not just in time."""
    import numpy as np

    import jax.numpy as jnp
    from ray_tpu.models import config as mcfg
    from ray_tpu.parallel import (OptimizerSpec, init_sharded_state,
                                  init_zero_state, make_mesh, make_train_step)

    cfg = mcfg.tiny()
    mesh = make_mesh(4, dp=4, fsdp=1)
    spec = OptimizerSpec(total_steps=1000, warmup_steps=5)
    opt = spec.build()
    zero, quant = "zero" in arm, "quant" in arm
    if zero:
        state, sh = init_zero_state(cfg, mesh, spec)
    else:
        state, sh = init_sharded_state(cfg, mesh, opt)
    step = make_train_step(cfg, mesh, opt, sh, compute_dtype=jnp.float32,
                           grad_quant_enabled=quant,
                           zero_sharded_update=zero, opt_spec=spec)
    rng = np.random.RandomState(0)
    batches = [{"tokens": rng.randint(0, cfg.vocab_size,
                                      (8, cfg.max_seq_len + 1))}
               for _ in range(steps)]
    losses = []
    state, m = step(state, batches[0])  # compile step, untimed
    jax_block = jnp.asarray(m["total_loss"]).block_until_ready()
    losses.append(float(jax_block))
    t0 = time.perf_counter()
    for b in batches[1:]:
        state, m = step(state, b)
        losses.append(float(m["total_loss"]))  # forces the step
    wall = time.perf_counter() - t0
    return {"arm": arm, "steps_per_s": round((steps - 1) / wall, 2),
            "final_loss": round(losses[-1], 6),
            "opt_state_bytes": step.opt_state_bytes,
            "wire_int8": any(d == "int8" for _, d in step.collective_bytes),
            "_losses": losses}


def run_ab_chipspeed(S: float, pairs: int) -> dict:
    """Interleaved CPU A/B of the chip-speed knobs (ISSUE-20 gates):

    - numerics: the ZeRO-sharded arm's per-step losses allclose to the
      replicated arm (same seed/batches, fp32); the int8 quantized
      round-trip stays inside the analytical amax/254-per-rank bound;
      splash interpret-mode forward parity vs ops/flash_attention.

    The quant/zero arms change the computation by design, so they get
    numerics bounds, not overhead bounds; their steps/s ratios are
    recorded for the record only (CPU time is not the TPU win).
    """
    jax = _chipspeed_jax()
    if len(jax.devices()) < 4:
        return {"skipped": f"need >= 4 devices, have {len(jax.devices())}"}
    import jax.numpy as jnp

    steps = max(int(10 * S), 6)
    arms = ("off", "quant+zero")
    runs = {a: [] for a in arms}
    for i in range(pairs):
        for a in arms:
            runs[a].append(_measure_chipspeed(S, a, steps))
        print(f"# chipspeed ab pair {i + 1}/{pairs}: " +
              " ".join(f"{a}={runs[a][-1]['steps_per_s']}/s" for a in arms),
              flush=True)

    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    ratio = {a: round(med([r["steps_per_s"] for r in runs[a]])
                      / max(med([r["steps_per_s"] for r in runs["off"]]),
                            1e-9), 3)
             for a in arms if a != "off"}

    # numerics gate 1: ZeRO == replicated, step for step (one fresh run
    # each, same batch schedule as the timed arms)
    l_ref = runs["off"][0]["_losses"]
    l_zero = _measure_chipspeed(S, "zero", steps)["_losses"]
    zero_err = max(abs(a - b) / max(abs(a), 1e-9)
                   for a, b in zip(l_ref, l_zero))
    zero_ok = zero_err < 1e-5

    # numerics gate 2: int8 block round-trip inside amax/254 per element
    from ray_tpu.parallel.quant_collectives import (dequantize_int8_block,
                                                    quantize_int8_block)
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 4096), jnp.float32) * 8
    q, s = quantize_int8_block(x, block=256)
    back = dequantize_int8_block(q, s, block=256)
    amax = jnp.max(jnp.abs(x.reshape(64, 16, 256)), -1, keepdims=True)
    bound = jnp.broadcast_to(amax / 254.0 + 1e-7, (64, 16, 256))
    quant_ok = bool(jnp.all(jnp.abs(back - x).reshape(64, 16, 256) <= bound))
    quant_max_err = float(jnp.max(jnp.abs(back - x)))

    # numerics gate 3: splash interpret-mode forward parity
    from ray_tpu.ops.splash_attention import splash_mha
    from ray_tpu.ops.flash_attention import flash_attention
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    qq = jax.random.normal(ks[0], (1, 256, 4, 128), jnp.float32)
    kk = jax.random.normal(ks[1], (1, 256, 2, 128), jnp.float32)
    vv = jax.random.normal(ks[2], (1, 256, 2, 128), jnp.float32)
    splash_err = float(jnp.max(jnp.abs(
        splash_mha(qq, kk, vv, causal=True)
        - flash_attention(qq, kk, vv, causal=True))))
    splash_ok = splash_err < 1e-4

    strip = lambda r: {k: v for k, v in r.items()  # noqa: E731
                       if k != "_losses"}
    return {"pairs_on": [strip(r) for r in runs["quant+zero"]],
            "pairs_off": [strip(r) for r in runs["off"]],
            "ratio_on_off": {"steps_per_s": ratio["quant+zero"]},
            "gate": {"zero_allclose_rtol": 1e-5,
                     "zero_max_rel_err": round(zero_err, 9),
                     "zero_allclose": zero_ok,
                     "quant_max_err": round(quant_max_err, 6),
                     "quant_bounded": quant_ok,
                     "splash_fwd_max_err": splash_err,
                     "splash_parity": splash_ok,
                     "passed": bool(zero_ok and quant_ok and splash_ok)}}


def run_profile_submit(S: float) -> dict:
    """Per-stage µs breakdown of one WARM submission: spec build / encode
    / events / refcount measured in isolation on live runtime objects,
    serialize+flush attributed from the owner histograms over a clean
    burst, plus the bare .remote() driver-thread p50 they decompose."""
    import ray_tpu
    from ray_tpu.core import common, sched_explain
    from ray_tpu.core.core_worker import global_worker
    from ray_tpu.core.ids import TaskID
    from ray_tpu.core.remote_function import serialize_args

    ray_tpu.init(num_cpus=8, object_store_memory=2 << 30,
                 _system_config={"sched_metrics_enabled": True})
    prof = {}

    @ray_tpu.remote
    def noop(_x=None):
        return None

    try:
        ray_tpu.get([noop.remote() for _ in range(8)])
        ray_tpu.get([noop.remote() for _ in range(500)])  # warm everything
        w = global_worker()
        k = max(int(2000 * S), 500)
        args_blob, _ = serialize_args((), {})
        tmpl = noop._spec_tmpl
        assert tmpl is not None, "warm template missing — submit plane off?"

        # stage: spec build (free-list pop + template slot copy)
        t0 = time.perf_counter()
        specs = [common.build_spec_from_template(
            tmpl, TaskID.from_random(), args_blob, None) for _ in range(k)]
        prof["spec_build_us"] = round((time.perf_counter() - t0) / k * 1e6, 3)

        # stage: encode (packed batch frame, warm templates, batch of 64)
        stub = type("C", (), {"_writer": object()})()
        batch = specs[:64]
        w.spec_encoder.encode_batch(stub, batch)  # deliver templates once
        reps = max(k // 64, 8)
        t0 = time.perf_counter()
        for _ in range(reps):
            w.spec_encoder.encode_batch(stub, batch)
        prof["encode_us"] = round(
            (time.perf_counter() - t0) / (reps * len(batch)) * 1e6, 3)

        # stage: task events (one SUBMITTED stamp per task, current
        # sampling config; buffers restored afterwards)
        saved = w._task_events
        w._task_events = []
        t0 = time.perf_counter()
        for s in specs:
            w.task_event(s, "SUBMITTED")
        prof["events_us"] = round((time.perf_counter() - t0) / k * 1e6, 3)
        w._task_events = saved
        for s in specs:
            w._submit_ts.pop(s.task_id, None)

        # stage: refcount (one-ref add+remove round trip, batched paths)
        from ray_tpu.core.ids import ObjectID
        rc = w.reference_counter
        oids = [ObjectID.for_task_return(s.task_id, 0) for s in specs]
        t0 = time.perf_counter()
        for oid in oids:
            rc.add_submitted_many((oid,))
            rc.remove_submitted_many(((oid, w.address),))
        prof["refcount_us"] = round((time.perf_counter() - t0) / k * 1e6, 3)

        # serialize+flush attribution over a clean burst (owner histograms)
        om = sched_explain.owner_metrics()

        def hist_totals(h):
            return (sum(h._sum.values()), sum(h._count.values()))

        s0, f0 = hist_totals(om["serialize"]), hist_totals(om["flush"])
        nb = int(1000 * S)
        t_sub = []
        refs = []
        t0 = time.perf_counter()
        for _ in range(nb):
            c0 = time.perf_counter()
            refs.append(noop.remote())
            t_sub.append(time.perf_counter() - c0)
        ray_tpu.get(refs)
        wall = time.perf_counter() - t0
        s1, f1 = hist_totals(om["serialize"]), hist_totals(om["flush"])
        prof["serialize_us_per_task"] = round((s1[0] - s0[0]) / nb * 1e6, 3)
        prof["flush_us_per_task"] = round((f1[0] - f0[0]) / nb * 1e6, 3)
        t_sub.sort()
        prof["bare_submit_us_p50"] = round(t_sub[len(t_sub) // 2] * 1e6, 3)
        prof["burst_tasks_per_s"] = round(nb / wall, 1)
        prof["note"] = ("spec_build/encode/events/refcount measured in "
                        "isolation on live objects; serialize/flush are "
                        "owner-histogram deltas over the burst; "
                        "bare_submit_us_p50 is the driver-thread .remote() "
                        "cost those stages decompose")
    finally:
        ray_tpu.shutdown()
    return prof


def run_ab_fastpath(S: float, pairs: int) -> dict:
    """Interleaved same-box A/B: fast path ON vs OFF, alternating fresh
    clusters so box drift lands evenly on both arms."""
    on_runs, off_runs = [], []
    for i in range(pairs):
        on_runs.append(_measure_submission(S, None))
        off_runs.append(_measure_submission(S, dict(FASTPATH_OFF)))
        print(f"# ab pair {i + 1}/{pairs}: on={on_runs[-1]} "
              f"off={off_runs[-1]}", flush=True)
    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    ratio = {k: round(med([r[k] for r in on_runs])
                      / max(med([r[k] for r in off_runs]), 1e-9), 3)
             for k in on_runs[0]}
    return {"pairs_on": on_runs, "pairs_off": off_runs,
            "off_config": FASTPATH_OFF, "ratio_on_off": ratio}


def main():
    global _REPS
    p = argparse.ArgumentParser()
    p.add_argument("--out", default=None)
    p.add_argument("--scale", type=float, default=1.0,
                   help="shrink/grow iteration counts")
    p.add_argument("--serve", action="store_true",
                   help="include the Serve noop benchmark (slower)")
    p.add_argument("--runs", type=int, default=3,
                   help="repeat the whole suite N times (fresh cluster "
                        "each); with --reps samples per metric per run the "
                        "aggregate reports median + IQR + min per metric")
    p.add_argument("--reps", type=int, default=_REPS,
                   help="timed repetitions per metric within one suite pass")
    p.add_argument("--ab-fastpath", type=int, default=0, metavar="PAIRS",
                   help="also run PAIRS interleaved A/B pairs of the "
                        "submission fast path (inlining + spec caching + "
                        "lease pipelining) on vs off and embed the ratios")
    p.add_argument("--ab-serve", type=int, default=0, metavar="PAIRS",
                   help="also run PAIRS interleaved A/B pairs of "
                        "serve_metrics_enabled on vs off (serve request "
                        "throughput; the serve-observability overhead gate)")
    p.add_argument("--ab-specroute", type=int, default=0, metavar="PAIRS",
                   help="also run PAIRS interleaved A/B pairs of "
                        "speculative decode + cache-aware routing on vs "
                        "dense decode + pure p2c over the same damped CPU "
                        "model and seeded shared-prefix traffic (the "
                        "spec-serving >= 1.3x gate)")
    p.add_argument("--ab-submit", type=int, default=0, metavar="PAIRS",
                   help="also run PAIRS interleaved A/B pairs of batched "
                        "submission on vs off (push/lease/actor-call "
                        "batching; the scale-envelope gate)")
    p.add_argument("--ab-train-obs", type=int, default=0, metavar="PAIRS",
                   help="also run PAIRS interleaved A/B pairs of "
                        "train_metrics_enabled on vs off (CPU train-loop "
                        "steps/s; the train-observability overhead gate)")
    p.add_argument("--ab-elastic", type=int, default=0, metavar="PAIRS",
                   help="also run PAIRS triples of a fixed train workload "
                        "under the same seeded mid-run preemption: elastic "
                        "resize vs restart-from-checkpoint vs undisturbed "
                        "baseline (the elastic-training recovery-cost gate)")
    p.add_argument("--ab-sched", type=int, default=0, metavar="PAIRS",
                   help="also run PAIRS interleaved A/B pairs of "
                        "sched_metrics_enabled on vs off (tasks_async + "
                        "submit_burst; the scheduler-observability "
                        "overhead gate)")
    p.add_argument("--ab-cpshard", type=int, default=0, metavar="PAIRS",
                   help="also run PAIRS interleaved A/B pairs of the "
                        "horizontal control plane (GCS shard processes + "
                        "completion batching) on vs the pre-PR "
                        "single-process single-lane plane")
    p.add_argument("--ab-zcput", type=int, default=0, metavar="PAIRS",
                   help="also run PAIRS interleaved A/B pairs of the "
                        "zero-copy put + multi-socket adaptive transfer "
                        "plane on vs the prior 1-copy/fixed-chunk plane "
                        "(put GB/s, large get, 8-way arg fan-out)")
    p.add_argument("--ab-autoscale", type=int, default=0, metavar="PAIRS",
                   help="also run PAIRS interleaved A/B pairs of the SLO "
                        "autoscaler policy on vs no autoscaling over a "
                        "steady noop deployment (the control-loop "
                        "overhead gate)")
    p.add_argument("--ab-submitplane", type=int, default=0, metavar="PAIRS",
                   help="also run PAIRS interleaved A/B pairs of the "
                        "native submission plane (pooled specs + packed "
                        "C frames + sampled events) on vs off")
    p.add_argument("--ab-health", type=int, default=0, metavar="PAIRS",
                   help="also run PAIRS interleaved A/B pairs of "
                        "health_metrics_enabled on vs off (submit_churn "
                        "+ serve_noop with hot detector cadences; the "
                        "health-plane overhead gate)")
    p.add_argument("--ab-chipspeed", type=int, default=0, metavar="PAIRS",
                   help="also run PAIRS interleaved CPU A/B triples of the "
                        "chip-speed knobs (splash attention / int8 grad "
                        "quant / ZeRO-sharded update) on vs off on a tiny "
                        "dp=4 config, gating numerics equivalence and the "
                        "<= 5% no-TPU fallback overhead")
    p.add_argument("--profile-submit", action="store_true",
                   help="profile one warm submission: per-stage µs "
                        "(spec build / encode / events / refcount / "
                        "serialize+flush) plus bare .remote() p50")
    p.add_argument("--ab-object", type=int, default=0, metavar="PAIRS",
                   help="also run PAIRS interleaved A/B pairs of "
                        "object_metrics_enabled on vs off (put GB/s, "
                        "large get, 8-way arg fan-out; the object-plane "
                        "observability overhead gate)")
    args = p.parse_args()
    _REPS = max(args.reps, 1)

    all_runs = []
    # --runs 0: skip the full suite (targeted A/B-only invocations)
    for r in range(args.runs):
        res = run_suite(args.scale, args.serve)
        all_runs.append(res)
        if args.runs > 1:
            print(f"# run {r + 1}/{args.runs}: "
                  f"{json.dumps({k: [round(x, 1) for x in v] for k, v in res.items()})}",
                  flush=True)

    def quantile(xs, q):
        xs = sorted(xs)
        i = (len(xs) - 1) * q
        lo, hi = int(i), min(int(i) + 1, len(xs) - 1)
        return xs[lo] + (xs[hi] - xs[lo]) * (i - lo)

    metrics = list(all_runs[0]) if all_runs else []
    samples = {k: [x for r in all_runs for x in r[k]] for k in metrics}
    med = {k: quantile(samples[k], 0.5) for k in metrics}
    iqr = {k: quantile(samples[k], 0.75) - quantile(samples[k], 0.25)
           for k in metrics}
    # Schema note: "results"/"iqr"/"vs_baseline" keep their PERF_r0X.json
    # meaning (median ops/s per metric); "min"/"samples_per_metric" are
    # additive so older rounds still diff cleanly.
    out = {"metric": "core_microbench", "unit": "ops/s",
           "runs": args.runs,
           "samples_per_metric": args.runs * max(args.reps, 1),
           "results": {k: round(v, 1) for k, v in med.items()},
           "iqr": {k: round(v, 1) for k, v in iqr.items()},
           "min": {k: round(min(samples[k]), 1) for k in metrics},
           "vs_baseline": {k: round(med[k] / BASELINE[k], 3)
                           for k in metrics if k in BASELINE}}
    if args.ab_fastpath > 0:
        out["fastpath_ab"] = run_ab_fastpath(args.scale, args.ab_fastpath)
    if args.ab_serve > 0:
        out["serve_metrics_ab"] = run_ab_serve_metrics(args.scale,
                                                       args.ab_serve)
    if args.ab_specroute > 0:
        out["specroute_ab"] = run_ab_specroute(args.scale,
                                               args.ab_specroute)
    if args.ab_submit > 0:
        out["submit_batching_ab"] = run_ab_submit_batching(args.scale,
                                                           args.ab_submit)
    if args.ab_train_obs > 0:
        out["train_obs_ab"] = run_ab_train_obs(args.scale,
                                               args.ab_train_obs)
    if args.ab_elastic > 0:
        out["elastic_ab"] = run_ab_elastic(args.scale, args.ab_elastic)
    if args.ab_sched > 0:
        out["sched_obs_ab"] = run_ab_sched_obs(args.scale, args.ab_sched)
    if args.ab_autoscale > 0:
        out["autoscale_ab"] = run_ab_autoscale(args.scale,
                                               args.ab_autoscale)
    if args.ab_object > 0:
        out["object_obs_ab"] = run_ab_object_obs(args.scale,
                                                 args.ab_object)
    if args.ab_health > 0:
        out["health_ab"] = run_ab_health(args.scale, args.ab_health)
    if args.ab_zcput > 0:
        out["zcput_ab"] = run_ab_zcput(args.scale, args.ab_zcput)
    if args.ab_submitplane > 0:
        out["submitplane_ab"] = run_ab_submitplane(args.scale,
                                                   args.ab_submitplane)
    if args.ab_chipspeed > 0:
        out["chipspeed_ab"] = run_ab_chipspeed(args.scale,
                                               args.ab_chipspeed)
    if args.profile_submit:
        out["submit_profile"] = run_profile_submit(args.scale)
    if args.ab_cpshard > 0:
        out["cpshard_ab"] = run_ab_cpshard(args.scale, args.ab_cpshard)
    line = json.dumps(out)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
