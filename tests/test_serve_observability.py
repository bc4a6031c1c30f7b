"""Serve-plane observability tests (ISSUE 6): request-scoped tracing
renders one connected proxy→router→replica→batch_wait→prefill→decode
chain, ``raytpu_serve_*`` metrics reach /metrics with bounded label sets,
the kill switch sheds every serve series, and the rolling SLO window
updates + ages out and surfaces through serve.status()/slo_signal()/
``/api/serve``.
"""

import asyncio
import importlib.util
import pathlib
import time

import pytest

import ray_tpu
from ray_tpu import serve

# ---------------------------------------------------------------- units


def test_slo_window_updates_and_ages_out():
    from ray_tpu.serve.observability import SLOWindow

    w = SLOWindow(window_s=10.0)
    for i, v in enumerate([0.1, 0.2, 0.3, 0.4]):
        w.observe(v, now=100.0 + i)
    s = w.summary(now=104.0)
    assert s["window_n"] == 4
    assert s["p50"] == 0.2
    assert s["p99"] == 0.4
    # newer, slower samples move the percentiles
    w.observe(1.0, now=105.0)
    assert w.summary(now=105.0)["p99"] == 1.0
    # age-out: only the last sample survives past the horizon ...
    s = w.summary(now=114.5)
    assert s["window_n"] == 1 and s["p50"] == 1.0
    # ... and an idle window empties completely
    assert w.summary(now=200.0) == {"window_n": 0}


class _FakeWorker:
    class _Id:
        @staticmethod
        def hex():
            return "f" * 24

    def __init__(self):
        self._task_events = []
        self.worker_id = self._Id()
        self.job_id = None


def test_span_buffers_without_worker_and_flushes():
    """Satellite: span() before init (no global worker) must buffer, not
    drop — the record lands in the event stream once a worker exists."""
    from ray_tpu.core import core_worker as cw
    from ray_tpu.util import tracing

    prev = cw.global_worker_or_none()
    cw.set_global_worker(None)
    try:
        tracing._pending.clear()
        with tracing.span("orphan_stage", who="pre-init"):
            pass
        assert [e["name"] for e in tracing._pending] == ["orphan_stage"]
        fw = _FakeWorker()
        cw.set_global_worker(fw)
        assert tracing.flush_pending_spans() == 1
        assert [e["name"] for e in fw._task_events] == ["orphan_stage"]
        # buffered records also drain implicitly on the NEXT span recorded
        # with a worker present, preserving ts order
        cw.set_global_worker(None)
        with tracing.span("orphan_2"):
            pass
        cw.set_global_worker(fw)
        with tracing.span("live"):
            pass
        assert [e["name"] for e in fw._task_events] == [
            "orphan_stage", "orphan_2", "live"]
        assert all(e["state"] == "SPAN" for e in fw._task_events)
    finally:
        cw.set_global_worker(prev)
        tracing._pending.clear()


def test_replica_installs_loop_monitor():
    """Satellite: serve replica processes run the event-loop stall
    detector on their ACTOR loop, tagged process=serve_replica:<dep>."""
    import cloudpickle

    from ray_tpu.core.config import Config, reset_config, set_config
    from ray_tpu.serve.replica import ReplicaActor
    from ray_tpu.util.loop_monitor import LoopMonitor

    try:
        set_config(Config(loop_monitor_enabled=True))
        blob = cloudpickle.dumps((lambda x: x, (), {}))
        rep = ReplicaActor("lmdep", "serve:lmdep:1", blob)

        async def drive():
            return await rep.handle_request((41,), {}, None)

        assert asyncio.run(drive()) == 41
        mon = rep._serve_loop_monitor
        assert isinstance(mon, LoopMonitor)
        assert mon.source == "serve_replica:lmdep"
        mon.stop()
    finally:
        reset_config()


def test_serve_metrics_kill_switch():
    """serve_metrics_enabled=False ⇒ zero serve series recorded, SLO
    snapshot degrades to queue depth only; flipping it back on records."""
    from ray_tpu.core.config import Config, reset_config, set_config
    from ray_tpu.serve import observability as obs
    from ray_tpu.util.metrics import get_metric

    key = (("deployment", "ksdep"), ("route", "/ks"), ("status", "200"))
    try:
        set_config(Config(serve_metrics_enabled=False))
        obs.record_request("ksdep", "/ks", "200", 0.01)
        obs.observe_ttft("ksdep", 0.005)
        obs.add_tokens("ksdep", "out", 3)
        obs.set_replica_queue_depth("ksdep", 7)
        m = get_metric("raytpu_serve_requests_total")
        assert m is None or key not in m.snapshot()["values"]
        t = get_metric("raytpu_serve_tokens_total")
        tkey = (("deployment", "ksdep"), ("direction", "out"))
        assert t is None or tkey not in t.snapshot()["values"]
        # the shed TTFT above must not have fed the window either
        assert obs.slo_snapshot("ksdep", queue_depth=2) == {"queue_depth": 2}

        set_config(Config(serve_metrics_enabled=True))
        obs.record_request("ksdep", "/ks", "200", 0.01)
        assert get_metric(
            "raytpu_serve_requests_total").snapshot()["values"][key] == 1
        obs.observe_ttft("ksdep", 0.005)
        snap = obs.slo_snapshot("ksdep", queue_depth=0)
        assert snap["window_n"] == 1 and snap["ttft_p95_ms"] == 5.0
    finally:
        reset_config()


def _load_bench_llm():
    path = pathlib.Path(__file__).resolve().parent.parent / "bench_llm.py"
    spec = importlib.util.spec_from_file_location("bench_llm", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_llm_breakdown_schema():
    """Satellite: the per-request breakdown bench_llm records is schema-
    guarded so the next chip window captures the full serving picture."""
    mod = _load_bench_llm()
    samples = [(0.1, 0.5, 5), (0.2, 0.6, 5), (0.05, 0.05, 1)]
    out = mod.request_rollup(samples, wall_s=2.0)
    assert set(out) == set(mod.REQUEST_KEYS)
    assert out["n_requests"] == 3
    assert out["req_per_s"] == 1.5
    assert out["decode_tok_per_s"] == 5.5
    # tpot only from multi-token requests: (0.5-0.1)/4 = (0.6-0.2)/4 = 0.1s
    assert out["p50_tpot_ms"] == 100.0
    assert out["p95_tpot_ms"] == 100.0
    assert out["p50_ttft_ms"] == 100.0
    with pytest.raises(ValueError):
        mod.request_rollup([], 1.0)


# ----------------------------------------------------------- integration

@pytest.fixture(scope="module")
def llm_http():
    """One cluster + one HTTP-fronted tiny-LLM deployment shared by the
    integration tests below."""
    from ray_tpu.serve.llm import llm_deployment
    from ray_tpu.utils.testing import CPU_WORKER_ENV

    ray_tpu.init(num_cpus=8, worker_env=dict(CPU_WORKER_ENV))
    dep = llm_deployment("tiny", num_slots=4, max_len=64,
                         route_prefix="/llm")
    h = serve.run(dep, timeout_s=180, http=True)
    cfg = serve.http_config()
    try:
        yield h, f"http://{cfg['host']}:{cfg['port']}"
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def _post_stream(base, path="/llm", tokens=(1, 2, 3), max_tokens=4):
    import requests
    r = requests.post(f"{base}{path}",
                      json={"tokens": list(tokens),
                            "max_tokens": max_tokens},
                      timeout=120, stream=True)
    body = b"".join(r.iter_content(None))
    assert r.status_code == 200, body[:500]
    return body


def _span_index(evs):
    spans = {}
    for e in evs:
        if e.get("state") == "SPAN" and e.get("span_id"):
            spans.setdefault(e.get("name"), []).append(e)
    return spans


def _find_chain(evs):
    """proxy_recv -> router_queue -> replica task -> ingress -> batch_wait
    -> prefill -> decode, linked by (trace_id, parent_id)."""
    spans = _span_index(evs)

    def child(name, trace_id, parent_span):
        for e in spans.get(name, []):
            if (e.get("trace_id") == trace_id
                    and e.get("parent_id") == parent_span):
                return e
        return None

    for proxy in spans.get("proxy_recv", []):
        tid = proxy["trace_id"]
        router = child("router_queue", tid, proxy["span_id"])
        if router is None:
            continue
        replica = next(
            (e for e in evs
             if e.get("state") in ("RUNNING", "FINISHED")
             and e.get("trace_id") == tid
             and e.get("parent_id") == router["span_id"]
             and "handle_request" in (e.get("name") or "")), None)
        if replica is None:
            continue
        ingress = child("ingress", tid, replica.get("span_id"))
        if ingress is None:
            continue
        batch = child("batch_wait", tid, ingress["span_id"])
        if batch is None:
            continue
        prefill = child("prefill", tid, batch["span_id"])
        if prefill is None:
            continue
        decode = child("decode", tid, prefill["span_id"])
        if decode is None:
            continue
        return [proxy, router, replica, ingress, batch, prefill, decode]
    return None


def test_traced_request_renders_one_connected_chain(llm_http):
    """Acceptance: ONE traced HTTP request = ONE connected cross-process
    trace with proxy → router → replica → ingress → batch_wait → prefill →
    decode, and chrome_trace() renders every link as a slice with flow arrows."""
    from ray_tpu.util.tracing import chrome_trace

    _h, base = llm_http
    _post_stream(base)
    deadline = time.monotonic() + 45
    chain, evs = None, []
    while time.monotonic() < deadline and chain is None:
        evs = ray_tpu.timeline()
        chain = _find_chain(evs)
        if chain is None:
            time.sleep(0.5)
    assert chain is not None, (
        f"no connected chain in {len(evs)} events; spans seen: "
        f"{sorted(_span_index(evs))}")
    proxy, router, replica, ingress, batch, prefill, decode = chain
    # the proxy's stamp rode the call: the way in is known from its send
    assert ingress["attributes"]["transit_s"] >= 0
    assert ingress["attributes"]["queue_s"] >= 0
    # the whole chain shares ONE trace id
    assert len({e.get("trace_id") for e in chain}) == 1
    # stage spans carry the deployment tag from config
    assert batch["attributes"]["deployment"] == "llm-tiny"
    # chrome_trace: every chain member renders as a complete slice, and
    # each parent link yields a flow start ("s") + finish ("f") pair so
    # Perfetto draws the arrows across process rows
    trace = chrome_trace(evs)
    slice_names = {t.get("name") for t in trace if t.get("ph") == "X"}
    for name in ("proxy_recv", "router_queue", "ingress", "batch_wait",
                 "prefill", "decode"):
        assert name in slice_names, f"no slice for {name}"
    flow_ids = {t.get("id") for t in trace if t.get("ph") == "s"}
    fin_ids = {t.get("id") for t in trace if t.get("ph") == "f"}
    for e in (router, ingress, batch, prefill, decode):
        assert e["parent_id"] in flow_ids, f"no flow start for {e['name']}"
        assert e["parent_id"] in fin_ids, f"no flow finish into {e['name']}"


def test_metrics_endpoint_serves_bounded_serve_series(llm_http):
    """/metrics grows raytpu_serve_* series; the route label stays the
    config route prefix even when raw request paths differ."""
    import requests

    _h, base = llm_http
    # two DIFFERENT raw paths under one route prefix -> one route label
    _post_stream(base, path="/llm")
    _post_stream(base, path="/llm/subpath/extra")
    port = next(n["Labels"].get("metrics_port") for n in ray_tpu.nodes()
                if n["Labels"].get("metrics_port"))
    # wait for BOTH the proxy's and the replica's registries to flush
    # their llm-tiny series to the agent (2 s flush cadence per process)
    want = ("raytpu_serve_requests_total", "raytpu_serve_ttft_seconds",
            "raytpu_serve_router_queue_depth",
            "raytpu_serve_engine_active_slots", "raytpu_serve_tokens_total")
    deadline = time.monotonic() + 30
    body, req_lines = "", []
    while time.monotonic() < deadline:
        body = requests.get(f"http://127.0.0.1:{port}/metrics",
                            timeout=10).text
        req_lines = [ln for ln in body.splitlines()
                     if ln.startswith("raytpu_serve_requests_total")]
        if (any('deployment="llm-tiny"' in ln and 'route="/llm"' in ln
                for ln in req_lines)
                and all(w in body for w in want)):
            break
        time.sleep(0.5)
    for w in want:
        assert w in body, f"{w} missing from /metrics:\n{body[:3000]}"
    assert any('deployment="llm-tiny"' in ln and 'route="/llm"' in ln
               for ln in req_lines), req_lines
    # cardinality bound: the raw subpath must never appear as a label
    assert not any("subpath" in ln for ln in req_lines), req_lines


def test_slo_signal_surface(llm_http):
    """Acceptance: serve.status() / slo_signal() / raytpu serve status /
    /api/serve all report per-deployment rolling TTFT + queue depth."""
    import requests

    h, base = llm_http
    _post_stream(base)
    deadline = time.monotonic() + 45
    slo = {}
    while time.monotonic() < deadline:
        slo = serve.status()["llm-tiny"].get("slo") or {}
        if slo.get("window_n", 0) > 0 and "ttft_p95_ms" in slo:
            break
        time.sleep(0.5)
    assert slo.get("window_n", 0) > 0, f"no SLO heartbeat landed: {slo}"
    assert slo["ttft_p95_ms"] > 0
    assert "queue_depth" in slo

    # the autoscaler input contract
    sig = serve.slo_signal()["llm-tiny"]
    assert {"queue_depth", "running_replicas", "target_replicas",
            "ts", "window_n"} <= set(sig)
    assert sig["ttft_p95_ms"] > 0

    # the CLI table renders from the same status dict
    from ray_tpu.scripts.cli import _print_serve_status
    _print_serve_status(serve.status())

    # dashboard REST: /api/serve embeds the rollup, /api/serve/signal
    # serves the contract shape
    from ray_tpu.dashboard import start_dashboard, stop_dashboard
    port = start_dashboard(port=0)
    try:
        api = requests.get(f"http://127.0.0.1:{port}/api/serve",
                           timeout=30).json()
        assert api["llm-tiny"]["slo"]["window_n"] > 0
        sig2 = requests.get(f"http://127.0.0.1:{port}/api/serve/signal",
                            timeout=30).json()
        assert sig2["llm-tiny"]["queue_depth"] >= 0
    finally:
        stop_dashboard()

    # engine-side breakdown reaches the handle path with the bench schema
    stats = h.stats.remote().result(timeout_s=60)
    assert _load_bench_llm().ENGINE_KEYS <= set(stats), stats
    assert stats["admit_batches"] > 0
