"""Workflow event tests (reference: ``python/ray/workflow/tests/
test_events.py`` + ``http_event_provider.py``): a DAG blocks on an
external event, the payload flows into dependents, durability holds
across GCS restart, and the dashboard POST endpoint delivers."""

import socket
import time

import pytest

import ray_tpu
from ray_tpu import workflow


@pytest.mark.timeout(120)
def test_wait_for_event_blocks_then_flows(ray_start_regular):
    @workflow.step
    def combine(event_payload, tag):
        return {"got": event_payload, "tag": tag}

    dag = combine.bind(workflow.wait_for_event("approval-1"), "t1")
    _, fut = workflow.run_async(dag, workflow_id="wf-ev-1")

    # blocked: the event step polls, nothing completes
    time.sleep(1.5)
    assert workflow.get_status("wf-ev-1")["status"] == "RUNNING"

    workflow.send_event("approval-1", {"approved": True, "by": "alice"})
    out = fut.result(timeout=60)
    assert out == {"got": {"approved": True, "by": "alice"}, "tag": "t1"}
    assert workflow.get_status("wf-ev-1")["status"] == "SUCCEEDED"


@pytest.mark.timeout(120)
def test_event_already_sent_resolves_immediately(ray_start_regular):
    """An event POSTed before anyone waits is latched in the KV."""
    workflow.send_event("pre-sent", 42)

    @workflow.step
    def double(x):
        return 2 * x

    out = workflow.run(double.bind(workflow.wait_for_event("pre-sent")),
                       workflow_id="wf-ev-2")
    assert out == 84


@pytest.mark.timeout(120)
def test_custom_event_listener(ray_start_regular):
    """A user listener (reference EventListener subclass) plugs in."""
    class AfterDelay(workflow.EventListener):
        def poll_for_event(self, delay_s):
            time.sleep(delay_s)
            return "ding"

    @workflow.step
    def tail(x):
        return x + "!"

    out = workflow.run(tail.bind(workflow.wait_for_event(AfterDelay, 0.5)),
                       workflow_id="wf-ev-3")
    assert out == "ding!"


@pytest.mark.timeout(120)
def test_event_survives_gcs_restart(tmp_path):
    """The full VERDICT scenario: workflow blocks on an event, the GCS
    crashes and restarts from its snapshot, the event THEN posts, and the
    workflow completes — the poller rides through the outage."""
    from ray_tpu.core.gcs import GcsServer
    from ray_tpu.core.node_agent import NodeAgent
    from ray_tpu.core.rpc import run_async
    from ray_tpu.utils.testing import CPU_WORKER_ENV

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    snap = str(tmp_path / "gcs.snap")
    gcs = GcsServer(port=port, persistence_path=snap)
    run_async(gcs.start())
    agent = NodeAgent(gcs.address, num_cpus=4,
                      worker_env=dict(CPU_WORKER_ENV))
    run_async(agent.start())
    ray_tpu.init(address=gcs.address, worker_env=dict(CPU_WORKER_ENV))
    gcs2 = None
    try:
        @workflow.step
        def finish(payload):
            return f"released:{payload}"

        _, fut = workflow.run_async(
            finish.bind(workflow.wait_for_event("gate")),
            workflow_id="wf-ev-crash")
        time.sleep(2.0)  # the event step is polling now

        gcs._persist()
        run_async(gcs.stop())
        time.sleep(1.0)
        gcs2 = GcsServer(port=port, persistence_path=snap)
        run_async(gcs2.start())

        # wait until the control plane serves KV again, then deliver
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                workflow.send_event("gate", "go")
                break
            except Exception:
                time.sleep(0.5)
        assert fut.result(timeout=120) == "released:go"
        assert workflow.get_status("wf-ev-crash")["status"] == "SUCCEEDED"
    finally:
        ray_tpu.shutdown()
        for g in (gcs2, gcs):
            if g is not None:
                try:
                    run_async(g.stop(), timeout=10)
                except Exception:
                    pass
        try:
            run_async(agent.stop(), timeout=10)
        except Exception:
            pass


@pytest.mark.timeout(120)
def test_http_event_provider(ray_start_regular):
    """POST /api/workflow/events/{key} on the dashboard unblocks the
    workflow (the http_event_provider.py parity path)."""
    import requests

    from ray_tpu.dashboard import head, start_dashboard

    port = start_dashboard()
    try:
        @workflow.step
        def receive(payload):
            return payload

        _, fut = workflow.run_async(
            receive.bind(workflow.wait_for_event("webhook")),
            workflow_id="wf-ev-http")
        time.sleep(1.0)

        base = f"http://127.0.0.1:{port}"
        r = requests.get(f"{base}/api/workflow/events/webhook", timeout=15)
        assert r.json() == {"key": "webhook", "received": False}
        r = requests.post(f"{base}/api/workflow/events/webhook",
                          json={"order": 7}, timeout=15)
        assert r.json()["delivered"] is True
        assert fut.result(timeout=60) == {"order": 7}
        r = requests.get(f"{base}/api/workflow/events/webhook", timeout=15)
        assert r.json()["received"] is True
    finally:
        head.stop_dashboard()
