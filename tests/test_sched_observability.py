"""Scheduler explain plane + control-plane saturation observability.

A wedged workload — infeasible resource ask, backpressured node, draining
node, gate-parked burst — must be diagnosable end to end from
``raytpu explain`` / ``state.summarize_tasks()["pending_reasons"]``
output alone; and the saturation half (loop busy fractions, per-GCS-
handler busy seconds, backpressure counters) must appear when
``sched_metrics_enabled`` is on and add ZERO series when it is off.

Reference: the Ray paper's debuggability-as-first-class bet (1712.05889)
and Podracer's provably-cheap control plane (2104.06272).
"""

import argparse
import asyncio
import threading
import time

import pytest

import ray_tpu
from ray_tpu.core import sched_explain
from ray_tpu.core.config import Config, reset_config, set_config
from ray_tpu.core.rpc import RpcClient, RpcServer, run_async
from ray_tpu.core.sched_explain import PendingReason
from ray_tpu.core.scheduling import NodeView, pack_bundles, pick_node
from ray_tpu.util.metrics import snapshot_registry


def _wait(cond, timeout=30.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.05)
    assert cond(), f"timed out waiting for {msg}"


# ------------------------------------------------------------------ units

def _view():
    return {
        "alive": NodeView("alive", "h:1", {"CPU": 2}, {"CPU": 2}),
        "drainy": NodeView("drainy", "h:2", {"CPU": 2}, {"CPU": 2},
                           draining=True),
        "deady": NodeView("deady", "h:3", {"CPU": 2}, {"CPU": 2},
                          alive=False),
        "tiny": NodeView("tiny", "h:4", {"CPU": 0.5}, {"CPU": 0.5}),
    }


def test_pick_node_explain_rejection_causes():
    ex = {}
    nid = pick_node(_view(), {"CPU": 1}, explain=ex)
    assert nid == "alive" and ex["chosen"] == "alive"
    assert ex["candidates"] == 4
    assert ex["rejected"] == {"drainy": "draining", "deady": "dead",
                              "tiny": "resources"}

    # hard affinity to a draining node: an affinity miss, typed as such
    from ray_tpu.core.common import NodeAffinitySchedulingStrategy
    ex = {}
    nid = pick_node(_view(), {"CPU": 1},
                    NodeAffinitySchedulingStrategy("drainy", soft=False),
                    explain=ex)
    assert nid is None and ex["chosen"] is None
    assert ex["rejected"]["drainy"] == "draining"

    # the None-explain path still works (and pays nothing)
    assert pick_node(_view(), {"CPU": 1}) == "alive"


def test_pack_bundles_explain():
    ex = {}
    placement = pack_bundles(_view(), [{"CPU": 1}, {"CPU": 1}],
                             "STRICT_SPREAD", explain=ex)
    assert placement is None  # only one schedulable node can hold CPU:1
    assert ex["chosen"] is None and ex["bundles"] == 2
    assert ex["rejected"]["drainy"] == "draining"
    assert ex["rejected"]["tiny"] == "resources"


def test_reason_for_no_node_mapping():
    assert sched_explain.reason_for_no_node(
        {"rejected": {"a": "draining"}}) == PendingReason.NODE_DRAINING
    assert sched_explain.reason_for_no_node(
        {"rejected": {"a": "draining", "b": "dead"}}) \
        == PendingReason.NODE_DRAINING
    # a draining cause marks an OTHERWISE-FEASIBLE host (infeasible nodes
    # read "resources" whatever their drain state), so it wins
    assert sched_explain.reason_for_no_node(
        {"rejected": {"a": "resources", "b": "draining"}}) \
        == PendingReason.NODE_DRAINING
    assert sched_explain.reason_for_no_node(
        {"rejected": {"a": "resources"}}) == PendingReason.NO_RESOURCES
    assert sched_explain.reason_for_no_node(
        {"rejected": {}}) == PendingReason.NO_RESOURCES
    assert sched_explain.reason_for_no_node(None) \
        == PendingReason.NO_RESOURCES


def test_decision_ring_bounds_and_age_out():
    """The GCS decision ring is bounded by count AND age."""
    from ray_tpu.core.gcs import GcsServer
    try:
        set_config(Config(sched_decision_ring_len=100,
                          sched_decision_max_age_s=60.0))
        gcs = GcsServer()

        async def drive():
            await gcs.handle_add_sched_decisions(
                [{"ts": time.time(), "kind": "task", "id": f"t{i}",
                  "outcome": "no_node"} for i in range(500)])
            assert len(gcs.sched_decisions) == 100  # count-bounded
            # age-out: a stale cohort is dropped on the next touch
            gcs.sched_decisions.clear()
            old = time.time() - 3600
            await gcs.handle_add_sched_decisions(
                [{"ts": old, "kind": "task", "id": "stale",
                  "outcome": "no_node"}])
            fresh = [{"ts": time.time(), "kind": "task", "id": "fresh",
                      "outcome": "no_node"}]
            await gcs.handle_add_sched_decisions(fresh)
            got = await gcs.handle_get_sched_decisions(limit=100)
            assert [r["id"] for r in got] == ["fresh"]
            # id filtering
            got = await gcs.handle_get_sched_decisions(id="fresh")
            assert len(got) == 1
            got = await gcs.handle_get_sched_decisions(id="absent")
            assert got == []

        asyncio.run(drive())
    finally:
        reset_config()


def test_loop_busy_fraction_sampling():
    """The loop monitor's busy fraction separates a spinning loop from an
    idle one (the thread-CPU clock sampled from inside the loop)."""
    from ray_tpu.util.loop_monitor import LoopMonitor

    loop = asyncio.new_event_loop()
    t = threading.Thread(target=loop.run_forever, daemon=True)
    t.start()
    try:
        mon = LoopMonitor(loop, source="", busy_enabled=True,
                          interval_s=0.05)
        mon.start()
        time.sleep(0.8)
        idle = mon.busy_fraction
        assert idle < 0.5  # parked in epoll

        def spin():
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < 0.03:
                pass
            loop.call_soon(spin)

        loop.call_soon_threadsafe(spin)
        # the fraction is the loop thread's CPU time over wall time, so
        # other processes on the same cores (the suite runs six workers)
        # take from it: wait for one window that reads a busy loop, and at
        # the deadline ask only that spinning reads far above idle
        want = max(0.3, idle)     # the idle reading stands for a window yet
        busy, deadline = 0.0, time.monotonic() + 20.0
        while busy <= want and time.monotonic() < deadline:
            time.sleep(0.05)
            busy = max(busy, mon.busy_fraction)
        assert busy > want or busy > 10 * max(idle, 0.005), (idle, busy)
        mon.stop()
    finally:
        loop.call_soon_threadsafe(loop.stop)
        t.join(timeout=5)
        loop.close()


def test_rpc_busy_attribution_excludes_awaits():
    """_BusyTimed attribution: a handler that PARKS attributes ~nothing;
    a handler that computes attributes its synchronous time — the
    distinction raytpu_rpc_server_seconds (wall) cannot make."""

    class H:
        async def handle_park(self):
            await asyncio.sleep(0.5)
            return "parked"

        async def handle_spin(self):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < 0.2:
                pass
            return "spun"

    busy = {}
    server = RpcServer(H())
    server.busy_cb = lambda m, s: busy.__setitem__(
        m, busy.get(m, 0.0) + s)
    run_async(server.start())
    client = RpcClient(server.address)
    try:
        assert run_async(client.call("park")) == "parked"
        assert run_async(client.call("spin")) == "spun"
        assert busy["spin"] >= 0.15, busy
        assert busy["park"] < 0.1, busy
    finally:
        run_async(client.close(), timeout=5)
        run_async(server.stop(), timeout=5)


# ------------------------------------------------- cluster: reason stamps

def _task_events(name=None, state=None, reason=None):
    from ray_tpu.util import state as state_api
    evs = state_api.list_tasks(limit=10000)
    out = []
    for e in evs:
        if name is not None and e.get("name") != name:
            continue
        if state is not None and e.get("state") != state:
            continue
        if reason is not None and e.get("reason") != reason:
            continue
        out.append(e)
    return out


@pytest.mark.timeout(120)
def test_infeasible_task_no_resources_end_to_end():
    """An infeasible ask is diagnosable from explain output ALONE: typed
    reason, per-node rejection cause, and the decision trail."""
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote(resources={"GPU": 1})
        def never():
            return 0

        ref = never.remote()
        from ray_tpu.util import state as state_api
        _wait(lambda: _task_events("never", "PENDING",
                                   PendingReason.NO_RESOURCES),
              30, "NO_RESOURCES stamp to flush")
        tid = _task_events("never")[0]["task_id"]
        report = state_api.explain(tid)
        assert report["kind"] == "task"
        assert report["pending_reason"] == PendingReason.NO_RESOURCES
        assert report["state"] == "PENDING"
        decisions = report["decisions"]
        assert decisions, "no decision records for the stuck task"
        rec = decisions[-1]
        assert rec["outcome"] == "no_node"
        assert "resources" in set(rec["rejected"].values())
        assert rec["label"] == "never"
        # rollup matches reality: exactly one task pending, on resources
        summary = state_api.summarize_tasks()
        assert summary["pending_reasons"].get(
            PendingReason.NO_RESOURCES) == 1
        del ref
    finally:
        ray_tpu.shutdown()


@pytest.mark.timeout(120)
def test_admission_gate_reason_stamped():
    """A gate-parked burst stamps ADMISSION_GATE on the parked
    submission (and everything still completes)."""
    ray_tpu.init(num_cpus=1,
                 _system_config={"submit_inflight_limit": 2})
    try:
        @ray_tpu.remote
        def slow():
            time.sleep(0.5)
            return 1

        # 2 in flight fill the window; the 3rd .remote() parks on the
        # gate (driver thread) until a completion drains it
        refs = [slow.remote() for _ in range(3)]
        assert sum(ray_tpu.get(refs, timeout=60)) == 3
        from ray_tpu.core.core_worker import global_worker
        assert global_worker().admission_gate.blocked_total >= 1
        _wait(lambda: _task_events("slow", "PENDING",
                                   PendingReason.ADMISSION_GATE),
              20, "ADMISSION_GATE stamp to flush")
    finally:
        ray_tpu.shutdown()


@pytest.mark.timeout(180)
def test_backpressured_lease_queue_reason_and_counters():
    """lease_queue_max_depth=1: a second pool's lease request is answered
    with backpressure while the first pool's spare request holds the
    queue slot — the typed reason lands on the task, the reject counter
    on the node, and everything still completes."""
    ray_tpu.init(num_cpus=1,
                 _system_config={"lease_queue_max_depth": 1})
    try:
        @ray_tpu.remote
        def hog():
            time.sleep(0.9)
            return 1

        @ray_tpu.remote
        def beta():
            return 2

        # 3 hogs on 1 CPU: one runs, the pool's lease request for the
        # queued rest PARKS at the agent (depth 1 = full)
        hogs = [hog.remote() for _ in range(3)]
        time.sleep(0.8)
        b = beta.remote()         # second pool -> backpressure reply
        assert sum(ray_tpu.get(hogs, timeout=60)) == 3
        assert ray_tpu.get(b, timeout=60) == 2
        _wait(lambda: _task_events("beta", "PENDING",
                                   PendingReason.BACKPRESSURED),
              20, "BACKPRESSURED stamp to flush")
        # agent-side reject accounting (always-on ints + metric mirror)
        from ray_tpu.core.api import _state
        agent = _state.node_agent
        assert agent._bp_rejects.get("depth", 0) >= 1
        snap = snapshot_registry()
        bp = snap.get("raytpu_sched_backpressure_total")
        assert bp is not None and any(
            dict(k).get("reason") == "depth" for k in bp["values"])
        # decision trail names the backpressure outcome
        from ray_tpu.util import state as state_api
        recs = state_api.sched_decisions(limit=200)
        assert any(r.get("outcome") == "backpressure" for r in recs)
    finally:
        ray_tpu.shutdown()


@pytest.mark.slow
@pytest.mark.timeout(180)
def test_draining_node_reason_via_preemption(ray_start_cluster):
    """The only node that could host the shape receives a preemption
    notice (the preempt/drain plane): tasks against it stamp
    NODE_DRAINING with the per-node cause in the decision record, and
    run after the drain is lifted... which cannot happen for a REAL
    preemption — so here the shape is re-homed by adding a fresh node
    carrying the resource, exactly the operator runbook."""
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=2)
    special = cluster.add_node(num_cpus=2, resources={"special": 1})
    assert cluster.wait_for_nodes(2)
    cluster.connect_driver()

    # a lease must be outstanding on the node or the graceful drain
    # completes instantly and deregisters (nothing to wait for)
    @ray_tpu.remote(resources={"special": 0.5})
    def occupy():
        time.sleep(12.0)
        return 7

    pin = occupy.remote()
    from ray_tpu.util import state as state_api
    _wait(lambda: _task_events("occupy", "RUNNING"), 40,
          "occupy to start on the special node")

    # deliver a long preemption notice to the special node
    client = RpcClient(special.address)
    try:
        assert run_async(client.call("drain_self", notice_s=120.0))
    finally:
        run_async(client.close(), timeout=5)

    from ray_tpu.core.core_worker import global_worker
    w = global_worker()

    def _draining_visible():
        view = run_async(w.gcs.call("get_cluster_view"))
        return any(v.get("draining") for v in view.values())

    _wait(_draining_visible, 30, "draining flag to reach the GCS view")

    @ray_tpu.remote(resources={"special": 1})
    def needs_special():
        return 42

    ref = needs_special.remote()
    _wait(lambda: _task_events("needs_special", "PENDING",
                               PendingReason.NODE_DRAINING),
          40, "NODE_DRAINING stamp to flush")
    tid = _task_events("needs_special")[0]["task_id"]
    report = state_api.explain(tid)
    assert report["pending_reason"] == PendingReason.NODE_DRAINING
    assert "draining" in set(
        (report["decisions"][-1].get("rejected") or {}).values())
    # the runbook's fix: bring up replacement capacity
    cluster.add_node(num_cpus=2, resources={"special": 1})
    assert ray_tpu.get(ref, timeout=90) == 42
    assert ray_tpu.get(pin, timeout=90) == 7


@pytest.mark.timeout(120)
def test_waiting_deps_actor_call_reason():
    """A call parked behind a slow actor __init__ stamps WAITING_DEPS —
    the dependency is the actor itself."""
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote
        class Slow:
            def __init__(self):
                time.sleep(1.2)

            def ping(self):
                return "up"

        a = Slow.remote()
        r = a.ping.remote()
        assert ray_tpu.get(r, timeout=60) == "up"
        _wait(lambda: _task_events(state="PENDING",
                                   reason=PendingReason.WAITING_DEPS),
              20, "WAITING_DEPS stamp to flush")
    finally:
        ray_tpu.shutdown()


@pytest.mark.timeout(120)
def test_explain_cli_on_stuck_task(capsys):
    """`raytpu explain <id>` prints the whole trail: state, typed
    reason, transition timeline and the rejection causes."""
    from ray_tpu.scripts import cli

    ray_tpu.init(num_cpus=1)
    try:
        @ray_tpu.remote(resources={"accelerator": 4})
        def wedged():
            return 0

        ref = wedged.remote()
        _wait(lambda: _task_events("wedged", "PENDING"),
              30, "pending stamp to flush")
        tid = _task_events("wedged")[0]["task_id"]
        cli.cmd_explain(argparse.Namespace(id=tid, json=False))
        out = capsys.readouterr().out
        assert "NO_RESOURCES" in out
        assert "PENDING" in out and "wedged" in out
        assert "no_node" in out and "resources" in out
        # and the PG path: an infeasible placement group explains itself
        pg = ray_tpu.placement_group([{"CPU": 64}])
        assert not pg.ready(timeout=2)
        cli.cmd_explain(argparse.Namespace(id=pg.id, json=False))
        out = capsys.readouterr().out
        assert "pg" in out and "NO_RESOURCES" in out
        ray_tpu.remove_placement_group(pg)
        del ref
    finally:
        ray_tpu.shutdown()


# ------------------------------------------------------- kill switch / A/B

def _series_fingerprint():
    """Count of values per raytpu_sched_/raytpu_loop_busy/raytpu_gcs_
    series — the registry is process-global, so the kill-switch test
    asserts NO NEW values appear, not that none ever existed."""
    snap = snapshot_registry()
    out = {}
    for name, s in snap.items():
        if name.startswith(("raytpu_sched_", "raytpu_loop_busy",
                            "raytpu_gcs_")):
            vals = s.get("values") or s.get("count") or {}
            out[name] = (len(vals), sum(vals.values()))
    return out


@pytest.mark.timeout(120)
def test_sched_metrics_kill_switch_zero_new_series():
    """sched_metrics_enabled=False ⇒ zero new raytpu_sched_*/
    raytpu_loop_busy*/raytpu_gcs_* samples, while the EXPLAIN half
    (reason stamps, decision records) still answers."""
    before = _series_fingerprint()
    ray_tpu.init(num_cpus=2,
                 _system_config={"sched_metrics_enabled": False,
                                 "lease_queue_max_depth": 1})
    try:
        @ray_tpu.remote
        def f():
            return 1

        assert sum(ray_tpu.get([f.remote() for _ in range(20)],
                               timeout=60)) == 20

        @ray_tpu.remote(resources={"GPU": 1})
        def g():
            return 2

        ref = g.remote()
        _wait(lambda: _task_events("g", "PENDING",
                                   PendingReason.NO_RESOURCES),
              30, "explain half still stamping")
        from ray_tpu.util import state as state_api
        stats = state_api.sched_stats()
        assert stats["sched_metrics_enabled"] is False
        assert not stats["handler_busy_s"]  # busy attribution off
        assert state_api.explain(
            _task_events("g")[0]["task_id"])["decisions"]
        # give monitors/flushers a tick, then compare
        time.sleep(1.0)
        assert _series_fingerprint() == before
        del ref
    finally:
        ray_tpu.shutdown()


@pytest.mark.timeout(180)
def test_two_node_pending_reason_rollup_matches_reality(ray_start_cluster):
    """2-node acceptance: summarize_tasks()["pending_reasons"] counts
    exactly the wedged tasks under their typed reason while runnable work
    keeps flowing, and the saturation stats answer cluster-wide."""
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=2)
    cluster.add_node(num_cpus=2)
    assert cluster.wait_for_nodes(2)
    cluster.connect_driver()

    @ray_tpu.remote
    def ok():
        return 1

    assert sum(ray_tpu.get([ok.remote() for _ in range(8)],
                           timeout=60)) == 8

    @ray_tpu.remote(resources={"GPU": 1})
    def wedged():
        return 0

    refs = [wedged.remote() for _ in range(3)]
    from ray_tpu.util import state as state_api

    def rollup_settled():
        pr = state_api.summarize_tasks()["pending_reasons"]
        return pr.get(PendingReason.NO_RESOURCES) == 3
    _wait(rollup_settled, 40, "rollup to count 3 NO_RESOURCES tasks")
    pr = state_api.summarize_tasks()["pending_reasons"]
    # nothing else is pending: the 8 ok() tasks all FINISHED
    assert pr.get(PendingReason.NO_RESOURCES) == 3
    assert sum(pr.values()) == 3, pr
    # saturation half: the GCS names its busiest handlers + loop fraction
    stats = state_api.sched_stats()
    assert stats["loop_busy_fraction"] is not None
    assert stats["top_handlers"], "no handler busy attribution"
    busiest = dict(stats["handler_busy_s"])
    assert busiest.get("heartbeat", 0) > 0  # 2 nodes heartbeating
    del refs
