"""Preemption-survivable durability plane: seeded node preemption
(``preempt_node`` chaos kind), graceful drain (notice -> spill ->
deregister), external-tier restore through surviving nodes, and workflow
resume across driver loss.

Reference: the Ray paper's lineage+spill bet and Podracer's
disposable-accelerator-node model — a node vanishing with state attached
must not lose objects (external spill tier), scheduling (drain +
backpressure), or workflow progress (GCS KV checkpoints)."""

import hashlib
import json
import os
import signal
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu.core import chaos, external_spill
from ray_tpu.core.config import Config, reset_config, set_config
from ray_tpu.core.ids import ObjectID
from ray_tpu.core.rpc import RpcServer, run_async


def _wait(cond, timeout=30.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.05)
    assert cond(), f"timed out waiting for {msg}"


# --------------------------------------------------------------- unit: drain

class _FakeOwner:
    """Owner-side location registry double (records add/remove calls)."""

    def __init__(self):
        self.added = []
        self.removed = []

    async def handle_add_object_location(self, object_id, node_id, address):
        self.added.append((object_id, node_id, address))
        return True

    async def handle_remove_object_location(self, object_id, node_id,
                                            address):
        self.removed.append((object_id, node_id, address))
        return True

    async def handle_ping(self):
        return "pong"


@pytest.fixture
def drain_cluster(tmp_path):
    """In-process GCS + two agents + a fake owner, external file:// tier."""
    from ray_tpu.core.gcs import GcsServer
    from ray_tpu.core.node_agent import NodeAgent
    base_uri = f"file://{tmp_path}/ext"
    set_config(Config(object_store_use_native_pool=False,
                      metrics_export_enabled=False,
                      object_spilling_external_uri=base_uri))
    chaos.install(None)
    gcs = GcsServer()
    run_async(gcs.start())
    a = NodeAgent(gcs.address, num_cpus=1,
                  session_dir=str(tmp_path / "sess-a"))
    b = NodeAgent(gcs.address, num_cpus=1,
                  session_dir=str(tmp_path / "sess-b"))
    run_async(a.start())
    run_async(b.start())
    owner = _FakeOwner()
    owner_server = RpcServer(owner).start_sync()
    yield gcs, a, b, owner, owner_server.address, base_uri
    for agent in (a, b):
        try:
            run_async(agent.stop(), timeout=10)
        except Exception:
            pass
    try:
        owner_server.stop_sync()
    except Exception:
        pass
    run_async(gcs.stop(), timeout=5)
    chaos.install(None)
    chaos.reset()
    reset_config()


@pytest.mark.chaos
def test_graceful_drain_rehomes_objects_and_deregisters(drain_cluster):
    """notice_s > 0: the draining node spills its sole-copy object to the
    external tier, registers the URI with the owner, deregisters from the
    GCS — and a node that never held the object restores it."""
    gcs, a, b, owner, owner_addr, base_uri = drain_cluster
    oid = ObjectID.from_random()
    data = os.urandom(400 * 1024)
    a.store.create_and_write(oid, data, owner=owner_addr)

    run_async(a.handle_drain_self(notice_s=10.0))
    _wait(lambda: a._shutting_down, 30, "drain to finish")
    # deregistered: the GCS marked the node dead via drain_node, not the
    # slow heartbeat-timeout path
    _wait(lambda: not gcs.nodes[a.node_id.hex()].alive, 10,
          "GCS to mark the drained node dead")
    # the owner learned the external location
    ext = [(o, n, addr) for (o, n, addr) in owner.added
           if n == external_spill.EXTERNAL_NODE_ID]
    assert ext and ext[0][0] == oid
    uri = ext[0][2]
    assert uri == external_spill.object_uri(base_uri, oid)
    assert external_spill.read(uri) == data
    # ANY node's pull path restores from the non-node location
    res = run_async(b.handle_fetch_object(
        oid, len(data), locations=[(a.node_id.hex(), a.address),
                                   (external_spill.EXTERNAL_NODE_ID, uri)]),
        timeout=60)
    assert res["size"] == len(data)
    assert b.store.read_chunk(oid, 0, len(data)) == data


@pytest.mark.chaos
def test_draining_agent_rejects_lease_requests(drain_cluster):
    _gcs, a, _b, _owner, _oa, _uri = drain_cluster
    a._draining = True
    res = run_async(a.handle_request_worker_lease(resources={"CPU": 1}))
    assert res.get("backpressure")
    res = run_async(a.handle_request_worker_leases(
        count=4, resources={"CPU": 1}))
    assert res.get("backpressure")


@pytest.mark.chaos
def test_hard_preempt_notice_zero_stops_immediately(drain_cluster):
    """notice_s = 0 is the no-warning path: no drain, no deregistration
    RPC — the agent just dies (the GCS health check finds out later)."""
    _gcs, a, _b, owner, owner_addr, _uri = drain_cluster
    oid = ObjectID.from_random()
    a.store.create_and_write(oid, os.urandom(64 * 1024), owner=owner_addr)
    run_async(a.handle_drain_self(notice_s=0.0))
    _wait(lambda: a._shutting_down, 20, "hard preempt to stop the agent")
    # ungraceful: nothing was re-homed (that is the point of the variant)
    assert not any(n == external_spill.EXTERNAL_NODE_ID
                   for (_o, n, _a) in owner.added)


@pytest.mark.chaos
def test_chaos_preempt_node_kind_arms_the_drain(drain_cluster):
    """A seeded {"kind": "preempt_node"} kills entry delivered through the
    runtime chaos path preempts the matching agent (and only it)."""
    gcs, a, b, _owner, _oa, _uri = drain_cluster
    spec = {"seed": 5, "kills": [
        {"kind": "preempt_node", "after_s": 0.05, "notice_s": 5.0,
         "node": a.node_id.hex()[:8]}]}
    # through the production path: chaos_set at the GCS, agents converge
    # via the heartbeat piggyback
    run_async(gcs.handle_chaos_set(spec))
    _wait(lambda: a._shutting_down, 30, "preempt_node to fire on A")
    inj = chaos.injector()
    assert inj is not None and inj.injected_counts().get("preempt_node")
    time.sleep(0.3)
    assert not b._shutting_down and b._preempt_task is None
    # same spec -> same schedule: the kills list is part of the seeded
    # spec, so a fresh injector replays the identical entry
    from ray_tpu.core.chaos import FaultInjector
    assert FaultInjector(spec).kills == FaultInjector(spec).kills == \
        spec["kills"]


# ----------------------------------------- integration: seeded preemption

def _blob_script_bytes(n):
    return (b"0123456789abcdef" * (n // 16 + 1))[:n]


@pytest.mark.chaos
@pytest.mark.timeout(240)  # the slow graceful case's; the hard one takes 7 s
@pytest.mark.parametrize(
    "notice_s",
    [0.0,
     pytest.param(2.0, marks=pytest.mark.slow)],  # graceful: also covered
    ids=["hard", "graceful"])                      # by the slow acceptance
def test_seeded_preemption_job_finishes(ray_start_cluster, tmp_path,
                                        notice_s):
    """Tier-1 preemption smoke (hard: notice_s=0, small objects, file://
    tier — the drain path can't silently rot): a seeded chaos schedule
    preempts one node that holds the sole copy of a task result (hard
    variant: the copy was already evicted to the external tier; graceful
    variant: the drain itself re-homes it) while other nodes
    broadcast-read it — the job finishes byte-exact WITHOUT re-running
    the producing task."""
    base_uri = f"file://{tmp_path}/ext"
    counter = tmp_path / "runs.txt"
    counter.write_text("0")
    os.environ["RAYTPU_OBJECT_SPILLING_EXTERNAL_URI"] = base_uri
    os.environ["RAYTPU_DISABLE_ZERO_COPY"] = "1"  # force the chunk path
    cluster = ray_start_cluster
    try:
        n1 = cluster.add_node(num_cpus=2,
                              object_store_memory=16 * 1024 * 1024)
        n2 = cluster.add_node(num_cpus=2,
                              object_store_memory=16 * 1024 * 1024)
        cluster.wait_for_nodes(2)
        cluster.connect_driver(
            _system_config={"object_spilling_external_uri": base_uri})
        from ray_tpu.core.common import NodeAffinitySchedulingStrategy
        from ray_tpu.core.core_worker import global_worker

        w = global_worker()
        # the victim must not be the agent the driver attached to
        victim = n1 if n2.address == w.agent_address else (
            n2 if n1.address == w.agent_address else n1)
        other = n2 if victim is n1 else n1

        blob_n = 4 * 1024 * 1024

        @ray_tpu.remote(num_cpus=1)
        def make_blob(counter_path, n):
            import pathlib
            p = pathlib.Path(counter_path)
            p.write_text(str(int(p.read_text()) + 1))
            return (b"0123456789abcdef" * (n // 16 + 1))[:n]

        ref = make_blob.options(scheduling_strategy=(
            NodeAffinitySchedulingStrategy(victim.node_id, soft=False))) \
            .remote(str(counter), blob_n)
        ready, _ = ray_tpu.wait([ref], timeout=120)
        assert ready, "producing task did not finish"

        if notice_s == 0.0:
            # hard variant: force the evict->external-spill BEFORE the
            # no-warning kill, so the copy is already durable
            @ray_tpu.remote(num_cpus=1)
            def filler(n):
                return b"f" * n

            fref = filler.options(scheduling_strategy=(
                NodeAffinitySchedulingStrategy(victim.node_id,
                                               soft=False))) \
                .remote(13 * 1024 * 1024)
            ready, _ = ray_tpu.wait([fref], timeout=120)
            assert ready

        def _has_external_location():
            rec = w.memory_store.get_if_exists(ref.id)
            return rec is not None and any(
                external_spill.is_external_address(addr)
                for _nid, addr in rec.locations)

        if notice_s == 0.0:
            _wait(_has_external_location, 60,
                  "external location to register with the owner")

        # seeded preemption of the victim via the runtime chaos plane
        spec = {"seed": 9, "kills": [
            {"kind": "preempt_node", "after_s": 0.1, "notice_s": notice_s,
             "node": victim.node_id[:8]}]}
        run_async(w.gcs.call("chaos_set", spec=spec))
        _wait(lambda: victim.proc.poll() is not None, 90,
              "victim node process to die")

        if notice_s > 0:
            # graceful drain re-homed the sole copy before exiting
            _wait(_has_external_location, 30,
                  "drain to register the external location")

        # broadcast the object across the survivors: every read restores
        # from the external tier (victim's RPC endpoint is dead)
        expect = hashlib.sha256(_blob_script_bytes(blob_n)).hexdigest()

        @ray_tpu.remote(num_cpus=1)
        def digest(obj):
            import hashlib as h
            return h.sha256(obj).hexdigest()

        drefs = [digest.options(scheduling_strategy=(
            NodeAffinitySchedulingStrategy(other.node_id, soft=False)))
            .remote(ref) for _ in range(2)]
        assert ray_tpu.get(drefs, timeout=120) == [expect, expect]
        # the driver's own get is byte-exact too
        assert hashlib.sha256(ray_tpu.get(ref, timeout=120)).hexdigest() \
            == expect
        # survivability, not lineage: the producing task ran exactly once
        assert counter.read_text() == "1"
    finally:
        os.environ.pop("RAYTPU_OBJECT_SPILLING_EXTERNAL_URI", None)
        os.environ.pop("RAYTPU_DISABLE_ZERO_COPY", None)


# ------------------------------------- workflow resume across driver loss

_DRIVER_SCRIPT = """
import sys
import ray_tpu
from ray_tpu import workflow

gcs_address, wf_id, counter, gate = sys.argv[1:5]
ray_tpu.init(address=gcs_address)


@workflow.step
def prepare(counter_path):
    import pathlib
    p = pathlib.Path(counter_path)
    p.write_text(str(int(p.read_text()) + 1))
    return 7


@workflow.step
def finish(x, gate_path):
    import os
    import time
    while not os.path.exists(gate_path):
        time.sleep(0.1)
    return x * 6


print("DRIVER_STARTED", flush=True)
out = workflow.run(finish.bind(prepare.bind(counter), gate),
                   workflow_id=wf_id)
print("DRIVER_DONE", out, flush=True)
"""


@pytest.mark.timeout(120)
def test_workflow_resume_after_driver_killed_mid_dag(ray_start_cluster,
                                                     tmp_path):
    """The durability property that makes 'durable' real: the DRIVER
    process dies mid-DAG (SIGKILL, no goodbye), and a fresh driver's
    ``workflow.resume`` finishes the workflow, loading committed steps
    from GCS storage instead of re-running them."""
    from ray_tpu import workflow

    cluster = ray_start_cluster
    # 4 CPUs: the killed driver's leases take one liveness-sweep cycle
    # (~30 s) to reclaim — the resume must not have to wait for that
    cluster.add_node(num_cpus=4)
    cluster.wait_for_nodes(1)
    cluster.connect_driver()

    counter = tmp_path / "prepare-runs.txt"
    counter.write_text("0")
    gate = tmp_path / "gate"
    script = tmp_path / "wf_driver.py"
    script.write_text(_DRIVER_SCRIPT)
    wf_id = "wf-driver-loss"

    env = dict(os.environ)
    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(
        ray_tpu.__file__)))
    env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, str(script), cluster.address, wf_id,
         str(counter), str(gate)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env)
    try:
        # wait (from THIS driver) until the first step's result committed
        _wait(lambda: any(k.startswith("step-000-prepare")
                          for k in workflow.list_committed_steps(wf_id)),
              120, "first step to commit")
        # the second step is parked on the gate file: kill the driver
        # mid-DAG with no chance to clean up
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()

    assert counter.read_text() == "1"
    gate.write_text("open")  # unblock finish for the resume

    @workflow.step
    def prepare(counter_path):
        import pathlib
        p = pathlib.Path(counter_path)
        p.write_text(str(int(p.read_text()) + 1))
        return 7

    @workflow.step
    def finish(x, gate_path):
        import os
        import time
        while not os.path.exists(gate_path):
            time.sleep(0.1)
        return x * 6

    out = workflow.resume(wf_id, finish.bind(prepare.bind(str(counter)),
                                             str(gate)))
    assert out == 42
    # the committed step was LOADED, not re-executed
    assert counter.read_text() == "1"
    assert workflow.get_status(wf_id)["status"] == "SUCCEEDED"
    assert workflow.get_output(wf_id) == 42


# ------------------------------------------------- slow acceptance soak

@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.timeout(600)
def test_preemption_acceptance_big_broadcast_and_workflow(ray_start_cluster,
                                                          tmp_path):
    """The full acceptance schedule at gs://-shaped scale (file:// tier,
    100 MB object): preempt a holder mid-broadcast while a workflow is
    mid-DAG with its driver killed; the broadcast completes byte-exact
    via external restore and resume() skips committed steps."""
    from ray_tpu import workflow

    base_uri = f"file://{tmp_path}/ext"
    counter = tmp_path / "runs.txt"
    counter.write_text("0")
    os.environ["RAYTPU_OBJECT_SPILLING_EXTERNAL_URI"] = base_uri
    os.environ["RAYTPU_DISABLE_ZERO_COPY"] = "1"
    cluster = ray_start_cluster
    try:
        nodes = [cluster.add_node(num_cpus=2,
                                  object_store_memory=256 * 1024 * 1024)
                 for _ in range(3)]
        cluster.wait_for_nodes(3)
        cluster.connect_driver(
            _system_config={"object_spilling_external_uri": base_uri})
        from ray_tpu.core.common import NodeAffinitySchedulingStrategy
        from ray_tpu.core.core_worker import global_worker

        w = global_worker()
        victim = next(n for n in nodes if n.address != w.agent_address)
        others = [n for n in nodes if n is not victim]

        # a workflow mid-DAG in its own (killable) driver process
        gate = tmp_path / "gate"
        script = tmp_path / "wf_driver.py"
        script.write_text(_DRIVER_SCRIPT)
        wf_counter = tmp_path / "wf-runs.txt"
        wf_counter.write_text("0")
        wf_id = "wf-acceptance"
        env = dict(os.environ)
        pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(
            ray_tpu.__file__)))
        env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
        wf_proc = subprocess.Popen(
            [sys.executable, str(script), cluster.address, wf_id,
             str(wf_counter), str(gate)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env)

        blob_n = 100 * 1024 * 1024

        @ray_tpu.remote(num_cpus=1)
        def make_blob(counter_path, n):
            import pathlib
            p = pathlib.Path(counter_path)
            p.write_text(str(int(p.read_text()) + 1))
            return (b"0123456789abcdef" * (n // 16 + 1))[:n]

        ref = make_blob.options(scheduling_strategy=(
            NodeAffinitySchedulingStrategy(victim.node_id, soft=False))) \
            .remote(str(counter), blob_n)
        ready, _ = ray_tpu.wait([ref], timeout=240)
        assert ready

        # start the broadcast, then preempt the origin mid-pull with a
        # 3 s notice: the drain re-homes the object to the external tier
        # and the pullers fold the new source in mid-stripe
        @ray_tpu.remote(num_cpus=1)
        def digest(obj):
            import hashlib as h
            return h.sha256(obj).hexdigest()

        drefs = [digest.options(scheduling_strategy=(
            NodeAffinitySchedulingStrategy(n.node_id, soft=False)))
            .remote(ref) for n in others for _ in range(2)]
        time.sleep(0.5)  # let the pulls get going
        spec = {"seed": 13, "kills": [
            {"kind": "preempt_node", "after_s": 0.0, "notice_s": 3.0,
             "node": victim.node_id[:8]}]}
        run_async(w.gcs.call("chaos_set", spec=spec))
        _wait(lambda: victim.proc.poll() is not None, 120,
              "victim to be preempted")

        # kill the workflow driver mid-DAG while the broadcast recovers
        _wait(lambda: any(k.startswith("step-000-prepare")
                          for k in workflow.list_committed_steps(wf_id)),
              120, "workflow first step to commit")
        wf_proc.send_signal(signal.SIGKILL)
        wf_proc.wait(timeout=30)

        expect = hashlib.sha256(_blob_script_bytes(blob_n)).hexdigest()
        assert all(d == expect for d in ray_tpu.get(drefs, timeout=300))
        assert counter.read_text() == "1"  # no lineage re-run

        gate.write_text("open")

        @workflow.step
        def prepare(counter_path):
            import pathlib
            p = pathlib.Path(counter_path)
            p.write_text(str(int(p.read_text()) + 1))
            return 7

        @workflow.step
        def finish(x, gate_path):
            import os as _os
            import time as _t
            while not _os.path.exists(gate_path):
                _t.sleep(0.1)
            return x * 6

        assert workflow.resume(
            wf_id, finish.bind(prepare.bind(str(wf_counter)),
                               str(gate))) == 42
        assert wf_counter.read_text() == "1"
    finally:
        os.environ.pop("RAYTPU_OBJECT_SPILLING_EXTERNAL_URI", None)
        os.environ.pop("RAYTPU_DISABLE_ZERO_COPY", None)
