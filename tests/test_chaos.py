"""Chaos fault-injection plane + idempotent retrying RPC layer.

Reference: the chaos release harness (chaos_network_delay.yaml, the
NodeKillerActor in test_utils.py:1401) and retryable gRPC clients.  These
tests drive the seeded FaultInjector (core/chaos.py) at three levels:
unit determinism, RPC-layer exactly-once retries, and real task/actor
workloads under seeded fault schedules (frame drops, a scheduled worker
kill, a GCS restart).
"""

import asyncio
import json
import os
import socket
import time

import pytest

import ray_tpu
from ray_tpu.core import chaos
from ray_tpu.core.chaos import FaultInjector
from ray_tpu.core.rpc import ConnectionLost, RpcClient, RpcServer, run_async


@pytest.fixture(autouse=True)
def _clean_injector():
    """Every test starts and ends without an installed injector."""
    chaos.install(None)
    yield
    chaos.install(None)
    chaos.reset()


# ---------------------------------------------------------------- injector


@pytest.mark.chaos
def test_injector_same_seed_same_fault_sequence():
    """The acceptance property: the same seed reproduces the same
    injected-fault sequence — decisions are a pure function of
    (spec, rule, method, evaluation index), not of an RNG stream."""
    spec = {"seed": 123,
            "rules": [{"kind": "drop_request", "prob": 0.3},
                      {"kind": "delay", "ms": 2, "prob": 0.5},
                      {"kind": "fail_after", "prob": 0.2, "method": "kv_put"}]}
    a, b = FaultInjector(spec), FaultInjector(spec)
    methods = ["kv_put", "heartbeat", "push_task"] * 40
    seq_a = [(m, a.should("drop_request", m), a.should("fail_after", m),
              a.delay_s(m)) for m in methods]
    seq_b = [(m, b.should("drop_request", m), b.should("fail_after", m),
              b.delay_s(m)) for m in methods]
    assert seq_a == seq_b
    assert a.decision_log() == b.decision_log()
    assert a.injected_counts() == b.injected_counts()
    # faults actually fired, and not on every call
    assert any(hit for _m, hit, _f, _d in seq_a)
    assert not all(hit for _m, hit, _f, _d in seq_a)
    # a different seed produces a different sequence
    c = FaultInjector({**spec, "seed": 124})
    seq_c = [(m, c.should("drop_request", m), c.should("fail_after", m),
              c.delay_s(m)) for m in methods]
    assert seq_c != seq_a


@pytest.mark.chaos
def test_injector_rule_scoping():
    """method= / peer= / times= bound where and how often a rule fires."""
    inj = FaultInjector({"seed": 0, "rules": [
        {"kind": "drop_reply", "prob": 1.0, "method": "kv_put", "times": 2},
        {"kind": "partition", "prob": 1.0, "peer": ":9999"}]})
    assert not inj.should("drop_reply", "kv_get")       # method-scoped
    assert inj.should("drop_reply", "kv_put")
    assert inj.should("drop_reply", "kv_put")
    assert not inj.should("drop_reply", "kv_put")       # times exhausted
    assert inj.should("partition", "anything", "127.0.0.1:9999")
    assert not inj.should("partition", "anything", "127.0.0.1:1234")
    # the chaos control plane is exempt — chaos can't lock itself out
    assert not inj.should("partition", "chaos_clear", "127.0.0.1:9999")


# ----------------------------------------------------------- rpc hardening


class _CountingHandler:
    def __init__(self):
        self.bumps = 0

    async def handle_bump(self):
        self.bumps += 1
        return self.bumps

    async def handle_ping(self):
        return "pong"


@pytest.mark.chaos
def test_call_retry_exactly_once_under_lost_replies():
    """A mutating RPC whose reply is lost (fail-after-commit AND a dropped
    reply frame) must apply exactly once: the retry carries the same
    idempotency token and the server's dedup window replays the committed
    result instead of re-executing the handler."""
    h = _CountingHandler()
    server = RpcServer(h).start_sync()
    client = RpcClient(server.address)
    try:
        # handler executes, reply replaced by a ChaosFault: retry must see
        # the COMMITTED result, not run the handler again
        chaos.install({"seed": 0, "rules": [
            {"kind": "fail_after", "prob": 1.0, "method": "bump",
             "times": 1}]})
        assert run_async(client.call_retry("bump", _timeout=10)) == 1
        assert h.bumps == 1
        # reply frame dropped (connection aborted): same exactly-once
        chaos.install({"seed": 0, "rules": [
            {"kind": "drop_reply", "prob": 1.0, "method": "bump",
             "times": 1}]})
        assert run_async(client.call_retry("bump", _timeout=10)) == 2
        assert h.bumps == 2
        # request frame dropped before it reaches the server
        chaos.install({"seed": 0, "rules": [
            {"kind": "drop_request", "prob": 1.0, "method": "bump",
             "times": 1}]})
        assert run_async(client.call_retry("bump", _timeout=10)) == 3
        assert h.bumps == 3
        # fail-before-commit: handler never ran on the failed attempt
        chaos.install({"seed": 0, "rules": [
            {"kind": "fail_before", "prob": 1.0, "method": "bump",
             "times": 1}]})
        assert run_async(client.call_retry("bump", _timeout=10)) == 4
        assert h.bumps == 4
        counts = chaos.injector().injected_counts()
        assert counts.get("fail_before") == 1
    finally:
        chaos.install(None)
        run_async(client.close())
        server.stop_sync()


@pytest.mark.chaos
def test_partition_fails_fast():
    h = _CountingHandler()
    server = RpcServer(h).start_sync()
    client = RpcClient(server.address)
    try:
        chaos.install({"seed": 0, "rules": [{"kind": "partition",
                                             "method": "bump"}]})
        t0 = time.monotonic()
        with pytest.raises(ConnectionLost):
            run_async(client.call_retry("bump", _timeout=5))
        assert time.monotonic() - t0 < 6
        assert h.bumps == 0
    finally:
        chaos.install(None)
        run_async(client.close())
        server.stop_sync()


@pytest.mark.chaos
def test_call_during_teardown_fails_fast():
    """Regression for the disconnect race: a call that validated the
    connection, then parked at an await (chaos link delay) while the read
    loop tore the connection down, must fail promptly with ConnectionLost
    — not insert into an orphaned pending table and hang to its full
    timeout."""
    h = _CountingHandler()
    server = RpcServer(h).start_sync()
    chaos.install({"seed": 0, "rules": [{"kind": "delay", "ms": 400,
                                         "method": "ping"}]})

    async def scenario():
        client = RpcClient(server.address)
        await client.call("bump")  # establish the connection
        fut = asyncio.ensure_future(client.call("ping", _timeout=30))
        await asyncio.sleep(0.1)   # the ping is parked in its delay window
        await server.stop()        # connection dies under it
        t0 = time.monotonic()
        try:
            await fut
        except ConnectionLost:
            return time.monotonic() - t0
        finally:
            await client.close()
        return None

    elapsed = run_async(scenario())
    chaos.install(None)
    assert elapsed is not None, "call during teardown did not fail"
    assert elapsed < 5.0, f"took {elapsed:.1f}s (hung to timeout?)"


# -------------------------------------------------------- seeded workloads


@pytest.mark.chaos
@pytest.mark.timeout(120)
def test_chaos_smoke_drop_frames_and_worker_kill():
    """Tier-1 chaos smoke (seeded, deterministic spec): 5% of frames
    dropped on every link plus one scheduled worker kill, over a real task
    workload — everything completes with correct results and the injector
    observably fired."""
    from ray_tpu.utils.testing import CPU_WORKER_ENV

    spec = {"seed": 7,
            "rules": [{"kind": "drop_request", "prob": 0.05},
                      {"kind": "drop_reply", "prob": 0.05}],
            "kills": [{"after_s": 2.0, "target": "worker"}]}
    spec_json = json.dumps(spec)
    os.environ["RAYTPU_CHAOS_SPEC"] = spec_json
    try:
        ray_tpu.init(num_cpus=2, worker_env=dict(CPU_WORKER_ENV),
                     _system_config={"chaos_spec": spec_json})

        @ray_tpu.remote(max_retries=5)
        def double(i):
            return i * 2

        refs = [double.remote(i) for i in range(60)]
        assert ray_tpu.get(refs, timeout=150) == [i * 2 for i in range(60)]

        inj = chaos.injector()
        assert inj is not None
        counts = inj.injected_counts()
        assert sum(counts.values()) > 0, counts
        # raytpu_chaos_injected_total mirrors the injector's counts
        from ray_tpu.util.metrics import get_metric
        metric = get_metric("raytpu_chaos_injected_total")
        assert metric is not None
        assert sum(metric.snapshot()["values"].values()) > 0
    finally:
        os.environ.pop("RAYTPU_CHAOS_SPEC", None)
        ray_tpu.shutdown()


@pytest.mark.chaos
@pytest.mark.timeout(120)
def test_chaos_sharded_control_plane_shard_restart():
    """The PR-13 horizontal-control-plane chaos arm: seeded frame drops +
    one scheduled worker kill over a real workload on a SHARDED GCS
    (gcs_table_shards=4, 2 shard processes), with a shard PROCESS killed
    mid-workload.  The supervisor respawns it at the same index, the
    replacement restores its KV slice from its own snapshot (the function
    registry lives in sharded KV — a respawn must not lose it), clients
    fall back through the router proxy meanwhile, and exactly-once
    registration holds (the named actor appears once despite retried
    RPCs)."""
    from ray_tpu.core.api import _state
    from ray_tpu.core.gcs_router import shard_index
    from ray_tpu.utils.testing import CPU_WORKER_ENV

    spec = {"seed": 5,
            "rules": [{"kind": "drop_request", "prob": 0.03},
                      {"kind": "drop_reply", "prob": 0.03}],
            "kills": [{"after_s": 2.0, "target": "worker"}]}
    spec_json = json.dumps(spec)
    os.environ["RAYTPU_CHAOS_SPEC"] = spec_json
    try:
        ray_tpu.init(num_cpus=2, worker_env=dict(CPU_WORKER_ENV),
                     _system_config={"chaos_spec": spec_json,
                                     "gcs_table_shards": 4,
                                     "gcs_shard_processes": 2})

        @ray_tpu.remote
        class Counter:
            def __init__(self):
                self.n = 0

            def bump(self):
                self.n += 1
                return self.n

        ctr = Counter.options(name="shard-chaos-singleton").remote()
        assert ray_tpu.get(ctr.bump.remote(), timeout=60) == 1

        @ray_tpu.remote(max_retries=5)
        def double(i):
            return i * 2

        refs = [double.remote(i) for i in range(80)]
        time.sleep(1.0)  # workload underway

        # kill the shard process that owns the FUNCTION REGISTRY slice —
        # the worst-case victim: lose it and no new worker can load defs
        gcs = _state.gcs_server
        victim_idx = shard_index("funcs", len(gcs._shard_addrs))
        victim = gcs._shard_procs[victim_idx]
        victim.kill()

        assert ray_tpu.get(refs, timeout=150) == [i * 2 for i in range(80)]
        # the supervisor respawned the shard at the same index
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if (gcs._shard_procs[victim_idx] is not victim
                    and gcs._shard_procs[victim_idx].poll() is None):
                break
            time.sleep(0.2)
        assert gcs._shard_procs[victim_idx] is not victim
        # the replacement restored its KV slice (function registry keys)
        from ray_tpu.core.core_worker import global_worker
        w = global_worker()
        fn_keys = run_async(w.gcs.call_retry("kv_keys", ns="funcs",
                                             _idempotent=False))
        assert fn_keys, "function registry lost across shard restart"
        # exactly-once across the chaos: one named actor, still alive
        assert ray_tpu.get(ctr.bump.remote(), timeout=60) == 2
        actors = run_async(w.gcs.call_retry("list_actors",
                                            _idempotent=False))
        singles = [a for a in actors
                   if a.get("name") == "shard-chaos-singleton"]
        assert len(singles) == 1, singles
        inj = chaos.injector()
        assert inj is not None and sum(inj.injected_counts().values()) > 0
    finally:
        os.environ.pop("RAYTPU_CHAOS_SPEC", None)
        ray_tpu.shutdown()


@pytest.mark.chaos
@pytest.mark.timeout(120)
def test_chaos_acceptance_drops_kill_and_gcs_restart(tmp_path):
    """The acceptance run: a seeded chaos spec (5% frame drop + 1 scheduled
    worker kill) over a 200-task workload WITH a GCS stop/restart in the
    middle — completes with correct results, exactly-once actor
    registration (no duplicates in list_actors), injected-fault counters
    > 0, and the fault sequence replays identically from the same seed."""
    from ray_tpu.core.config import Config, set_config
    from ray_tpu.core.gcs import GcsServer
    from ray_tpu.core.node_agent import NodeAgent
    from ray_tpu.utils.testing import CPU_WORKER_ENV

    spec = {"seed": 11,
            "rules": [{"kind": "drop_request", "prob": 0.05},
                      {"kind": "drop_reply", "prob": 0.05}],
            "kills": [{"after_s": 3.0, "target": "worker"}]}
    spec_json = json.dumps(spec)
    # fixed port so the restarted GCS has the same address
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    snap = str(tmp_path / "gcs.snap")

    os.environ["RAYTPU_CHAOS_SPEC"] = spec_json
    set_config(Config.from_env())
    chaos.reset()
    gcs = GcsServer(port=port, persistence_path=snap)
    run_async(gcs.start())
    agent = NodeAgent(gcs.address, num_cpus=2,
                      worker_env=dict(CPU_WORKER_ENV))
    run_async(agent.start())
    gcs2 = None
    try:
        ray_tpu.init(address=gcs.address, worker_env=dict(CPU_WORKER_ENV),
                     _system_config={"chaos_spec": spec_json})

        @ray_tpu.remote
        class Counter:
            def __init__(self):
                self.n = 0

            def bump(self):
                self.n += 1
                return self.n

        ctr = Counter.options(name="chaos-singleton").remote()
        assert ray_tpu.get(ctr.bump.remote(), timeout=60) == 1

        @ray_tpu.remote(max_retries=5)
        def double(i):
            return i * 2

        refs = [double.remote(i) for i in range(200)]
        time.sleep(2.0)  # let the workload (and the worker kill) get going

        # GCS blip: stop it and restart from the snapshot at the same
        # address — agents re-register via the heartbeat unknown path,
        # retrying clients reconnect, and the driver must not notice.
        gcs._persist()
        run_async(gcs.stop())
        gcs2 = GcsServer(port=port, persistence_path=snap)
        run_async(gcs2.start())

        assert ray_tpu.get(refs, timeout=200) == [i * 2 for i in range(200)]
        # the actor survives (it was never a chaos-kill victim) and is
        # registered exactly once despite retried register_actor RPCs
        assert ray_tpu.get(ctr.bump.remote(), timeout=60) == 2
        from ray_tpu.core.core_worker import global_worker
        actors = run_async(global_worker().gcs.call_retry(
            "list_actors", _idempotent=False))
        singletons = [a for a in actors if a.get("name") == "chaos-singleton"]
        assert len(singletons) == 1, singletons

        inj = chaos.injector()
        assert inj is not None
        counts = inj.injected_counts()
        assert sum(counts.values()) > 0, counts

        # Same-seed reproducibility: replay the per-(rule, method)
        # evaluation counts against a FRESH injector from the same spec —
        # the injected-fault set must come out identical.
        replay = FaultInjector(spec)
        with inj._lock:
            evaluations = dict(inj._counters)
        for (rule_idx, method), n in evaluations.items():
            for _ in range(n):
                replay._roll(rule_idx, replay.rules[rule_idx], method)
        assert sorted(replay.decision_log()) == sorted(inj.decision_log())
    finally:
        os.environ.pop("RAYTPU_CHAOS_SPEC", None)
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        try:
            run_async(agent.stop(), timeout=10)
        except Exception:
            pass
        for g in (gcs2, gcs):
            if g is not None:
                try:
                    run_async(g.stop(), timeout=5)
                except Exception:
                    pass
