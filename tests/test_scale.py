"""Scale-envelope + chaos tests.

Reference: ``release/benchmarks/README.md:27-31`` (many-tasks /
many-actors / many-PGs release envelope; the single-box CI analogue
pushes counts, not cluster size) and
``python/ray/tests/chaos/chaos_network_delay.yaml`` (inject link latency,
assert the cluster survives).  Every test also asserts the bookkeeping
drains: leaked refcounts / stream states / pending tables are exactly the
regressions these envelopes exist to catch.
"""

import gc
import os
import time

import pytest

import ray_tpu
from ray_tpu.util.procmem import PeakRssSampler, rss_mb


def _worker_tables():
    from ray_tpu.core.core_worker import global_worker
    w = global_worker()
    rc = w.reference_counter
    return {
        "pending_tasks": dict(w.task_manager.pending),
        "streams": dict(w.streams),
        "gen_emitters": dict(w._gen_emitters),
        "refs_local": {k: v for k, v in rc.local.items() if v},
        "refs_submitted": {k: v for k, v in rc.submitted.items() if v},
        "refs_borrowed": {k: v for k, v in rc.borrowers.items() if v},
    }


def _assert_tables_drain(timeout_s: float = 15.0):
    """All owner-side tables return to zero once refs are gone."""
    gc.collect()
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        tables = _worker_tables()
        if not any(tables.values()):
            return
        time.sleep(0.2)
        gc.collect()
    leaked = {k: len(v) for k, v in _worker_tables().items() if v}
    assert not leaked, f"tables did not drain: {leaked}"


@pytest.mark.parametrize("depth", [
    pytest.param(10_000, id="10k", marks=pytest.mark.timeout(120)),
    pytest.param(100_000, id="100k",
                 marks=[pytest.mark.slow, pytest.mark.timeout(900)]),
])
def test_queued_tasks_drain(ray_start_regular, depth):
    """``depth`` tasks queue far beyond the CPUs and ALL complete, under an
    asserted peak-RSS ceiling (the admission gate + bounded event buffers
    keep owner memory flat in the queue depth), and every owner-side
    per-task table returns to its baseline (empty) size afterwards."""
    from ray_tpu.core.core_worker import global_worker

    @ray_tpu.remote
    def inc(x):
        return x + 1

    ray_tpu.get([inc.remote(0) for _ in range(8)])  # warm the pool
    gc.collect()
    rss0 = rss_mb()
    sampler = PeakRssSampler()
    refs = [inc.remote(i) for i in range(depth)]
    # Drain in chunks: completion order tracks submission order closely
    # enough that each get() chunk is mostly resolved already, and the
    # driver never parks 100k get-coroutines at once.
    total, count, first, last = 0, 0, None, None
    for i in range(0, depth, 10_000):
        chunk = ray_tpu.get(refs[i:i + 10_000], timeout=600)
        count += len(chunk)
        total += sum(chunk)
        if first is None:
            first = chunk[0]
        last = chunk[-1]
    peak = sampler.stop()
    assert count == depth
    assert first == 1 and last == depth
    assert total == depth * (depth + 1) // 2
    # Memory ceiling: flat base + a small per-task budget.  The budget is
    # generous (refs, result records, and event buffers all scale with
    # depth by design) — the assertion exists to catch the regression
    # class where retained-per-task state grows by an extra struct, not
    # to pin exact allocator behavior.
    ceiling_mb = 300.0 + depth * 0.004
    assert peak - rss0 < ceiling_mb, (
        f"peak RSS grew {peak - rss0:.0f} MB over a {depth}-task drain "
        f"(ceiling {ceiling_mb:.0f} MB)")
    w = global_worker()
    assert w.admission_gate.inflight == 0
    # the bounded owner event buffer never exceeded its cap
    from ray_tpu.core.config import get_config
    assert len(w._task_events) <= get_config().task_events_max_buffer
    del refs, chunk
    _assert_tables_drain()


@pytest.mark.timeout(600)
@pytest.mark.slow
def test_500_actors_register(ray_start_regular):
    """500 actors register with the GCS and answer a call (waves of 50 so
    the 1-core box never hosts more than 50 worker processes at once —
    the reference envelope runs `many_actors` on a real cluster)."""
    from ray_tpu.util.state import list_actors

    @ray_tpu.remote(num_cpus=0)
    class A:
        def pid(self):
            return os.getpid()

    total, wave = 500, 50
    seen_pids = set()
    for w in range(total // wave):
        actors = [A.remote() for _ in range(wave)]
        pids = ray_tpu.get([a.pid.remote() for a in actors], timeout=240)
        seen_pids.update(pids)
        for a in actors:
            ray_tpu.kill(a)
    assert len(seen_pids) == total  # every actor had its own process
    rows = list_actors(limit=2000)
    assert len(rows) >= total
    alive = [r for r in rows if r.get("state") == "ALIVE"]
    assert not alive, f"{len(alive)} actors still alive after kill"
    _assert_tables_drain()


@pytest.mark.timeout(120)
def test_100_placement_groups_cycle(ray_start_regular):
    """100 PGs schedule concurrently, all become ready, all remove; agent
    resources return to the starting level and the GCS table empties."""
    from ray_tpu.util.state import list_placement_groups

    start_cpu = ray_tpu.available_resources().get("CPU", 0)
    pgs = [ray_tpu.placement_group([{"CPU": 0.01}]) for _ in range(100)]
    assert all(pg.ready(timeout=60) for pg in pgs)
    assert len(list_placement_groups(limit=1000)) >= 100
    for pg in pgs:
        ray_tpu.remove_placement_group(pg)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if (not list_placement_groups(limit=1000)
                and abs(ray_tpu.available_resources().get("CPU", 0)
                        - start_cpu) < 1e-6):
            break
        time.sleep(0.2)
    assert not list_placement_groups(limit=1000)
    assert abs(ray_tpu.available_resources().get("CPU", 0)
               - start_cpu) < 1e-6
    _assert_tables_drain()


@pytest.mark.timeout(240)
def test_network_delay_chaos(ray_start_cluster):
    """200 ms on every RPC link via the seeded fault-injection plane
    (RAYTPU_CHAOS_SPEC — the driver AND the agent subprocesses inherit
    it): tasks, actors, and cross-node health checking all survive — the
    chaos_network_delay.yaml analogue, now on core/chaos.py's injector."""
    import json

    from ray_tpu.utils.testing import CPU_WORKER_ENV
    from ray_tpu.util.state import list_nodes

    cluster = ray_start_cluster
    spec = json.dumps({"seed": 0,
                       "rules": [{"kind": "delay", "ms": 200}]})
    os.environ["RAYTPU_CHAOS_SPEC"] = spec
    try:
        cluster.add_node(num_cpus=2)
        cluster.add_node(num_cpus=2)
        cluster.wait_for_nodes(2, timeout=60)
        env = dict(CPU_WORKER_ENV)
        env["RAYTPU_CHAOS_SPEC"] = spec
        ray_tpu.init(address=cluster.address, worker_env=env,
                     _system_config={"chaos_spec": spec})

        @ray_tpu.remote
        def f(x):
            return x * 2

        assert ray_tpu.get(f.remote(21), timeout=120) == 42

        @ray_tpu.remote
        class C:
            def __init__(self):
                self.n = 0

            def bump(self):
                self.n += 1
                return self.n

        c = C.remote()
        assert ray_tpu.get([c.bump.remote() for _ in range(3)],
                           timeout=120) == [1, 2, 3]

        # laggy heartbeats must NOT trip the failure detector: the links
        # are slow (0.2 s << period 1 s x threshold 5), not dead
        time.sleep(8)
        nodes = list_nodes()
        assert sum(1 for n in nodes if n.get("alive")) == 2, nodes
        # the injector observably carried the delays in this process
        from ray_tpu.core import chaos
        inj = chaos.injector()
        assert inj is not None
        assert inj.injected_counts().get("delay", 0) > 0
    finally:
        os.environ.pop("RAYTPU_CHAOS_SPEC", None)
