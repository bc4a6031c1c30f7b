"""Broadcast-grade object transfer: tree location spreading, concurrent-pull
dedup (reference: push_manager.h:30 chunked push, pull_manager.h:52
admission control — here pull-based with owner-registered sources)."""

import numpy as np
import pytest

import ray_tpu


@pytest.mark.timeout(120)
def test_broadcast_tree_and_dedup(ray_start_cluster):
    cluster = ray_start_cluster
    nids = []
    for _ in range(3):
        node = cluster.add_node(num_cpus=1,
                                object_store_memory=128 * 1024 * 1024)
        nids.append(node.node_id)
    cluster.wait_for_nodes(3)
    cluster.connect_driver()

    from ray_tpu.core.common import NodeAffinitySchedulingStrategy

    payload = np.arange(3_000_000, dtype=np.float64)  # ~24 MB -> plasma
    ref = ray_tpu.put(payload)

    @ray_tpu.remote(num_cpus=1)
    def check(obj):
        return float(obj.sum())

    refs = [check.options(scheduling_strategy=(
        NodeAffinitySchedulingStrategy(nid, soft=False))).remote(ref)
        for nid in nids]
    expect = float(payload.sum())
    assert all(v == expect for v in ray_tpu.get(refs, timeout=240))

    # every puller registered as a source with the owner (tree propagation)
    w = ray_tpu.core.core_worker.global_worker()
    rec = w.memory_store.get_if_exists(ref.id)
    assert len(rec.locations) >= 3

    # a second wave on the same nodes is served locally (no re-pull): the
    # agents already contain the object, so this is fast and correct
    refs = [check.options(scheduling_strategy=(
        NodeAffinitySchedulingStrategy(nid, soft=False))).remote(ref)
        for nid in nids]
    assert all(v == expect for v in ray_tpu.get(refs, timeout=120))
