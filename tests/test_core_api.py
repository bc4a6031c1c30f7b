"""Core API tests: put/get/wait/tasks/errors (reference analogue:
python/ray/tests/test_basic.py family)."""

import time

import numpy as np
import pytest

import ray_tpu


def test_put_get_small(ray_start_regular):
    ref = ray_tpu.put({"a": 1, "b": [1, 2, 3]})
    assert ray_tpu.get(ref) == {"a": 1, "b": [1, 2, 3]}


def test_put_get_large_numpy(ray_start_regular):
    x = np.arange(1_000_000, dtype=np.float32)
    ref = ray_tpu.put(x)
    y = ray_tpu.get(ref)
    np.testing.assert_array_equal(x, y)


def test_simple_task(ray_start_regular):
    @ray_tpu.remote
    def add(a, b):
        return a + b

    assert ray_tpu.get(add.remote(1, 2)) == 3


def test_task_with_ref_args(ray_start_regular):
    @ray_tpu.remote
    def add(a, b):
        return a + b

    a = ray_tpu.put(10)
    b = add.remote(a, 5)
    c = add.remote(b, a)
    assert ray_tpu.get(c) == 25


def test_task_large_return(ray_start_regular):
    @ray_tpu.remote
    def make(n):
        return np.ones(n, dtype=np.float64)

    ref = make.remote(500_000)
    out = ray_tpu.get(ref)
    assert out.shape == (500_000,)
    assert out.sum() == 500_000


def test_multiple_returns(ray_start_regular):
    @ray_tpu.remote(num_returns=3)
    def three():
        return 1, 2, 3

    r1, r2, r3 = three.remote()
    assert ray_tpu.get([r1, r2, r3]) == [1, 2, 3]


def test_task_error_propagates(ray_start_regular):
    @ray_tpu.remote
    def boom():
        raise ValueError("kaboom")

    with pytest.raises(ray_tpu.TaskError) as ei:
        ray_tpu.get(boom.remote())
    assert "kaboom" in str(ei.value)


def test_wait(ray_start_regular):
    @ray_tpu.remote
    def fast():
        return "fast"

    @ray_tpu.remote
    def slow():
        time.sleep(5)
        return "slow"

    # Warm both worker pools so the timing below isn't dominated by process
    # spawn (first-task latency) on a small machine.
    ray_tpu.get(fast.remote())

    f, s = fast.remote(), slow.remote()
    ready, not_ready = ray_tpu.wait([f, s], num_returns=1, timeout=4)
    assert ready == [f]
    assert not_ready == [s]


def test_get_timeout(ray_start_regular):
    @ray_tpu.remote
    def slow():
        time.sleep(10)

    with pytest.raises(ray_tpu.GetTimeoutError):
        ray_tpu.get(slow.remote(), timeout=0.5)


def test_many_small_tasks(ray_start_regular):
    @ray_tpu.remote
    def sq(i):
        return i * i

    refs = [sq.remote(i) for i in range(50)]
    assert ray_tpu.get(refs) == [i * i for i in range(50)]


def test_nested_refs_pass_through(ray_start_regular):
    @ray_tpu.remote
    def inner():
        return 42

    @ray_tpu.remote
    def outer(wrapped):
        # wrapped is a dict holding a ref — nested refs are NOT auto-resolved.
        (ref,) = wrapped["refs"]
        return ray_tpu.get(ref) + 1

    ref = inner.remote()
    assert ray_tpu.get(outer.remote({"refs": [ref]})) == 43


def test_task_in_task(ray_start_regular):
    @ray_tpu.remote
    def child(x):
        return x * 2

    @ray_tpu.remote
    def parent(x):
        return ray_tpu.get(child.remote(x)) + 1

    assert ray_tpu.get(parent.remote(10)) == 21


def test_a_tasks_return_ref_deleted_first_lowers_the_count(ray_start_regular,
                                                           monkeypatch):
    """The submit path counts a task's one return ref itself, past
    ``_ref_created``: in a process whose first ref is such a one (the
    module's worker lookup still unbound) its deletion has to reach the
    counter all the same."""
    from ray_tpu.core import object_ref
    from ray_tpu.core.core_worker import global_worker

    @ray_tpu.remote
    def one():
        return 1

    monkeypatch.setattr(object_ref, "_global_worker_or_none", None)
    ref = one.remote()
    assert object_ref._global_worker_or_none is None
    oid, local = ref.id, global_worker().reference_counter.local
    assert local.get(oid) == 1
    del ref
    assert local.get(oid, 0) == 0
    assert object_ref._global_worker_or_none is not None


def test_nested_get_no_deadlock():
    """Parents blocking on children must not deadlock the worker pool: blocked
    workers release their lease resources (reference: raylet blocked-worker
    accounting)."""
    import ray_tpu
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote
        def child(x):
            return x + 1

        @ray_tpu.remote
        def parent(x):
            return ray_tpu.get(child.remote(x)) * 10

        # 2 parents saturate both CPUs, then each needs a child to finish.
        refs = [parent.remote(i) for i in range(2)]
        assert ray_tpu.get(refs, timeout=60) == [10, 20]
    finally:
        ray_tpu.shutdown()


def test_cluster_and_available_resources(ray_start_regular):
    total = ray_tpu.cluster_resources()
    assert total.get("CPU") == 4.0
    assert len(ray_tpu.nodes()) == 1


def test_num_returns_options(ray_start_regular):
    @ray_tpu.remote
    def pair():
        return 1, 2

    r = pair.options(num_returns=2).remote()
    assert ray_tpu.get(list(r)) == [1, 2]


def test_intra_batch_dependencies(ray_start_regular):
    """Tasks batched onto one worker may depend on each other — directly,
    through a closure capture, or through a ref hidden inside a put object.
    Per-task result streaming (handle_push_task_batch) must keep all three
    deadlock-free."""
    @ray_tpu.remote
    def produce():
        return 7

    @ray_tpu.remote
    def add(a, b):
        return a + b

    # direct: consumer's arg is the producer's return, submitted back-to-back
    r1 = produce.remote()
    r2 = add.remote(r1, 1)
    # indirect: the dependency rides inside a plain put() object
    box = ray_tpu.put({"hidden": r1})

    @ray_tpu.remote
    def open_box(b):
        return ray_tpu.get(b["hidden"]) + 100

    r3 = open_box.remote(box)
    assert ray_tpu.get([r2, r3], timeout=60) == [8, 107]


def test_returned_ref_survives_escrow_grace():
    """Regression (round-2 ADVICE): a ref serialized in a task result must
    survive the owner's escrow grace even if the caller only deserializes it
    long after the producing task finished — borrows are registered at result
    receipt (TaskManager.complete), not at ray.get time."""
    from ray_tpu.utils.testing import CPU_WORKER_ENV
    ray_tpu.init(num_cpus=2, worker_env=dict(CPU_WORKER_ENV),
                 _system_config={"ref_escrow_grace_s": 0.3})
    try:
        @ray_tpu.remote
        def produce():
            inner = ray_tpu.put(np.arange(1000))
            return {"ref": inner}

        res = produce.remote()
        # Wait for the task to finish WITHOUT deserializing its result, then
        # sit past the grace window: the producer's own counts hit zero at
        # task exit, and before the fix the owner freed the inner object here.
        ray_tpu.wait([res], timeout=30)
        time.sleep(1.5)
        inner_val = ray_tpu.get(ray_tpu.get(res)["ref"])
        np.testing.assert_array_equal(inner_val, np.arange(1000))
    finally:
        ray_tpu.shutdown()
