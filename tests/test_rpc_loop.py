"""``core/rpc.py``'s IO lanes: no wait without a bound its caller chose.

A lane that exists is one dict read (no lock), so ``run_async``'s ``timeout``
counts from its first line whatever another thread is doing under
``_loop_lock``; a lane whose thread never starts raises instead of holding
the lock for ever.
"""

import asyncio
import threading
import time
import types

import pytest

from ray_tpu.core import rpc


class _HeldBackThread(threading.Thread):
    """A lane's thread that starts but does not run until the gate opens."""
    gate = threading.Event()

    def run(self):
        self.gate.wait(30)
        super().run()


@pytest.fixture
def held_back_lane(monkeypatch):
    """Lane creation whose thread is held back; yields the gate.  Lanes
    the test made are stopped and forgotten afterwards."""
    rpc.get_loop()  # lane 0 exists before anything is held back
    before = set(rpc._lanes)
    gate = _HeldBackThread.gate = threading.Event()
    # rpc's own view of the module: no other code's threads are held back
    monkeypatch.setattr(rpc, "threading", types.SimpleNamespace(
        **{**vars(threading), "Thread": _HeldBackThread}))
    yield gate
    gate.set()
    for lane in set(rpc._lanes) - before:
        loop, thread = rpc._lanes.pop(lane)
        loop.call_soon_threadsafe(loop.stop)
        thread.join(5)
        assert not thread.is_alive()


def _in_thread(fn):
    out = []

    def body():
        try:
            out.append(("ok", fn()))
        except Exception as e:  # noqa: BLE001 — handed to the asserting thread
            out.append(("raised", e))
    t = threading.Thread(target=body, daemon=True)
    t.start()
    return t, out


def test_get_loop_returns_while_another_thread_holds_the_lock():
    loop = rpc.get_loop()
    with rpc._loop_lock:  # any holder: a creator, or one that never lets go
        t, out = _in_thread(rpc.get_loop)
        t.join(2)
        assert not t.is_alive(), "get_loop waits on _loop_lock for lane 0"
    assert out == [("ok", loop)]


def test_run_async_timeout_counts_while_a_lane_is_being_created(
        held_back_lane):
    creator, made = _in_thread(lambda: rpc.get_loop("held-back"))
    deadline = time.monotonic() + 5
    while not rpc._loop_lock.locked() and time.monotonic() < deadline:
        time.sleep(0.005)
    assert rpc._loop_lock.locked(), "the creator never took the lock"

    async def never():
        await asyncio.Event().wait()

    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        rpc.run_async(never(), timeout=0.2)
    assert time.monotonic() - t0 < 0.5
    held_back_lane.set()
    creator.join(5)
    assert not creator.is_alive() and made[0][0] == "ok", made
    assert made[0][1] is rpc.get_loop("held-back")
    assert rpc.run_async(asyncio.sleep(0, "ran"), timeout=5,
                         lane="held-back") == "ran"


def test_lane_that_cannot_start_raises_and_frees_the_lock(
        held_back_lane, monkeypatch):
    monkeypatch.setattr(rpc, "_LANE_START_TIMEOUT_S", 0.3)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="did not start"):
        rpc.get_loop("never-starts")
    assert 0.25 < time.monotonic() - t0 < 2.0
    assert "never-starts" not in rpc._lanes
    assert not rpc._loop_lock.locked()
    # a creator waiting behind a holder that never lets go is bounded too
    with rpc._loop_lock:
        t, out = _in_thread(lambda: rpc.get_loop("behind-it"))
        t.join(2)
        assert not t.is_alive()
    assert out[0][0] == "raised" and "_loop_lock" in str(out[0][1]), out


def test_a_collection_inside_the_counters_lock_does_not_wait_for_itself(
        ray_start_regular):
    """The holder ISSUE 51 looked for: a cyclic collection that starts
    inside a critical section finalizes a dead ``ObjectRef``, whose
    decrement wants the lock its own thread holds.  ``gc.collect()`` under
    the lock is what an eval-breaker check does between two bytecodes."""
    import gc

    import ray_tpu
    from ray_tpu.core.core_worker import global_worker

    counter = global_worker().reference_counter
    ref = ray_tpu.put(b"x")
    oid = ref.id
    box = [ref]
    box.append(box)             # a cycle: only the collector frees the ref
    del ref, box
    assert counter.local.get(oid) == 1

    def collect_under_the_lock():
        with counter._lock:
            gc.collect()
            return counter.local.get(oid)   # handed on, not yet taken

    t, out = _in_thread(collect_under_the_lock)
    t.join(10)
    assert not t.is_alive(), "the finalizer waits for its own thread's lock"
    assert out == [("ok", 1)]
    deadline = time.monotonic() + 10        # late, never lost
    while counter.local.get(oid, 0) and time.monotonic() < deadline:
        time.sleep(0.01)
    assert counter.local.get(oid, 0) == 0


def test_the_io_loop_is_quiet_after_a_shutdown_with_tasks_still_queued():
    """A lease pool that still holds a queued task at shutdown used to
    pump for ever on the IO loop (``_acquire_leases`` left at once, its
    ``finally`` pumped, the deficit asked again): a core of the process
    gone, and every later test of that xdist worker many times slower."""
    import os

    import ray_tpu
    from ray_tpu.utils.testing import CPU_WORKER_ENV

    def cpu_s():
        """CPU seconds of the IO loop's own thread (lane 0)."""
        tid = rpc._lanes[0][1].native_id
        with open(f"/proc/self/task/{tid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")

    ray_tpu.init(num_cpus=1, worker_env=dict(CPU_WORKER_ENV))
    try:
        @ray_tpu.remote
        def one():
            return 1

        refs = [one.remote() for _ in range(8)]   # no worker is up yet
    finally:
        ray_tpu.shutdown()
    del refs
    time.sleep(0.5)
    before = cpu_s()
    time.sleep(1.0)
    assert cpu_s() - before < 0.5, "the IO loop spins after shutdown"
