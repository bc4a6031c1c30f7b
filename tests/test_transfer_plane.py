"""Chunk-ledger transfer plane (core/transfer.py + the striped pull path in
node_agent): multi-source striping, work-stealing, chunk-granular retry and
resume after source death, partial-object serving, zero-extra-copy sink
receive, and the bench-timeline schema the broadcast artifact depends on."""

import asyncio
import glob
import json
import os
import shutil

import numpy as np
import pytest

from ray_tpu.core.object_store import (ChunkNotAvailable, range_add,
                                       range_covers)
from ray_tpu.core.transfer import ChunkLedger, StripedPull, TransferStalled


# --------------------------------------------------------------- unit: ranges

def test_range_helpers_merge_and_cover():
    r = []
    r = range_add(r, 0, 10)
    r = range_add(r, 20, 30)
    assert r == [[0, 10], [20, 30]]
    r = range_add(r, 10, 20)          # bridges the gap
    assert r == [[0, 30]]
    r = range_add(r, 50, 60)
    r = range_add(r, 45, 55)          # left-overlap merge
    assert r == [[0, 30], [45, 60]]
    assert range_covers(r, 0, 30)
    assert range_covers(r, 46, 59)
    assert not range_covers(r, 29, 31)
    assert not range_covers(r, 30, 45)


def test_ledger_sealed_ranges_and_stats():
    led = ChunkLedger(10, 4)          # chunks: [0,4) [4,8) [8,10)
    assert len(led) == 3
    assert led.chunk_len(2) == 2
    i = led.claim("a", lambda o, n: True)
    assert i == 0 and led.claim("a", lambda o, n: o >= 8) == 2
    assert led.complete(0, 0.01) and led.complete(2, 0.01)
    assert led.sealed_ranges() == [[0, 4], [8, 10]]
    led.claim("b", lambda o, n: True)
    assert led.complete(1, 0.01)
    assert led.sealed_ranges() == [[0, 10]]
    assert led.done and led.stats()["chunks_done"] == 3


# ------------------------------------------------------------- unit: engine

def _payload(size: int) -> bytes:
    return bytes(np.random.default_rng(7).integers(0, 255, size,
                                                   dtype=np.uint8))


def _engine(size, chunk, dest, payload, behaviors, **kw):
    """StripedPull over fake in-memory sources.  ``behaviors[addr]`` is a
    dict: delay (s), dead_after (chunks served before the source starts
    raising), short (serve n-1 bytes), partial (ranges list)."""
    served = {a: 0 for a in behaviors}

    async def fetch(addr, off, n):
        b = behaviors[addr]
        if b.get("dead_after") is not None \
                and served[addr] >= b["dead_after"]:
            raise ConnectionError(f"{addr} is down")
        if b.get("partial") is not None \
                and not range_covers(b["partial"], off, off + n):
            raise ChunkNotAvailable(f"{addr} lacks [{off}, {off + n})")
        await asyncio.sleep(b.get("delay", 0.0))
        if b.get("dead_after") is not None \
                and served[addr] >= b["dead_after"]:
            raise ConnectionError(f"{addr} died mid-chunk")
        take = n - 1 if b.get("short") else n
        dest[off:off + take] = payload[off:off + take]
        served[addr] += 1
        return take

    ledger = ChunkLedger(size, chunk)
    kw.setdefault("refresh_period_s", 0.05)
    kw.setdefault("stall_timeout_s", 10.0)
    return ledger, StripedPull(ledger, fetch_chunk=fetch, **kw), served


@pytest.mark.timeout(60)
def test_striping_across_three_sources():
    size, chunk = 96 * 1024, 4 * 1024          # 24 chunks
    payload, dest = _payload(size), bytearray(size)
    behaviors = {a: {"delay": 0.01} for a in ("s1", "s2", "s3")}
    ledger, eng, served = _engine(size, chunk, dest, payload, behaviors,
                                  per_source_window=2, total_window=8)
    stats = asyncio.run(eng.run(list(behaviors)))
    assert bytes(dest) == payload
    # every source carried part of the stripe concurrently
    assert set(stats["sources_used"]) == {"s1", "s2", "s3"}
    assert stats["chunks_done"] == 24
    assert sum(s["chunks"] for s in stats["per_source"].values()) == 24


@pytest.mark.timeout(60)
def test_steal_from_slow_source():
    size, chunk = 32 * 1024, 4 * 1024          # 8 chunks
    payload, dest = _payload(size), bytearray(size)
    behaviors = {"slow": {"delay": 5.0}, "fast": {"delay": 0.005}}
    ledger, eng, served = _engine(size, chunk, dest, payload, behaviors,
                                  per_source_window=1, total_window=8,
                                  steal_after_s=0.05)

    async def run():
        return await asyncio.wait_for(eng.run(list(behaviors)), 20)

    import time
    t0 = time.monotonic()
    stats = asyncio.run(run())
    elapsed = time.monotonic() - t0
    assert bytes(dest) == payload
    # the fast source hedged the slow source's in-flight chunk instead of
    # waiting out its 5 s fetch
    assert ledger.steals >= 1
    assert elapsed < 4.0, elapsed
    assert stats["per_source"]["fast"]["chunks"] == 8


@pytest.mark.timeout(60)
def test_resume_after_source_death_mid_pull():
    size, chunk = 64 * 1024, 4 * 1024          # 16 chunks
    payload, dest = _payload(size), bytearray(size)
    # "dying" serves 3 chunks then fails every fetch; "healthy" is slower
    # but steady — the pull must finish WITHOUT restarting from offset 0
    behaviors = {"dying": {"delay": 0.002, "dead_after": 3},
                 "healthy": {"delay": 0.01}}
    ledger, eng, served = _engine(size, chunk, dest, payload, behaviors,
                                  per_source_window=2, total_window=8,
                                  max_source_failures=2)
    stats = asyncio.run(eng.run(list(behaviors)))
    assert bytes(dest) == payload
    # the dying source stopped being useful (failures noted; "dead" only
    # latches if the pull outlives the failure debounce window)
    assert stats["per_source"]["dying"]["failures"] >= 1 \
        or stats["per_source"]["dying"]["dead"]
    # chunks the dead source landed stayed DONE in the ledger (resume, not
    # restart): the healthy source served only the remainder
    assert stats["per_source"]["dying"]["chunks"] == 3
    assert stats["per_source"]["healthy"]["chunks"] == 13
    assert stats["retried"] >= 1


@pytest.mark.timeout(60)
def test_short_chunk_rejected_and_repulled():
    size, chunk = 32 * 1024, 4 * 1024
    payload, dest = _payload(size), bytearray(size)
    behaviors = {"corrupt": {"short": True},
                 "good": {"delay": 0.005}}
    ledger, eng, served = _engine(size, chunk, dest, payload, behaviors,
                                  per_source_window=1, total_window=4,
                                  max_source_failures=2)
    stats = asyncio.run(eng.run(list(behaviors)))
    # short replies were detected (never sealed into the ledger) and every
    # chunk was re-pulled from the good source byte-exactly
    assert bytes(dest) == payload
    assert ledger.short_chunks >= 1
    assert stats["per_source"]["good"]["chunks"] == 8


@pytest.mark.timeout(60)
def test_mid_pull_source_refresh_folds_new_source():
    size, chunk = 64 * 1024, 4 * 1024
    payload, dest = _payload(size), bytearray(size)
    behaviors = {"origin": {"delay": 0.05}, "late": {"delay": 0.002}}

    async def refresh():
        return ["origin", "late"]     # the owner learned of a new holder

    ledger, eng, served = _engine(size, chunk, dest, payload, behaviors,
                                  per_source_window=2, total_window=8,
                                  refresh_sources=refresh,
                                  refresh_period_s=0.03)
    stats = asyncio.run(eng.run(["origin"]))   # starts with origin only
    assert bytes(dest) == payload
    assert "late" in stats["sources_used"]


@pytest.mark.timeout(60)
def test_partial_source_narrow_then_widened_ranges():
    size, chunk = 32 * 1024, 4 * 1024
    payload, dest = _payload(size), bytearray(size)
    # "part" only holds the first half; ChunkNotAvailable beyond it must
    # re-stripe onto the origin, not kill the source
    behaviors = {"origin": {"delay": 0.02},
                 "part": {"delay": 0.002, "partial": [[0, size // 2]]}}

    async def probe(addr):
        if addr == "part":
            return {"full": False, "ranges": [[0, size // 2]]}
        return {"full": True}

    ledger, eng, served = _engine(size, chunk, dest, payload, behaviors,
                                  per_source_window=2, total_window=8,
                                  probe_source=probe,
                                  refresh_period_s=0.03)
    stats = asyncio.run(eng.run(list(behaviors)))
    assert bytes(dest) == payload
    assert stats["per_source"]["part"]["dead"] is False
    assert stats["per_source"]["part"]["chunks"] >= 1


@pytest.mark.timeout(60)
def test_adaptive_runs_grow_under_clean_completions():
    """With run growth enabled, a healthy source's per-request size grows
    geometrically (1 -> 2 -> 4 ... base chunks) under clean completions —
    the engine issues FEWER, BIGGER fetches while the ledger keeps its
    base-chunk bookkeeping."""
    size, chunk = 256 * 1024, 4 * 1024          # 64 base chunks
    payload, dest = _payload(size), bytearray(size)
    sizes = []

    async def fetch(addr, off, n):
        sizes.append(n)
        dest[off:off + n] = payload[off:off + n]
        return n

    ledger = ChunkLedger(size, chunk)
    eng = StripedPull(ledger, fetch_chunk=fetch, per_source_window=1,
                      total_window=4, refresh_period_s=0.05,
                      stall_timeout_s=10.0, run_max_chunks=16)
    asyncio.run(eng.run(["s1"]))
    assert bytes(dest) == payload
    assert max(sizes) > chunk, "runs never grew past the base chunk"
    assert max(sizes) <= 16 * chunk
    # growth means fewer requests than chunks
    assert len(sizes) < 64
    assert eng.sources["s1"].run_len > 1


@pytest.mark.timeout(60)
def test_adaptive_runs_shrink_on_failure():
    """A failing fetch halves the source's run length (and requeues every
    base chunk of the failed run chunk-granularly)."""
    size, chunk = 64 * 1024, 4 * 1024
    payload, dest = _payload(size), bytearray(size)
    fails = [0]

    async def fetch(addr, off, n):
        # fail exactly once, after growth started
        if n > chunk and not fails[0]:
            fails[0] += 1
            raise ConnectionError("transient")
        dest[off:off + n] = payload[off:off + n]
        return n

    ledger = ChunkLedger(size, chunk)
    eng = StripedPull(ledger, fetch_chunk=fetch, per_source_window=1,
                      total_window=4, refresh_period_s=0.05,
                      stall_timeout_s=10.0, max_source_failures=10,
                      run_max_chunks=8)
    asyncio.run(eng.run(["s1"]))
    assert bytes(dest) == payload
    assert fails[0] == 1
    assert ledger.retries >= 1          # the failed run's chunks requeued


@pytest.mark.timeout(60)
def test_adaptive_run_clamped_by_receiver_largest_free():
    """The receiver-side re-clamp: with a fragmented receiving arena
    (small largest_free), grown runs are capped so no single request ever
    exceeds what the receiver's arena could absorb — chunk growth must
    never be able to force a spill mid-pull."""
    size, chunk = 256 * 1024, 4 * 1024
    payload, dest = _payload(size), bytearray(size)
    sizes = []

    async def fetch(addr, off, n):
        sizes.append(n)
        dest[off:off + n] = payload[off:off + n]
        return n

    clamp_chunks = 3                     # "largest_free" = 3 base chunks

    ledger = ChunkLedger(size, chunk)
    eng = StripedPull(ledger, fetch_chunk=fetch, per_source_window=1,
                      total_window=4, refresh_period_s=0.05,
                      stall_timeout_s=10.0, run_max_chunks=16,
                      clamp_run_chunks=lambda: clamp_chunks)
    asyncio.run(eng.run(["s1"]))
    assert bytes(dest) == payload
    assert max(sizes) <= clamp_chunks * chunk, \
        "a grown run exceeded the receiver's largest free block"


@pytest.mark.timeout(180)
def test_clamp_regression_fragmented_receiving_arena(ray_start_cluster,
                                                     tmp_path, monkeypatch):
    """End-to-end clamp regression: a receiving store whose arena is
    FRAGMENTED (largest_free far below object_transfer_chunk_max) pulls a
    multi-chunk object with adaptive growth on — every grown request
    stays within the receiver's largest free arena block, and the pull
    never evicts or spills an unrelated object mid-pull."""
    trace = str(tmp_path / "trace")
    os.makedirs(trace)
    base = 64 * 1024
    monkeypatch.setenv("RAYTPU_DISABLE_ZERO_COPY", "1")
    monkeypatch.setenv("RAYTPU_TRANSFER_TRACE_DIR", trace)
    monkeypatch.setenv("RAYTPU_OBJECT_TRANSFER_CHUNK_BYTES", str(base))
    monkeypatch.setenv("RAYTPU_OBJECT_TRANSFER_CHUNK_MAX",
                       str(16 * 1024 * 1024))

    cluster = ray_start_cluster
    origin = cluster.add_node(num_cpus=1,
                              object_store_memory=128 * 1024 * 1024)
    receiver = cluster.add_node(num_cpus=1,
                                object_store_memory=64 * 1024 * 1024)
    cluster.wait_for_nodes(2)
    cluster.connect_driver()

    import ray_tpu
    from ray_tpu.core.common import NodeAffinitySchedulingStrategy
    from ray_tpu.core.ids import ObjectID as OID
    from ray_tpu.core.rpc import RpcClient, run_async

    agent = RpcClient(receiver.address)
    if run_async(agent.call("store_stats")).get(
            "largest_free_block", 0) <= 0:
        pytest.skip("native arena unavailable: no largest_free to clamp on")

    def mk_filler(size):
        oid = OID.from_random()
        run_async(agent.call("store_create", object_id=oid, size=size))
        run_async(agent.call("store_seal", object_id=oid))
        return oid

    # the 8 MB payload is PRODUCED on the origin node (its task result
    # lands in that node's store), so the receiver must chunk-pull it
    mb = 1024 * 1024

    @ray_tpu.remote(num_cpus=1)
    def produce():
        return np.random.default_rng(5).integers(0, 255, 8 * mb,
                                                 dtype=np.uint8)

    ref = produce.options(scheduling_strategy=NodeAffinitySchedulingStrategy(
        origin.node_id, soft=False)).remote()
    expect = int(np.random.default_rng(5).integers(
        0, 255, 8 * mb, dtype=np.uint8).sum())

    # fragment the RECEIVING arena: [pin 20M][hole ~9M][pin 20M][pin 13M]
    # -> largest_free ~= the 9 MB hole; once the 8 MB pull destination
    # lands there, largest_free collapses to ~1 MB slivers while the
    # adaptive ceiling (16 MB) stays far above them
    pinned = []
    hole = None
    for size, pin in ((20 * mb, True), (9 * mb, False), (20 * mb, True),
                      (13 * mb, True)):
        oid = mk_filler(size)
        if pin:
            run_async(agent.call("pin_object", object_id=oid))
            pinned.append(oid)
        else:
            hole = oid
    run_async(agent.call("store_free", object_ids=[hole]))
    st0 = run_async(agent.call("store_stats"))
    assert st0["largest_free_block"] < 16 * mb, \
        f"arena not fragmented enough: {st0}"
    evictions_before = st0["num_evictions"]

    @ray_tpu.remote(num_cpus=1)
    def check(obj):
        return int(obj.sum())

    task = check.options(scheduling_strategy=NodeAffinitySchedulingStrategy(
        receiver.node_id, soft=False)).remote(ref)
    assert ray_tpu.get(task, timeout=120) == expect

    st1 = run_async(agent.call("store_stats"))
    assert st1["num_evictions"] == evictions_before, \
        "adaptive chunk growth forced an eviction/spill mid-pull"
    # every request the receiver issued stayed within what its arena
    # could absorb AFTER the destination landed (the live clamp bound)
    events = []
    for p in glob.glob(os.path.join(trace, "transfer-*.jsonl")):
        with open(p) as f:
            events += [json.loads(l) for l in f if l.strip()]
    sizes = [e["bytes"] for e in events if e["kind"] == "chunk"]
    assert sizes, "no chunk events traced"
    bound = max(st1["largest_free_block"], base)
    assert max(sizes) <= bound, \
        (f"grown request {max(sizes)} B exceeds the receiver's largest "
         f"free block {st1['largest_free_block']} B")
    for oid in pinned:
        run_async(agent.call("unpin_object", object_id=oid))
    run_async(agent.close())


@pytest.mark.timeout(60)
def test_all_sources_dead_raises_stall():
    size, chunk = 16 * 1024, 4 * 1024
    payload, dest = _payload(size), bytearray(size)
    behaviors = {"gone": {"dead_after": 0}}
    ledger, eng, served = _engine(size, chunk, dest, payload, behaviors,
                                  per_source_window=1, total_window=2,
                                  max_source_failures=1,
                                  refresh_period_s=0.05)
    with pytest.raises(TransferStalled):
        asyncio.run(eng.run(list(behaviors)))


# -------------------------------------------- unit: store partial serving

def test_store_partial_serving_and_object_ranges():
    from ray_tpu.core.ids import ObjectID
    from ray_tpu.core.object_store import NodeObjectStore

    async def run():
        store = NodeObjectStore("tp-test", 16 * 1024 * 1024)
        try:
            oid = ObjectID.from_random()
            store.create(oid, 8192)
            seg = store._entries[oid].segment
            seg.view()[0:4096] = b"a" * 4096
            store.mark_available(oid, 0, 4096)
            assert store.available_ranges(oid) == [[0, 4096]]
            # covered range serves; uncovered raises the typed miss
            assert store.read_chunk(oid, 0, 4096) == b"a" * 4096
            with pytest.raises(ChunkNotAvailable):
                store.read_chunk(oid, 2048, 4096)
            # an unsealed entry with NO landed ranges is also a typed miss
            seg.view()[4096:8192] = b"b" * 4096
            store.mark_available(oid, 4096, 4096)
            assert store.read_chunk(oid, 2048, 4096) == \
                b"a" * 2048 + b"b" * 2048
            store.seal(oid)
            assert store.available_ranges(oid) is None  # full now
            assert store.read_chunk(oid, 0, 8192) == \
                b"a" * 4096 + b"b" * 4096
        finally:
            store.shutdown()

    asyncio.run(run())


def test_owner_free_mid_pull_defers_under_transfer_pin():
    """Partial serving registers a puller with the owner after its FIRST
    chunk, so an owner-side store_free can now arrive mid-pull.  The pull
    holds a transfer pin (node_agent._pull_object_chunks), so the free
    must DEFER — the arena range stays valid under in-flight landings —
    and complete on the pull's unpin, after which the object is gone."""
    from ray_tpu.core.ids import ObjectID
    from ray_tpu.core.object_store import NodeObjectStore

    async def run():
        store = NodeObjectStore("tp-midfree-test", 16 * 1024 * 1024)
        try:
            oid = ObjectID.from_random()
            store.create(oid, 8192)
            store.pin(oid)                       # the pull's transfer pin
            seg = store._entries[oid].segment
            view = seg.view()
            store.mark_available(oid, 0, 4096)
            store.free(oid)                      # owner free mid-pull
            assert oid in store._entries, "free must defer under the pin"
            view[4096:8192] = b"z" * 4096        # late landings stay safe
            # freed-deferred: invisible to fetchers and chunk servers
            assert not store.contains(oid)
            with pytest.raises(KeyError):
                store.read_chunk(oid, 0, 4096)
            store.seal(oid)                      # pull completes
            store.unpin(oid)                     # releases the pin...
            assert oid not in store._entries     # ...completing the free
            assert store.get_path(oid) is None   # -> "vanished during pull"
        finally:
            store.shutdown()

    asyncio.run(run())


def test_free_of_unsealed_entry_wakes_seal_waiters():
    """A failed striped pull frees its unsealed segment — a concurrent
    fetcher parked on wait_sealed must wake immediately (and re-resolve),
    not sleep out its full timeout against an orphaned event."""
    from ray_tpu.core.ids import ObjectID
    from ray_tpu.core.object_store import NodeObjectStore

    async def run():
        store = NodeObjectStore("tp-free-test", 16 * 1024 * 1024)
        try:
            oid = ObjectID.from_random()
            store.create(oid, 4096)
            waiter = asyncio.ensure_future(store.wait_sealed(oid, 30.0))
            await asyncio.sleep(0.05)  # park the waiter
            store.free(oid)
            done, _ = await asyncio.wait({waiter}, timeout=2.0)
            assert waiter in done, "wait_sealed still parked after free"
            assert store.get_path(oid) is None
            assert oid not in store._sealed_events
        finally:
            store.shutdown()

    asyncio.run(run())


# ------------------------------------------------------- unit: bulk channel

@pytest.mark.timeout(60)
def test_bulk_channel_round_trip_partial_and_crc():
    """The threaded bulk transfer channel (core/bulk_transfer.py): sealed
    objects serve through a cached pinned full-object grant, covered
    ranges of partial holders serve per-chunk, uncovered ranges raise the
    typed ChunkNotAvailable, CRC replies verify — and every pin taken by
    the serving side is released afterwards."""
    import time as _time

    from ray_tpu.core.bulk_transfer import BulkPool, BulkServer
    from ray_tpu.core.ids import ObjectID
    from ray_tpu.core.object_store import NodeObjectStore
    from ray_tpu.core.rpc import get_loop

    store = NodeObjectStore("bulk-test", 64 * 1024 * 1024)
    payload = _payload(4 * 1024 * 1024)
    sealed = ObjectID.from_random()
    store.create(sealed, len(payload))
    store._entries[sealed].segment.view()[:len(payload)] = payload
    store.seal(sealed)
    part = ObjectID.from_random()
    store.create(part, len(payload))
    store._entries[part].segment.view()[0:65536] = payload[0:65536]
    store.mark_available(part, 0, 65536)

    loop = get_loop()

    async def acquire(oid, off, n):
        e = store._entries.get(oid)
        full = e is not None and e.sealed and not e.freed
        view = store.read_chunk_view(oid, 0, e.size) if full \
            else store.read_chunk_view(oid, off, n)
        return view, store.pin_for_serve(oid), full

    async def release(oid, kind):
        store.unpin(oid, kind)

    server = BulkServer(acquire, release, loop)
    pool = BulkPool()
    bulk_addr = f"127.0.0.1:{server.port}"
    try:
        sink = bytearray(len(payload))
        mv = memoryview(sink)
        # two chunks of the sealed object: the second rides the cached
        # grant (one acquire round trip for both)
        assert pool.fetch("rpc:0", bulk_addr, 0, sealed, 0, 1 << 20,
                          mv[0:1 << 20], False, 10.0) == 1 << 20
        assert pool.fetch("rpc:0", bulk_addr, 0, sealed, 1 << 20,
                          len(payload) - (1 << 20),
                          mv[1 << 20:], False, 10.0) \
            == len(payload) - (1 << 20)
        assert bytes(sink) == payload
        # CRC round trip verifies
        sink2 = bytearray(65536)
        assert pool.fetch("rpc:0", bulk_addr, 1, sealed, 0, 65536,
                          memoryview(sink2), True, 10.0) == 65536
        assert bytes(sink2) == payload[:65536]
        # partial holder: covered range serves, uncovered is typed
        sink3 = bytearray(65536)
        assert pool.fetch("rpc:0", bulk_addr, 0, part, 0, 65536,
                          memoryview(sink3), False, 10.0) == 65536
        assert bytes(sink3) == payload[:65536]
        with pytest.raises(ChunkNotAvailable):
            pool.fetch("rpc:0", bulk_addr, 0, part, 65536, 65536,
                       memoryview(bytearray(65536)), False, 10.0)
        # pins drain once the grants are released (partial grants release
        # per chunk; the cached sealed grant releases on close below)
        deadline = _time.monotonic() + 5
        while _time.monotonic() < deadline:
            if store._entries[part].pinned == 0:
                break
            _time.sleep(0.02)
        assert store._entries[part].pinned == 0
    finally:
        pool.close()
        server.close()
    deadline = _time.monotonic() + 5
    while _time.monotonic() < deadline:
        if store._entries[sealed].pinned == 0:
            break
        _time.sleep(0.02)
    assert store._entries[sealed].pinned == 0, \
        "cached grant's pin leaked past connection close"
    store.shutdown()


# ------------------------------------------------ unit: sink (readinto) RPC

@pytest.mark.timeout(60)
def test_call_into_lands_oob_reply_in_sink():
    """A >=256 KB PickleBuffer reply lands DIRECTLY into the registered
    sink view (no intermediate bytes, no slice-assign) and the returned
    value is a view over that memory; small in-band replies still come
    back as bytes for the caller to place."""
    from ray_tpu.core.rpc import RpcClient, RpcServer, run_async

    blob = _payload(512 * 1024)

    class H:
        async def handle_read(self, offset: int, length: int):
            import pickle
            return pickle.PickleBuffer(blob[offset:offset + length])

    async def run():
        server = await RpcServer(H(), "127.0.0.1", 0).start()
        client = RpcClient(server.address)
        try:
            dest = bytearray(512 * 1024)
            sink = memoryview(dest)[0:300 * 1024]
            got = await client.call_into("read", sink, offset=0,
                                         length=300 * 1024)
            assert isinstance(got, memoryview)
            assert got.nbytes == 300 * 1024
            assert bytes(dest[:300 * 1024]) == blob[:300 * 1024]
            # in-band (below _VEC_MIN_BUF): bytes back, sink untouched
            tail = await client.call_into(
                "read", memoryview(dest)[300 * 1024:], offset=300 * 1024,
                length=8 * 1024)
            assert isinstance(tail, (bytes, bytearray))
            assert bytes(tail) == blob[300 * 1024:308 * 1024]
        finally:
            await client.close()
            await server.stop()

    run_async(run())


def test_chunk_checksum_bytes_and_memoryview_agree():
    from ray_tpu.core.transfer import chunk_checksum
    data = _payload(100_000)
    c1, a1 = chunk_checksum(data)
    view = memoryview(bytearray(data))          # writable, like a segment
    c2, a2 = chunk_checksum(view)
    assert (c1, a1) == (c2, a2)
    c3, _ = chunk_checksum(data[:-1])
    assert c3 != c1


# ----------------------------------------- cluster: schema guard (tier-1)

@pytest.mark.timeout(180)
def test_chunked_pull_timeline_schema(ray_start_cluster, tmp_path,
                                      monkeypatch):
    """Schema guard for the broadcast bench artifact: a 2-node chunked
    pull must emit timeline events from which bench_broadcast's summary —
    per-source throughput, ledger breakdown, and a computable
    relay_fraction_of_chunk_bytes — can be built.  Fails if the event or
    summary fields silently drift."""
    trace = str(tmp_path / "trace")
    os.makedirs(trace)
    monkeypatch.setenv("RAYTPU_DISABLE_ZERO_COPY", "1")
    monkeypatch.setenv("RAYTPU_TRANSFER_TRACE_DIR", trace)
    monkeypatch.setenv("RAYTPU_OBJECT_TRANSFER_CHUNK_BYTES", str(256 * 1024))

    cluster = ray_start_cluster
    nids = []
    for _ in range(2):
        node = cluster.add_node(num_cpus=1,
                                object_store_memory=128 * 1024 * 1024)
        nids.append(node.node_id)
    cluster.wait_for_nodes(2)
    cluster.connect_driver()

    import ray_tpu
    from ray_tpu.core.common import NodeAffinitySchedulingStrategy

    payload = np.random.default_rng(1).integers(0, 255, 2 * 1024 * 1024,
                                                dtype=np.uint8)
    ref = ray_tpu.put(payload)

    @ray_tpu.remote(num_cpus=1)
    def check(obj):
        return int(obj.sum())

    refs = [check.options(scheduling_strategy=(
        NodeAffinitySchedulingStrategy(nid, soft=False))).remote(ref)
        for nid in nids]
    expect = int(payload.sum())
    assert all(v == expect for v in ray_tpu.get(refs, timeout=120))

    from bench_broadcast import _collect_timeline
    # any agent address works as "origin" for the schema check
    events = []
    for p in glob.glob(os.path.join(trace, "transfer-*.jsonl")):
        with open(p) as f:
            events += [json.loads(l) for l in f if l.strip()]
    chunks = [e for e in events if e["kind"] == "chunk"]
    assert chunks, "chunked path emitted no chunk events"
    for e in chunks:
        for k in ("source", "offset", "bytes", "t0", "t1", "stolen",
                  "socket"):
            assert k in e, (k, e)
    summaries = [e for e in events if e["kind"] == "pull_summary"]
    assert summaries, "no pull_summary events"
    for s in summaries:
        for k in ("sources_used", "per_source", "chunks_done", "retried",
                  "stolen", "short", "sockets_per_source",
                  "chunk_max_bytes"):
            assert k in s, (k, s)
    origin = chunks[0]["source"]
    summary, _ = _collect_timeline(trace, origin)
    # relay fraction must be COMPUTABLE from the new fields
    assert summary["relay_fraction_of_chunk_bytes"] is not None
    assert 0.0 <= summary["relay_fraction_of_chunk_bytes"] <= 1.0
    assert summary["chunk_pulls"] == len(chunks)
    assert isinstance(summary["per_source"], dict) and summary["per_source"]
    for addr, row in summary["per_source"].items():
        assert {"bytes", "chunks", "gbps", "sockets"} <= set(row), row
        assert row["sockets"] >= 1
    assert {"chunks_done", "retried", "stolen", "short"} \
        <= set(summary["ledger"]), summary["ledger"]
    # adaptive-chunk + multi-socket schema: the trajectory lists every
    # request's byte size in start order, sockets_per_source surfaces the
    # plane's socket fan-out
    assert summary["chunk_bytes_trajectory"], summary
    assert all(isinstance(b, int) and b > 0
               for b in summary["chunk_bytes_trajectory"])
    assert summary["sockets_per_source"] >= 1


# --------------------------------------------------- cluster: chaos drops

@pytest.mark.chaos
@pytest.mark.timeout(120)
def test_broadcast_survives_frame_drops_byte_exact(tmp_path, monkeypatch):
    """Chunked broadcast through 5% frame drops on the read_chunk link
    (seeded, deterministic): every puller completes with byte-exact
    content — chunk-granular retry against the ledger, never a silent
    short/corrupt seal."""
    from ray_tpu.core.cluster import Cluster

    spec = json.dumps({"seed": 11, "rules": [
        {"kind": "drop_request", "prob": 0.05, "method": "read_chunk"},
        {"kind": "drop_reply", "prob": 0.05, "method": "read_chunk"},
    ]})
    monkeypatch.setenv("RAYTPU_CHAOS_SPEC", spec)
    monkeypatch.setenv("RAYTPU_DISABLE_ZERO_COPY", "1")
    monkeypatch.setenv("RAYTPU_OBJECT_TRANSFER_CHUNK_BYTES", str(256 * 1024))
    # checksum mode ON: exercises the verify-then-copy scratch path (a
    # work-steal straggler must never land unverified bytes over a DONE
    # chunk) on top of the frame drops
    monkeypatch.setenv("RAYTPU_OBJECT_TRANSFER_CHECKSUM", "1")

    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 2})
    nids = []
    try:
        for _ in range(3):
            node = cluster.add_node(num_cpus=1,
                                    object_store_memory=128 * 1024 * 1024)
            nids.append(node.node_id)
        cluster.wait_for_nodes(4)
        cluster.connect_driver()

        import ray_tpu
        from ray_tpu.core.common import NodeAffinitySchedulingStrategy

        payload = np.random.default_rng(3).integers(
            0, 255, 8 * 1024 * 1024, dtype=np.uint8)
        ref = ray_tpu.put(payload)
        digest = int(payload.sum())
        head = int(payload[:4096].sum())
        tail = int(payload[-4096:].sum())

        @ray_tpu.remote(num_cpus=1, max_retries=5)
        def verify(obj):
            # byte-exact evidence beyond a single checksum: whole-object
            # sum plus head/tail windows (catches offset shifts a sum of
            # permuted chunks would hide)
            return (int(obj.sum()), int(obj[:4096].sum()),
                    int(obj[-4096:].sum()))

        refs = [verify.options(scheduling_strategy=(
            NodeAffinitySchedulingStrategy(nid, soft=False))).remote(ref)
            for nid in nids]
        for v in ray_tpu.get(refs, timeout=180):
            assert v == (digest, head, tail)
    finally:
        import ray_tpu
        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
        cluster.shutdown()
