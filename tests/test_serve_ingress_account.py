"""A request's way into a replica and out of it, accounted where it happens
(PR 56): the three legs of the way in (the caller's send to the call's
arrival, that to the replica's method, that to the engine's submit), the
buffered stream's polls and chunks, the native stream's delivery in its two
parts, and the end of a stream.  A replica actor over stub deployments in
this process for the stamps, a small runtime for what only the wire shows;
one tiny model where the engine's own stamps are the subject.  Counts and
differences of stamps the test chose, never speeds."""

import asyncio
import time

import cloudpickle
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.core.runtime_context import _task_context
from ray_tpu.serve.replica import ReplicaActor


class Counted:
    """A generator deployment with no model: ``n`` chunks, ``gap_s`` apart."""

    async def __call__(self, body):
        for i in range(body["n"]):
            await asyncio.sleep(body.get("gap_s", 0.0))
            yield i

    #: the replica hands its account to a deployment that declares this
    request_account = None

    def stats(self):
        return self.request_account.snapshot()


def _replica(cls=Counted, *args, **kwargs):
    return ReplicaActor("ingdep", "serve:ingdep:1",
                        cloudpickle.dumps((cls, args, kwargs)))


async def _drain(agen):
    return [c async for c in agen]


def _call(rep, received_at=None, sent_at=None, n=3):
    """One native streaming call as the core worker would make it: the
    task context carries the arrival stamp."""
    async def run():
        ctx = {"task_id": None, "job_id": None}
        if received_at is not None:
            ctx["received_at"] = received_at
        token = _task_context.set(ctx)
        try:
            return await _drain(rep.handle_request_gen(
                ({"n": n},), {}, None, sent_at))
        finally:
            _task_context.reset(token)
    return asyncio.run(run())


def _delta(rep, before=None):
    """How the replica's account grew since ``before`` (since its start:
    every replica of these tests has an account of its own)."""
    now = rep.account.snapshot()
    return {k: now[k] - (before[k] if before else 0) for k in now}


def test_the_way_in_is_booked_from_the_two_stamps():
    """``ingress_queue_s`` grows by what a ``received_at`` in the past
    says, ``ingress_transit_s`` by a ``sent_at`` before it."""
    rep = _replica()
    received_at = time.time() - 0.5
    assert _call(rep, received_at, received_at - 0.25) == [0, 1, 2]
    d = _delta(rep)
    assert d["ingress_requests"] == d["ingress_transit_n"] == 1
    assert d["ingress_transit_s"] == pytest.approx(0.25, abs=1e-6)
    assert 0.5 <= d["ingress_queue_s"] < 0.5 + 5.0
    assert d["ingress_clock_skew_n"] == 0
    # the stream's end was delivered: a deployment that says nothing of
    # its request's end has it at its generator's end
    assert d["finished_streams"] == 1 and d["finish_deliver_s"] >= 0


def test_a_call_without_sent_at_is_a_request_with_no_transit():
    rep = _replica()
    assert _call(rep, time.time() - 0.1) == [0, 1, 2]
    d = _delta(rep)
    assert d["ingress_requests"] == 1
    assert d["ingress_transit_n"] == 0 and d["ingress_transit_s"] == 0
    assert d["ingress_queue_s"] >= 0.1
    # outside a core worker there is no arrival stamp: the first line is it
    before = rep.account.snapshot()
    assert _call(rep) == [0, 1, 2]
    d = _delta(rep, before)
    assert d["ingress_requests"] == 1 and d["ingress_queue_s"] == 0


def test_named_methods_are_no_requests():
    """``stats`` through ``handle_request`` and ``next_chunks`` on the actor
    count no request; a streaming call of a named method none either."""
    rep = _replica()

    async def run():
        await rep.handle_request((), {}, "stats", time.time())
        await rep.handle_request_streaming("named", (), {}, "stats")
        return await rep.next_chunks("named", 0)

    chunks, nxt, done = asyncio.run(run())
    assert (len(chunks), nxt, done) == (1, 1, True)
    d = _delta(rep)
    assert d["ingress_requests"] == d["ingress_transit_n"] == 0
    assert d["polls"] == 1
    # not a user request's stream: its chunks and its end are not booked
    assert d["buffered_chunks"] == d["finished_streams"] == 0


def test_a_sent_at_after_the_arrival_is_clamped_and_counted():
    rep = _replica()
    received_at = time.time() - 0.2
    assert _call(rep, received_at, received_at + 0.1) == [0, 1, 2]
    d = _delta(rep)
    assert d["ingress_clock_skew_n"] == 1
    assert d["ingress_transit_n"] == 1 and d["ingress_transit_s"] == 0
    assert d["ingress_requests"] == 1 and d["ingress_queue_s"] >= 0.2


def test_a_buffered_streams_polls_and_chunks_are_booked():
    """In process, the polls interleaved by hand: one that arrives before
    the first chunk and waits for it, one that takes the rest at the end."""
    rep = _replica()

    async def run():
        serving = asyncio.ensure_future(rep.handle_request_streaming(
            "s1", ({"n": 3, "gap_s": 0.05},), {}, None, time.time()))
        await asyncio.sleep(0)
        first = await rep.next_chunks("s1", 0)
        await serving                          # the generator has ended
        await asyncio.sleep(0.05)
        return first, await rep.next_chunks("s1", first[1])

    (chunks, cursor, done), (rest, end, done2) = asyncio.run(run())
    assert (chunks, cursor, done) == ([0], 1, False)
    assert (rest, end, done2) == ([1, 2], 3, True)
    d = _delta(rep)
    assert d["ingress_requests"] == 1
    assert (d["polls"], d["polls_empty"], d["polls_before_end"]) == (2, 0, 1)
    assert d["buffered_chunks"] == 3 and d["first_chunks"] == 1
    # the first chunk was taken by a poll that stood waiting for it; the
    # last waited the 50 ms between the generator's end and its poll
    assert 0 <= d["first_chunk_wait_s"] < 0.045
    assert d["buffer_wait_s"] >= 0.05 + d["first_chunk_wait_s"]
    assert d["finished_streams"] == 1 and d["finish_deliver_s"] >= 0.05
    assert not rep._stream_tracks and not rep._streams


def test_an_abandoned_stream_is_forgotten_and_not_finished():
    rep = _replica()

    async def run():
        await rep.handle_request_streaming(
            "gone", ({"n": 2},), {}, None, time.time())
        await rep.cancel_stream("gone")

    asyncio.run(run())
    d = _delta(rep)
    assert d["ingress_requests"] == 1 and d["finished_streams"] == 0
    assert not rep._stream_tracks and not rep._streams


def test_a_poll_that_times_out_is_counted_empty(monkeypatch):
    rep = _replica()
    naps = []

    async def no_nap(s):
        naps.append(s)

    async def run():
        rep._streams["idle"], rep._stream_done["idle"] = [], False
        monkeypatch.setattr(asyncio, "sleep", no_nap)
        return await rep.next_chunks("idle", 0)

    assert asyncio.run(run()) == ([], 0, False)
    assert len(naps) == 200
    d = _delta(rep)
    assert (d["polls"], d["polls_empty"]) == (1, 1)


# ------------------------------------------------- with the engine's stamps

@pytest.fixture(scope="module")
def llm_replica():
    from ray_tpu.serve.llm import LLMServer
    rep = _replica(LLMServer, "tiny", num_slots=4, max_len=64,
                   engine_kwargs={"buckets": (16, 32)})
    try:
        yield rep
    finally:
        rep.callable.engine.shutdown()


def test_native_delivery_is_thread_plus_loop(llm_replica):
    """Over ``handle_request_gen``: a token's way from its emit to its
    yield is the executor thread's part plus the loop's, to the float; the
    yield's hold and the submit leg are booked; the stream's end is timed
    from the engine's retire."""
    rep, server = llm_replica, llm_replica.callable
    s0 = server.stats()
    body = {"tokens": [1, 2, 3], "max_tokens": 6}

    async def two():
        return await asyncio.gather(
            _drain(rep.handle_request_gen((body,), {}, None, time.time())),
            _drain(rep.handle_request_gen((body,), {}, None)))

    outs = asyncio.run(two())
    assert [len(o) for o in outs] == [6, 6]
    s1 = server.stats()
    d = {k: s1[k] - s0[k] for k in (
        "delivered_tokens", "deliver_lag_s", "deliver_thread_s",
        "deliver_loop_s", "yield_hold_s", "ingress_requests",
        "ingress_transit_n", "ingress_submit_s", "finished_streams",
        "finish_deliver_s", "retired_requests")}
    assert d["delivered_tokens"] == 12
    assert d["deliver_thread_s"] > 0 and d["deliver_loop_s"] > 0
    assert d["deliver_thread_s"] + d["deliver_loop_s"] == pytest.approx(
        d["deliver_lag_s"], abs=1e-9)
    assert d["yield_hold_s"] > 0
    assert (d["ingress_requests"], d["ingress_transit_n"]) == (2, 1)
    assert 0 < d["ingress_submit_s"] < 5.0
    assert d["finished_streams"] == d["retired_requests"] == 2
    assert 0 < d["finish_deliver_s"] < 5.0


# ------------------------------------------------------- over the wire

@pytest.fixture(scope="module")
def served():
    from ray_tpu.utils.testing import CPU_WORKER_ENV
    ray_tpu.init(num_cpus=4, worker_env=dict(CPU_WORKER_ENV))
    try:
        yield serve.run(serve.deployment(Counted, name="ingdep").bind(),
                        timeout_s=120)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


@pytest.mark.timeout(120)
def test_an_async_actors_method_sees_its_task_context(served):
    """The core worker hands an async actor's method the task context the
    sync path always had, with the call's arrival in it."""
    @ray_tpu.remote
    class Asker:
        async def ask(self):
            import time
            from ray_tpu.core import runtime_context as rc
            ctx = rc._task_context.get()
            return (rc.get_runtime_context().get_task_id(),
                    ctx and ctx.get("received_at"), time.time())

    a = Asker.remote()
    t0 = time.time()
    task_id, received_at, ran_at = ray_tpu.get(a.ask.remote(), timeout=60)
    assert task_id is not None
    assert t0 <= received_at <= ran_at <= time.time()


@pytest.mark.timeout(120)
def test_a_handles_stream_carries_the_callers_stamp(served):
    """Over ``DeploymentHandle.stream``: every request carries the router's
    ``sent_at``, the first chunk's wait and the polls before the end are
    recorded, and the end's delivery is timed."""
    h = served
    before = h.stats.remote().result(timeout_s=60)
    assert list(h.stream({"n": 4, "gap_s": 0.02})) == [0, 1, 2, 3]
    deadline = time.monotonic() + 30
    while True:
        now = h.stats.remote().result(timeout_s=60)
        d = {k: now[k] - before[k] for k in now}
        if d["finished_streams"] >= 1 or time.monotonic() > deadline:
            break
        time.sleep(0.1)
    assert d["ingress_requests"] == d["ingress_transit_n"] == 1
    assert d["ingress_transit_s"] > 0 and d["ingress_queue_s"] > 0
    assert d["first_chunks"] == 1 and d["first_chunk_wait_s"] >= 0
    assert d["buffered_chunks"] == 4
    assert d["polls"] >= 1 and d["polls_before_end"] >= 0
    assert d["finished_streams"] == 1 and d["finish_deliver_s"] > 0
