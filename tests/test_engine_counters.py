"""The serve path measured from inside: the counters ``LLMEngine`` /
``LLMServer`` keep where the work happens (admitted tokens, request stages,
engine-loop phases, deliver lag), which the benchmark's per-layer readers
difference over a window.  Tiny preset on the CPU: counts, never speeds."""

import asyncio
import time

import pytest

from ray_tpu.util.profiler import ENGINE_PHASES


@pytest.fixture(scope="module")
def tiny_cfg():
    from ray_tpu.models import config as mcfg
    return mcfg.tiny()


def _engine(cfg, **kw):
    from ray_tpu.serve.llm import LLMEngine
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_len", 128)
    kw.setdefault("buckets", (16, 32, 64))
    return LLMEngine(cfg, **kw)


def _spy_admits(eng):
    """Every admit batch as ``(rows, bucket, real tokens)``."""
    seen, orig = [], eng._admitted

    def spy(reqs, slots, first, bucket, tokens_real):
        seen.append((len(reqs), bucket, tokens_real))
        orig(reqs, slots, first, bucket, tokens_real)

    eng._admitted = spy
    return seen


def _run(eng, prompts, max_tokens=3):
    reqs = [eng.submit(p, max_tokens=max_tokens) for p in prompts]
    from ray_tpu.serve.llm import _FLUSH
    for r in reqs:
        while r.out.get(timeout=120) is not _FLUSH:
            pass
    return reqs


PROMPT_LENS = (5, 20, 40, 7, 33, 12)


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_admitted_tokens_are_counted_real_and_padded(tiny_cfg, paged):
    kw = dict(paged=True, page_size=8, num_pages=96) if paged else {}
    eng = _engine(tiny_cfg, **kw)
    try:
        seen, programs = _spy_admits(eng), _spy_programs(eng)
        prompts = [[1 + (i + j) % 50 for j in range(n)]
                   for i, n in enumerate(PROMPT_LENS)]
        _run(eng, prompts)
        c = eng.counters()
        # buckets this short are walked whole: no chunks
        assert not any(p.chunks for p, _ in programs)
        assert c["admit_tokens_real"] == sum(PROMPT_LENS)
        assert c["admit_tokens_real"] == sum(real for _, _, real in seen)
        # every position the chip walked is counted once, as a prompt token
        # or as padding: the rows that held a request, each rounded up to
        # its bucket, and no row of the [prefill_batch, bucket] arrays
        # besides
        assert c["admit_tokens_real"] + c["admit_tokens_padded"] == sum(
            rows * bucket for rows, bucket, _ in seen)
        assert c["admit_tokens_padded"] > 0
        assert any(rows < eng.prefill_batch for rows, _, _ in seen)
        assert c["admitted_requests"] == c["first_tokens"] == len(prompts)
        assert sum(rows for rows, _, _ in seen) == len(prompts)
        # the row accounting the committed readers use is untouched
        assert eng.breakdown()["admit_batches"] == len(seen)
    finally:
        eng.shutdown()


def _latent_engine():
    """The tiny latent + dropless tree of ``tests/test_latent.py`` behind an
    engine, with a shortest chunk of 4 and an expert's tile of 2 rows
    slipped in while its programs are traced (8 experts, 2 a token: a chunk
    of 8, and the bucket of 32 is four)."""
    import functools
    from unittest import mock

    import jax.numpy as jnp

    import kinds
    from ray_tpu.models import decode
    cfg, params = kinds.tiny("xing4_0")
    width = decode.prefill_width
    small = mock.patch.multiple(
        decode, EXPERT_TILE=2,
        prefill=functools.partial(decode.prefill, chunk=4),
        prefill_width=lambda cache, bucket, cfg, chunk=4: width(
            cache, bucket, cfg, 4))
    small.start()
    eng = _engine(cfg, params=params, num_slots=2, max_len=64,
                  buckets=(16, 32), compute_dtype=jnp.float32)
    return eng, small.stop


@pytest.mark.parametrize("kind", ["dense", "latent"])
def test_chunked_rows_count_the_chunks_they_walked(tiny_cfg, kind):
    """A bucket of four chunks of a tree of rows alone, K/V or latent, is
    walked in the chunks a prompt fills (``decode.prefill_width``; the
    latent tree's chunk is the one its experts ask for, not the shortest):
    the padding counted is what the chip walked less the prompt, a row
    rounded up to whole chunks and not to its bucket, and the admit programs
    (their ``chunks``, which ride the ``raytpu:engine.admit`` span) say how
    often."""
    from ray_tpu.models import decode
    if kind == "latent":
        chunk, bucket, short = 8, 32, 16
        eng, undo = _latent_engine()
    else:
        chunk, bucket, short = (decode.PREFILL_CHUNK,
                                4 * decode.PREFILL_CHUNK, 64)
        eng, undo = _engine(tiny_cfg, num_slots=2, max_len=bucket + 64,
                            buckets=(short, bucket)), lambda: None
    try:
        assert decode.prefill_width(eng.cache, bucket, eng.cfg) == chunk
        assert decode.prefill_width(eng.cache, short, eng.cfg) == short
        seen, programs = _spy_admits(eng), _spy_programs(eng)
        # (3, whole, 4, 4 and 3 chunks; the second sits in the short bucket)
        lens = (chunk * 2 + chunk // 8, short * 5 // 8,
                chunk * 3 + chunk // 4, bucket, chunk * 3)
        _run(eng, [[1 + (i + j) % 50 for j in range(n)]
                   for i, n in enumerate(lens)], max_tokens=2)
        c = eng.counters()
        assert c["admit_tokens_real"] == sum(lens)
        chunked = [p for p, _ in programs if p.chunks]
        assert sum(p.chunks for p in chunked) == 3 + 4 + 4 + 3
        assert sum(len(p.rows) for p in chunked) == 4
        assert c["admit_tokens_padded"] == (
            (chunk - chunk // 8) + (short - short * 5 // 8)
            + (chunk - chunk // 4) + 0 + 0)
        # every admit of the long bucket walked fewer positions than its
        # rows times the bucket, unless every prompt needed all four chunks
        assert c["admit_tokens_real"] + c["admit_tokens_padded"] < sum(
            rows * b for rows, b, _ in seen)
        assert c["first_tokens"] == len(lens)
    finally:
        eng.shutdown()
        undo()


def test_prefix_hit_counts_only_the_prefilled_suffix(tiny_cfg):
    eng = _engine(tiny_cfg, paged=True, page_size=8, num_pages=96)
    try:
        seen = _spy_admits(eng)
        shared = [3 + i % 40 for i in range(32)]          # four full pages
        first, second = shared + [7, 8, 9], shared + [11, 12, 13, 14, 15]
        _run(eng, [first])
        before = eng.counters()
        _run(eng, [second])
        after = eng.counters()
        reused = eng.prefix.stats()["tokens_reused"]
        assert reused == 32
        assert (after["admit_tokens_real"] - before["admit_tokens_real"]
                == len(second) - reused)
        rows, bucket, real = seen[-1]
        assert (rows, real) == (1, len(second) - reused)
        assert bucket == eng._bucket_for(len(second) - reused)
        # one row an admit, walked to the end of its bucket
        assert (after["admit_tokens_real"] + after["admit_tokens_padded"]
                == sum(rows * b for rows, b, _ in seen))
    finally:
        eng.shutdown()


def test_request_stages_sum_waits_per_request(tiny_cfg):
    eng = _engine(tiny_cfg)
    try:
        t0 = time.monotonic()
        reqs = _run(eng, [[1, 2, 3, 4]] * 5, max_tokens=4)
        wall = time.monotonic() - t0
        c = eng.counters()
        assert c["admitted_requests"] == c["first_tokens"] == 5
        # submit -> dispatch of the admit, that dispatch -> first token:
        # the two stages of every request, in order, inside the run
        for r in reqs:
            assert r.submitted_at <= r.admitted_at <= r.emit_times[0]
            assert len(r.emit_times) == r.generated == 4
            assert r.emit_times == sorted(r.emit_times)
        assert c["queue_wait_s"] == pytest.approx(
            sum(r.admitted_at - r.submitted_at for r in reqs))
        assert c["first_token_wait_s"] == pytest.approx(
            sum(r.emit_times[0] - r.admitted_at for r in reqs))
        assert 0 < c["first_token_wait_s"] <= 5 * wall
        assert 0 <= c["queue_wait_s"] <= 5 * wall
    finally:
        eng.shutdown()


def test_gen_request_keeps_one_clock_per_stage():
    from ray_tpu.serve.llm import GenRequest
    clocks = [s for s in GenRequest.__slots__
              if s.endswith(("_at", "_wall", "_times"))]
    assert sorted(clocks) == ["admitted_at", "emit_times", "retired_at",
                              "seen_at", "submitted_at"]


def test_phases_partition_the_engine_threads_time(tiny_cfg):
    eng = _engine(tiny_cfg)
    try:
        eng.warmup(16)                       # compile outside the interval
        c0 = eng.counters()
        _run(eng, [[1, 2, 3]] * 6, max_tokens=12)
        time.sleep(0.15)                     # some idle passes too
        c1 = eng.counters()
        wall = c1["t_mono"] - c0["t_mono"]
        deltas = {ph: c1[f"loop_{ph}_s"] - c0[f"loop_{ph}_s"]
                  for ph in ENGINE_PHASES}
        counts = {ph: c1[f"loop_{ph}_n"] - c0[f"loop_{ph}_n"]
                  for ph in ENGINE_PHASES}
        assert all(d >= 0 for d in deltas.values()), deltas
        assert 0.9 * wall <= sum(deltas.values()) <= wall * 1.0001, (
            deltas, wall)
        # one interval per stretch, never one per token: 72 tokens came
        # out of far fewer emit intervals, each drain one fetch + one emit
        assert counts["fetch"] == counts["emit"] > 0
        assert counts["emit"] < 72
        assert counts["admit"] >= 1 and counts["dispatch"] >= 1
        assert counts["idle"] >= 1 and deltas["idle"] > 0
        assert c1["loop_iterations"] > c0["loop_iterations"]
    finally:
        eng.shutdown()


def test_open_phase_counts_up_to_the_snapshot(tiny_cfg):
    """A snapshot taken while the thread sits in one long interval (here:
    idle) includes the part of it already spent."""
    eng = _engine(tiny_cfg)
    try:
        time.sleep(0.05)
        c0 = eng.counters()
        time.sleep(0.3)
        c1 = eng.counters()
        wall = c1["t_mono"] - c0["t_mono"]
        assert c1["loop_idle_s"] - c0["loop_idle_s"] >= 0.9 * wall
    finally:
        eng.shutdown()


# --------------------------------- a request's wait, by cause (PR 42)

WAIT_KEYS = ("queue_look_s", "queue_held_s", "first_token_ahead_s",
             "first_token_own_row_s", "first_token_other_rows_s",
             "stream_s", "stream_admit_s")
ENGINES = {
    "dense": {},
    "paged": dict(paged=True, page_size=8, num_pages=96),
    "spec": dict(spec_decode_enabled=True, spec_k=2, spec_draft_layers=1),
}
EPS = 1e-9          # float additions in another order, never a clock


def _two_layers(cfg):
    import dataclasses
    return dataclasses.replace(cfg, num_layers=2)   # a draft needs one less


def _live_stream(eng, max_tokens=100):
    """A request with its first token out and most of its answer to come."""
    from ray_tpu.serve.llm import _FLUSH
    long = eng.submit([5, 6, 7], max_tokens=max_tokens)
    assert long.out.get(timeout=120) is not _FLUSH
    return long


def _submit_together(eng, prompts, max_tokens=3):
    """Requests that one look of the loop finds together: they join the
    queue under its own lock, which the loop's every reading takes."""
    from ray_tpu.serve.llm import GenRequest
    reqs = [GenRequest(list(p), max_tokens, 0.0, 0, None) for p in prompts]
    with eng._pending.mutex:
        eng._pending.queue.extend(reqs)
    eng._wake.set()
    return reqs


def _finish(reqs):
    from ray_tpu.serve.llm import _FLUSH
    for r in reqs:
        while r.out.get(timeout=120) is not _FLUSH:
            pass


@pytest.mark.parametrize("kind", list(ENGINES))
def test_the_waits_are_partitioned_by_cause(tiny_cfg, kind):
    """Queue wait = the wait for a look + the looks that left the request
    + the host's admit; first-token wait = the programs ahead + the own
    row + the other rows + the fetch's return to the emit.  Sums and signs
    on every engine kind, from the counters and from each request."""
    eng = _engine(_two_layers(tiny_cfg), **ENGINES[kind])
    try:
        prompts = [[1 + (i + j) % 50 for j in range(n)]
                   for i, n in enumerate(PROMPT_LENS)]
        reqs = _run(eng, prompts, max_tokens=12)
        c = eng.counters()
        assert all(c[k] >= 0 for k in WAIT_KEYS), c
        assert c["queue_look_s"] + c["queue_held_s"] <= c["queue_wait_s"] + EPS
        parts = [c["first_token_ahead_s"], c["first_token_own_row_s"],
                 c["first_token_other_rows_s"]]
        assert sum(parts) <= c["first_token_wait_s"] + EPS
        assert c["first_token_own_row_s"] > 0
        assert c["stream_admit_s"] <= c["stream_s"]
        for r in reqs:
            assert r.submitted_at <= r.seen_at <= r.admitted_at
            a = r.prefill_attrs
            assert a["ahead_s"] >= 0 and a["own_row_s"] > 0
            assert a["rows"] >= 1 and a["chunks"] == 0
            assert (a["ahead_s"] + a["own_row_s"]
                    <= r.emit_times[0] - r.admitted_at + EPS)
        assert c["queue_look_s"] == pytest.approx(
            sum(r.seen_at - r.submitted_at for r in reqs))
        assert c["first_token_ahead_s"] == pytest.approx(
            sum(r.prefill_attrs["ahead_s"] for r in reqs))
        assert c["first_token_own_row_s"] == pytest.approx(
            sum(r.prefill_attrs["own_row_s"] for r in reqs))
    finally:
        eng.shutdown()


@pytest.mark.parametrize("why,kw,lens", [
    ("bucket", dict(num_slots=4), (5, 40)),
    ("slot", dict(num_slots=1), (5, 6)),
    ("batch", dict(num_slots=4, prefill_batch=1), (5, 6)),
])
def test_a_look_that_leaves_a_request_says_why(tiny_cfg, why, kw, lens):
    """Two requests that one look finds together, of which the admit takes
    the first: the other bucket, the one free slot, the full admit."""
    eng = _engine(tiny_cfg, **kw)
    try:
        first, second = reqs = _submit_together(
            eng, [[1 + j % 50 for j in range(n)] for n in lens])
        _finish(reqs)
        c = eng.counters()
        assert first.held_by is None and second.held_by == why
        assert first.seen_at == second.seen_at
        assert first.admitted_at < second.admitted_at
        # the look that took the first left the second, and a later look
        # took it: all of the held time is the second's
        assert 0 < c["queue_held_s"] <= second.admitted_at - second.seen_at
        assert c["queue_look_s"] == pytest.approx(sum(
            r.seen_at - r.submitted_at for r in reqs))
    finally:
        eng.shutdown()


def test_an_admit_stalls_the_streams_live_at_its_dispatch(tiny_cfg):
    """``stream_s`` grows by every program's run times the streams live at
    its dispatch, ``stream_admit_s`` by the admits' alone: nothing while no
    admit runs beside a stream, something once one does."""
    from ray_tpu.serve.llm import _FLUSH
    eng = _engine(tiny_cfg)
    try:
        _run(eng, [[1, 2, 3, 4]], max_tokens=40)
        alone = eng.counters()
        assert alone["stream_admit_s"] == 0
        assert alone["stream_s"] > 0
        long = eng.submit([5, 6, 7], max_tokens=100)
        assert long.out.get(timeout=120) is not _FLUSH   # a live stream
        _run(eng, [[8, 9, 10, 11]], max_tokens=3)        # an admit beside it
        _finish([long])
        both = eng.counters()
        assert 0 < both["stream_admit_s"] <= both["stream_s"]
    finally:
        eng.shutdown()


# ------------------------------- a slot's time, by what it was doing (PR 56)

SLOT_KEYS = tuple(f"slot_{k}_s" for k in
                  ("prefill", "live", "tail", "queued", "unfed"))


def _slot_seconds(c0, c1):
    return sum(c1[k] - c0[k] for k in SLOT_KEYS)


@pytest.mark.parametrize("kind", list(ENGINES))
def test_the_five_slot_states_add_up_between_any_two_snapshots(tiny_cfg,
                                                               kind):
    """Every slot-second belongs to one state: between ANY two snapshots,
    with requests queued, in prefill, mid-answer or just retired, the five
    sums grow by slots x the time between them, to the millisecond."""
    eng = _engine(_two_layers(tiny_cfg), num_slots=3, **ENGINES[kind])
    try:
        snaps = [eng.counters()]                       # nothing asked yet
        reqs = [eng.submit([1 + i, 2, 3, 4 + i], max_tokens=12 + 9 * i)
                for i in range(5)]                     # two have to queue
        while any(r.retired_at is None for r in reqs):
            snaps.append(eng.counters())               # mid-request
            time.sleep(0.003)
        _finish(reqs)
        time.sleep(0.05)
        snaps.append(eng.counters())                   # every slot free
        assert len(snaps) > 5
        for c0, c1 in zip(snaps, snaps[1:]):
            want = eng.num_slots * (c1["t_mono"] - c0["t_mono"])
            assert abs(_slot_seconds(c0, c1) - want) < 1e-3
        want = eng.num_slots * (snaps[-1]["t_mono"] - snaps[0]["t_mono"])
        assert abs(_slot_seconds(snaps[0], snaps[-1]) - want) < 1e-3
        last = snaps[-1]
        assert all(last[k] > 0 for k in SLOT_KEYS), last
        assert last["retired_requests"] == 5
        # a free slot waits for a request no longer than that request waits
        # for anything: the queued seconds lie inside the queue wait
        assert last["slot_queued_s"] <= last["queue_wait_s"] + EPS
    finally:
        eng.shutdown()


def _spy_slots(eng):
    """What the engine thread books: every ``take`` as (request's submit,
    the admit's dispatch, the unfed seconds returned) and every ``retire``
    as (end on the chip, retire, the run of the program whose tokens were
    being emitted)."""
    takes, retires, acct = [], [], eng._slots
    take, retire = acct.take, acct.retire

    def spy_take(slot, submitted_at, now):
        unfed = take(slot, submitted_at, now)
        takes.append((submitted_at, now, unfed))
        return unfed

    def spy_retire(slot, ended, now):
        prog = eng._emitting
        retires.append((ended, now, prog.start, prog.done))
        return retire(slot, ended, now)

    acct.take, acct.retire = spy_take, spy_retire
    return takes, retires


def test_a_vacancy_is_queued_or_unfed_by_the_submit(tiny_cfg):
    """One slot.  A request submitted while the slot is taken finds it free
    later than its own submit: the vacancy is all queued, none unfed.  One
    submitted after the slot has stood free books the time up to its
    submit as unfed and the rest, to its admit, as queued."""
    eng = _engine(tiny_cfg, num_slots=1)
    try:
        _run(eng, [[1, 2, 3]], max_tokens=2)      # compiled, slot free again
        takes, _retires = _spy_slots(eng)
        first = eng.submit([1, 2, 3, 4], max_tokens=30)
        behind = eng.submit([2, 3, 4, 5], max_tokens=4)   # the slot is taken
        _finish([first, behind])
        c0 = eng.counters()
        time.sleep(0.25)
        late = eng.submit([3, 4, 5, 6], max_tokens=4)
        _finish([late])
        c1 = eng.counters()
        assert [t[0] for t in takes] == [first.submitted_at,
                                         behind.submitted_at,
                                         late.submitted_at]
        assert behind.submitted_at < first.retired_at
        assert takes[1][2] == 0.0                          # none unfed
        assert takes[2][2] == pytest.approx(
            late.submitted_at - behind.retired_at, abs=EPS)
        assert takes[2][2] >= 0.25
        # the counters grew by just that: unfed up to the submit, and after
        # the last retire up to the second snapshot
        unfed = c1["slot_unfed_s"] - c0["slot_unfed_s"]
        assert unfed == pytest.approx(
            (late.submitted_at - c0["t_mono"])
            + (c1["t_mono"] - late.retired_at), abs=1e-6)
        queued = c1["slot_queued_s"] - c0["slot_queued_s"]
        assert queued == pytest.approx(
            late.admitted_at - late.submitted_at, abs=1e-6)
    finally:
        eng.shutdown()


@pytest.mark.parametrize("kind", list(ENGINES))
def test_the_tail_is_what_was_left_of_the_dispatch(tiny_cfg, kind):
    """A request ends on the chip with the step (a speculative engine's
    round) that made its last token.  Where that is the last of its
    dispatch, nothing of the program's run is tail; where it is not, the
    steps after it are."""
    eng = _engine(_two_layers(tiny_cfg), steps_per_dispatch=8,
                  **ENGINES[kind])
    try:
        _run(eng, [[1, 2, 3]], max_tokens=2)
        _takes, retires = _spy_slots(eng)
        c0 = eng.counters()
        # the admit makes a token; a dispatch makes 8 (4 rounds of up to 2)
        whole = _run(eng, [[1, 2, 3, 4]], max_tokens=9 if kind != "spec"
                     else 64)[0]
        (ended, now, start, done), = retires
        if kind != "spec":
            assert whole.generated == 9
            assert ended == pytest.approx(done, abs=EPS)
        assert start < ended <= done + EPS <= now + EPS
        del retires[:]
        part = _run(eng, [[1, 2, 3, 4]], max_tokens=5 if kind != "spec"
                    else 3)[0]
        (ended, now, start, done), = retires
        assert part.retired_at == now
        if kind != "spec":
            # tokens 2-5 are steps 0-3 of 8: half the run was left
            assert done - ended == pytest.approx((done - start) / 2, abs=EPS)
        else:
            # the first round makes tokens 2 and perhaps 3, the second
            # the third at the latest: of four rounds two or three were left
            assert done - ended >= (done - start) / 2 - EPS
        c1 = eng.counters()
        assert c1["slot_tail_s"] - c0["slot_tail_s"] >= done - ended
        assert c1["retired_requests"] - c0["retired_requests"] == 2
    finally:
        eng.shutdown()


def test_an_unbound_chip_is_counted_where_a_stream_is_live(tiny_cfg):
    """With a stream live and nothing in flight, the next program's
    dispatch finds the chip standing since the last program's end."""
    from ray_tpu.serve.llm import _Program
    eng = _engine(tiny_cfg)
    try:
        _run(eng, [[1, 2, 3]], max_tokens=20)
        c = eng.counters()
        assert c["chip_unbound_n"] >= 0 and c["chip_unbound_s"] >= 0.0
        eng.shutdown()                       # the thread gone: by hand
        n, s = eng.chip_unbound_n, eng.chip_unbound_s
        assert not eng._unfetched
        eng._done_at = 10.0
        idle = _Program("decode", (), 10.5)
        eng._in_flight(idle)                 # no stream live: not counted
        assert (eng.chip_unbound_n, idle.unbound) == (n, 0.0)
        del eng._unfetched[:]
        live = eng.submit([1, 2], max_tokens=4)
        live.emit_times.append(9.0)
        eng._active[0] = live
        prog = _Program("decode", (), 10.5)
        eng._in_flight(prog)
        assert prog.unbound == pytest.approx(0.5)
        assert eng.chip_unbound_n == n + 1
        assert eng.chip_unbound_s == pytest.approx(s + 0.5)
    finally:
        eng.shutdown()


# ------------------- an admit is bound one program ahead of the chip (PR 43)

def _spy_programs(eng):
    """Every program the engine thread binds, in order, beside the programs
    that were in flight (dispatched, not fetched) at its bind."""
    log, orig = [], eng._in_flight

    def spy(prog):
        log.append((prog, list(eng._unfetched)))
        orig(prog)

    eng._in_flight = spy
    return log


def test_the_depth_of_the_loop_is_no_argument():
    import inspect
    from ray_tpu.serve.llm import LLMEngine
    assert "fetch_lag" not in inspect.signature(LLMEngine.__init__).parameters


@pytest.mark.parametrize("kind", list(ENGINES))
def test_an_admit_is_bound_behind_the_one_dispatch_in_flight(tiny_cfg, kind):
    """A request that arrives while a dispatch is the newest program bound
    is admitted by the very next program, and when the loop binds that admit
    the dispatch is all that is in flight: the pass before it has fetched
    everything older, so the first tokens wait out one dispatch and no
    admit."""
    eng = _engine(_two_layers(tiny_cfg), **ENGINES[kind])
    try:
        eng.warmup(16)
        log = _spy_programs(eng)
        long = _live_stream(eng)
        late, dispatch = {}, eng._dispatch_step

        def dispatch_then_submit():
            dispatch()
            if not late:    # on the engine thread, right behind a dispatch
                late["behind"] = eng._unfetched[-1]
                late["req"] = eng.submit([8, 9, 10, 11], max_tokens=3)

        eng._dispatch_step = dispatch_then_submit
        while "req" not in late:    # (the engine's thread sets "behind" first)
            time.sleep(0.001)
        _finish([late["req"], long])
        progs = [p for p, _ahead in log]
        at = progs.index(late["behind"])
        admit, ahead = log[at + 1]
        assert late["behind"].kind in ("decode", "spec")
        assert admit.kind == "admit"
        assert [r for r, _n in admit.rows] == [late["req"]]
        assert ahead == [late["behind"]]
        # the chip's next program but for a draft's prefill, which is one
        # of the chip's and never fetched
        assert admit.seq == late["behind"].seq + 1 + (kind == "spec")
        assert progs[at + 2].kind == late["behind"].kind
        assert late["req"].prefill_attrs["rows"] == 1
    finally:
        eng.shutdown()


def _burst(eng, admit):
    """More than half of what an admit may take: the loop looks again as
    such an admit starts, not as it ends."""
    return admit.kind == "admit" and 2 * len(admit.rows) > eng.prefill_batch


@pytest.mark.parametrize("kind", list(ENGINES))
def test_admits_and_dispatches_alternate_one_ahead(tiny_cfg, kind):
    """Requests arriving at their own times through few slots: the chip's
    programs alternate (an admit always has a dispatch bound behind it, so
    no two admits stand back to back), and an admit bound beside a live
    stream finds one program in flight, a dispatch; two only behind a
    burst's admit, which is then the one running."""
    eng = _engine(_two_layers(tiny_cfg), num_slots=3, **ENGINES[kind])
    try:
        log = _spy_programs(eng)
        reqs = []
        for i, n in enumerate(PROMPT_LENS * 2):
            reqs.append(eng.submit([1 + (i + j) % 50 for j in range(n)],
                                   max_tokens=4 + 5 * (i % 4)))
            time.sleep(0.003 * (i % 3))
        _finish(reqs)
        assert [r.generated for r in reqs] == [
            4 + 5 * (i % 4) for i in range(len(reqs))]
        kinds = [p.kind for p, _ahead in log]
        admits = [(p, ahead) for p, ahead in log if p.kind == "admit"]
        assert sum(len(p.rows) for p, _a in admits) == len(reqs)
        assert all(a != "admit" or b != "admit"
                   for a, b in zip(kinds, kinds[1:])), kinds
        for p, ahead in admits:
            assert len(ahead) <= 2
            assert not ahead or ahead[-1].kind != "admit"
            if len(ahead) == 2:
                assert _burst(eng, ahead[0])
            # a live stream means a dispatch in flight
            assert ahead or not p.streams
        assert any(p.streams for p, _ahead in admits)
        # each fetched once: the dispatch bound behind the last answer's is
        # drained too, once no stream is left
        deadline = time.monotonic() + 30
        while eng._unfetched and time.monotonic() < deadline:
            time.sleep(0.005)
        time.sleep(0.05)
        assert eng.counters()["loop_fetch_n"] == len(log)
    finally:
        eng.shutdown()


@pytest.mark.parametrize("kind", list(ENGINES))
def test_behind_a_bursts_admit_the_loop_looks_as_it_starts(tiny_cfg, kind):
    """An admit more than half full is a burst.  The loop does not wait for
    its end to look again: a request that arrives as it is bound is bound
    behind it and its dispatch, and what arrives while it runs waits a pass
    more, so two long admits never hold the live streams with one dispatch
    between.  The pass after is one ahead again."""
    eng = _engine(_two_layers(tiny_cfg), num_slots=8, prefill_batch=4,
                  **ENGINES[kind])
    try:
        eng.warmup(16)
        log = _spy_programs(eng)
        long = _live_stream(eng)
        late, admitted = {}, eng._admitted

        def admitted_then_submit(reqs, *rest):
            admitted(reqs, *rest)
            if len(reqs) == 3 and not late:   # on the engine thread
                late["req"] = eng.submit([8, 9, 10, 11], max_tokens=3)

        eng._admitted = admitted_then_submit
        wave = _submit_together(
            eng, [[1 + (i + j) % 50 for j in range(5)] for i in range(3)])
        _finish(wave + [long])
        _finish([late["req"]])
        admits = [(p, ahead) for p, ahead in log if p.kind == "admit"]
        burst = next(p for p, _a in admits
                     if [r for r, _n in p.rows] == wave)
        after, ahead = next((p, a) for p, a in admits
                            if [r for r, _n in p.rows] == [late["req"]])
        assert _burst(eng, burst) and not _burst(eng, after)
        assert len(ahead) == 2 and ahead[0] is burst
        assert ahead[1].kind in ("decode", "spec")
        # and the look after that one found a single dispatch in flight
        later = [a for p, a in log if p.seq > after.seq]
        assert later and all(len(a) <= 1 for a in later[1:])
    finally:
        eng.shutdown()


@pytest.mark.parametrize("kind", list(ENGINES))
def test_a_mixed_batch_is_greedy_token_for_token(tiny_cfg, kind):
    """When the loop binds a program decides which requests share an admit
    and a dispatch, never what any of them is answered: a mixed batch
    through few slots reads what ``generate`` gives one request at a time."""
    cfg = _two_layers(tiny_cfg)
    prompts = [[1 + (3 * i + j) % 50 for j in range(n)]
               for i, n in enumerate(PROMPT_LENS)]
    budgets = [12, 5, 9, 1, 16, 7]
    eng = _engine(cfg, num_slots=3, seed=7, **ENGINES[kind])
    try:
        reqs = []
        for p, m in zip(prompts, budgets):
            reqs.append(eng.submit(list(p), max_tokens=m))
            time.sleep(0.002)
        _finish(reqs)
        mixed = [r.tokens[r.prompt_len:] for r in reqs]
    finally:
        eng.shutdown()
    alone = _engine(cfg, num_slots=3, seed=7, **ENGINES[kind])
    try:
        one_by_one = [alone.generate(list(p), max_tokens=m)
                      for p, m in zip(prompts, budgets)]
    finally:
        alone.shutdown()
    assert [len(o) for o in mixed] == budgets
    assert mixed == one_by_one


async def _consume(server, body):
    return [tok async for tok in server(body)]


def test_server_counts_delivered_tokens_and_their_lag():
    from ray_tpu.serve.llm import LLMServer
    server = LLMServer("tiny", num_slots=4, max_len=64,
                       engine_kwargs={"buckets": (16, 32)})
    try:
        async def three():
            return await asyncio.gather(*(
                _consume(server, {"tokens": [1, 2, 3 + i], "max_tokens": 6})
                for i in range(3)))
        outs = asyncio.run(three())
        assert [len(o) for o in outs] == [6, 6, 6]
        st = server.stats()
        assert st["delivered_tokens"] == st["tokens_out"] == 18
        assert 0 <= st["deliver_lag_s"] < 18 * 5.0
        # what the committed readers difference keeps its names
        for key in ("steps", "tokens_out", "admit_batches", "num_slots",
                    "active", "free_slots", "prefill_buckets"):
            assert key in st
        # and the new keys ride along
        for key in ["t_mono", "loop_iterations", "admitted_requests",
                    "queue_wait_s", "first_tokens", "first_token_wait_s",
                    "admit_tokens_real", "admit_tokens_padded",
                    *WAIT_KEYS] + [
                        f"loop_{ph}_{k}" for ph in ENGINE_PHASES
                        for k in ("s", "n")]:
            assert key in st, key
        assert st["admitted_requests"] == st["first_tokens"] == 3
        assert abs(st["t_mono"] - time.monotonic()) < 5
    finally:
        server.engine.shutdown()


@pytest.mark.parametrize("enabled", [False, True],
                         ids=["metrics-off", "metrics-on"])
def test_counters_do_not_depend_on_the_metrics_switch(tiny_cfg, enabled,
                                                      monkeypatch):
    """``serve_metrics_enabled=False`` sheds the operator's spans and
    series; the counters are plain numbers and still count.  With the
    switch on, the three stage spans keep wall-clock starts (derived from
    the one monotonic stamp per stage and the engine's offset)."""
    from ray_tpu.core.config import Config, reset_config, set_config
    from ray_tpu.util import tracing

    spans = []
    monkeypatch.setattr(
        tracing, "record_span",
        lambda name, t0, dur, **kw: spans.append((name, t0, dur)) or "sid")
    try:
        set_config(Config(serve_metrics_enabled=enabled))
        eng = _engine(tiny_cfg)
        try:
            t_wall = time.time()
            _run(eng, [[1, 2, 3, 4, 5]] * 2, max_tokens=3)
            c = eng.counters()
        finally:
            eng.shutdown()
        assert c["admitted_requests"] == c["first_tokens"] == 2
        assert c["admit_tokens_real"] == 10 and eng.tokens_out == 6
        assert c["loop_admit_n"] >= 1 and c["loop_emit_n"] >= 1
        if not enabled:
            assert spans == []
            return
        assert sorted(n for n, _, _ in spans) == [
            "batch_wait", "batch_wait", "decode", "decode", "prefill",
            "prefill"]
        for _name, t0, dur in spans:
            assert dur >= 0
            assert t_wall - 1 <= t0 <= time.time() + 1
    finally:
        reset_config()
