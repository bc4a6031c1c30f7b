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
        seen = _spy_admits(eng)
        prompts = [[1 + (i + j) % 50 for j in range(n)]
                   for i, n in enumerate(PROMPT_LENS)]
        _run(eng, prompts)
        c = eng.counters()
        # buckets this short are walked whole: no chunks
        assert c["admit_chunks"] == c["admit_rows_chunked"] == 0
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
    rounded up to whole chunks and not to its bucket, and ``admit_chunks`` /
    ``admit_rows_chunked`` say how often."""
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
        seen = _spy_admits(eng)
        # (3, whole, 4, 4 and 3 chunks; the second sits in the short bucket)
        lens = (chunk * 2 + chunk // 8, short * 5 // 8,
                chunk * 3 + chunk // 4, bucket, chunk * 3)
        _run(eng, [[1 + (i + j) % 50 for j in range(n)]
                   for i, n in enumerate(lens)], max_tokens=2)
        c = eng.counters()
        assert c["admit_tokens_real"] == sum(lens)
        assert c["admit_chunks"] == 3 + 4 + 4 + 3
        assert c["admit_rows_chunked"] == 4
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
    assert sorted(clocks) == ["admitted_at", "emit_times", "seen_at",
                              "submitted_at"]


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
        for key in ("steps", "tokens_out", "admit_batches",
                    "batch_occupancy", "num_slots",
                    "active", "free_slots", "prefill_buckets"):
            assert key in st
        # and the new keys ride along
        for key in ["t_mono", "loop_iterations", "admitted_requests",
                    "queue_wait_s", "first_tokens", "first_token_wait_s",
                    "admit_tokens_real", "admit_tokens_padded",
                    "admit_chunks", "admit_rows_chunked", *WAIT_KEYS] + [
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
