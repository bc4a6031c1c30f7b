"""Window and ``failed`` accounting, percentiles, and the generator's
promise that every seed holds the same work."""

import collections

from benchmark.lib import loadgen, rollup
from benchmark.lib.loadgen import Planned, Sample


def sample(t_start, first, n, gap, expected=None, err="", prompt=10):
    times = [t_start + first + i * gap for i in range(n)]
    return Sample(t_start, t_start + 0.001, times, expected or n, prompt,
                  times[-1] if times else t_start + first, err)


def test_pct_is_request_rollups_rank_rule():
    xs = list(range(1, 101))
    assert rollup.pct(xs, 0.95) == 96 and rollup.pct(xs, 0.5) == 51
    assert rollup.pct([7], 0.95) == 7 and rollup.pct([], 0.5) is None


def test_serve_window_counts_tokens_where_they_arrive_and_failures():
    samples = [
        sample(-1.0, 0.5, 10, 0.2),            # pre-roll: tokens at -0.5..1.3
        sample(1.0, 0.1, 5, 0.1),              # good
        sample(2.0, 0.3, 3, 0.1, expected=5),  # short answer: failed
        sample(3.0, 0.2, 4, 0.1, err="boom"),  # raised: failed
        sample(9.5, 0.2, 5, 0.2),              # ends after the window: good
        sample(9.9, 0.2, 5, 5.0),              # ends after the deadline
        sample(12.0, 0.2, 5, 0.1),             # after the window: not counted
    ]
    unfinished = [(Planned(5.0, 8, 8, -1, 99), 5.0),
                  (Planned(-0.5, 8, 8, -1, 98), -0.5)]
    r = rollup.serve_window(samples, unfinished, window_s=10.0,
                            deadline_s=15.0)
    assert r["attempted"] == 6 and r["failed"] == 4 and r["completed"] == 2
    # pre-roll tokens inside [0, 10): t = -0.5 + 0.2 i >= 0 -> i = 3..9
    want = 7 + 5 + 3 + 4 + 2 + 0
    assert r["tokens_in_window"] == want
    assert r["serve_out_tokens_per_s"] == want / 10.0
    assert abs(r["ttft_p95_ms"] - 200.0) < 1e-6      # of the two good ones
    assert abs(r["tpot_p50_ms"] - 200.0) < 1e-6
    # (1.5 - 1.0) / 5 and (10.5 - 9.5) / 5 seconds a token
    assert abs(r["latency_per_token_p95_ms"] - 200.0) < 1e-6
    assert r["errors"] == ["boom"]


def test_tpot_needs_two_tokens():
    r = rollup.serve_window([sample(0.0, 0.1, 1, 0.0)], [], 1.0, 2.0)
    assert r["n_ttft"] == 1 and r["n_tpot"] == 0 and r["tpot_p95_ms"] is None


def test_train_window_is_every_step_started_over_all_their_time():
    # the step that straddles --seconds is run to its end and counted, so
    # a stall after the last step inside --seconds cannot hide
    r = rollup.train_window([0.5, 1.0, 1.5, 2.6],
                            [3.0, 2.9, 2.8, float("nan")],
                            tokens_per_step=100, chips=4)
    assert r["steps"] == 4 and r["nonfinite"] == 1 and r["window_s"] == 2.6
    assert r["train_tokens_per_s_per_chip"] == 4 * 100 / 2.6 / 4
    assert abs(r["step_ms_median"] - 500.0) < 1e-6


TRAFFIC = {"loop": "open", "rate_per_s": 4.0, "preroll_s": 5, "shape_seed": 3,
           "prompt": {"dist": "lognormal", "median": 100, "sigma": 0.8,
                      "lo": 10, "hi": 400},
           "output": {"dist": "uniform", "lo": 4, "hi": 40}}


def test_every_seed_holds_the_same_work_in_another_order():
    a = loadgen.open_schedule(TRAFFIC, 20.0, 1)
    b = loadgen.open_schedule(TRAFFIC, 20.0, 3_000_000_019)   # > 2**31
    assert len(a) == len(b) == 20 + 80
    shape = lambda plan: collections.Counter(           # noqa: E731
        (p.prompt_len, p.output_len, p.t_sched < 0) for p in plan)
    assert shape(a) == shape(b)
    assert [p.prompt_len for p in a] != [p.prompt_len for p in b]
    for plan in (a, b):
        times = [p.t_sched for p in plan]
        assert times == sorted(times) and -5.0 <= times[0]
        assert times[-1] < 20.0
        assert all(10 <= p.prompt_len <= 400 and 4 <= p.output_len <= 40
                   for p in plan)
    assert loadgen.open_schedule(TRAFFIC, 20.0, 1) == a
    fixed = dict(TRAFFIC, order="fixed")
    assert loadgen.open_schedule(fixed, 20.0, 1) == \
        loadgen.open_schedule(fixed, 20.0, 3_000_000_019)
    assert loadgen.Payloads(fixed, 1000, 1).make(a[0]) != \
        loadgen.Payloads(fixed, 1000, 2).make(a[0])


def test_burst_adds_arrivals_inside_its_interval_only():
    t = dict(TRAFFIC, preroll_s=0,
             burst={"start_s": 5, "end_s": 10, "mult": 4.0})
    plan = loadgen.open_schedule(t, 20.0, 9)
    inside = sum(1 for p in plan if 5 <= p.t_sched < 10)
    assert len(plan) == 80 + 60 and inside >= 60


def test_closed_schedule_and_payloads():
    t = dict(TRAFFIC, loop="closed", clients=4,
             prefix={"pool": 2, "len": 16})
    per = loadgen.closed_schedule(t, 10, 5)
    assert len(per) == 4 and all(len(c) == 10 for c in per)
    pay = loadgen.Payloads(t, vocab=1000, seed=3_000_000_019)
    req = per[0][0]
    one, two = pay.make(req), pay.make(req)
    assert one == two and len(one["tokens"]) == req.prompt_len
    assert one["max_tokens"] == req.output_len
    assert all(1 <= x < 1000 for x in one["tokens"])
    same = [r for c in per for r in c
            if r.prefix_index == req.prefix_index and r is not req][0]
    assert pay.make(same)["tokens"][:16] == one["tokens"][:16]
    assert pay.make(same)["tokens"][16:] != one["tokens"][16:]


def test_load_run_abandons_what_outlives_the_grace():
    import threading
    import time
    release = threading.Event()

    def fire(req, t_start):
        if req.index == 1:
            release.wait(5)
        return Sample(t_start, t_start, [t_start + 0.01], 1, 1,
                      t_start + 0.01)

    epoch = time.monotonic()
    run = loadgen.LoadRun(fire, epoch, stop_at=0.2, drain_grace_s=0.2)
    run.run_open([Planned(0.0, 1, 1, -1, 0), Planned(0.05, 1, 1, -1, 1)])
    release.set()
    assert [s.t_start for s in run.samples] == [0.0]
    assert [r.index for r, _t in run.unfinished] == [1]


def test_load_run_stops_waiting_once_nothing_more_can_come():
    """The runner sets ``nothing_more`` when the system holds no request
    any more: the drain ends there and not at the grace's end, and what is
    still open counts as unfinished either way."""
    import threading
    import time
    release = threading.Event()

    def fire(req, t_start):
        if req.index == 1:
            release.wait(10)
        return Sample(t_start, t_start, [t_start + 0.01], 1, 1,
                      t_start + 0.01)

    epoch = time.monotonic()
    run = loadgen.LoadRun(fire, epoch, stop_at=0.1, drain_grace_s=8.0)
    threading.Timer(0.5, run.nothing_more.set).start()
    assert run.running
    run.run_open([Planned(0.0, 1, 1, -1, 0), Planned(0.05, 1, 1, -1, 1),
                  Planned(0.06, 1, 1, -1, 2)])
    took = time.monotonic() - epoch
    release.set()
    assert 0.4 < took < 3.0 and not run.running
    assert sorted(s.t_start for s in run.samples) == [0.0, 0.06]
    assert [r.index for r, _t in run.unfinished] == [1]


def test_stall_clock_counts_the_stops_that_ended_in_a_span():
    """``StallClock.between`` reads the window's stops and no others; the
    thread starts, ticks and ends with its block."""
    import time

    from benchmark.runners.common import StallClock
    with StallClock(tick_s=0.002, late_s=5.0) as clock:
        time.sleep(0.02)
    assert not clock._thread.is_alive() and clock.stalls == []
    clock.stalls = [(9.9, 0.2), (10.5, 0.1), (12.0, 6.9), (60.0, 1.0)]
    got = clock.between(10.0, 60.0)
    assert got["n"] == 2 and abs(got["longest_ms"] - 6900.0) < 1e-6
    assert abs(got["total_ms"] - 7000.0) < 1e-6
    assert clock.between(0.0, 9.0) == {"n": 0, "longest_ms": 0.0,
                                       "total_ms": 0.0}
