"""Every open-loop traffic mix of the benchmark holds enough requests in a
window of ``run_seconds`` for its 95th percentile to be a percentile: at
least 100, so five or more requests lie above it.  At 60 requests the p95
is the third largest, and one request that makes or misses a decode
dispatch by a millisecond moved ``ttft_p95_ms`` between two levels 6 ms
apart (PERF.md, PR 34).  A later edit of a rate cannot put a p95 back on
three requests.  That no arrival waited for a caller is read on the chip
(a run's ``lateness_p95_ms``), not here."""

import glob
import json
import os

import pytest

from benchmark.lib import loadgen, rollup

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
RUN_SECONDS = json.load(open(os.path.join(REPO, "BENCHMARK.json")))[
    "run_seconds"]
OPEN = sorted(
    os.path.basename(p)[:-len(".json")]
    for p in glob.glob(os.path.join(BENCH, "traffic", "*.json"))
    if json.load(open(p)).get("loop") == "open")


def test_there_are_open_loop_mixes():
    assert OPEN


@pytest.mark.parametrize("mix", OPEN)
def test_a_window_holds_a_hundred_requests(mix):
    traffic = json.load(open(os.path.join(BENCH, "traffic", mix + ".json")))
    plan = loadgen.open_schedule(traffic, RUN_SECONDS, seed=1)
    window = [r for r in plan if 0.0 <= r.t_sched < RUN_SECONDS]
    assert len(window) >= 100, (mix, len(window))
    # the rank the roll-up reads as the p95 leaves five requests above it
    ranks = list(range(len(window)))
    assert len(window) - 1 - rollup.pct(ranks, 0.95) >= 5
    # every seed replays this schedule where the mix says so
    if traffic.get("order") == "fixed":
        again = loadgen.open_schedule(traffic, RUN_SECONDS, seed=2**31 + 5)
        assert [(r.t_sched, r.prompt_len, r.output_len) for r in again] == \
            [(r.t_sched, r.prompt_len, r.output_len) for r in plan]
