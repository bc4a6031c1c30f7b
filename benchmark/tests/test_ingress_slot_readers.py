"""The readers of a slot's time by state and of a request's way in and out
(PR 56), each against two made-up ``LLMServer.stats()`` snapshots: its
value, nothing when a key is missing (a parent commit's program has none of
them), the twins reading the very same function, and every new name listed
in ``BENCHMARK.json`` with its cells: held as a set, not by position, so
that a later PR may append to the list."""

import copy
import importlib
import importlib.util
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m", os.path.join(HERE, "..", "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def ctx():
    """A window of 50 s that took in 60 requests and handed out 3,000
    tokens, and inside it a traced span of 6 s over 32 slots."""
    stats0 = {
        "t_mono": 1000.0, "num_slots": 32, "ingress_requests": 100,
        "ingress_queue_s": 1.0, "ingress_submit_s": 0.5,
        "ingress_transit_s": 2.0, "ingress_transit_n": 100,
        "finish_deliver_s": 3.0, "finished_streams": 90,
        "first_chunk_wait_s": 40.0, "first_chunks": 95,
        "delivered_tokens": 5000, "deliver_thread_s": 10.0,
        "deliver_loop_s": 20.0, "yield_hold_s": 5.0,
        "deliver_lag_s": 30.0}
    stats1 = dict(
        stats0, t_mono=1050.0, ingress_requests=160,
        ingress_queue_s=1.0 + 60 * 0.004, ingress_submit_s=0.5 + 60 * 0.001,
        ingress_transit_s=2.0 + 60 * 0.002, ingress_transit_n=160,
        finish_deliver_s=3.0 + 50 * 0.012, finished_streams=140,
        first_chunk_wait_s=40.0 + 50 * 2.5, first_chunks=145,
        delivered_tokens=8000, deliver_thread_s=10.0 + 3000 * 0.008,
        deliver_loop_s=20.0 + 3000 * 0.017, yield_hold_s=5.0 + 3000 * 0.0004,
        deliver_lag_s=30.0 + 3000 * 0.025)
    span0 = {"t_mono": 1010.0, "num_slots": 32, "slot_prefill_s": 10.0,
             "slot_live_s": 900.0, "slot_tail_s": 5.0, "slot_queued_s": 8.0,
             "slot_unfed_s": 37.0, "chip_unbound_s": 0.25,
             "admitted_requests": 110, "queue_wait_s": 9.0,
             "retired_requests": 100}
    slot_s = 32 * 6.0
    span1 = dict(
        span0, t_mono=1016.0, slot_prefill_s=10.0 + 0.02 * slot_s,
        slot_live_s=900.0 + 0.93 * slot_s, slot_tail_s=5.0 + 0.01 * slot_s,
        slot_queued_s=8.0 + 0.015 * slot_s,
        slot_unfed_s=37.0 + 0.025 * slot_s, chip_unbound_s=0.25 + 0.003,
        admitted_requests=146, queue_wait_s=9.0 + 36 * 0.09,
        retired_requests=136)
    return {"stats0": stats0, "stats1": stats1,
            "span": {"stats0": span0, "stats1": span1}}


#: name -> (value on ctx(), which pair it reads, the keys it needs)
READERS = {
    "slot_unfed_share.batch": (2.5, "span", ["slot_unfed_s", "t_mono"]),
    "slot_queued_share.batch": (1.5, "span", ["slot_queued_s", "t_mono"]),
    "slot_prefill_share.batch": (2.0, "span", ["slot_prefill_s", "t_mono"]),
    "slot_tail_share.batch": (1.0, "span", ["slot_tail_s", "t_mono"]),
    "chip_unbound_share": (0.05, "span", ["chip_unbound_s", "t_mono"]),
    "ingress_wait_ms": (5.0, "window", [
        "ingress_queue_s", "ingress_submit_s", "ingress_requests"]),
    "ingress_transit_ms.batch": (2.0, "window", [
        "ingress_transit_s", "ingress_transit_n"]),
    "finish_deliver_ms.batch": (12.0, "window", [
        "finish_deliver_s", "finished_streams"]),
    "stream_first_chunk_wait_ms.batch": (2500.0, "window", [
        "first_chunk_wait_s", "first_chunks"]),
    "stream_deliver_thread_ms": (8.0, "window", [
        "deliver_thread_s", "delivered_tokens"]),
    "stream_deliver_loop_ms": (17.0, "window", [
        "deliver_loop_s", "delivered_tokens"]),
    "stream_yield_hold_ms": (0.4, "window", [
        "yield_hold_s", "delivered_tokens"]),
}
TWINS = {"chip_unbound_share.batch": "chip_unbound_share",
         "ingress_wait_ms.chat": "ingress_wait_ms",
         "ingress_wait_ms.batch": "ingress_wait_ms"}


def _pair(c, which):
    return c["span"] if which == "span" else c


@pytest.mark.parametrize("name", list(READERS))
def test_a_reader_differences_its_pair_of_snapshots(name):
    want, which, keys = READERS[name]
    read = reader(name)
    assert read(ctx()) == pytest.approx(want)
    for key in keys:
        for snap in ("stats0", "stats1"):
            c = ctx()
            del _pair(c, which)[snap][key]       # a parent commit's program
            assert read(c) is None, (name, key, snap)
    c = ctx()                                    # nothing counted in the pair
    _pair(c, which)["stats1"] = copy.deepcopy(_pair(c, which)["stats0"])
    assert read(c) is None


def test_a_slot_share_needs_the_slot_count():
    c = ctx()
    del c["span"]["stats1"]["num_slots"]
    assert reader("slot_tail_share.batch")(c) is None


@pytest.mark.parametrize("twin", list(TWINS))
def test_a_twin_is_the_same_function(twin):
    module = importlib.import_module("benchmark.layer_metrics."
                                     + TWINS[twin])
    assert reader(twin) is module.read


def test_the_parts_add_up(capfd):
    """The four slot shares and the live share make 100 (the reader of the
    unfed share says all five on an information line); thread + loop make the
    deliver lag."""
    c = ctx()
    four = sum(reader(f"slot_{s}_share.batch")(c)
               for s in ("unfed", "queued", "prefill", "tail"))
    assert four == pytest.approx(7.0)
    said = capfd.readouterr().out
    assert "slot account over the traced span" in said
    account = json.loads(said[said.index("{"):])
    assert account["live"] == pytest.approx(93.0)
    assert account["sum"] == pytest.approx(100.0)
    assert account["slot_queued_ms_per_admit"] == pytest.approx(80.0)
    assert account["queue_wait_ms_per_admit"] == pytest.approx(90.0)
    lag = importlib.import_module(
        "benchmark.layer_metrics.stream_deliver_lag_ms").read(c)
    assert (reader("stream_deliver_thread_ms")(c)
            + reader("stream_deliver_loop_ms")(c)) == pytest.approx(lag)


def test_the_fifteen_are_listed_with_their_cells():
    m = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    listed = {e["name"]: e for e in m["per_layer"]}
    cells = {e["name"]: e.get("workloads") for e in m["end_to_end"]}
    closed = ["serve-decode-saturated", "serve-hybrid-longgen-closed",
              "serve-mla-moe-longctx-closed",
              "serve-kda-moe-reasoning-closed",
              "serve-ssm-moe-mixedlen-closed",
              "serve-swa-moe-mtp-longreason-closed",
              "serve-ssm-dense-agents-closed"]
    tokens = ["serve-chat-steady", "serve-decode-saturated",
              "serve-hybrid-longgen-closed", "serve-longprompt-steady",
              "serve-mla-moe-longctx-closed"]
    chat, longprompt = "serve-chat-steady", "serve-longprompt-steady"
    engine, prefill = "LLM engine (host loop)", "prefill programs"
    ingress = "serve ingress + router"
    lat, tpot = "latency_per_token_p95_ms", "tpot_p95_ms"
    want = {
        "slot_unfed_share.batch": ("%", engine, lat, closed),
        "slot_queued_share.batch": ("%", engine, lat, closed),
        "slot_prefill_share.batch": ("%", prefill, lat, closed),
        "slot_tail_share.batch": ("%", engine, lat, closed),
        "chip_unbound_share": ("%", engine, "serve_out_tokens_per_s",
                               tokens),
        "chip_unbound_share.batch": ("%", engine, lat, closed[3:]),
        "ingress_wait_ms": ("ms", ingress, "ttft_p95_ms", [longprompt]),
        "ingress_wait_ms.chat": ("ms", ingress, tpot, [chat]),
        "ingress_wait_ms.batch": ("ms", ingress, lat, closed),
        "ingress_transit_ms.batch": ("ms", ingress, lat, closed),
        "finish_deliver_ms.batch": ("ms", ingress, lat, closed),
        "stream_first_chunk_wait_ms.batch": ("ms", ingress, lat, closed),
        "stream_deliver_thread_ms": ("ms", ingress, tpot,
                                     [chat, longprompt]),
        "stream_deliver_loop_ms": ("ms", ingress, tpot, [chat, longprompt]),
        "stream_yield_hold_ms": ("ms", ingress, tpot, [chat, longprompt]),
    }
    assert set(want) == set(READERS) | set(TWINS)
    assert set(want) <= set(listed)
    for name, (unit, layer, moves, workloads) in want.items():
        e = listed[name]
        assert (e["unit"], e["layer"], e["moves"], e["workloads"]) == (
            unit, layer, moves, workloads), name
        assert (e["source"], e["better"]) == ("program_counter", "lower")
        # every cell it lists reports the end-to-end metric it moves
        assert set(workloads) <= set(cells[moves]), name
        assert callable(reader(name))
