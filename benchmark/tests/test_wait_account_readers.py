"""The readers of a request's wait by cause (PR 42), each against two
synthetic ``LLMServer.stats()`` snapshots: its value, nothing when a key is
missing (a parent commit's program has none of them), and the twins of a
cell that judges another end-to-end metric reading the very same function."""

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
    """A window that admitted 60 requests and gave out 50 first tokens."""
    stats0 = {"admitted_requests": 200, "first_tokens": 200,
              "queue_wait_s": 50.0, "queue_look_s": 30.0,
              "queue_held_s": 15.0, "first_token_wait_s": 80.0,
              "first_token_ahead_s": 20.0, "first_token_own_row_s": 12.0,
              "first_token_other_rows_s": 40.0, "stream_s": 1000.0,
              "stream_admit_s": 400.0}
    stats1 = {"admitted_requests": 260, "first_tokens": 250,
              "queue_wait_s": 50.0 + 60 * 0.130,
              "queue_look_s": 30.0 + 60 * 0.110,
              "queue_held_s": 15.0 + 60 * 0.015,
              "first_token_wait_s": 80.0 + 50 * 0.380,
              "first_token_ahead_s": 20.0 + 50 * 0.150,
              "first_token_own_row_s": 12.0 + 50 * 0.070,
              "first_token_other_rows_s": 40.0 + 50 * 0.120,
              "stream_s": 1000.0 + 300.0, "stream_admit_s": 400.0 + 180.0}
    return {"stats0": stats0, "stats1": stats1}


READERS = [
    ("queue_wait_look_ms", 110.0, ["queue_look_s", "admitted_requests"]),
    ("queue_wait_held_ms", 15.0, ["queue_held_s", "admitted_requests"]),
    ("first_token_ahead_ms", 150.0, ["first_token_ahead_s", "first_tokens"]),
    ("first_token_own_row_ms", 70.0,
     ["first_token_own_row_s", "first_tokens"]),
    ("first_token_other_rows_ms", 120.0,
     ["first_token_other_rows_s", "first_tokens"]),
    ("stream_admit_stall_share", 60.0, ["stream_admit_s", "stream_s"]),
]
TWINS = {name: name + (".batch" if name.startswith("stream_") else ".chat")
         for name, _want, _keys in READERS}


@pytest.mark.parametrize("name,want,keys", READERS,
                         ids=[r[0] for r in READERS])
def test_a_reader_differences_the_windows_two_snapshots(name, want, keys):
    for which in (name, TWINS[name]):
        read = reader(which)
        assert read(ctx()) == pytest.approx(want)
        for key in keys:
            for snap in ("stats0", "stats1"):
                c = ctx()
                del c[snap][key]               # a parent commit's program
                assert read(c) is None, (which, key, snap)
        c = ctx()                              # nothing counted in the window
        c["stats1"] = copy.deepcopy(c["stats0"])
        assert read(c) is None


@pytest.mark.parametrize("name", list(TWINS))
def test_a_twin_is_the_same_function(name):
    module = importlib.import_module("benchmark.layer_metrics." + name)
    assert reader(TWINS[name]) is module.read


def test_the_parts_add_up_to_the_sums_they_partition():
    """On the synthetic window as on the chip: look + held under the queue
    wait, ahead + own row + other rows under the first-token wait."""
    c = ctx()
    queue = reader("engine_queue_wait_ms")(c)
    first = reader("engine_first_token_ms")(c)
    assert queue == pytest.approx(130.0) and first == pytest.approx(380.0)
    assert (reader("queue_wait_look_ms")(c)
            + reader("queue_wait_held_ms")(c)) <= queue
    assert sum(reader(n)(c) for n in (
        "first_token_ahead_ms", "first_token_own_row_ms",
        "first_token_other_rows_ms")) <= first


def test_the_twelve_are_listed_with_their_cells():
    m = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    listed = {e["name"]: e for e in m["per_layer"]}
    chat, longprompt = "serve-chat-steady", "serve-longprompt-steady"
    closed = ["serve-decode-saturated", "serve-hybrid-longgen-closed",
              "serve-mla-moe-longctx-closed"]
    engine, prefill = "LLM engine (host loop)", "prefill programs"
    want = {}
    for name, layer in (("queue_wait_look_ms", engine),
                        ("queue_wait_held_ms", engine),
                        ("first_token_ahead_ms", engine),
                        ("first_token_other_rows_ms", prefill),
                        ("first_token_own_row_ms", prefill)):
        want[name] = (layer, "ttft_p95_ms", [longprompt])
        want[name + ".chat"] = (layer, "tpot_p95_ms", [chat])
    want["stream_admit_stall_share"] = (prefill, "tpot_p95_ms",
                                        [chat, longprompt])
    want["stream_admit_stall_share.batch"] = (
        prefill, "latency_per_token_p95_ms", closed)
    assert list(listed)[-12:] == [
        "queue_wait_look_ms", "queue_wait_look_ms.chat",
        "queue_wait_held_ms", "queue_wait_held_ms.chat",
        "first_token_ahead_ms", "first_token_ahead_ms.chat",
        "first_token_other_rows_ms", "first_token_other_rows_ms.chat",
        "first_token_own_row_ms", "first_token_own_row_ms.chat",
        "stream_admit_stall_share", "stream_admit_stall_share.batch"]
    for name, (layer, moves, cells) in want.items():
        e = listed[name]
        assert (e["layer"], e["moves"], e["workloads"]) == (
            layer, moves, cells), name
        assert (e["source"], e["better"]) == ("program_counter", "lower")
        assert e["unit"] == ("%" if name.startswith("stream_") else "ms")
        assert callable(reader(name))
