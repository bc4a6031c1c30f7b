"""The readers of the program's own counters and program names (PR 25),
each against a synthetic ``ctx``: its value, and nothing when a key or a
program name is missing (a parent commit's program has neither)."""

import copy
import importlib.util
import json
import os
import re

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m", os.path.join(HERE, "..", "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def stats(t, **kw):
    base = {"t_mono": t, "steps": 0, "admit_batches": 0, "tokens_out": 0,
            "admitted_requests": 0, "queue_wait_s": 0.0, "first_tokens": 0,
            "first_token_wait_s": 0.0, "admit_tokens_real": 0,
            "admit_tokens_padded": 0, "delivered_tokens": 0,
            "deliver_lag_s": 0.0, "loop_admit_s": 0.0,
            "loop_dispatch_s": 0.0, "loop_fetch_s": 0.0, "loop_emit_s": 0.0,
            "loop_idle_s": 0.0}
    base.update(kw)
    return base


def serve_ctx():
    """A 50 s window holding a 6 s traced span."""
    window0 = stats(100.0, steps=1000, admit_batches=100, queue_wait_s=50.0,
                    admitted_requests=200, first_token_wait_s=80.0,
                    first_tokens=200, admit_tokens_real=100_000,
                    admit_tokens_padded=900_000, delivered_tokens=20_000,
                    deliver_lag_s=10.0, loop_admit_s=1.0,
                    loop_dispatch_s=2.0, loop_fetch_s=90.0, loop_emit_s=3.0)
    window1 = stats(150.0, steps=2000, admit_batches=160,
                    queue_wait_s=50.0 + 60 * 0.9, admitted_requests=260,
                    first_token_wait_s=80.0 + 60 * 0.7, first_tokens=260,
                    admit_tokens_real=100_000 + 30_000,
                    admit_tokens_padded=900_000 + 270_000,
                    delivered_tokens=20_000 + 11_200,
                    deliver_lag_s=10.0 + 11_200 * 0.002,
                    loop_admit_s=1.0 + 0.5, loop_dispatch_s=2.0 + 0.25,
                    loop_fetch_s=90.0 + 47.0, loop_emit_s=3.0 + 0.75)
    span0 = stats(120.0, steps=1400, admit_batches=124,
                  admit_tokens_real=112_000)
    span1 = stats(126.0, steps=1488, admit_batches=132,
                  admit_tokens_real=112_000 + 5_000)
    return {"stats0": window0, "stats1": window1,
            "trace": {"programs": [["jit_engine_decode", 3.76, 10],
                                   ["jit_admit_fn", 2.0, 8]],
                      "busy_s": 5.9},
            "span": {"t0": 20.0, "t1": 26.0, "stats0": span0,
                     "stats1": span1}}


def train_ctx():
    return {"trace": {"programs": [["jit_train_step", 2.8, 5],
                                   ["jit__lambda", 0.1, 1]]},
            "span": {"steps": 5, "seconds": 2.82}}


SERVE = [
    ("engine_queue_wait_ms", 900.0, ["queue_wait_s", "admitted_requests"]),
    ("engine_queue_wait_ms.chat", 900.0,
     ["queue_wait_s", "admitted_requests"]),
    ("engine_first_token_ms", 700.0,
     ["first_token_wait_s", "first_tokens"]),
    ("engine_first_token_ms.chat", 700.0,
     ["first_token_wait_s", "first_tokens"]),
    ("stream_deliver_lag_ms", 2.0, ["deliver_lag_s", "delivered_tokens"]),
    ("admit_padding_token_share", 90.0,
     ["admit_tokens_padded", "admit_tokens_real"]),
    ("admit_padding_token_share.chat", 90.0,
     ["admit_tokens_padded", "admit_tokens_real"]),
    ("engine_host_busy_share", 100.0 * 1.5 / 50.0,
     ["loop_admit_s", "loop_dispatch_s", "loop_emit_s", "t_mono"]),
]


@pytest.mark.parametrize("name,want,keys", SERVE, ids=[s[0] for s in SERVE])
def test_counter_readers_difference_the_window(name, want, keys):
    read = reader(name)
    assert read(serve_ctx()) == pytest.approx(want)
    for key in keys:
        for which in ("stats0", "stats1"):
            ctx = serve_ctx()
            del ctx[which][key]
            assert read(ctx) is None, (key, which)
    # nothing counted in the window: nothing to divide by
    ctx = serve_ctx()
    ctx["stats1"] = copy.deepcopy(ctx["stats0"])
    assert read(ctx) is None


def test_prefill_per_admitted_ktoken_reads_the_spans_counter():
    read = reader("prefill_ms_per_admitted_ktoken")
    assert read(serve_ctx()) == pytest.approx(2.0 * 1000.0 / 5.0)
    ctx = serve_ctx()
    del ctx["span"]["stats1"]["admit_tokens_real"]      # a parent commit
    assert read(ctx) is None
    ctx = serve_ctx()                                   # the name changed
    ctx["trace"]["programs"] = [["jit_engine_decode", 3.76, 10]]
    assert read(ctx) is None
    ctx = serve_ctx()                                   # nothing admitted
    ctx["span"]["stats1"]["admit_tokens_real"] = 112_000
    assert read(ctx) is None


@pytest.mark.parametrize("which", ["stream", "batch"])
def test_decode_step_device_ms(which):
    read = reader("decode_step_device_ms." + which)
    # 88 counted steps, 8 of them admits: 80 decode steps in 10 dispatches
    assert read(serve_ctx()) == pytest.approx(3760.0 / 80)
    ctx = serve_ctx()                 # a parent commit calls it jit__lambda_
    ctx["trace"]["programs"] = [["jit__lambda", 3.76, 10],
                                ["jit_admit_fn", 2.0, 8]]
    assert read(ctx) is None
    ctx = serve_ctx()                 # the speculative program is another
    ctx["trace"]["programs"] = [["jit_engine_spec_decode", 3.76, 10]]
    assert read(ctx) is None
    ctx = serve_ctx()
    del ctx["span"]["stats0"]["steps"]
    assert read(ctx) is None
    ctx = serve_ctx()                 # only admits in the span
    ctx["span"]["stats1"]["steps"] = 1408
    assert read(ctx) is None


def test_train_step_device_ms():
    read = reader("train_step_device_ms")
    assert read(train_ctx()) == pytest.approx(560.0)
    ctx = train_ctx()                 # a parent commit calls it jit_step_fn
    ctx["trace"]["programs"] = [["jit_step_fn", 2.8, 5]]
    assert read(ctx) is None
    ctx = train_ctx()
    ctx["span"]["steps"] = 0
    assert read(ctx) is None


def test_train_step_mfu_is_the_steps_flops_over_the_peak_and_its_time():
    """A kind that counts 1e9 FLOPs a token, 4 x 4096 tokens a step over 4
    chips, 0.56 s a step on the device, a peak of 100 TFLOP/s."""
    import types
    read = reader("train_step_mfu")
    ctx = dict(train_ctx(), chips=4, peaks={"bf16_flops_per_s": 1e14},
               config={"train": {"global_batch": 4, "sequence_length": 4096}},
               model=types.SimpleNamespace(
                   train_flops_per_token=lambda doc, seq: 1e9))
    assert read(ctx) == pytest.approx(100.0 * 4096e9 / (1e14 * 0.56))
    assert read(dict(ctx, peaks=None)) is None      # a device with no peak
    ctx["trace"]["programs"] = [["jit_step_fn", 2.8, 5]]
    assert read(ctx) is None


def test_the_names_matched_are_the_programs_own():
    """``_counted.py`` spells the names out (it also runs over a parent
    commit); here they are held to what ``ray_tpu`` pins."""
    from benchmark.layer_metrics import _counted
    from benchmark.lib import readers
    from ray_tpu.util import profiler

    assert re.fullmatch(_counted.DECODE_PROGRAM, profiler.PROGRAM_DECODE)
    assert re.fullmatch(_counted.TRAIN_PROGRAM, profiler.PROGRAM_TRAIN_STEP)
    assert re.fullmatch(readers.PREFILL_PROGRAM, profiler.PROGRAM_PREFILL)
    for other in (profiler.PROGRAM_PREFILL, profiler.PROGRAM_SPEC_DECODE,
                  profiler.PROGRAM_DRAFT_PREFILL):
        assert not re.search(_counted.DECODE_PROGRAM, "jit_" + other)


def test_every_new_metric_is_listed_with_its_cells():
    m = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    listed = {e["name"]: e for e in m["per_layer"]}
    train = ["train-fsdp4-s4096"]
    # in the order of ``workloads``: the open loops stream, the closed wait
    streams = ["serve-chat-steady", "serve-longprompt-steady"]
    waits = ["serve-decode-saturated", "serve-hybrid-longgen-closed"]
    serve = [streams[0]] + waits + [streams[1]]
    # ``ttft_p95_ms`` is judged in the long-prompt cell alone (PERF.md,
    # PR 34's first check): what moves it is listed there, and under a
    # name of its own, moving ``tpot_p95_ms``, in the chat cell
    want = {
        "engine_queue_wait_ms": streams[1:],
        "engine_first_token_ms": streams[1:],
        "stream_deliver_lag_ms": streams,
        "admit_padding_token_share": streams[1:],
        "engine_queue_wait_ms.chat": streams[:1],
        "engine_first_token_ms.chat": streams[:1],
        "admit_padding_token_share.chat": streams[:1],
        "ttft_p95_ms.chat": streams[:1],
        "ttft_p50_ms.chat": streams[:1],
        "engine_host_busy_share": serve,
        "prefill_ms_per_admitted_ktoken": serve,
        "decode_step_device_ms.stream": streams,
        "decode_step_device_ms.batch": waits,
        "train_step_device_ms": train,
        "train_step_mfu": train,
    }
    for name, cells in want.items():
        assert listed[name]["workloads"] == cells, name
        assert callable(reader(name))
    # the sources say where the number comes from
    assert {listed[n]["source"] for n in want
            if n.startswith(("engine_", "stream_", "admit_"))} == {
                "program_counter"}
    # every metric of a cell moves an end-to-end metric that the cell reports
    judged = {e["name"]: e.get("workloads") for e in m["end_to_end"]}
    for e in m["per_layer"]:
        for cell in e["workloads"]:
            assert judged[e["moves"]] is None or cell in judged[e["moves"]], (
                e["name"], cell)


@pytest.mark.parametrize("name,key", [("ttft_p95_ms.chat", "ttft_p95_ms"),
                                      ("ttft_p50_ms.chat", "ttft_p50_ms")])
def test_a_tail_reported_and_not_judged_is_the_roll_ups_own(name, key):
    read = reader(name)
    assert read({"roll": {key: 541.5}}) == 541.5
    assert read({"roll": {}}) is None      # a window that finished nothing
