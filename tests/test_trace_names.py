"""What a profiler capture calls the program's pieces: the jitted programs'
names (the benchmark's readers match them in the device planes' ``XLA
Modules`` line), the named scopes on the parts of a block, the Pallas
kernels' names, and the engine thread's ``raytpu:engine.*`` host spans.
Compile-time text and a CPU capture: names, never speeds."""

import dataclasses
import glob
import re
import time

import pytest

import jax
import jax.numpy as jnp

import kinds
from ray_tpu.util import profiler


@pytest.fixture(scope="module")
def tiny_cfg():
    from ray_tpu.models import config as mcfg
    return mcfg.tiny()


def _engine(cfg, **kw):
    from ray_tpu.serve.llm import LLMEngine
    return LLMEngine(cfg, num_slots=4, max_len=64, buckets=(16, 32), **kw)


def _module_name(lowered) -> str:
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


def _scopes(lowered) -> set:
    """Every word of every operation's name stack in the lowered text,
    debug info on (``jit(train_step)/transpose(jvp(attn))/dot_general``
    gives jit, train_step, transpose, jvp, attn, dot_general)."""
    out = set()
    for name in re.findall(r'loc\("([^"]+)"', lowered.as_text(
            debug_info=True)):
        out.update(re.findall(r"\w+", name))
    return out


def test_the_pinned_names():
    """The benchmark's committed readers find prefill by ``admit_fn``; the
    new ones find decode and the train step by these."""
    assert profiler.PROGRAM_PREFILL == "admit_fn"
    assert profiler.PROGRAM_DECODE == "engine_decode"
    assert profiler.PROGRAM_SPEC_DECODE == "engine_spec_decode"
    assert profiler.PROGRAM_DRAFT_PREFILL == "engine_draft_prefill"
    assert profiler.PROGRAM_TRAIN_STEP == "train_step"
    assert profiler.SPAN_PREFIX == "raytpu:"
    assert profiler.ENGINE_PHASES == ("admit", "dispatch", "fetch", "emit",
                                      "idle")
    # no program's name contains another's: a substring match tells them
    # apart
    names = [profiler.PROGRAM_PREFILL, profiler.PROGRAM_DECODE,
             profiler.PROGRAM_SPEC_DECODE, profiler.PROGRAM_DRAFT_PREFILL,
             profiler.PROGRAM_TRAIN_STEP]
    for a in names:
        assert not any(a in b for b in names if b is not a)
    readers = pytest.importorskip("benchmark.lib.readers")
    assert re.search(readers.PREFILL_PROGRAM, "jit_admit_fn(123)")
    assert not re.search(readers.PREFILL_PROGRAM, "jit_engine_decode(123)")


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_serve_programs_lower_under_their_names(tiny_cfg, paged):
    kw = dict(paged=True, page_size=8, num_pages=64) if paged else {}
    eng = _engine(tiny_cfg, **kw)
    try:
        decode = eng._decode_fn.lower(eng.params, eng.cache, eng._state)
        assert _module_name(decode) == "jit_engine_decode"
        assert {"attn", "mlp", "norm", "lm_head"} <= _scopes(decode)
        if not paged:
            assert {"kv_write", "kv_read"} <= _scopes(decode)
            admit = eng._prefill_fn(16).lower(
                eng.params, eng.cache, eng._state,
                *eng._admit_arrays([], 16, []))
            assert _module_name(admit) == "jit_admit_fn"
            assert {"attn", "mlp", "norm", "kv_write",
                    "lm_head"} <= _scopes(admit)
        # run one request: the programs the engine really compiled
        assert len(eng.generate([1, 2, 3], max_tokens=3)) == 3
        assert all(f.__name__ == "admit_fn"
                   for f in eng._prefill_fns.values())
        assert eng._decode_fn.__name__ == "engine_decode"
    finally:
        eng.shutdown()


def test_speculative_programs_lower_under_their_names(tiny_cfg):
    cfg = dataclasses.replace(tiny_cfg, num_layers=2)
    eng = _engine(cfg, spec_decode_enabled=True, spec_k=2,
                  spec_draft_layers=1)
    try:
        assert len(eng.generate([1, 2, 3, 4], max_tokens=5)) == 5
        assert {fn.__name__ for fn, _rounds in eng._spec_fns.values()} == {
            "engine_spec_decode"}
        assert {fn.__name__ for fn in eng._draft_prefill_fns.values()} == {
            "engine_draft_prefill"}
        fn, _rounds = next(iter(eng._spec_fns.values()))
        low = fn.lower(eng.params, eng.cache, eng._draft_params,
                       eng._draft_cache, eng._state)
        assert _module_name(low) == "jit_engine_spec_decode"
    finally:
        eng.shutdown()


@pytest.mark.parametrize("zero", [False, True], ids=["train_step", "zero"])
def test_train_step_lowers_under_its_name(tiny_cfg, zero):
    from ray_tpu.parallel import MeshSpec, make_optimizer, make_train_step
    from ray_tpu.parallel.train_step import init_sharded_state

    mesh = (MeshSpec(dp=2) if zero else MeshSpec(fsdp=2)).build(
        jax.devices()[:2])
    opt = make_optimizer()
    state, sh = init_sharded_state(tiny_cfg, mesh, opt)
    step = make_train_step(tiny_cfg, mesh, opt, sh, remat=False,
                           grad_quant_enabled=zero)
    tok = jnp.zeros((4, 32), jnp.int32)
    low = step._jitted.lower(state, {"tokens": tok, "targets": tok})
    assert _module_name(low) == "jit_train_step"
    assert {"attn", "mlp", "norm", "lm_head", "loss",
            "optimizer"} <= _scopes(low)


def test_chunked_loss_carries_its_scope(tiny_cfg):
    from ray_tpu.models import transformer

    x = jnp.ones((2, 16, tiny_cfg.hidden_size), jnp.float32)
    w = jnp.ones((tiny_cfg.hidden_size, tiny_cfg.vocab_size), jnp.float32)
    t = jnp.zeros((2, 16), jnp.int32)
    low = jax.jit(lambda x, w, t: transformer.chunked_cross_entropy(
        x, w, t, 8).sum()).lower(x, w, t)
    assert "loss" in _scopes(low)


def test_flash_kernels_are_named():
    from ray_tpu.ops.flash_attention import flash_attention

    q = jnp.ones((1, 128, 2, 64), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=True).sum(),
        argnums=(0, 1, 2)))(q, q, q)
    # the backward is one kernel since PR 48, under the name the benchmark's
    # ``mla_flash_train_roofline`` sums beside ``flash_fwd``
    assert set(re.findall(r"flash_\w+", str(jaxpr))) == {"flash_fwd",
                                                          "flash_dkv"}


def test_the_offset_flash_kernel_is_named():
    """``flash_fwd_rows [pallas]`` in a device trace: the forward kernel
    with a query offset, a name of its own beside the whole row's
    ``flash_fwd``, so a reader of one does not read the other unawares."""
    from ray_tpu.ops import flash_attention as fa

    assert fa.KERNEL_FLASH_ROWS == "flash_fwd_rows"
    stack = jnp.ones((2, 3, 256, 2 * 128), jnp.float32)
    jaxpr = str(jax.make_jaxpr(lambda q, k, v, at: fa.flash_attention_rows(
        q, k, v, 1, 2, at, 256, 2, interpret=True))(
            jnp.ones((1, 128, 4, 128), jnp.float32), stack, stack,
            jnp.int32(128)))
    assert set(re.findall(r"flash_\w+", jaxpr)) == {"flash_fwd_rows"}


def test_the_decode_attention_kernel_is_named():
    """``decode_attn [pallas]`` in a device trace; the benchmark's
    ``decode_attn_roofline`` spells the name out for itself."""
    import importlib.util
    import os

    from ray_tpu.ops import decode_attention as da

    assert da.KERNEL_DECODE_ATTN == "decode_attn"
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "layer_metrics",
        "decode_attn_roofline.py")
    spec = importlib.util.spec_from_file_location("_decode_attn_reader", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    assert reader.DECODE_ATTN == da.KERNEL_DECODE_ATTN
    stack = jnp.ones((2, 3, 32, 4 * 8), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q, k, v: da.decode_attn(
        q, k, v, jnp.int32(1), jnp.asarray([1, 0, 32]), 4, interpret=True))(
            jnp.ones((3, 8, 8), jnp.float32), stack, stack)
    assert "decode_attn" in str(jaxpr)


def test_named_jit_names_a_lambda():
    fn = profiler.named_jit("some_program", lambda x, y: x + y,
                            donate_argnums=(0,))
    low = fn.lower(jnp.ones(3), jnp.ones(3))
    assert _module_name(low) == "jit_some_program"
    assert float(fn(jnp.ones(3), jnp.ones(3))[0]) == 2.0


def test_engine_spans_land_in_a_profiler_capture(tiny_cfg, tmp_path):
    """An engine run inside ``jax.profiler.trace``: the five phases are
    events of a host line of the same ``.xplane.pb`` the device's
    operations go to, and their seconds are the counters' seconds."""
    from jax.profiler import ProfileData

    eng = _engine(tiny_cfg)

    def drained():
        """Wait until the loop has fetched all it dispatched and sits idle
        (a fixed nap is too short on a machine six workers share)."""
        busy = ("loop_admit_n", "loop_dispatch_n", "loop_fetch_n",
                "loop_emit_n")
        last = None
        for _ in range(100):
            time.sleep(0.1)
            now = eng.counters()
            if last and all(now[k] == last[k] for k in busy):
                return now
            last = now
        return last

    try:
        eng.warmup(16)
        drained()            # the warm-up's last dispatches drain outside it
        with jax.profiler.trace(str(tmp_path)):
            c0 = eng.counters()
            outs = [eng.generate([1, 2, 3 + i], max_tokens=9)
                    for i in range(3)]
            # idle passes inside the capture; the dispatch the engine bound
            # ahead of the last answer drains before the second snapshot
            # (its fetch would be the test's whole tolerance)
            c1 = drained()
        assert [len(o) for o in outs] == [9, 9, 9]
    finally:
        eng.shutdown()
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert files, "the profiler wrote no .xplane.pb"
    events = [(plane.name, ev.name, ev.duration_ns, dict(ev.stats))
              for plane in ProfileData.from_file(files[0]).planes
              for line in plane.lines for ev in line.events
              if ev.name.startswith("raytpu:")]
    if not events:
        pytest.skip("the CPU profiler recorded no host TraceMe events here")
    assert {plane for plane, *_ in events} == {"/host:CPU"}
    by_name = {}
    for _plane, name, dur, stats in events:
        by_name.setdefault(name, []).append((dur, stats))
    assert set(by_name) == {f"raytpu:engine.{ph}"
                            for ph in profiler.ENGINE_PHASES}
    admits = by_name["raytpu:engine.admit"]
    assert len(admits) == 3
    assert all(st.get("bucket") == 16 and st.get("rows") == 1
               and st.get("chunks") == 0 for _d, st in admits)
    # the programs in flight ahead of an admit at its dispatch, and which
    # program a fetch waited for: its kind and its ordinal since the engine
    # started, one apart from fetch to fetch (they are drained in order)
    assert all(0 <= st["ahead"] <= 1 for _d, st in admits)
    fetches = [st for _d, st in by_name["raytpu:engine.fetch"]]
    assert {st["program"] for st in fetches} == {"admit", "decode"}
    assert sum(st["program"] == "admit" for st in fetches) == 3
    seqs = [st["seq"] for st in fetches]
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
    assert sum(st["tokens"] for _d, st in by_name["raytpu:engine.emit"]) \
        == 27
    # a request that ended with a program rides its emit span as
    # "step:ms before the fetch's return" (PR 56): 9 tokens are the admit's
    # one and a whole dispatch's eight, so each ended with step 7, at the
    # program's end
    ended = [st["ended"] for _d, st in by_name["raytpu:engine.emit"]
             if "ended" in st]
    assert [e.split(":")[0] for e in ended] == ["7", "7", "7"]
    assert all(float(e.split(":")[1]) == 0.0 for e in ended)
    # the capture brackets the two snapshots, so it holds at least the
    # intervals the counters counted between them, and about their seconds
    for ph in ("admit", "dispatch", "fetch", "emit"):
        n = c1[f"loop_{ph}_n"] - c0[f"loop_{ph}_n"]
        assert n <= len(by_name[f"raytpu:engine.{ph}"]) <= n + 2, ph
    counted = sum(c1[f"loop_{ph}_s"] - c0[f"loop_{ph}_s"]
                  for ph in ("admit", "dispatch", "fetch", "emit"))
    spanned = sum(d for ph in ("admit", "dispatch", "fetch", "emit")
                  for d, _st in by_name[f"raytpu:engine.{ph}"]) / 1e9
    assert spanned == pytest.approx(counted, rel=0.2, abs=0.02)


def test_stage_spans_carry_the_wait_account(tiny_cfg, monkeypatch):
    """The task-event spans of a request say what its counters sum: a
    ``batch_wait`` its wait for a look, the looks that left it and why, a
    ``prefill`` the programs ahead of its admit, its own row's part of the
    admit's run, and the admit's rows and chunks; since PR 56 a
    ``batch_wait`` also how long the slot it took had stood free with
    nobody asking (``unfed_s``) and a ``decode`` how long the request had
    been over on the chip when the host retired it (``tail_s``)."""
    from ray_tpu.core.config import Config, reset_config, set_config
    from ray_tpu.serve.llm import _FLUSH, GenRequest
    from ray_tpu.util import tracing

    spans = {}
    monkeypatch.setattr(
        tracing, "record_span",
        lambda name, t0, dur, **kw: spans.setdefault(name, []).append(
            dict(kw, dur=dur)) or "sid")
    try:
        set_config(Config(serve_metrics_enabled=True))
        eng = _engine(tiny_cfg)
        try:
            # two buckets that one look finds together: the second is left
            reqs = [GenRequest([1 + j for j in range(n)], 3, 0.0, 0, None)
                    for n in (5, 20)]
            with eng._pending.mutex:
                eng._pending.queue.extend(reqs)
            eng._wake.set()
            for r in reqs:
                while r.out.get(timeout=120) is not _FLUSH:
                    pass
            c = eng.counters()
        finally:
            eng.shutdown()
    finally:
        reset_config()
    waits, prefills = spans["batch_wait"], spans["prefill"]
    assert len(waits) == len(prefills) == len(spans["decode"]) == 2
    assert "ingress" not in spans        # the replica's span, not the engine's
    for sp in spans["decode"]:
        assert {"tokens", "tail_s"} <= set(sp) and sp["tail_s"] >= 0
        # an engine's own caller has no buffered stream to account
        assert not {"first_chunk_wait_s", "polls_before_end"} & set(sp)
    assert sum(sp["tail_s"] for sp in spans["decode"]) == pytest.approx(
        c["slot_tail_s"])
    # both slots had stood free since the engine started
    assert sum(sp["unfed_s"] for sp in waits) <= c["slot_unfed_s"]
    for sp in waits:
        assert {"look_s", "held_s", "held_by", "unfed_s"} <= set(sp)
        assert sp["unfed_s"] > 0
        assert sp["look_s"] >= 0 and sp["held_s"] >= 0
        assert sp["look_s"] + sp["held_s"] <= sp["dur"] + 1e-9
    assert [sp["held_by"] for sp in waits] == [None, "bucket"]
    assert waits[0]["held_s"] == 0 < waits[1]["held_s"]
    for sp in prefills:
        assert {"ahead_s", "own_row_s", "rows", "chunks",
                "prompt_len"} <= set(sp)
        assert (sp["rows"], sp["chunks"]) == (1, 0)
        assert sp["ahead_s"] >= 0 and sp["own_row_s"] > 0
        assert sp["ahead_s"] + sp["own_row_s"] <= sp["dur"] + 1e-9
    assert sum(sp["held_s"] for sp in waits) == pytest.approx(
        c["queue_held_s"])
    assert sum(sp["own_row_s"] for sp in prefills) == pytest.approx(
        c["first_token_own_row_s"])


def test_the_ingress_span_heads_the_requests_chain(monkeypatch):
    """``ingress`` (transit, queue, submit) is the parent of ``batch_wait``;
    a buffered stream's ``decode`` is stamped once its end is taken, with
    what its first chunk waited and the polls that came before its end."""
    import asyncio

    import cloudpickle

    from ray_tpu.core.config import Config, reset_config, set_config
    from ray_tpu.serve.llm import LLMServer
    from ray_tpu.serve.replica import ReplicaActor
    from ray_tpu.util import tracing

    spans = []
    rep = ReplicaActor("spandep", "serve:spandep:1", cloudpickle.dumps((
        LLMServer, ("tiny",), dict(num_slots=4, max_len=64,
                                   engine_kwargs={"buckets": (16, 32)}))))
    monkeypatch.setattr(
        tracing, "record_span",
        lambda name, t0, dur, **kw: spans.append(
            dict(kw, name=name, t0=t0, dur=dur)) or f"sid-{len(spans)}")
    body = {"tokens": [1, 2, 3, 4], "max_tokens": 5}

    async def run():
        sent_at = time.time() - 0.125
        await rep.handle_request_streaming("sp", (body,), {}, None, sent_at)
        assert [s["name"] for s in spans] == ["ingress", "batch_wait",
                                              "prefill"]
        return sent_at, await rep.next_chunks("sp", 0)

    try:
        set_config(Config(serve_metrics_enabled=True))
        sent_at, (chunks, cursor, done) = asyncio.run(run())
    finally:
        reset_config()
        rep.callable.engine.shutdown()
    assert (len(chunks), cursor, done) == (5, 5, True)
    ingress, wait, _prefill, decode = spans
    assert decode["name"] == "decode"
    assert ingress["t0"] == sent_at
    assert ingress["transit_s"] == pytest.approx(0.125, abs=0.05)
    assert ingress["queue_s"] >= 0 and ingress["submit_s"] > 0
    assert ingress["dur"] == pytest.approx(
        ingress["transit_s"] + ingress["queue_s"] + ingress["submit_s"])
    assert wait["parent_id"] == "sid-1" and wait["unfed_s"] > 0
    assert wait["trace_id"] == ingress["trace_id"] == decode["trace_id"]
    assert decode["tail_s"] >= 0 and decode["tokens"] == 5
    assert decode["polls_before_end"] == 0
    assert decode["first_chunk_wait_s"] > 0


# ------------------- the kinds of model the benchmark serves (tests/kinds.py)

def _reader(name):
    import importlib.util
    import os
    path = os.path.join(kinds.BENCH, "layer_metrics", name)
    spec = importlib.util.spec_from_file_location(
        "_reader_" + name.split(".")[0], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _traced():
    """A call of each kernel a kind names, interpreted, as a jaxpr's text."""
    from ray_tpu.ops import decode_attention as da
    from ray_tpu.ops import flash_attention as fa
    from ray_tpu.ops import gated_delta as gd
    from ray_tpu.ops import kda, moe, selective_scan, ssd
    f32 = jnp.float32
    u, bc = jnp.ones((1, 128, 128), f32), jnp.ones((1, 128, 2), f32)
    q, v = jnp.ones((1, 64, 2, 8), f32), jnp.ones((1, 64, 2, 16), f32)
    g, state = jnp.zeros((1, 64, 2, 8), f32), jnp.zeros((2, 1, 2, 8, 16))
    x, b = jnp.ones((1, 128, 2, 8), f32), jnp.ones((1, 128, 1, 16), f32)
    a = jnp.zeros((2,), f32)
    rows = jnp.ones((32, 64), f32)
    plan = (jnp.zeros((2,), jnp.int32), jnp.int32(2), 16)

    def gmm(x, w, layer):
        return moe.moe_gmm(x, (w, w), jnp.int32(layer), *plan,
                           interpret=True)

    return {
        "gdn_chunk_fwd": lambda: jax.make_jaxpr(lambda *a: gd.gdn_chunk_fwd(
            *a, interpret=True))(q, q, v, g[..., 0], g[..., 0]),
        # (the packed stack: two heads of 64 lanes side by side in one tile)
        "gdn_recurrent_step": lambda: jax.make_jaxpr(
            lambda *a: gd.gdn_recurrent_step(*a, interpret=True))(
                gd.pack_state(jnp.zeros((2, 1, 2, 8, 64))), jnp.int32(1),
                q[:, 0], q[:, 0], jnp.ones((1, 2, 64), f32), g[:, 0, :, 0],
                g[:, 0, :, 0]),
        "kda_chunk_fwd": lambda: jax.make_jaxpr(lambda *a: kda.kda_chunk_fwd(
            *a, interpret=True))(q, q, v, g, g[..., 0]),
        "kda_recurrent_step": lambda: jax.make_jaxpr(
            lambda *a: kda.kda_recurrent_step(*a, interpret=True))(
                state, jnp.int32(1), q[:, 0], q[:, 0], v[:, 0], g[:, 0],
                g[:, 0, :, 0]),
        "ssd_chunk_fwd": lambda: jax.make_jaxpr(lambda *a: ssd.ssd_chunk_fwd(
            *a, interpret=True))(x, x[..., 0], a, b, b, a),
        "ssd_recurrent_step": lambda: jax.make_jaxpr(
            lambda *a: ssd.ssd_recurrent_step(*a, interpret=True))(
                state, jnp.int32(1), x[:, 0], x[:, 0, :, 0], a, b[:, 0],
                b[:, 0], a),
        "selective_scan_chunk_fwd": lambda: jax.make_jaxpr(
            lambda *a: selective_scan.selective_scan_chunk_fwd(
                *a, interpret=True))(u, u, -u[0, :2], bc, bc),
        "selective_scan_step": lambda: jax.make_jaxpr(
            lambda *a: selective_scan.selective_scan_step(
                *a, interpret=True))(
                    jnp.zeros((2, 1, 2, 1, 128)), jnp.int32(1), u[:, 0],
                    u[:, 0], -u[0, :2], bc[:, 0], bc[:, 0]),
        "decode_attn": lambda: jax.make_jaxpr(lambda q, k: da.decode_attn(
            q, k, k, jnp.int32(0), jnp.array([3, 0]), 2, interpret=True,
            tokens=2))(jnp.ones((2, 8, 8)), jnp.ones((1, 2, 16, 16))),
        "window_decode_attn": lambda: jax.make_jaxpr(
            lambda q, k: da.window_decode_attn(
                q, k, k, jnp.int32(0), jnp.array([3, 0]), 2, 8, 2,
                interpret=True))(jnp.ones((2, 8, 8)),
                                 jnp.ones((1, 2, 16, 16))),
        "flash_fwd": lambda: jax.make_jaxpr(
            lambda q, k: fa.flash_attention(q, k, k, interpret=True,
                                            scale=0.1))(
                jnp.ones((1, 128, 4, 8)), jnp.ones((1, 128, 2, 8))),
        "flash_window_prefill": lambda: jax.make_jaxpr(
            lambda q, k: fa.flash_attention(q, k, k, window=8,
                                            interpret=True))(
                jnp.ones((1, 128, 4, 8)), jnp.ones((1, 128, 2, 8))),
        "flash_window_bwd": lambda: jax.make_jaxpr(jax.grad(
            lambda q, k: fa.flash_attention(q, k, k, window=8,
                                            interpret=True).sum()))(
                jnp.ones((1, 128, 4, 8)), jnp.ones((1, 128, 2, 8))),
        "moe_gmm": lambda: jax.make_jaxpr(lambda x, w: gmm(x, w, 1))(
            rows, jnp.ones((2, 4, 64, 32), f32)),
        "mla_decode_attn": lambda: jax.make_jaxpr(
            lambda q, r, c, k: da.mla_decode_attn(
                q, r, c, k, jnp.int32(0), jnp.array([3, 0]), 0.1,
                interpret=True))(
                    jnp.ones((2, 4, 32)), jnp.ones((2, 4, 8)),
                    jnp.ones((1, 2, 16, 32)), jnp.ones((1, 2, 8, 16))),
        # the backward's two kernels beside the forward's
        "moe_gmm_dx": lambda: jax.make_jaxpr(jax.grad(
            lambda x, w: gmm(x, w, 0).sum(), argnums=(0, 1)))(
                rows, jnp.ones((1, 4, 64, 32), f32)),
    }


@pytest.mark.parametrize("name", [k.name for k in kinds.KINDS.values()
                                  if k.kernels])
def test_the_kinds_kernels_are_named(name):
    """The names a device trace shows (``gdn_chunk_fwd [pallas]``, ...),
    which the benchmark's readers spell out for themselves."""
    import importlib
    row, traced = kinds.KINDS[name], _traced()
    for module, const, kernel in row.kernels:
        ops = importlib.import_module("ray_tpu.ops." + module)
        assert getattr(ops, const) == kernel
        # a kind that trains names its kernels in one derivative's trace
        text = str(traced.get(kernel, traced["moe_gmm_dx"])())
        assert re.search(r"\b" + kernel + r"\b", text), kernel
    for reader, const, value in row.readers:
        assert getattr(_reader(reader), const) == value


def test_the_walks_buffer_call_is_named_under_the_sorts_scope(monkeypatch):
    """``moe_rows_blank``, the call that hands a walk over the sorted layout
    its buffer and writes nothing (``ops.moe._blank``; compiled kernels
    only, so the trace is of a program bound for the chip): its name, which
    ``tests/test_chip_compile_*.py`` count calls by, once a layer's part
    under ``moe_sort``, beside the grouped products' three under
    ``moe_experts``."""
    from ray_tpu.ops import moe
    assert moe.KERNEL_MOE_ROWS_BLANK == "moe_rows_blank"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(moe, "WALK_ROWS", 64)
    monkeypatch.setattr(moe, "WALK_TOKENS", 16)
    tokens, k, hidden, mid, experts, held = 96, 4, 32, 48, 16, 2
    idx = (jnp.arange(tokens * k, dtype=jnp.int32) * 7 % experts).reshape(
        tokens, k)
    stacks = {name: jnp.ones((1, held) + shape) for name, shape in (
        ("w_gate", (hidden, mid)), ("w_in", (hidden, mid)),
        ("w_out", (mid, hidden)))}

    def part(x, gates, stacks):
        return moe._held_part(x, idx, gates, None, stacks, 0, 6, k, True,
                              False, experts)[0].sum()

    calls = []

    def walk(jaxpr, above):
        for eqn in jaxpr.eqns:
            stack = f"{above}/{eqn.source_info.name_stack}"
            if eqn.primitive.name == "pallas_call":
                calls.append((eqn.params["name"], stack))
            for v in eqn.params.values():
                for sub in v if isinstance(v, (list, tuple)) else [v]:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub, stack)

    walk(jax.make_jaxpr(jax.grad(part, argnums=(0, 1, 2)))(
        jnp.ones((tokens, hidden)), jnp.ones((tokens, k)), stacks).jaxpr, "")
    blank = [stack for name, stack in calls if name == "moe_rows_blank"]
    assert len(blank) == 1 and "moe_sort" in blank[0]
    assert {name for name, _ in calls} == {
        "moe_rows_blank", "moe_gmm", "moe_gmm_dx", "moe_gmm_dw"}
    assert all("moe_experts" in stack for name, stack in calls
               if name != "moe_rows_blank")


@pytest.mark.parametrize("name", [k.name for k in kinds.KINDS.values()
                                  if k.scopes])
def test_the_kinds_serve_programs_carry_their_scopes(name):
    """The engine's two programs of the kind's tiny configuration: the
    scopes of its mixers, caches and experts (the row's), and the gauges and
    counters its engine reports."""
    from ray_tpu.models import transformer
    row = kinds.KINDS[name]
    sc, cfg = row.scopes, kinds.load(name).program_config(kinds.doc(name))
    # (any weights do: the program's own initialiser, as the engine's)
    params = kinds.init(transformer.init_params, cfg, jnp.bfloat16)
    eng = _engine(cfg, params=params,
                  **{k: v for k, v in row.engine["kw"].items()
                     if k == "prefill_batch"})
    try:
        decode = eng._decode_fn.lower(eng.params, eng.cache, eng._state)
        assert _module_name(decode) == "jit_engine_decode"
        assert sc["both"] | sc["decode"] <= _scopes(decode)
        assert not sc["neither"] & _scopes(decode)
        admit = eng._prefill_fn(16).lower(
            eng.params, eng.cache, eng._state, *eng._admit_arrays([], 16, []))
        assert _module_name(admit) == "jit_admit_fn"
        assert sc["both"] <= _scopes(admit)
        if "decode_text" in sc:
            assert re.search(sc["decode_text"],
                             decode.as_text(debug_info=True))
        assert len(eng.generate([1, 2, 3], max_tokens=3)) == 3
        stats = {**eng.counters(), **eng.breakdown()}
    finally:
        eng.shutdown()
    assert set(sc["stats"]) <= set(stats)
    layers = {k: v for k, v in row.engine["gauges"].items()
              if not k.endswith("_bytes")}
    assert {k: stats[k] for k in layers} == layers


# ---------- latent attention and dropless experts in the train step (PR 39)

def test_the_train_step_carries_the_latent_and_expert_scopes():
    """``jit_train_step`` of a configuration with latent attention, a dense
    prefix and dropless experts: the same scopes as its serve programs have
    (no cache write or read), forward and backward."""
    from ray_tpu.parallel import MeshSpec, make_optimizer, make_train_step
    from ray_tpu.parallel.train_step import TrainState, state_shardings
    from ray_tpu.models import transformer

    cfg = dataclasses.replace(kinds.tiny("xing4_0")[0], hc_mult=0,
                              q_lora_rank=0, experts_held=4, expert_start=4)
    mesh = MeshSpec(fsdp=-1).build(jax.devices()[:1])
    opt = make_optimizer()

    def init():
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        return TrainState(params=params, opt_state=opt.init(params),
                          step=jnp.zeros((), jnp.int32))

    shapes = jax.eval_shape(init)
    sh = state_shardings(cfg, mesh, opt, shapes)
    step = make_train_step(cfg, mesh, opt, sh, remat="save_acts")
    tok = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    lowered = step._jitted.lower(shapes, {"tokens": tok, "targets": tok})
    assert _module_name(lowered) == "jit_train_step"
    assert {"attn", "mlp", "norm", "loss", "optimizer", "mla_down", "mla_up",
            "moe_route", "moe_sort", "moe_experts", "moe_shared",
            "moe_combine", "transpose", "jvp"} <= _scopes(lowered)
