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
    try:
        eng.warmup(16)
        time.sleep(0.1)      # the warm-up's last dispatches drain outside it
        with jax.profiler.trace(str(tmp_path)):
            c0 = eng.counters()
            outs = [eng.generate([1, 2, 3 + i], max_tokens=9)
                    for i in range(3)]
            # idle passes inside the capture; the dispatch the engine bound
            # ahead of the last answer drains before the second snapshot
            # (its fetch would be the test's whole tolerance)
            time.sleep(0.1)
            c1 = eng.counters()
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
    admit's run, and the admit's rows and chunks."""
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
    assert len(waits) == len(prefills) == 2
    for sp in waits:
        assert {"look_s", "held_s", "held_by"} <= set(sp)
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


# ------------------------------------------------ layers of two kinds (PR 29)

@pytest.fixture(scope="module")
def hybrid_cfg():
    from ray_tpu.models.config import TransformerConfig
    return TransformerConfig(
        vocab_size=256, num_layers=4, hidden_size=64, num_heads=4,
        num_kv_heads=4, mlp_size=192, max_seq_len=64, use_rope=False,
        no_positions=True, qk_norm=True, norm_on_output=True,
        layer_pattern=("linear", "linear", "linear", "full"),
        linear_num_heads=4, linear_key_dim=8, linear_value_dim=16,
        linear_neg_eigval=True)


def test_the_gdn_kernels_are_named():
    """The names a device trace shows (``gdn_chunk_fwd [pallas]``,
    ``gdn_recurrent_step [pallas]``), which the benchmark's gdn_* readers
    spell out for themselves."""
    import importlib.util
    import os

    from ray_tpu.ops import gated_delta as gd

    assert gd.KERNEL_CHUNK_FWD == "gdn_chunk_fwd"
    assert gd.KERNEL_RECURRENT_STEP == "gdn_recurrent_step"
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "layer_metrics", "_gdn.py")
    spec = importlib.util.spec_from_file_location("_gdn_readers", path)
    readers = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(readers)
    assert (readers.CHUNK_FWD, readers.RECURRENT_STEP) == (
        gd.KERNEL_CHUNK_FWD, gd.KERNEL_RECURRENT_STEP)
    q = jnp.ones((1, 64, 2, 8), jnp.float32)
    v = jnp.ones((1, 64, 2, 16), jnp.float32)
    g = jnp.zeros((1, 64, 2), jnp.float32)
    chunk = jax.make_jaxpr(lambda *a: gd.gdn_chunk_fwd(
        *a, interpret=True))(q, q, v, g, g)
    assert "gdn_chunk_fwd" in str(chunk)
    step = jax.make_jaxpr(lambda *a: gd.gdn_recurrent_step(
        *a, interpret=True))(jnp.zeros((2, 1, 2, 8, 16)), jnp.int32(1),
                             q[:, 0], q[:, 0], v[:, 0], g[:, 0], g[:, 0])
    assert "gdn_recurrent_step" in str(step)


def test_hybrid_serve_programs_carry_their_scopes(hybrid_cfg):
    eng = _engine(hybrid_cfg, prefill_batch=2)
    try:
        decode = eng._decode_fn.lower(eng.params, eng.cache, eng._state)
        assert _module_name(decode) == "jit_engine_decode"
        assert {"attn", "mlp", "norm", "lm_head", "kv_write", "kv_read",
                "gdn", "gdn_conv", "state_read",
                "state_write"} <= _scopes(decode)
        admit = eng._prefill_fn(16).lower(
            eng.params, eng.cache, eng._state, *eng._admit_arrays([], 16, []))
        assert _module_name(admit) == "jit_admit_fn"
        assert {"attn", "mlp", "norm", "lm_head", "kv_write", "gdn",
                "gdn_conv", "state_write"} <= _scopes(admit)
        assert len(eng.generate([1, 2, 3], max_tokens=3)) == 3
        stats = eng.breakdown()
        assert {"cache_kv_bytes", "cache_state_bytes", "linear_layers",
                "full_layers"} <= set(stats)
    finally:
        eng.shutdown()


# ------- latent attention, dropless experts, residual streams (PR 35)

@pytest.fixture(scope="module")
def latent_cfg():
    from ray_tpu.models.config import TransformerConfig
    return TransformerConfig(
        vocab_size=256, num_layers=3, hidden_size=64, num_heads=4,
        num_kv_heads=4, mlp_size=128, max_seq_len=64, rope_theta=10000.0,
        norm_eps=1e-6, q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_yarn_factor=64.0,
        rope_yarn_original_max=16, rope_yarn_mscale_all_dim=1.0,
        moe_dropless=True, num_experts=8, experts_per_token=2,
        expert_mlp_size=32, shared_experts=1, routed_scaling_factor=2.0,
        dense_prefix_layers=1, hc_mult=4)


def _reader(name):
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "layer_metrics", name)
    spec = importlib.util.spec_from_file_location(
        "_reader_" + name.split(".")[0], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_moe_and_latent_kernels_are_named():
    """The names a device trace shows (``moe_gmm [pallas]``,
    ``mla_decode_attn [pallas]``), which the benchmark's readers spell out
    for themselves."""
    from ray_tpu.ops import decode_attention as da
    from ray_tpu.ops import moe

    assert moe.KERNEL_MOE_GMM == "moe_gmm"
    assert da.KERNEL_MLA_DECODE_ATTN == "mla_decode_attn"
    assert _reader("moe_gmm_roofline.py").MOE_GMM == moe.KERNEL_MOE_GMM
    assert (_reader("mla_decode_attn_roofline.py").MLA_DECODE_ATTN
            == da.KERNEL_MLA_DECODE_ATTN)
    assert _reader("moe_mla_kernels_device_share.py").KERNELS == (
        moe.KERNEL_MOE_GMM, da.KERNEL_MLA_DECODE_ATTN)
    x = jnp.ones((32, 64), jnp.float32)
    w = jnp.ones((2, 4, 64, 32), jnp.float32)
    gmm = jax.make_jaxpr(lambda x, w: moe.moe_gmm(
        x, (w, w), jnp.int32(1), jnp.zeros((2,), jnp.int32), jnp.int32(2),
        16, interpret=True))(x, w)
    assert "moe_gmm" in str(gmm)
    attn = jax.make_jaxpr(lambda q, r, c, k: da.mla_decode_attn(
        q, r, c, k, jnp.int32(0), jnp.array([3, 0]), 0.1, interpret=True))(
            jnp.ones((2, 4, 32)), jnp.ones((2, 4, 8)),
            jnp.ones((1, 2, 16, 32)), jnp.ones((1, 2, 8, 16)))
    assert "mla_decode_attn" in str(attn)


def test_latent_serve_programs_carry_their_scopes(latent_cfg):
    eng = _engine(latent_cfg)
    try:
        decode = eng._decode_fn.lower(eng.params, eng.cache, eng._state)
        assert _module_name(decode) == "jit_engine_decode"
        shared = {"attn", "mlp", "norm", "lm_head", "mla_down", "mla_up",
                  "latent_write", "moe_route", "moe_sort", "moe_experts",
                  "moe_shared", "moe_combine", "hc_coeff", "hc_mix"}
        assert shared | {"latent_read"} <= _scopes(decode)
        admit = eng._prefill_fn(16).lower(
            eng.params, eng.cache, eng._state, *eng._admit_arrays([], 16, []))
        assert _module_name(admit) == "jit_admit_fn"
        assert shared <= _scopes(admit)
        assert len(eng.generate([1, 2, 3], max_tokens=3)) == 3
        assert {"cache_latent_bytes", "experts_held", "expert_layers",
                "moe_assignments", "moe_experts_touched",
                "moe_expert_layer_steps", "moe_assignments_prefill"} <= set(
                    {**eng.breakdown(), **eng.counters()})
    finally:
        eng.shutdown()


# ---------- latent attention and dropless experts in the train step (PR 39)

def test_the_train_steps_moe_kernels_are_named():
    """The backward's two kernels beside the forward's, as a device trace
    shows them (``moe_gmm_dx [pallas]``, ``moe_gmm_dw [pallas]``), which the
    benchmark's train readers spell out for themselves."""
    from ray_tpu.ops import moe

    assert (moe.KERNEL_MOE_GMM, moe.KERNEL_MOE_GMM_DX,
            moe.KERNEL_MOE_GMM_DW) == ("moe_gmm", "moe_gmm_dx", "moe_gmm_dw")
    shared = _reader("_moe_train.py")
    assert shared.MOE_GMM_TRAIN == (
        moe.KERNEL_MOE_GMM, moe.KERNEL_MOE_GMM_DX, moe.KERNEL_MOE_GMM_DW)
    # the reader's list still holds ``flash_dq``, a kernel that is gone
    # since PR 48 (the backward is ``flash_dkv`` alone); it sums what it
    # finds, and the entry is a ``benchmark`` PR's to drop
    assert shared.FLASH_TRAIN == ("flash_fwd", "flash_dq", "flash_dkv")
    x = jnp.ones((32, 64), jnp.float32)
    w = jnp.ones((1, 4, 64, 32), jnp.float32)
    grad = jax.make_jaxpr(jax.grad(lambda x, w: moe.moe_gmm(
        x, (w, w), jnp.int32(0), jnp.zeros((2,), jnp.int32), jnp.int32(2),
        16, interpret=True).sum(), argnums=(0, 1)))(x, w)
    for name in shared.MOE_GMM_TRAIN:
        assert re.search(r"\b" + name + r"\b", str(grad)), name


def test_the_train_step_carries_the_latent_and_expert_scopes(latent_cfg):
    """``jit_train_step`` of a configuration with latent attention, a dense
    prefix and dropless experts: the same scopes as its serve programs have
    (no cache write or read), forward and backward."""
    from ray_tpu.parallel import MeshSpec, make_optimizer, make_train_step
    from ray_tpu.parallel.train_step import TrainState, state_shardings
    from ray_tpu.models import transformer

    cfg = dataclasses.replace(latent_cfg, hc_mult=0, q_lora_rank=0,
                              experts_held=4, expert_start=4)
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


# ---- a decay a channel, a gated full layer, experts under a pattern (PR 44)

@pytest.fixture(scope="module")
def kda_cfg():
    from ray_tpu.models.config import TransformerConfig
    return TransformerConfig(
        vocab_size=256, num_layers=4, hidden_size=64, num_heads=4,
        num_kv_heads=2, mlp_size=128, max_seq_len=64, use_rope=False,
        no_positions=True, attn_head_dim=32, attn_output_gate=True,
        layer_pattern=("full", "linear", "linear", "linear"),
        linear_num_heads=4, linear_key_dim=16, linear_value_dim=16,
        linear_neg_eigval=True, linear_decay_per_channel=True,
        linear_gate_rank=16, moe_dropless=True,
        num_experts=16, experts_per_token=4, expert_mlp_size=32,
        shared_experts=1, expert_start=4, experts_held=4)


def test_the_kda_kernels_are_named():
    """The names a device trace shows (``kda_chunk_fwd [pallas]``,
    ``kda_recurrent_step [pallas]``), which the benchmark's kda_* readers
    spell out for themselves."""
    from ray_tpu.ops import kda

    assert kda.KERNEL_KDA_CHUNK_FWD == "kda_chunk_fwd"
    assert kda.KERNEL_KDA_RECURRENT_STEP == "kda_recurrent_step"
    readers = _reader("_kda.py")
    assert (readers.CHUNK_FWD, readers.RECURRENT_STEP, readers.MOE_GMM) == (
        kda.KERNEL_KDA_CHUNK_FWD, kda.KERNEL_KDA_RECURRENT_STEP, "moe_gmm")
    assert _reader("kda_moe_kernels_device_share.py").KERNELS == (
        "kda_chunk_fwd", "kda_recurrent_step", "moe_gmm")
    q = jnp.ones((1, 64, 2, 8), jnp.float32)
    v = jnp.ones((1, 64, 2, 16), jnp.float32)
    g = jnp.zeros((1, 64, 2, 8), jnp.float32)
    chunk = jax.make_jaxpr(lambda *a: kda.kda_chunk_fwd(
        *a, interpret=True))(q, q, v, g, g[..., 0])
    assert "kda_chunk_fwd" in str(chunk)
    step = jax.make_jaxpr(lambda *a: kda.kda_recurrent_step(
        *a, interpret=True))(jnp.zeros((2, 1, 2, 8, 16)), jnp.int32(1),
                             q[:, 0], q[:, 0], v[:, 0], g[:, 0], g[:, 0, :, 0])
    assert "kda_recurrent_step" in str(step)


def test_kda_serve_programs_carry_their_scopes(kda_cfg):
    """The KDA mixer's pieces under ``kda`` / ``kda_conv`` / ``kda_gate``
    (state reads and writes keep ``state_read`` / ``state_write``), the
    gated full layer's gate under ``attn``, the experts' under ``moe_*``;
    none of the scalar-decay mixer's ``gdn`` scopes."""
    eng = _engine(kda_cfg)
    try:
        decode = eng._decode_fn.lower(eng.params, eng.cache, eng._state)
        assert _module_name(decode) == "jit_engine_decode"
        shared = {"attn", "norm", "lm_head", "kv_write", "kda", "kda_conv",
                  "kda_gate", "state_write", "moe_route", "moe_sort",
                  "moe_experts", "moe_shared", "moe_combine"}
        assert shared | {"kv_read", "state_read"} <= _scopes(decode)
        assert not {"gdn", "gdn_conv"} & _scopes(decode)
        admit = eng._prefill_fn(16).lower(
            eng.params, eng.cache, eng._state, *eng._admit_arrays([], 16, []))
        assert _module_name(admit) == "jit_admit_fn"
        assert shared <= _scopes(admit)
        # the gate's sigmoid sits under the full layer's ``attn``
        assert re.search(r'attn/logistic', decode.as_text(debug_info=True))
        assert len(eng.generate([1, 2, 3], max_tokens=3)) == 3
        stats = {**eng.counters(), **eng.breakdown()}
        assert {"cache_kv_bytes", "cache_state_bytes", "linear_layers",
                "full_layers", "experts_held", "expert_layers",
                "moe_assignments", "moe_experts_touched",
                "moe_expert_layer_steps", "moe_assignments_prefill"} <= set(
                    stats)
        assert (stats["experts_held"], stats["expert_layers"],
                stats["linear_layers"], stats["full_layers"]) == (4, 4, 3, 1)
    finally:
        eng.shutdown()


# ---- a state-space mixer, layers that are one sublayer alone, experts of
# ---- two matrices (PR 46)

@pytest.fixture(scope="module")
def ssm_cfg():
    from ray_tpu.models.config import TransformerConfig
    return TransformerConfig(
        vocab_size=256, num_layers=9, hidden_size=64, num_heads=4,
        num_kv_heads=2, mlp_size=24, max_seq_len=64, use_rope=False,
        no_positions=True, attn_head_dim=32,
        layer_pattern=("ssm", "mlp", "ssm", "mlp", "ssm", "full", "mlp",
                       "ssm", "mlp"),
        mlp_act="relu2", linear_num_heads=4,
        linear_key_dim=32, linear_value_dim=16, ssm_groups=2,
        moe_dropless=True, num_experts=16, experts_per_token=3,
        expert_mlp_size=24, shared_experts=2, routed_scaling_factor=2.5,
        expert_start=8, experts_held=8)


def test_the_ssd_kernels_are_named():
    """The names a device trace shows (``ssd_chunk_fwd [pallas]``,
    ``ssd_recurrent_step [pallas]``), which the benchmark's ssd_* readers
    spell out for themselves."""
    from ray_tpu.ops import ssd

    assert ssd.KERNEL_SSD_CHUNK_FWD == "ssd_chunk_fwd"
    assert ssd.KERNEL_SSD_RECURRENT_STEP == "ssd_recurrent_step"
    readers = _reader("_ssd.py")
    assert (readers.CHUNK_FWD, readers.RECURRENT_STEP, readers.MOE_GMM) == (
        ssd.KERNEL_SSD_CHUNK_FWD, ssd.KERNEL_SSD_RECURRENT_STEP, "moe_gmm")
    assert _reader("ssm_moe_kernels_device_share.py").KERNELS == (
        "ssd_chunk_fwd", "ssd_recurrent_step", "moe_gmm")
    x = jnp.ones((1, 128, 2, 8), jnp.float32)
    b = jnp.ones((1, 128, 1, 16), jnp.float32)
    a = jnp.zeros((2,), jnp.float32)
    chunk = jax.make_jaxpr(lambda *args: ssd.ssd_chunk_fwd(
        *args, interpret=True))(x, x[..., 0], a, b, b, a)
    assert "ssd_chunk_fwd" in str(chunk)
    step = jax.make_jaxpr(lambda *args: ssd.ssd_recurrent_step(
        *args, interpret=True))(jnp.zeros((2, 1, 2, 8, 16)), jnp.int32(1),
                                x[:, 0], x[:, 0, :, 0], a, b[:, 0], b[:, 0],
                                a)
    assert "ssd_recurrent_step" in str(step)


def test_ssm_serve_programs_carry_their_scopes(ssm_cfg):
    """The state-space mixer's pieces under ``ssm`` / ``ssm_conv`` (state
    reads and writes keep ``state_read`` / ``state_write``), the expert
    layer's under ``moe_*`` as under any layer."""
    eng = _engine(ssm_cfg)
    try:
        decode = eng._decode_fn.lower(eng.params, eng.cache, eng._state)
        assert _module_name(decode) == "jit_engine_decode"
        shared = {"attn", "norm", "lm_head", "kv_write", "ssm", "ssm_conv",
                  "state_write", "moe_route", "moe_sort", "moe_experts",
                  "moe_shared", "moe_combine"}
        assert shared | {"kv_read", "state_read"} <= _scopes(decode)
        admit = eng._prefill_fn(16).lower(
            eng.params, eng.cache, eng._state, *eng._admit_arrays([], 16, []))
        assert _module_name(admit) == "jit_admit_fn"
        assert shared <= _scopes(admit)
        assert len(eng.generate([1, 2, 3], max_tokens=3)) == 3
        stats = {**eng.counters(), **eng.breakdown()}
        assert {"cache_kv_bytes", "cache_state_bytes", "linear_layers",
                "ssm_layers", "full_layers", "experts_held", "expert_layers",
                "moe_assignments", "moe_experts_touched",
                "moe_expert_layer_steps", "moe_assignments_prefill"} <= set(
                    stats)
        assert (stats["experts_held"], stats["expert_layers"],
                stats["linear_layers"], stats["ssm_layers"],
                stats["full_layers"]) == (8, 4, 0, 4, 1)
    finally:
        eng.shutdown()
