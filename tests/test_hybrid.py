"""Layers of two kinds on the serving path (models/hybrid.py, ops/
gated_delta.py): a tiny hybrid of 2 periods (3 gated-delta-rule layers + 1
full-attention layer each), hidden 64, key / value heads 4 x 8 / 4 x 16,
seeded random weights, on the CPU.  The independent side of every
comparison is the block kind's plain float32 reference
(benchmark/models/olmo_hybrid.py: the delta rule one token at a time, no
chunks, no cache, nothing imported from ray_tpu.models or ray_tpu.ops) or
``gated_delta.gdn_recurrence``.  Numbers here are about results, never
speed."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decode, transformer
from ray_tpu.ops import gated_delta as gd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KIND = os.path.join(REPO, "benchmark", "models", "olmo_hybrid.py")
L12 = os.path.join(REPO, "benchmark", "configs",
                   "olmo-hybrid-7b-serve-l12.json")

TINY_DOC = dict(
    model_type="olmo_hybrid", vocab_size=256, hidden_size=64,
    intermediate_size=192, num_hidden_layers=8, num_attention_heads=4,
    num_key_value_heads=4, hidden_act="silu", max_position_embeddings=256,
    attention_bias=False, rms_norm_eps=1e-6, tie_word_embeddings=False,
    layer_types=(["linear_attention"] * 3 + ["full_attention"]) * 2,
    linear_num_key_heads=4, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rope_parameters={"rope_theta": None})


@pytest.fixture(scope="module")
def kind():
    from benchmark.lib.manifest import load_model
    return load_model(KIND)


@pytest.fixture(scope="module")
def tiny(kind):
    cfg = kind.program_config(TINY_DOC)
    params = kind.init_params(jax.random.PRNGKey(3), cfg, jnp.float32)
    return cfg, params


def _qkvgb(b, t, nh, dk, dv, seed, beta_max=2.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, t, nh, dk))
    k = jax.random.normal(ks[1], (b, t, nh, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, nh, dv))
    g = -0.2 * jax.random.uniform(ks[3], (b, t, nh))
    beta = beta_max * jax.random.uniform(ks[4], (b, t, nh))
    return q, k, v, g, beta


# ------------------------------------------------- (a) the chunked form

@pytest.mark.parametrize("t", [64, 192, 37, 100, 129])
def test_chunked_form_equals_the_recurrence(t):
    """Lengths that are and are not multiples of the chunk, beta up to 2.
    Float32 both sides; 2e-5 on outputs of order 1 is rounding of a few
    dozen float32 products a chunk."""
    q, k, v, g, beta = _qkvgb(2, t, 4, 8, 16, seed=t)
    o_ref, h_ref = gd.gdn_recurrence(q, k, v, g, beta)
    o, h = jax.jit(gd.gdn_chunk_fwd_jnp)(q, k, v, g, beta)
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    np.testing.assert_allclose(h, h_ref, atol=2e-5)


def test_chunked_form_stops_each_row_at_its_length():
    q, k, v, g, beta = _qkvgb(3, 128, 4, 8, 16, seed=5)
    lengths = jnp.array([50, 128, 1])
    o, h = gd.gdn_chunk_fwd_jnp(q, k, v, g, beta, lengths)
    for row, n in enumerate([50, 128, 1]):
        cut = tuple(a[row:row + 1, :n] for a in (q, k, v, g, beta))
        o_ref, h_ref = gd.gdn_recurrence(*cut)
        np.testing.assert_allclose(o[row:row + 1, :n], o_ref, atol=2e-5)
        np.testing.assert_allclose(h[row:row + 1], h_ref, atol=2e-5)


def test_unit_lower_inverse_against_linalg():
    """Entries up to 2 (beta 2, aligned keys): the worst case of the solve."""
    low = jnp.tril(2.0 * jax.random.uniform(jax.random.PRNGKey(0), (64, 64),
                                            minval=-1.0), -1)
    want = np.linalg.inv(np.eye(64) + np.asarray(low, np.float64))
    got = np.asarray(jax.jit(gd.inv_unit_lower)(low))
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


# --------------------------------------- the Pallas kernels, interpreted

@pytest.mark.parametrize("t,dk,dv", [(128, 8, 16), (100, 96, 192)])
def test_chunk_kernel_interpreted_equals_its_twin(t, dk, dv):
    q, k, v, g, beta = _qkvgb(2, t, 2, dk, dv, seed=7)
    lengths = jnp.array([t - 9, t])
    o_t, h_t = gd.gdn_chunk_fwd_jnp(q, k, v, g, beta, lengths)
    o, h = gd.gdn_chunk_fwd(q, k, v, g, beta, lengths, interpret=True)
    np.testing.assert_allclose(o, o_t, atol=1e-5)
    np.testing.assert_allclose(h, h_t, atol=1e-5)


def test_step_kernel_interpreted_equals_its_twin_and_touches_one_layer():
    layers, slots, nh, dk, dv = 3, 5, 6, 8, 16
    state = jax.random.normal(jax.random.PRNGKey(1),
                              (layers, slots, nh, dk, dv))
    q, k, v, g, beta = (a[:, 0] for a in _qkvgb(slots, 1, nh, dk, dv, 2))
    g = g.at[3].set(0.0)
    beta = beta.at[3].set(0.0)                 # an inactive slot
    s_t, o_t = gd.gdn_recurrent_step_jnp(state, jnp.int32(1), q, k, v, g,
                                         beta)
    s, o = jax.jit(lambda *a: gd.gdn_recurrent_step(*a, interpret=True))(
        state, jnp.int32(1), q, k, v, g, beta)
    np.testing.assert_allclose(o, o_t, atol=1e-6)
    np.testing.assert_allclose(s, s_t, atol=1e-6)
    np.testing.assert_array_equal(s[0], state[0])
    np.testing.assert_array_equal(s[2], state[2])
    np.testing.assert_array_equal(s[1, 3], state[1, 3])
    o_ref, h_ref = gd.gdn_recurrence(q[:, None], k[:, None], v[:, None],
                                     g[:, None], beta[:, None], state[1])
    np.testing.assert_allclose(o, o_ref[:, 0], atol=1e-5)
    np.testing.assert_allclose(s[1], h_ref, atol=1e-5)


# ------------------------------------ (b) the engine's path == reference

def _padded(rows, bucket):
    out = np.zeros((len(rows), bucket), np.int32)
    for i, r in enumerate(rows):
        out[i, :len(r)] = r
    return out


def test_prefill_then_decode_equals_the_reference(kind, tiny):
    """Right-padded rows of unequal length in one bucket, then 12 decode
    steps, logits against the kind's reference.  Float32 compute on both
    sides: 5e-4 on logits of std 1 covers the CPU's default float32 matmuls
    through 8 layers; a wrong pad position, tail or state lands at 1e-1."""
    cfg, params = tiny
    rng = np.random.default_rng(0)
    lens, steps, slots = [37, 61], 12, [2, 0]
    toks = [rng.integers(1, 256, size=n + steps).astype(np.int32)
            for n in lens]
    cache = decode.init_kv_cache(cfg, 4, 128, jnp.float32)
    cache, lg = decode.prefill(
        params, cache, _padded([t[:n] for t, n in zip(toks, lens)], 64),
        np.array(lens, np.int32), np.array(slots, np.int32), cfg,
        jnp.float32)
    got = [[np.asarray(lg[i])] for i in range(2)]
    step = jax.jit(lambda p, c, t, a: decode.decode_step(
        p, c, t, a, cfg, jnp.float32))
    active = np.array([True, False, True, False])
    for s in range(steps):
        tk = np.zeros(4, np.int32)
        for i in range(2):
            tk[slots[i]] = toks[i][lens[i] + s]
        cache, lg = step(params, cache, tk, active)
        for i in range(2):
            got[i].append(np.asarray(lg[slots[i]]))
    for i in range(2):
        ref = np.asarray(kind.logits(
            params, toks[i], TINY_DOC,
            jnp.arange(lens[i] - 1, lens[i] + steps)))
        assert ref.std() > 0.5
        np.testing.assert_allclose(np.stack(got[i]), ref, atol=5e-4)
    # the slots nobody used hold nothing
    for name in ("state", "conv"):
        assert float(jnp.abs(cache[name][:, [1, 3]]).max()) == 0.0
    assert cache["length"].tolist() == [61 + steps, 0, 37 + steps, 0]


def test_a_decode_step_continues_a_prefill(tiny):
    """Prefill of n tokens == prefill of n - 1 and one decode step: logits,
    recurrent state and convolution tail."""
    cfg, params = tiny
    toks = np.random.default_rng(4).integers(1, 256, size=(1, 37)).astype(
        np.int32)

    def run(n):
        cache = decode.init_kv_cache(cfg, 2, 64, jnp.float32)
        return decode.prefill(params, cache, toks[:, :n],
                              np.array([n], np.int32),
                              np.array([1], np.int32), cfg, jnp.float32)

    whole, lg_whole = run(37)
    part, _ = run(36)
    part, lg = decode.decode_step(
        params, part, np.array([0, toks[0, 36]], np.int32),
        np.array([False, True]), cfg, jnp.float32)
    np.testing.assert_allclose(lg[1], lg_whole[0], atol=2e-4)
    for name in ("state", "conv"):
        np.testing.assert_allclose(part[name][:, 1], whole[name][:, 1],
                                   atol=5e-4)
    assert part["length"].tolist() == [0, 37]


# ----------------------- (c) a slot re-admitted carries nothing over

def test_a_readmitted_slot_and_the_scratch_slot_carry_nothing(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(1)
    old = rng.integers(1, 256, size=50).astype(np.int32)
    new = rng.integers(1, 256, size=21).astype(np.int32)

    def admit(cache, rows, slots):
        return decode.prefill(
            params, cache, _padded(rows, 64),
            np.array([len(r) for r in rows], np.int32),
            np.array(slots, np.int32), cfg, jnp.float32)

    fresh, lg_fresh = admit(decode.init_kv_cache(cfg, 3, 128, jnp.float32),
                            [new, [1]], [0, 2])
    used, _ = admit(decode.init_kv_cache(cfg, 3, 128, jnp.float32),
                    [old, [1]], [0, 2])
    for _ in range(3):                   # the old request decodes a while
        used, _ = decode.decode_step(
            params, used, np.array([7, 0, 0], np.int32),
            np.array([True, False, False]), cfg, jnp.float32)
    used, lg_used = admit(used, [new, [1]], [0, 2])   # slot 0 again
    np.testing.assert_array_equal(lg_used[0], lg_fresh[0])
    for name in ("state", "conv"):
        np.testing.assert_array_equal(used[name][:, 0], fresh[name][:, 0])
        # slot 1 was never admitted; the padding row went to scratch slot 2
        assert float(jnp.abs(used[name][:, 1]).max()) == 0.0
    step = lambda c: decode.decode_step(                # noqa: E731
        params, c, np.array([9, 0, 0], np.int32),
        np.array([True, False, False]), cfg, jnp.float32)
    np.testing.assert_array_equal(step(used)[1][0], step(fresh)[1][0])


# ------------------------------- (d) LLMEngine end to end, greedy tokens

def test_engine_generates_the_references_greedy_tokens(kind, tiny):
    from ray_tpu.serve.llm import LLMEngine
    cfg, params = tiny
    eng = LLMEngine(cfg, params=params, num_slots=3, max_len=96,
                    buckets=(32, 64), compute_dtype=jnp.float32,
                    prefill_batch=2)
    try:
        rng = np.random.default_rng(2)
        prompts = [rng.integers(1, 256, size=n).tolist() for n in (19, 40, 7)]
        reqs = [eng.submit(p, max_tokens=10) for p in prompts]
        outs = []
        for r in reqs:
            toks = []
            while True:
                item = r.out.get(timeout=120)
                if not isinstance(item, int):
                    assert not isinstance(item, BaseException), item
                    break
                toks.append(item)
            outs.append(toks)
        gauges = eng.breakdown()
    finally:
        eng.shutdown()
    for prompt, out in zip(prompts, outs):
        assert len(out) == 10
        seq = np.array(prompt + out, np.int32)
        ref = np.asarray(kind.logits(
            params, seq[:-1], TINY_DOC,
            jnp.arange(len(prompt) - 1, len(seq) - 1)))
        top2 = np.sort(ref, axis=-1)[:, -2:]
        assert (top2[:, 1] - top2[:, 0]).min() > 1e-3     # no near tie
        assert out == ref.argmax(-1).tolist()
    assert gauges["linear_layers"] == 6 and gauges["full_layers"] == 2
    # 4 cache rows (3 slots + scratch): K and V of 2 full layers, the state
    # and the convolution tail of 6 linear ones, float32 here
    assert gauges["cache_kv_bytes"] == 2 * 2 * 4 * 96 * 64 * 4
    assert gauges["cache_state_bytes"] == 6 * 4 * (4 * 8 * 16 + 3 * 128) * 4


def test_a_dense_engine_reports_the_same_gauges():
    from ray_tpu.models import config as mcfg
    from ray_tpu.serve.llm import LLMEngine
    cfg = mcfg.tiny()
    eng = LLMEngine(cfg, num_slots=2, max_len=32, buckets=(16,))
    try:
        g = eng.breakdown()
    finally:
        eng.shutdown()
    assert g["linear_layers"] == 0 and g["full_layers"] == cfg.num_layers
    assert g["cache_state_bytes"] == 0
    assert g["cache_kv_bytes"] == decode.cache_bytes(cfg, 3, 32)


# --------------------------------------------------- (e) the refusals

@pytest.mark.parametrize("kw,match", [
    (dict(paged=True), "paged"),
    (dict(spec_decode_enabled=True), "spec_decode_enabled"),
    (dict(tp=2), "tp=2"),
])
def test_the_engine_refuses_what_a_hybrid_cache_cannot_do(tiny, kw, match):
    from ray_tpu.serve.llm import LLMEngine
    cfg, params = tiny
    with pytest.raises(ValueError, match=match):
        LLMEngine(cfg, params=params, num_slots=2, max_len=32, **kw)


@pytest.mark.parametrize("what", ["make_train_step", "apply_trunk"])
def test_training_refuses_a_layer_pattern(tiny, what):
    cfg, params = tiny
    with pytest.raises(NotImplementedError, match="layer_pattern"):
        if what == "apply_trunk":
            transformer.apply_trunk(params, jnp.zeros((1, 8), jnp.int32), cfg)
        else:
            from ray_tpu.parallel import MeshSpec, make_optimizer, \
                make_train_step
            mesh = MeshSpec(fsdp=2).build(jax.devices()[:2])
            make_train_step(cfg, mesh, make_optimizer(), None)


@pytest.mark.parametrize("change,match", [
    (dict(layer_types=["linear_attention"] * 5 + ["full_attention"] * 3
          + ["linear_attention"], num_hidden_layers=9), "whole periods"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(rope_parameters={"rope_theta": 500000.0}), "rope_theta"),
    (dict(layer_types=["linear_attention"] * 7 + ["sliding_attention"]),
     "sliding_attention"),
])
def test_the_kind_refuses_what_the_block_cannot_express(kind, change, match):
    with pytest.raises(ValueError, match=match):
        kind.program_config({**TINY_DOC, **change})


@pytest.mark.parametrize("kw,match", [
    (dict(num_layers=5), "whole periods"),
    (dict(layer_pattern=("linear", "window")), "kinds"),
    (dict(linear_decay_per_channel=True), "linear_gate_rank"),
    (dict(linear_key_dim=0), "linear_key_dim"),
    (dict(layer_pattern=()), "layer_pattern only"),
], ids=["half-a-period", "unknown-kind", "decay-a-channel-without-its-rank",
        "no-mixer-sizes",
        "norms-without-a-pattern"])
def test_config_refuses_what_the_hybrid_blocks_are_not(kw, match):
    from ray_tpu.models.config import TransformerConfig
    base = dict(vocab_size=8, hidden_size=8, num_heads=1, num_kv_heads=1,
                mlp_size=8, max_seq_len=8, num_layers=4, linear_num_heads=1,
                linear_key_dim=4, linear_value_dim=4, norm_on_output=True,
                layer_pattern=("linear", "full"))
    TransformerConfig(**base)
    with pytest.raises(ValueError, match=match):
        TransformerConfig(**{**base, **kw})


# ------------------------------------------ the kind's counts (l12 file)

def test_counts_of_the_l12_configuration(kind):
    """``num_params`` is the program's tree to the parameter (3.27B); the
    decode step's three byte terms: weights once, the state read and written
    per active slot per linear layer at 4 bytes, K/V per live token for the
    full layers only."""
    import json
    doc = json.load(open(L12))
    cfg = kind.program_config(doc)
    tree = jax.eval_shape(lambda k: kind.init_params(k, cfg, jnp.bfloat16),
                          jax.random.PRNGKey(0))
    leaves = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert kind.num_params(doc) == leaves == 3_268_268_508
    assert round(kind.num_params(doc) / 1e9, 2) == 3.27
    per = kind.layer_matrix_params(doc)
    assert per == {"linear": 215_516_160, "full": 185_794_560}
    weights = (9 * per["linear"] + 3 * per["full"] + 100352 * 3840) * 2
    assert kind.decode_step_bytes(doc, 0, 0) == weights
    assert kind.state_bytes_per_slot(doc) == 9 * 30 * 96 * 192 * 4
    assert kind.decode_step_bytes(doc, 1, 0) - weights \
        == 2 * 9 * 30 * 96 * 192 * 4
    assert kind.kv_bytes_per_token(doc) == 46_080
    assert kind.decode_step_bytes(doc, 0, 1) - weights == 46_080
    assert kind.decode_step_flops(doc, 1, 0) == weights + 9 * 30 * 6 * 96 * 192
    assert kind.gdn_recurrent_step_bytes(doc, 24) == 24 * (
        2 * 9 * 30 * 96 * 192 * 4 + 9 * 30 * (2 * 96 + 2 * 192) * 2)
    assert kind.gdn_chunk_fwd_bytes(doc, 1000) == 9 * 30 * 1160 * 1000
    assert kind.CHUNK == gd.CHUNK == 64      # the counts' chunk is the kernel's
    assert kind.gdn_chunk_fwd_flops(doc, 1) == pytest.approx(
        9 * 30 * (6 * 64 * 96 + 4 * 64 * 192 + 6 * 96 * 192 + 2 * 64 * 64 / 3))
    # the cache the engine would hold for this file: 24 + 1 rows
    cache = jax.eval_shape(lambda: kind.init_cache(cfg, 25, 4096,
                                                   jnp.bfloat16))
    assert decode.cache_gauges(cfg, cache) == {
        "cache_kv_bytes": 25 * 4096 * 46_080,
        "cache_state_bytes": 25 * (kind.state_bytes_per_slot(doc)
                                   + 9 * 3 * 11520 * 2),
        "linear_layers": 9, "full_layers": 3,
        # no latent rows and no experts here (PR 35's gauges)
        "cache_latent_bytes": 0, "expert_layers": 0, "experts_held": 0}


# ------------- nothing in benchmark/ outside models/ and tests/ names a model

def test_only_a_kinds_file_names_the_programs_models():
    names = re.compile(r"ray_tpu\.models|TransformerConfig")
    bench = os.path.join(REPO, "benchmark")
    hits = []
    for d, _, files in os.walk(bench):
        if os.path.relpath(d, bench).split(os.sep)[0] in ("models", "tests"):
            continue
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(d, f)
                hits += [f"{path}:{n}: {line}" for n, line in
                         enumerate(open(path), 1) if names.search(line)]
    assert not hits, "".join(hits)


# ------------- one walk over the layers, and no cache through a scan (PR 31)

def _scans(jaxpr):
    """Every ``scan`` of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


def test_one_walk_over_the_layers_and_no_cache_through_a_scan(tiny):
    """In ``ray_tpu/models/`` two functions loop over the layers' weights:
    ``decode.layer_stack`` (serving, every kind of cache) and
    ``transformer.apply_trunk`` (training).  And whichever tree the serving
    programs are traced on, no scan takes or returns a cache array as
    xs / ys: that slices every layer out and restacks all of it (PR 26)."""
    import ast

    from ray_tpu.models import paged_decode, speculative
    from ray_tpu.models.config import TransformerConfig

    loops = {"scan", "fori_loop", "while_loop", "map"}
    models = os.path.join(REPO, "ray_tpu", "models")
    over_layers = set()
    for name in sorted(os.listdir(models)):
        if not name.endswith(".py"):
            continue
        src = open(os.path.join(models, name)).read()
        for fn in ast.parse(src).body:
            if isinstance(fn, ast.FunctionDef) and any(
                    isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Attribute)
                    and n.func.attr in loops
                    and ast.unparse(n.func.value).endswith("lax")
                    for n in ast.walk(fn)) and '"blocks"' in ast.get_source_segment(src, fn):
                over_layers.add((name, fn.name))
    assert over_layers == {("decode.py", "layer_stack"),
                           ("transformer.py", "apply_trunk")}

    hybrid_cfg, hybrid_params = tiny
    dense_cfg = TransformerConfig(
        vocab_size=128, num_layers=3, hidden_size=64, num_heads=4,
        num_kv_heads=2, mlp_size=128, max_seq_len=96)
    dense_params = transformer.init_params(jax.random.PRNGKey(0), dense_cfg,
                                           dtype=jnp.float32)
    draft_cfg = TransformerConfig(**{**dense_cfg.__dict__, "num_layers": 1})
    slots, max_len, rows, bucket = 5, 96, 2, 16
    trees = {
        "rows": (dense_cfg, dense_params,
                 decode.init_kv_cache(dense_cfg, slots, max_len)),
        "pages": (dense_cfg, dense_params, paged_decode.init_paged_cache(
            dense_cfg, 20, 8, slots, max_len // 8)),
        "recurrent": (hybrid_cfg, hybrid_params,
                      decode.init_kv_cache(hybrid_cfg, slots, max_len)),
    }
    state = decode.init_decode_state(slots, jax.random.PRNGKey(1))
    admit = (jnp.zeros((rows, bucket), jnp.int32),) + tuple(
        jnp.ones((rows,), dt) for dt in (jnp.int32, jnp.int32, jnp.float32,
                                         jnp.int32, jnp.int32, jnp.bool_))
    for name, (cfg, params, cache) in trees.items():
        held = {a.shape for k, a in cache.items()
                if k not in ("length", "block_table")}
        programs = [
            (lambda p, c, st: decode.decode_state_loop(p, c, st, 2, cfg),
             (params, cache, state)),
            (lambda p, c, st, *a: decode.prefill_admit(p, c, st, *a, cfg),
             (params, cache, state) + admit),
        ]
        if name != "recurrent":
            draft = speculative.make_draft_params(params, 1)
            programs.append((
                lambda p, c, dp, dc, st: speculative.spec_decode_state_loop(
                    p, c, dp, dc, st, 3, 2, cfg, draft_cfg),
                (params, cache, draft,
                 decode.init_kv_cache(draft_cfg, slots, max_len), state)))
        for fn, args in programs:
            found = list(_scans(jax.make_jaxpr(fn)(*args).jaxpr))
            assert found
            for eqn in found:
                skip = eqn.params["num_consts"] + eqn.params["num_carry"]
                through = ([v.aval.shape for v in eqn.invars[skip:]]
                           + [v.aval.shape for v in
                              eqn.outvars[eqn.params["num_carry"]:]])
                assert not held & set(through), (name, through)
