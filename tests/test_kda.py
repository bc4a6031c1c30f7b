"""The delta rule with a decay a channel (ops/kda.py), the KDA variant of
the linear mixer and the gated full layer (models/hybrid.py), dropless
experts under a layer pattern and a served share of them (models/decode.py
``layer_stack``): a tiny model of 2 periods (gated GQA + 3 KDA), hidden 64,
16 routed experts of which a share of 4 is held, seeded random weights, on
the CPU.  The independent side of every comparison is the block kind's plain
float32 reference (benchmark/models/solar_open2.py: the rule one token at a
time, no chunks, no cache, nothing imported from ray_tpu.models or
ray_tpu.ops) or ``kda.kda_recurrence``.  Numbers here are about results,
never speed."""

import copy
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decode
from ray_tpu.models.config import TransformerConfig
from ray_tpu.ops import kda

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KIND = os.path.join(REPO, "benchmark", "models", "solar_open2.py")
L4 = os.path.join(REPO, "benchmark", "configs",
                  "solar-open2-250b-serve-l4-e40.json")
TINY = os.path.join(REPO, "benchmark", "tests", "tiny", "configs",
                    "tiny-solar.json")


@pytest.fixture(scope="module")
def kind():
    from benchmark.lib.manifest import load_model
    return load_model(KIND)


@pytest.fixture(scope="module")
def tiny_doc():
    with open(TINY) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny(kind, tiny_doc):
    cfg = kind.program_config(tiny_doc)
    params = kind.init_params(jax.random.PRNGKey(3), cfg, jnp.float32)
    return cfg, params


def _qkvgb(b, t, nh, dk, dv, seed, low=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, t, nh, dk))
    k = jax.random.normal(ks[1], (b, t, nh, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, nh, dv))
    g = -0.2 * jax.random.uniform(ks[3], (b, t, nh, dk))
    if low is not None:          # one channel's alpha 0.05 at every step
        g = g.at[..., low].set(jnp.log(0.05))
    beta = 2.0 * jax.random.uniform(ks[4], (b, t, nh))
    return q, k, v, g, beta


# ------------------------------------------------- (a) the chunked form

@pytest.mark.parametrize("t", [64, 192, 37, 100, 129])
def test_chunked_form_equals_the_recurrence(t):
    """Lengths that are and are not multiples of the chunk, beta up to 2, a
    decay of its own for every channel.  Float32 both sides."""
    q, k, v, g, beta = _qkvgb(2, t, 3, 8, 16, seed=t)
    o_ref, h_ref = kda.kda_recurrence(q, k, v, g, beta)
    o, h = jax.jit(kda.kda_chunk_fwd_jnp)(q, k, v, g, beta)
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    np.testing.assert_allclose(h, h_ref, atol=2e-5)


def test_chunked_form_stops_each_row_at_its_length():
    q, k, v, g, beta = _qkvgb(3, 128, 2, 8, 16, seed=5)
    lengths = jnp.array([50, 128, 1])
    o, h = kda.kda_chunk_fwd_jnp(q, k, v, g, beta, lengths)
    for row, n in enumerate([50, 128, 1]):
        cut = tuple(a[row:row + 1, :n] for a in (q, k, v, g, beta))
        o_ref, h_ref = kda.kda_recurrence(*cut)
        np.testing.assert_allclose(o[row:row + 1, :n], o_ref, atol=2e-5)
        np.testing.assert_allclose(h[row:row + 1], h_ref, atol=2e-5)


@pytest.mark.parametrize("form", ["twin", "kernel"])
def test_a_channel_that_forgets_at_once_stays_finite_and_equal(form):
    """alpha 0.05 in one channel for 128 steps: the cumulative log decay
    reaches -383 there, and exp(+192) inside a chunk would be infinite.
    Every exponent the tile takes is a difference <= 0."""
    q, k, v, g, beta = _qkvgb(1, 128, 2, 8, 16, seed=11, low=3)
    assert float(jnp.cumsum(g, axis=1).min()) < -380
    o_ref, h_ref = kda.kda_recurrence(q, k, v, g, beta)
    if form == "twin":
        o, h = kda.kda_chunk_fwd_jnp(q, k, v, g, beta)
    else:
        o, h = kda.kda_chunk_fwd(q, k, v, g, beta, interpret=True)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(h).all())
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    np.testing.assert_allclose(h, h_ref, atol=2e-5)


# --------------------------------------- the Pallas kernels, interpreted

@pytest.mark.parametrize("t,dk,dv", [(128, 8, 16), (100, 128, 128)])
def test_chunk_kernel_interpreted_equals_its_twin(t, dk, dv):
    q, k, v, g, beta = _qkvgb(2, t, 2, dk, dv, seed=7, low=0)
    lengths = jnp.array([t - 9, t])
    o_t, h_t = kda.kda_chunk_fwd_jnp(q, k, v, g, beta, lengths)
    o, h = kda.kda_chunk_fwd(q, k, v, g, beta, lengths, interpret=True)
    np.testing.assert_allclose(o, o_t, atol=1e-5)
    np.testing.assert_allclose(h, h_t, atol=1e-5)


def test_step_kernel_interpreted_equals_its_twin_and_touches_one_layer():
    layers, slots, nh, dk, dv = 3, 5, 4, 8, 16
    state = jax.random.normal(jax.random.PRNGKey(1),
                              (layers, slots, nh, dk, dv))
    q, k, v, g, beta = (a[:, 0] for a in _qkvgb(slots, 1, nh, dk, dv, 2))
    g = g.at[3].set(0.0)
    beta = beta.at[3].set(0.0)                 # an inactive slot
    s_t, o_t = kda.kda_recurrent_step_jnp(state, jnp.int32(1), q, k, v, g,
                                          beta)
    s, o = jax.jit(lambda *a: kda.kda_recurrent_step(*a, interpret=True))(
        state, jnp.int32(1), q, k, v, g, beta)
    np.testing.assert_allclose(o, o_t, atol=1e-6)
    np.testing.assert_allclose(s, s_t, atol=1e-6)
    np.testing.assert_array_equal(s[0], state[0])
    np.testing.assert_array_equal(s[2], state[2])
    np.testing.assert_array_equal(s[1, 3], state[1, 3])
    o_ref, h_ref = kda.kda_recurrence(q[:, None], k[:, None], v[:, None],
                                      g[:, None], beta[:, None], state[1])
    np.testing.assert_allclose(o, o_ref[:, 0], atol=1e-5)
    np.testing.assert_allclose(s[1], h_ref, atol=1e-5)


# ------------------------------------ (b) the engine's path == reference

def _through_the_cache(cfg, params, toks, n_prompt, slot=1):
    """Logits of a prefill of ``n_prompt`` tokens and a decode step for each
    of the rest, in float32, in slot ``slot`` of two."""
    cache = decode.init_kv_cache(cfg, 2, 128, jnp.float32,
                                 expert_choices=True)
    bucket = np.zeros((1, 64), np.int32)
    bucket[0, :n_prompt] = toks[:n_prompt]
    cache, lg = decode.prefill(params, cache, bucket,
                               np.array([n_prompt], np.int32),
                               np.array([slot], np.int32), cfg,
                               compute_dtype=jnp.float32)
    got = [np.asarray(lg)[0]]
    active = np.arange(2) == slot
    for token in toks[n_prompt:]:
        cache, lg = decode.decode_step(
            params, cache, np.where(active, token, 0).astype(np.int32),
            active, cfg, compute_dtype=jnp.float32)
        got.append(np.asarray(lg)[slot])
    return np.stack(got), cache


def test_prefill_then_decode_equals_the_reference(kind, tiny, tiny_doc):
    """A padded prefill (29 of a bucket of 64: no multiple of the chunk)
    then 11 decode steps through the cache, against the reference's one
    forward over the 40 tokens: the KDA state and the convolution tail a
    prefill leaves, the gated attention's K/V rows, the share's experts."""
    cfg, params = tiny
    toks = np.random.default_rng(0).integers(1, 256, size=40).astype(np.int32)
    got, cache = _through_the_cache(cfg, params, toks, 29)
    ref = kind.logits(params, toks, tiny_doc, jnp.arange(28, 40), follow=None)
    assert float(np.asarray(ref).std()) > 0.5
    np.testing.assert_allclose(got, ref, atol=2e-4)
    # the routing the cache recorded is the reference's own
    assert cache["expert_choices"].shape == (8, 2, 128, 4)
    assert int(cache["expert_choices"][:, 1, :39].min()) >= 0
    assert int(cache["expert_choices"][:, 0].max()) == -1
    assert int(cache["moe_counts"][0]) > 0


def test_the_reference_is_told_the_compared_runs_own_routing(kind, tiny,
                                                             tiny_doc):
    """``program_run`` (the program run a second time, inside the
    reference's jit) gives what the compared run gave, choices and logits,
    run as the harness's comparison runs it (a jitted prefill, a jitted step
    a token, in bf16); each of its two programs is fenced in, and its logits
    are read by ``logits``, so that nothing of it is pruned: on the chip the
    unfenced step rounded at other points than the compared run's, and the
    pruned one fell the other way at a near-tie about once a run (PERF.md,
    PR 44)."""
    cfg, params = tiny
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    toks = np.random.default_rng(5).integers(1, 256, size=40).astype(np.int32)
    cache = kind.init_cache(cfg, 1, 128, jnp.bfloat16)
    cache, lg = jax.jit(lambda p, c, t, ln, sl: kind.prefill(
        p, c, t, ln, sl, cfg))(params, cache, toks[None, :29],
                               np.array([29], np.int32),
                               np.array([0], np.int32))
    got = [np.asarray(lg)[0]]
    step = jax.jit(lambda p, c, t, a: kind.decode_step(p, c, t, a, cfg))
    for i in range(29, 40):
        cache, lg = step(params, cache, toks[i:i + 1], np.ones((1,), bool))
        got.append(np.asarray(lg)[0])
    told, ran = jax.jit(lambda p, t: kind.program_run(p, t, tiny_doc, 29))(
        params, toks)
    np.testing.assert_array_equal(told, cache["expert_choices"][:, 0, :40])
    np.testing.assert_array_equal(ran, np.stack(got))
    fences = str(jax.make_jaxpr(
        lambda p, t: kind.program_run(p, t, tiny_doc, 29))(
            params, toks)).count("optimization_barrier")
    assert fences == 4          # around the prefill and around the step
    # the reference reads the second run's logits: not finite, no number
    pos = jnp.arange(28, 40)
    assert np.isfinite(kind.logits(params, toks, tiny_doc, pos)).all()
    with mock.patch.object(kind, "decode_step", lambda *a: (
            lambda cache, lg: (cache, lg * jnp.nan))(
                *decode.decode_step(*a))):
        assert np.isnan(kind.logits(params, toks, tiny_doc, pos)).all()


def test_the_shares_add_up_to_the_whole_layer(kind, tiny_doc):
    """16 experts in 4 shares of 4: the four shares' routed parts plus the
    shared expert counted once equal the uncut reference's layer, in the
    reference and in the program (``decode._experts``) alike."""
    whole = copy.deepcopy(tiny_doc)
    whole["n_routed_experts"] = 16
    del whole["reduced"], whole["share"]
    cfg = kind.program_config(whole)
    params = kind.init_params(jax.random.PRNGKey(5), cfg, jnp.float32)
    lp = jax.tree.map(lambda a: a[0, 1], params["blocks"]["linear"]["moe"])
    stacks = params["blocks"]["experts"]
    x = jax.random.normal(jax.random.PRNGKey(6), (24, 64))
    with jax.default_matmul_precision("highest"):
        want, _ = kind.expert_layer(x, lp, stacks, 2, whole)
        routed, _ = kind.expert_layer(x, lp, stacks, 2, whole, shared=False)
        shared = want - routed
        parts, program = [], []
        for share in range(4):
            doc = copy.deepcopy(tiny_doc)
            doc["share"]["expert_start"] = 4 * share
            held = jax.tree.map(lambda a: a[:, 4 * share:4 * share + 4],
                                stacks)
            parts.append(kind.expert_layer(x, lp, held, 2, doc,
                                           shared=False)[0])
            out, (counts, chosen) = decode._experts(
                x[None], {"moe": lp}, kind.program_config(doc), None,
                jnp.float32, 2, held)
            program.append(out[0] - shared)
            assert chosen.shape == (1, 24, 4)
    assert float(jnp.abs(want).mean()) > 0.1
    np.testing.assert_allclose(sum(parts) + shared, want, atol=1e-5)
    np.testing.assert_allclose(sum(program) + shared, want, atol=1e-4)
    # a share is a part, not the whole
    assert float(jnp.abs(parts[0] + shared - want).max()) > 0.05


def test_engine_generates_the_references_greedy_tokens(kind, tiny, tiny_doc):
    from ray_tpu.serve.llm import LLMEngine
    cfg, params = tiny
    eng = LLMEngine(cfg, params=params, num_slots=3, max_len=64,
                    buckets=(16, 32), compute_dtype=jnp.float32,
                    steps_per_dispatch=2)
    prompt = [int(t) for t in np.random.default_rng(1).integers(1, 256, 11)]
    try:
        out = eng.generate(prompt, max_tokens=6)
        stats = {**eng.counters(), **eng.breakdown()}
    finally:
        eng.shutdown()
    toks = list(prompt)
    for _ in range(6):
        lg = kind.logits(params, jnp.asarray(toks, jnp.int32), tiny_doc,
                         follow=None)
        toks.append(int(jnp.argmax(lg[-1])))
    assert list(out) == toks[len(prompt):]
    state = 6 * 4 * (4 * 16 * 16 * 4 + 3 * 3 * 4 * 16 * 4)
    assert {k: stats[k] for k in (
        "experts_held", "expert_layers", "linear_layers", "full_layers",
        "cache_state_bytes", "cache_kv_bytes", "cache_latent_bytes")} == {
        "experts_held": 4, "expert_layers": 8, "linear_layers": 6,
        "full_layers": 2, "cache_state_bytes": state,
        "cache_kv_bytes": 2 * 2 * 4 * 64 * 2 * 32 * 4,
        "cache_latent_bytes": 0}
    assert stats["moe_assignments"] > 0 and stats["moe_experts_touched"] > 0
    # an admit's assignments: the held quarter of 11 tokens x 4 x 8 layers
    assert stats["moe_assignments_prefill"] == 11 * 4 * 8 // 4


# ------------------------ one walk, with or without experts or a pattern

def _scans(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


WALKS = {
    "pattern-with-experts": dict(
        layer_pattern=("full", "linear"), num_layers=6, moe_dropless=True,
        num_experts=8, experts_per_token=2, expert_mlp_size=16,
        shared_experts=1, linear_decay_per_channel=True, linear_gate_rank=4,
        attn_output_gate=True, attn_head_dim=16),
    "pattern-without": dict(
        layer_pattern=("linear", "full"), num_layers=6, norm_on_output=True,
        qk_norm=True),
    "experts-without-a-pattern": dict(
        num_layers=3, moe_dropless=True, num_experts=8, experts_per_token=2,
        expert_mlp_size=16, shared_experts=1, use_rope=True),
}


@pytest.mark.parametrize("name", list(WALKS))
def test_every_tree_walks_the_same_layer_stack(name):
    """A pattern with experts, a pattern without and dropless experts
    without a pattern each trace to one scan over their periods (three
    here), whose body holds no scan over layers, and the experts' counts
    and choices come back a layer."""
    from ray_tpu.models import transformer
    kw = dict(vocab_size=64, hidden_size=32, num_heads=2, num_kv_heads=1,
              mlp_size=48, max_seq_len=64, use_rope=False, no_positions=True,
              linear_num_heads=2, linear_key_dim=8, linear_value_dim=8)
    cfg = TransformerConfig(**{**kw, **WALKS[name]})
    params = transformer.init_params(jax.random.PRNGKey(0), cfg,
                                     dtype=jnp.float32)
    cache = decode.init_kv_cache(cfg, 2, 32, jnp.float32,
                                 expert_choices=cfg.moe_dropless)
    step = lambda p, c: decode.decode_step(  # noqa: E731
        p, c, jnp.ones((2,), jnp.int32), jnp.ones((2,), bool), cfg,
        jnp.float32)
    over_layers = [e for e in _scans(jax.make_jaxpr(step)(params, cache).jaxpr)
                   if e.params["length"] == 3]
    assert len(over_layers) == 1
    new, logits = jax.jit(step)(params, cache)
    assert bool(jnp.isfinite(logits).all())
    if cfg.moe_dropless:
        layers = cfg.expert_layers
        assert new["expert_choices"].shape[0] == layers
        # every layer's router chose for both slots' tokens
        assert int((new["expert_choices"][:, :, 0] >= 0).all())
        assert int(new["moe_counts"][0]) == 2 * 2 * layers


# ------------------------------------------------------------- refusals

BASE = dict(vocab_size=8, hidden_size=8, num_heads=1, num_kv_heads=1,
            mlp_size=8, max_seq_len=8, num_layers=4, linear_num_heads=1,
            linear_key_dim=4, linear_value_dim=4,
            layer_pattern=("linear", "full"))
EXPERTS = dict(moe_dropless=True, num_experts=4, experts_per_token=2,
               expert_mlp_size=8)


@pytest.mark.parametrize("kw,match", [
    (dict(kv_lora_rank=4, qk_nope_head_dim=4, qk_rope_head_dim=2,
          v_head_dim=4), "latent"),
    (dict(hc_mult=2), "residual stream"),
    (dict(**EXPERTS, dense_prefix_layers=1), "same MLP"),
    (dict(linear_decay_per_channel=True), "linear_gate_rank"),
    (dict(linear_gate_rank=4), "go together"),
    (dict(layer_pattern=(), attn_output_gate=True), "layer_pattern only"),
    (dict(layer_pattern=(), linear_gate_rank=4), "layer_pattern only"),
], ids=["latent-attention", "residual-streams", "a-dense-prefix",
        "a-decay-a-channel-without-its-rank",
        "a-rank-without-a-decay-a-channel", "a-gate-without-a-pattern",
        "a-rank-without-a-pattern"])
def test_config_refuses_what_a_pattern_cannot_carry(kw, match):
    TransformerConfig(**BASE)
    TransformerConfig(**BASE, **EXPERTS)         # experts, pre-norm wiring
    with pytest.raises(ValueError, match=match):
        TransformerConfig(**{**BASE, **kw})


@pytest.mark.parametrize("kw,match", [
    (dict(paged=True), "page arena"),
    (dict(spec_decode_enabled=True), "rolled out"),
    (dict(tp=2), "sharding rule"),
], ids=["paged", "speculative", "tp"])
def test_the_engine_refuses_what_a_pattern_with_experts_cannot_do(tiny, kw,
                                                                  match):
    from ray_tpu.serve.llm import LLMEngine
    cfg, params = tiny
    with pytest.raises(ValueError, match=match):
        LLMEngine(cfg, params=params, num_slots=2, max_len=32, **kw)


def test_a_share_by_position_is_not_served_under_a_pattern_either(tiny):
    import dataclasses
    from ray_tpu.serve.llm import LLMEngine
    cfg, params = tiny
    by_position = dataclasses.replace(cfg, share_by_position=True)
    with pytest.raises(ValueError, match="share_by_position"):
        LLMEngine(by_position, params=params, num_slots=2, max_len=32)


@pytest.mark.parametrize("change,match", [
    (dict(use_rope=True), "use_rope"),
    (dict(kda_use_full_proj=True), "kda_use_full_proj"),
    (dict(first_k_dense_replace=1), "first_k_dense_replace"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(n_group=2), "n_group"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(gqa_layers=[0, 9]), "of 8 layers"),
    (dict(share=dict(expert_start=14)), "past the router"),
], ids=["rotary", "full-projections", "a-dense-layer", "unnormalised-gates",
        "router-groups", "tied-head", "a-layer-past-the-depth", "a-share-past-the-end"])
def test_the_kind_refuses_what_the_block_cannot_express(kind, tiny_doc,
                                                        change, match):
    kind.program_config(tiny_doc)
    with pytest.raises(ValueError, match=match):
        kind.program_config({**tiny_doc, **change})


# ------------------------------------------ the kind's counts (l4-e40 file)

def test_counts_of_the_l4_e40_configuration(kind):
    """``num_params`` is the program's tree to the parameter (3.31B held);
    the decode step's four byte terms: weights outside the experts once,
    the held experts the live tokens reach, the state read and written per
    active slot per KDA layer at 4 bytes, K/V per live token for the one GQA
    layer."""
    with open(L4) as f:
        doc = json.load(f)
    cfg = kind.program_config(doc)
    assert cfg.layer_pattern == ("full", "linear", "linear", "linear")
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_start) == (320, 40,
                                                                     0)
    tree = jax.eval_shape(lambda k: kind.init_params(k, cfg, jnp.bfloat16),
                          jax.random.PRNGKey(0))
    leaves = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert kind.num_params(doc) == leaves == 3_308_353_344
    assert cfg.num_params() == 3_307_995_136      # the matrices alone
    per = kind.layer_matrix_params(doc)
    assert per == {"kda": 137_625_600, "gqa": 109_051_904,
                   "expert": 15_728_640, "shared": 15_728_640,
                   "router": 1_310_720}
    # ISSUE 44's check of the reading: 154.8M and 126.1M beside the experts
    assert round((per["kda"] + per["shared"] + per["router"]) / 1e6, 1) \
        == 154.7
    assert round((per["gqa"] + per["shared"] + per["router"]) / 1e6, 1) \
        == 126.1
    outside = 3 * per["kda"] + per["gqa"] + 4 * (per["shared"]
                                                 + per["router"])
    weights = (outside + 24576 * 4096) * 2
    assert kind.decode_step_bytes(doc, 0, 0) == weights
    assert kind.state_bytes_per_slot(doc) == 3 * 64 * 128 * 128 * 4
    assert kind.kv_bytes_per_token(doc) == 4096
    touched = 40 * (1 - (1 - 8 / 320) ** 64)
    assert 32 < touched < 32.2 == pytest.approx(
        kind.experts_touched(doc, 64), abs=0.2)
    assert kind.decode_step_bytes(doc, 64, 1000) == pytest.approx(
        weights + 4 * touched * per["expert"] * 2
        + 2 * 64 * 3 * 64 * 128 * 128 * 4 + 1000 * 4096)
    assert kind.kda_recurrent_step_bytes(doc, 65) == 65 * (
        2 * 3 * 64 * 128 * 128 * 4 + 3 * 64 * (4 * 128 * 2 + 4 * 128 + 4))
    assert kind.kda_recurrent_step_flops(doc, 1) == 3 * 64 * 7 * 128 * 128
    assert kind.kda_chunk_fwd_bytes(doc, 1000) == 3 * 64 * 1540 * 1000
    assert kind.CHUNK == kda.CHUNK == 64     # the counts' chunk is the kernel's
    assert kind.kda_chunk_fwd_flops(doc, 1) == pytest.approx(
        3 * 64 * (10 * 64 * 128 + 6 * 128 * 128 + 2 * 64 * 64 / 3))
    assert kind.moe_gmm_flops(doc, 10) == 2 * 15_728_640 * 10
    assert kind.moe_gmm_bytes(doc, 10, 3) == (
        3 * 15_728_640 + 10 * 2 * (4096 + 1280)) * 2
    assert kind.decode_attn_bytes(doc, 7) == 7 * 4096
    # the cache the engine would hold for this file: 64 + 1 rows
    cache = jax.eval_shape(lambda: decode.init_kv_cache(cfg, 65, 4096,
                                                        jnp.bfloat16))
    assert decode.cache_gauges(cfg, cache) == {
        "cache_kv_bytes": 65 * 4096 * 4096,
        "cache_state_bytes": 65 * (kind.state_bytes_per_slot(doc)
                                   + 3 * 3 * 24576 * 2),
        "linear_layers": 3, "full_layers": 1, "cache_latent_bytes": 0,
        "expert_layers": 4, "experts_held": 40}
