"""The state-space dual (ops/ssd.py), the "ssm" kind of layer and layers
that are one sublayer alone (models/hybrid.py, models/decode.py
``layer_stack``), experts of two matrices (ops/moe.py) and a served share of
them: a tiny model of Nemotron-H's first nine layers (``MEMEM*EME``), hidden
64, 16 routed experts of width 24 of which a share of 8 is held, seeded
random weights, on the CPU.  The independent side of every comparison is
the block kind's plain float32 reference (benchmark/models/nemotron_h.py: the
recurrence one token at a time, no chunks, no cache, nothing imported from
ray_tpu.models or ray_tpu.ops), ``ssd.ssd_recurrence`` or
``jax.lax.ragged_dot``.  Numbers here are about results, never speed."""

import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decode, transformer
from ray_tpu.models.config import TransformerConfig
from ray_tpu.ops import moe, ssd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KIND = os.path.join(REPO, "benchmark", "models", "nemotron_h.py")
L9 = os.path.join(REPO, "benchmark", "configs",
                  "nemotron-3-nano-30b-a3b-serve-l9-e64.json")
TINY = os.path.join(REPO, "benchmark", "tests", "tiny", "configs",
                    "tiny-nemotron.json")
NINE = ("ssm", "mlp", "ssm", "mlp", "ssm", "full", "mlp", "ssm", "mlp")


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


def _inputs(b, t, nh, p, g, n, seed, decay=None):
    """x, dt, a_log, b, c, d; ``decay``: head 0's decay a step, throughout."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, nh)) - 2.0)
    a_log = jnp.log(jax.random.uniform(ks[2], (nh,), minval=1.0, maxval=16.0))
    if decay is not None:        # exp(-exp(A_log) dt) = decay at dt = 1
        dt = dt.at[:, :, 0].set(1.0)
        a_log = a_log.at[0].set(jnp.log(-jnp.log(decay)))
    return (jax.random.normal(ks[0], (b, t, nh, p)), dt, a_log,
            0.3 * jax.random.normal(ks[3], (b, t, g, n)),
            0.3 * jax.random.normal(ks[4], (b, t, g, n)),
            jax.random.normal(ks[5], (nh,)))


FORMS = {"twin": dict(use_kernel=False), "kernel": dict(interpret=True)}


# ------------------------------------------- the kernels and the recurrence

@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("t", [128, 300], ids=["a-chunk", "ragged-300"])
def test_chunked_form_equals_the_recurrence(t, form):
    """Whole rows and rows that stop early, a length that is no multiple of
    the chunk of 128: outputs up to each row's length and the state as of
    it."""
    x, dt, a_log, b, c, d = _inputs(2, t, 4, 16, 2, 32, seed=t)
    lengths = jnp.array([t, t * 4 // 7])
    live = (jnp.arange(t)[None] < lengths[:, None])[..., None]
    want, state = ssd.ssd_recurrence(x, jnp.where(live, dt, 0.0), a_log, b,
                                     c, d)
    got, s = ssd.ssd_chunk_fwd(x, dt, a_log, b, c, d, lengths, **FORMS[form])
    assert got.shape == x.shape and s.shape == (2, 4, 16, 32)
    np.testing.assert_allclose(jnp.where(live[..., None], got, 0.0),
                               jnp.where(live[..., None], want, 0.0),
                               atol=2e-4)
    np.testing.assert_allclose(s, state, atol=2e-5)
    # a row's state is untouched by what lies past its length
    alone, s1 = ssd.ssd_chunk_fwd(x[1:, :int(lengths[1])],
                                  dt[1:, :int(lengths[1])], a_log,
                                  b[1:, :int(lengths[1])],
                                  c[1:, :int(lengths[1])], d, **FORMS[form])
    np.testing.assert_allclose(s1[0], s[1], atol=2e-5)


@pytest.mark.parametrize("form", list(FORMS))
def test_a_head_that_forgets_at_once_stays_finite_and_equal(form):
    """A decay of 0.05 a step throughout: ``exp(G_i - G_j)`` of non-positive
    numbers only, where ``exp(G_i) exp(-G_j)`` reaches e^380 in a chunk."""
    x, dt, a_log, b, c, d = _inputs(1, 256, 4, 16, 2, 32, seed=7, decay=0.05)
    want, state = ssd.ssd_recurrence(x, dt, a_log, b, c, d)
    got, s = ssd.ssd_chunk_fwd(x, dt, a_log, b, c, d, **FORMS[form])
    assert bool(jnp.isfinite(got).all() and jnp.isfinite(s).all())
    np.testing.assert_allclose(got, want, atol=2e-4)
    np.testing.assert_allclose(s, state, atol=2e-5)


@pytest.mark.parametrize("form", list(FORMS))
def test_recurrent_step_equals_one_step_and_touches_one_layer(form):
    """One step of the recurrence on layer 1 of a stack of 3; an idle slot
    (dt 0) keeps its state to the bit, and so do the other layers."""
    layers, slots, nh, p, g, n = 3, 5, 4, 16, 2, 32
    x, dt, a_log, b, c, d = _inputs(slots, 1, nh, p, g, n, seed=11)
    x, dt, b, c = x[:, 0], dt[:, 0].at[2].set(0.0), b[:, 0], c[:, 0]
    state = jax.random.normal(jax.random.PRNGKey(12),
                              (layers, slots, nh, p, n))
    want, after = ssd.ssd_recurrence(x[:, None], dt[:, None], a_log,
                                     b[:, None], c[:, None], d, state[1])
    new, got = ssd.ssd_recurrent_step(state, jnp.int32(1), x, dt, a_log, b,
                                      c, d, **FORMS[form])
    np.testing.assert_allclose(got, want[:, 0], atol=1e-5)
    np.testing.assert_allclose(new[1], after, atol=1e-5)
    assert bool((new[0] == state[0]).all() and (new[2] == state[2]).all())
    assert bool((new[1, 2] == state[1, 2]).all())


# ----------------------------------------------- experts of two matrices

@pytest.mark.parametrize("form", list(FORMS))
def test_squared_relu_grouped_matmul_equals_ragged_dot(form):
    """The up projection stored [experts, M, H] with the squared ReLU in the
    epilogue, at a width that is no multiple of anything (24): against
    ``ragged_dot`` over the same groups in float32."""
    layers, experts, h, m, tile = 2, 4, 32, 24, 8
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    w_up = jax.random.normal(ks[0], (layers, experts, m, h)) * h ** -0.5
    idx = jax.random.randint(ks[1], (20, 2), 0, experts)
    x = jax.random.normal(ks[2], (20, h))
    dest, source, tile_expert, tiles, sizes = moe.sort_by_expert(
        idx, jnp.ones_like(idx, bool), experts, tile)
    xs = jnp.take(x, source, axis=0, mode="fill", fill_value=0)
    got = moe.moe_gmm(xs, (w_up,), 1, tile_expert, tiles, tile,
                      activation="relu2", transposed=True, **FORMS[form])
    groups = jnp.zeros((experts,), jnp.int32).at[tile_expert].add(tile)
    want = jnp.square(jnp.maximum(jax.lax.ragged_dot(
        xs, w_up[1].swapaxes(1, 2), groups,
        precision=jax.lax.Precision.HIGHEST), 0.0))
    rows = int(tiles) * tile
    assert rows >= 40 and float(jnp.abs(want[:rows]).mean()) > 0.1
    np.testing.assert_allclose(got[:rows], want[:rows], atol=1e-5)
    with pytest.raises(ValueError, match="one-matrix form"):
        moe.moe_gmm(xs, (w_up, w_up), 1, tile_expert, tiles, tile,
                    activation="relu2")


def test_the_kernel_refuses_to_differentiate_an_epilogue():
    w = jnp.ones((1, 2, 8, 16))
    plan = moe.sort_by_expert(jnp.zeros((8, 1), jnp.int32),
                              jnp.ones((8, 1), bool), 2, 8)

    def f(x):
        return moe.moe_gmm(x, (w,), 0, plan[2], plan[3], 8, interpret=True,
                           activation="relu2", transposed=True).sum()

    with pytest.raises(NotImplementedError, match="no backward"):
        jax.grad(f)(jnp.ones((16, 16)))


# --------------------------------------- the model against its reference

def test_prefill_then_decode_equals_the_reference(kind, tiny, tiny_doc):
    """Two rows of other lengths into slots 2 and 0, then decode steps with
    an idle slot between: logits against the reference's full forward."""
    cfg, params = tiny
    toks = np.random.default_rng(1).integers(1, 256, (2, 40)).astype(np.int32)
    lens = np.array([29, 18], np.int32)
    cache = decode.init_kv_cache(cfg, 3, 64, jnp.float32)
    cache, lg = decode.prefill(params, cache, np.pad(toks[:, :32], (
        (0, 0), (0, 0))), lens, np.array([2, 0], np.int32), cfg, jnp.float32)
    got = [[lg[0]], [lg[1]]]
    for i in range(8):
        cache, lg = decode.decode_step(
            params, cache, np.array([toks[1, 18 + i], 0, toks[0, 29 + i]],
                                    np.int32),
            np.array([True, False, True]), cfg, jnp.float32)
        got[0].append(lg[2])
        got[1].append(lg[0])
    for row, n in enumerate(lens):
        want = kind.logits(params, jnp.asarray(toks[row, :n + 8]), tiny_doc,
                           jnp.arange(n - 1, n + 8))
        assert float(want.std()) > 0.5
        np.testing.assert_allclose(jnp.stack(got[row]), want, atol=2e-4)
    # the idle slot kept its (zero) state and tail
    assert not bool(cache["state"][:, 1].any() or cache["conv"][:, 1].any())
    assert cache["length"].tolist() == [26, 0, 37]


def test_the_shares_add_up_to_the_whole_layer(kind, tiny_doc):
    """16 experts in 2 shares of 8: the two shares' routed parts plus the
    shared expert counted once equal the uncut reference's layer, in the
    reference and in the program (``decode._experts``) alike."""
    whole = copy.deepcopy(tiny_doc)
    whole["n_routed_experts"] = 16
    del whole["reduced"], whole["share"]
    cfg = kind.program_config(whole)
    params = kind.init_params(jax.random.PRNGKey(5), cfg, jnp.float32)
    lp = jax.tree.map(lambda a: a[0, 2], params["blocks"]["mlp"]["moe"])
    stacks = params["blocks"]["experts"]
    assert sorted(stacks) == ["w_out", "w_up"] and "shared_gate" not in lp
    x = jax.random.normal(jax.random.PRNGKey(6), (24, 64))
    with jax.default_matmul_precision("highest"):
        want, _ = kind.expert_layer(x, lp, stacks, 2, whole)
        routed, _ = kind.expert_layer(x, lp, stacks, 2, whole, shared=False)
        shared = want - routed
        parts, program = [], []
        for share in range(2):
            doc = copy.deepcopy(tiny_doc)
            doc["share"]["expert_start"] = 8 * share
            held = jax.tree.map(lambda a: a[:, 8 * share:8 * share + 8],
                                stacks)
            parts.append(kind.expert_layer(x, lp, held, 2, doc,
                                           shared=False)[0])
            out, (counts, chosen) = decode._experts(
                x[None], {"moe": lp}, kind.program_config(doc), None,
                jnp.float32, 2, held)
            program.append(out[0] - shared)
            assert chosen.shape == (1, 24, 3)
    assert float(jnp.abs(want).mean()) > 0.1
    np.testing.assert_allclose(sum(parts) + shared, want, atol=1e-5)
    np.testing.assert_allclose(sum(program) + shared, want, atol=1e-4)
    # a share is a part, not the whole
    assert float(jnp.abs(parts[0] + shared - want).max()) > 0.05


@pytest.mark.parametrize("case", ["tie-break", "another-set", "nothing"])
def test_the_reference_takes_a_recorded_choice_only_as_a_tie_break(
        kind, tiny_doc, case):
    """``route(follow=)`` takes data, not a program: a recorded choice that
    swaps the reference's own k-th expert for its next one is taken where
    the two score within ``FOLLOW_MARGIN``; one that swaps in the lowest
    scorer is not; a token with nothing recorded (-1) keeps the reference's
    own set.  The gates are the reference's own scores either way."""
    k = tiny_doc["num_experts_per_tok"]
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((16, 16)) * 0.2, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(16) * 0.01, jnp.float32)
    own, gates, short = kind.route(x, router, bias, tiny_doc)
    assert not np.asarray(short).any()
    biased = jax.nn.sigmoid(x @ router) + bias
    order = jnp.argsort(-biased, axis=-1)
    swap = {"tie-break": order[:, k], "another-set": order[:, -1],
            "nothing": jnp.full((64,), -1)}[case]
    told = own.at[:, -1].set(swap)
    idx, gates_told, short = kind.route(x, router, bias, tiny_doc, follow=told)
    gap = np.asarray(jnp.take_along_axis(biased, order[:, k - 1:k + 1], -1))
    near = gap[:, 0] - gap[:, 1] <= kind.FOLLOW_MARGIN
    if case == "tie-break":
        assert near.any() and not near.all()
        np.testing.assert_array_equal(idx[near], told[near])
        np.testing.assert_array_equal(idx[~near], own[~near])
        np.testing.assert_allclose(np.asarray(short), gap[:, 0] - gap[:, 1],
                                   atol=1e-6)
    else:
        np.testing.assert_array_equal(idx, own)
        np.testing.assert_allclose(gates_told, gates)
        assert (np.asarray(short) > kind.FOLLOW_MARGIN).all()
    scores = jax.nn.sigmoid(x @ router)
    picked = jnp.take_along_axis(scores, idx, -1)
    np.testing.assert_allclose(
        gates_told, picked / picked.sum(-1, keepdims=True)
        * tiny_doc["routed_scaling_factor"], rtol=1e-6)


def test_engine_generates_the_references_greedy_tokens(kind, tiny, tiny_doc):
    """Through ``LLMEngine``'s three calls, with its gauges."""
    from ray_tpu.serve.llm import LLMEngine
    cfg, params = tiny
    eng = LLMEngine(cfg, params=params, num_slots=4, max_len=64,
                    buckets=(32, 64), compute_dtype=jnp.float32,
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
    state = 4 * 5 * (4 * 16 * 32 * 4 + 3 * (4 * 16 + 2 * 2 * 32) * 4)
    assert {k: stats[k] for k in (
        "experts_held", "expert_layers", "linear_layers", "ssm_layers",
        "full_layers", "cache_state_bytes", "cache_kv_bytes",
        "cache_latent_bytes")} == {
        "experts_held": 8, "expert_layers": 4, "linear_layers": 0,
        "ssm_layers": 4, "full_layers": 1, "cache_state_bytes": state,
        "cache_kv_bytes": 2 * 1 * 5 * 64 * 2 * 32 * 4,
        "cache_latent_bytes": 0}
    assert stats["moe_assignments"] > 0 and stats["moe_experts_touched"] > 0
    # an admit's assignments: 11 tokens x 3 x 4 expert layers (of which the
    # held half is computed)
    assert stats["moe_assignments_prefill"] == 11 * 3 * 4 // 2


# ------------------------ one walk, whichever sublayers a kind has

def _scans(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


WALKS = {
    "sublayers-alone-with-experts": dict(
        layer_pattern=("ssm", "mlp", "full", "mlp"), num_layers=12,
        ssm_groups=1, mlp_act="relu2",
        moe_dropless=True, num_experts=8, experts_per_token=2,
        expert_mlp_size=24, shared_experts=2),
    "sublayers-alone-dense": dict(
        layer_pattern=("ssm", "mlp", "full"), num_layers=9,
        ssm_groups=2, mlp_act="relu2"),
    "an-mlp-under-every-mixer": dict(
        layer_pattern=("ssm", "full"), num_layers=6, ssm_groups=2),
    "without-a-pattern": dict(num_layers=3, use_rope=True),
}


@pytest.mark.parametrize("name", list(WALKS))
def test_every_tree_walks_the_same_layer_stack(name):
    """A pattern of sublayers alone (with experts under its "mlp" layers,
    or a dense MLP), a pattern with an MLP under every mixer and a model
    without a pattern each trace to one scan over their periods (three
    here) whose body holds no scan over layers; an expert layer's index is
    its rank among the expert layers."""
    kw = dict(vocab_size=64, hidden_size=32, num_heads=2, num_kv_heads=1,
              mlp_size=48, max_seq_len=64, use_rope=False, no_positions=True,
              linear_num_heads=2, linear_key_dim=8, linear_value_dim=8)
    if name == "without-a-pattern":
        kw = {k: v for k, v in kw.items() if not k.startswith("linear_")}
        kw.update(no_positions=False)
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
    if cfg.ssm_layers:
        assert new["state"].shape[0] == cfg.ssm_layers == 3
        assert bool((jnp.abs(new["state"]).sum((1, 2, 3, 4)) > 0).all())
    if cfg.moe_dropless:
        assert (cfg.expert_layers, cfg.mlp_layers, cfg.full_layers) == (6, 6,
                                                                        3)
        assert params["blocks"]["experts"]["w_up"].shape[:2] == (6, 8)
        assert new["expert_choices"].shape[0] == 6
        # every expert layer's router chose for both slots' tokens
        assert int((new["expert_choices"][:, :, 0] >= 0).all())
        assert int(new["moe_counts"][0]) == 2 * 2 * 6


# ------------------------------------------------------------- refusals

BASE = dict(vocab_size=8, hidden_size=8, num_heads=1, num_kv_heads=1,
            mlp_size=8, max_seq_len=8, num_layers=4, linear_num_heads=2,
            linear_key_dim=4, linear_value_dim=4, ssm_groups=1,
            layer_pattern=("ssm", "full"))


@pytest.mark.parametrize("kw,match", [
    (dict(layer_pattern=("ssm", "mlp", "full")), "whole periods"),
    (dict(layer_pattern=("ssm", "moe")), "kinds are"),
    (dict(layer_pattern=("ssm", "linear")), "one recurrent kind"),
    (dict(ssm_groups=0), "ssm_groups"),
    (dict(linear_num_heads=3, ssm_groups=2), "ssm_groups"),
    (dict(layer_pattern=("linear", "full")), "ssm_groups"),
    (dict(mlp_act="gelu"), "mlp_act"),
    (dict(layer_pattern=(), ssm_groups=0, mlp_act="relu2"),
     "layer_pattern only"),
    (dict(moe_dropless=True, num_experts=4, experts_per_token=2,
          expert_mlp_size=8, use_swiglu=False), "SwiGLU"),
    (dict(dense_prefix_layers=1, moe_dropless=True, num_experts=4,
          experts_per_token=2, expert_mlp_size=8), "same MLP"),
], ids=["layers-not-whole-periods", "a-kind-it-does-not-know",
        "two-recurrent-kinds", "no-groups", "heads-not-whole-groups",
        "groups-without-ssm", "another-activation", "relu2-without-a-pattern",
        "ungated-experts-of-no-activation", "a-dense-prefix"])
def test_config_refuses_what_it_cannot_wire(kw, match):
    TransformerConfig(**BASE)
    with pytest.raises(ValueError, match=match):
        TransformerConfig(**{**BASE, **kw})


@pytest.mark.parametrize("kw,match", [
    (dict(paged=True), "page arena"),
    (dict(spec_decode_enabled=True), "rolled out"),
    (dict(tp=2), "sharding rule"),
], ids=["paged", "speculative", "tp"])
def test_the_engine_refuses_what_a_recurrent_state_cannot_do(tiny, kw, match):
    from ray_tpu.serve.llm import LLMEngine
    cfg, params = tiny
    with pytest.raises(ValueError, match=match):
        LLMEngine(cfg, params=params, num_slots=2, max_len=32, **kw)


@pytest.mark.parametrize("what", ["apply_trunk", "make_train_step"])
def test_training_refuses_the_pattern(tiny, what):
    cfg, params = tiny
    with pytest.raises(NotImplementedError, match="layer_pattern"):
        if what == "apply_trunk":
            transformer.apply_trunk(params, jnp.ones((1, 8), jnp.int32), cfg)
        else:
            from ray_tpu.parallel import MeshSpec, make_optimizer, \
                make_train_step
            mesh = MeshSpec(fsdp=2).build(jax.devices()[:2])
            make_train_step(cfg, mesh, make_optimizer(), None)


@pytest.mark.parametrize("change,match", [
    (dict(hybrid_override_pattern="MEMEM-EME"), "fifth kind"),
    (dict(hybrid_override_pattern="MEMEM*EM"), "num_hidden_layers"),
    (dict(mlp_hidden_act="silu"), "mlp_hidden_act"),
    (dict(mamba_hidden_act="gelu"), "mamba_hidden_act"),
    (dict(use_conv_bias=False), "use_conv_bias"),
    (dict(mlp_bias=True), "mlp_bias"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(n_group=2), "n_group"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(sliding_window=128), "sliding_window"),
    (dict(moe_shared_expert_intermediate_size=40), "whole multiple"),
    (dict(share=dict(expert_start=14)), "past the router"),
], ids=["a-dense-mlp-layer", "a-pattern-of-another-depth", "gated-experts",
        "another-mixer-activation", "no-conv-bias", "biases",
        "unnormalised-gates", "router-groups", "tied-head", "a-window",
        "a-shared-width-between", "a-share-past-the-end"])
def test_the_kind_refuses_what_the_block_cannot_express(kind, tiny_doc,
                                                        change, match):
    kind.program_config(tiny_doc)
    with pytest.raises(ValueError, match=match):
        kind.program_config({**tiny_doc, **change})


# --------------------------------------- the kind's counts (l9-e64 file)

def test_counts_of_the_l9_e64_configuration(kind):
    """``num_params`` is the program's tree to the parameter (3.17B held);
    ISSUE 46's check of the reading against the published count; the decode
    step's four byte terms; the kernels' counts against their own loads and
    stores."""
    with open(L9) as f:
        doc = json.load(f)
    cfg = kind.program_config(doc)
    assert cfg.layer_pattern == NINE and cfg.sublayers_alone
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_start,
            cfg.shared_experts, cfg.mlp_act) == (128, 64, 0, 2, "relu2")
    assert (cfg.expert_layers, cfg.linear_layers, cfg.full_layers,
            cfg.ssm_layers) == (4, 0, 1, 4)
    tree = jax.eval_shape(lambda k: kind.init_params(k, cfg, jnp.bfloat16),
                          jax.random.PRNGKey(0))
    leaves = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    per = kind.layer_matrix_params(doc)
    assert per == {"mamba": 38_707_200, "attention": 23_396_352,
                   "expert": 9_977_856, "shared": 19_955_712,
                   "router": 344_064}
    matrices = (4 * (per["mamba"] + 64 * per["expert"] + per["shared"]
                     + per["router"]) + per["attention"] + 2 * 65536 * 2688)
    assert cfg.num_params() == matrices == 3_166_076_928
    assert kind.num_params(doc) == leaves == matrices + 4 * (
        5 * 6144 + 3 * 64 + 4096) + 9 * 2688 + 4 * 128 + 2688
    # the published model by the same reading: 31.6B, 3.2B a token
    full = 23 * (per["mamba"] + 128 * per["expert"] + per["shared"]
                 + per["router"]) + 6 * per["attention"] + 2 * 131072 * 2688
    assert round(full / 1e9, 1) == 31.6
    active = full - 23 * 122 * per["expert"]
    assert round(active / 1e9, 1) == 3.6          # with both embeddings
    assert round((active - 131072 * 2688) / 1e9, 1) == 3.2
    # what a token meets here: 3 of its 6 experts, one embedding's worth
    met = matrices - 4 * 61 * per["expert"] - 65536 * 2688
    assert cfg.flops_per_token(1024) == (
        6 * met + 12 * 1024 * 4096 + 3 * 4 * 64 * 4 * 64 * 128)
    assert kind.train_flops_per_token(doc, 1024) == (
        6 * met + 6 * 1024 * 4096 + 3 * 4 * 64 * 5 * 64 * 128)
    outside = 4 * per["mamba"] + per["attention"] + 4 * (per["shared"]
                                                         + per["router"])
    weights = (outside + 65536 * 2688) * 2
    assert kind.decode_step_bytes(doc, 0, 0) == weights
    assert kind.state_bytes_per_slot(doc) == 4 * 64 * 64 * 128 * 4
    assert kind.kv_bytes_per_token(doc) == 1024
    touched = 64 * (1 - (1 - 6 / 128) ** 64)
    assert 60.9 < touched < 61.1 == pytest.approx(
        kind.experts_touched(doc, 64), abs=0.2)
    assert kind.decode_step_bytes(doc, 64, 1000) == pytest.approx(
        weights + 4 * touched * per["expert"] * 2
        + 2 * 64 * 4 * 64 * 64 * 128 * 4 + 1000 * 1024)
    assert kind.ssd_recurrent_step_bytes(doc, 65) == 65 * (
        2 * 4 * 64 * 64 * 128 * 4
        + 4 * ((2 * 4096 + 2 * 1024) * 2 + 2 * 64 * 4))
    assert kind.ssd_recurrent_step_flops(doc, 1) == 4 * 64 * 5 * 64 * 128
    assert kind.CHUNK == ssd.CHUNK == 128   # the counts' chunk is the kernel's
    assert kind.ssd_chunk_fwd_bytes(doc, 1000) == 4 * (
        (2 * 4096 + 2 * 1024) * 2 + 3 * 64 * 4) * 1000
    assert kind.ssd_chunk_fwd_flops(doc, 1) == 4 * (
        8 * 2 * 128 * 128 + 64 * (2 * 128 * 64 + 4 * 64 * 128))
    assert kind.moe_gmm_flops(doc, 10) == 2 * 9_977_856 * 10
    assert kind.moe_gmm_bytes(doc, 10, 3) == (
        3 * 9_977_856 + 10 * 2 * (2688 + 1856)) * 2
    assert kind.decode_attn_bytes(doc, 7) == 7 * 1024
    # the cache the engine would hold for this file: 64 + 1 rows
    cache = jax.eval_shape(lambda: decode.init_kv_cache(cfg, 65, 8192,
                                                        jnp.bfloat16))
    assert decode.cache_gauges(cfg, cache) == {
        "cache_kv_bytes": 65 * 8192 * 1024,
        "cache_state_bytes": 65 * (kind.state_bytes_per_slot(doc)
                                   + 4 * 3 * 6144 * 2),
        "linear_layers": 0, "ssm_layers": 4, "full_layers": 1,
        "cache_latent_bytes": 0, "expert_layers": 4, "experts_held": 64}
