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

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import contract
import kinds
from ray_tpu.models import decode
from ray_tpu.ops import moe, ssd

ROW = kinds.KINDS["nemotron_h"]
NINE = ("ssm", "mlp", "ssm", "mlp", "ssm", "full", "mlp", "ssm", "mlp")


class TestNemotronH(contract.OnlyServed, contract.Shares):
    row = ROW


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
#: (heads, groups, head width P, state width N): Nemotron's 8 groups of 8
#: (two groups a decode grid step, one a prefill step), and Granite's one
#: group wider than a grid step: 64 heads (four blocks of 16) and 40 (no
#: multiple of 16: four blocks of 10)
HEADS = {"two-groups": (4, 2, 16, 32), "eight-groups-of-8": (64, 8, 8, 16),
         "a-group-of-64": (64, 1, 8, 16), "a-group-of-40": (40, 1, 8, 16)}


# ------------------------------------------- the kernels and the recurrence

@pytest.mark.parametrize("slots,heads,per,p,n,block,unrolled,chunk", [
    # Nemotron's and Granite's cells: whole slots of 2 MiB, the two whose
    # blocks fit ``STEP_STATE_VMEM`` in and out and double-buffered
    pytest.param(65, 64, 8, 64, 128, (2, 64), 16, 8, id="nemotron"),
    pytest.param(65, 64, 64, 64, 128, (2, 64), 16, 16, id="granite"),
    # toy states: every slot there is in one block ([8, 16] float32 is one
    # (8, 128) tile in VMEM)
    pytest.param(5, 40, 40, 8, 16, (5, 40), 10, 10, id="40-heads"),
    pytest.param(5, 4, 2, 16, 32, (5, 4), 4, 2, id="4-heads"),
    pytest.param(5, 34, 17, 8, 16, (5, 34), 1, 1, id="34-heads"),
    # a state too large for a whole slot (512 KiB a head: eight heads fit):
    # of 8 groups of 8 one whole group, of 16 groups of 4 two, of one group
    # of 64 a block of 8 of its heads, of 2 groups of 24 a block of 8
    pytest.param(65, 64, 8, 256, 512, (1, 8), 8, 8, id="no-slot-fits-8x8"),
    pytest.param(65, 64, 4, 256, 512, (1, 8), 8, 4, id="no-slot-fits-16x4"),
    pytest.param(65, 64, 64, 256, 512, (1, 8), 8, 16,
                 id="no-slot-fits-1x64"),
    pytest.param(65, 48, 24, 256, 512, (1, 8), 8, 12,
                 id="no-slot-fits-2x24"),
    # fewer slots than would fit are one block; one slot of half the heads
    # and four of them a block
    pytest.param(1, 64, 64, 64, 128, (1, 64), 16, 16, id="one-slot"),
    pytest.param(65, 32, 8, 64, 128, (4, 32), 16, 8, id="32-heads"),
])
def test_a_grid_step_is_whole_slots_or_a_block_of_one_slots_heads(
        slots, heads, per, p, n, block, unrolled, chunk):
    """What a grid step of the decode kernel moves of the state, planned from
    its shape and ``STEP_STATE_VMEM`` alone (``step_block``): whole slots,
    or of one slot whole groups or a block of one group's heads; the heads
    the body unrolls (whole groups or a block of one group's, ``STEP_UNROLL``
    at most); and the prefill kernel's heads a step, as they were."""
    sb, hb = ssd.step_block(slots, heads, per, p, n)
    assert (sb, hb) == block and heads % hb == 0
    assert hb % per == 0 or per % hb == 0
    assert 4 * sb * hb * ssd._head_bytes(p, n) <= ssd.STEP_STATE_VMEM
    assert ssd.STEP_STATE_VMEM < ssd.STEP_VMEM_LIMIT
    hu = ssd._unrolled_heads(hb, per)
    assert hu == unrolled and hb % hu == 0
    assert ssd._head_block(per, ssd.CHUNK_HEADS_A_STEP) == chunk
    assert per % chunk == 0


def test_a_head_too_large_for_the_kernels_vmem_is_refused():
    with pytest.raises(ValueError, match="do not fit"):
        ssd.step_block(4, 8, 8, 2048, 2048)


@pytest.mark.parametrize("t,form,heads", [
    *((t, form, "two-groups") for form in FORMS for t in (128, 300)),
    *((300, form, heads) for form in FORMS for heads in list(HEADS)[1:])])
def test_chunked_form_equals_the_recurrence(t, form, heads):
    """Whole rows and rows that stop early, a length that is no multiple of
    the chunk of 128: outputs up to each row's length and the state as of
    it; groups of heads that are a grid step and a group that is several."""
    nh, g, p, n = HEADS[heads]
    x, dt, a_log, b, c, d = _inputs(2, t, nh, p, g, n, seed=t)
    lengths = jnp.array([t, t * 4 // 7])
    live = (jnp.arange(t)[None] < lengths[:, None])[..., None]
    want, state = ssd.ssd_recurrence(x, jnp.where(live, dt, 0.0), a_log, b,
                                     c, d)
    got, s = ssd.ssd_chunk_fwd(x, dt, a_log, b, c, d, lengths, **FORMS[form])
    assert got.shape == x.shape and s.shape == (2, nh, p, n)
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


@pytest.mark.parametrize("heads", list(HEADS))
@pytest.mark.parametrize("form", list(FORMS))
def test_recurrent_step_equals_one_step_and_touches_one_layer(form, heads):
    """One step of the recurrence on layer 1 of a stack of 3; an idle slot
    (dt 0) keeps its state to the bit, and so do the other layers."""
    layers, slots, (nh, g, p, n) = 3, 5, HEADS[heads]
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


@pytest.mark.parametrize("heads", ["eight-groups-of-8", "a-group-of-64"])
@pytest.mark.parametrize("slots,fit", [(5, 4), (9, 4), (65, 16), (9, 0.25)])
def test_recurrent_step_walks_a_last_block_that_is_not_full(monkeypatch,
                                                            slots, fit,
                                                            heads):
    """A slot count the plan's block does not divide: blocks of 4 slots over
    5 (4 + 1) and 9 (4 + 4 + 1), of 16 over 65 (4 x 16 + 1), and a budget
    that a whole slot does not fit (blocks of 16 heads of one slot, the
    grid of PR 53's kernel).  The last slot, alone in the last block, is
    idle (dt 0) and keeps its state to the bit beside an idle slot in a full
    block; the neighbouring layers are untouched.  The kernel (interpreted)
    is held to the twin, which ``vmap``s the same tile function over slots
    and heads, to the last place or two: every head's arithmetic is
    ``_step_tile`` whatever the block, but the CPU's compiler fuses ``s a +
    x B`` into one rounding or two and sums a row in its own order as it
    sees fit in each program (on the chip every plan read the parent
    kernel's bits: ``PERF.md`` section 6, PR 55)."""
    nh, g, p, n = HEADS[heads]
    x, dt, a_log, b, c, d = _inputs(slots, 1, nh, p, g, n, seed=slots)
    x, b, c = x[:, 0], b[:, 0], c[:, 0]
    dt = dt[:, 0].at[slots - 1].set(0.0).at[1].set(0.0)
    state = jax.random.normal(jax.random.PRNGKey(13), (3, slots, nh, p, n))
    args = (state, jnp.int32(1), x, dt, a_log, b, c, d)
    want, y_want = ssd.ssd_recurrent_step(*args, use_kernel=False)
    monkeypatch.setattr(ssd, "STEP_STATE_VMEM",         # ``fit`` slots' worth
                        int(fit * 4 * nh * ssd._head_bytes(p, n)))
    sb, hb = ssd.step_block(slots, nh, nh // g, p, n)
    assert (sb, hb) == ((fit, nh) if fit >= 1 else (1, 16))
    assert slots % sb or sb == 1
    new, y = ssd.ssd_recurrent_step(*args, interpret=True)
    np.testing.assert_allclose(y, y_want, atol=2e-6)
    np.testing.assert_allclose(new[1], want[1], atol=1e-6)
    assert float(jnp.abs(new[1, 0] - state[1, 0]).max()) > 0.01
    assert bool((new[0] == state[0]).all() and (new[2] == state[2]).all())
    for idle in (1, slots - 1):
        assert bool((new[1, idle] == state[1, idle]).all())


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


# ------------------------------------------------ the kind's reference

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


# ------------------------ one walk, whichever sublayers a kind has

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


def _walked(cfg, params, new):
    """An expert layer's index is its rank among the expert layers."""
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


def _base(name):
    kw = dict(vocab_size=64, hidden_size=32, num_heads=2, num_kv_heads=1,
              mlp_size=48, max_seq_len=64, use_rope=False, no_positions=True,
              linear_num_heads=2, linear_key_dim=8, linear_value_dim=8)
    if name == "without-a-pattern":
        kw = {k: v for k, v in kw.items() if not k.startswith("linear_")}
        kw.update(no_positions=False)
    return kw


# a pattern of sublayers alone (with experts under its "mlp" layers, or a
# dense MLP), a pattern with an MLP under every mixer and a model without a
# pattern
test_every_tree_walks_the_same_layer_stack = contract.walks(WALKS, _base,
                                                            _walked)


# --------------------------------------- the kind's counts (l9-e64 file)

def test_counts_of_the_l9_e64_configurations_step_and_kernels(kind):
    """(The tree, the matrices a layer and the cache's gauges: the
    contract's.)  ISSUE 46's check of the reading against the published
    count; the decode step's four byte terms; the kernels' counts against
    their own loads and stores."""
    doc, cfg = kinds.cell_doc(ROW.name), kinds.cell_cfg(ROW.name)
    assert cfg.layer_pattern == NINE and cfg.sublayers_alone
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_start,
            cfg.shared_experts, cfg.mlp_act) == (128, 64, 0, 2, "relu2")
    assert (cfg.expert_layers, cfg.linear_layers, cfg.full_layers,
            cfg.ssm_layers) == (4, 0, 1, 4)
    per = kind.layer_matrix_params(doc)
    matrices = (4 * (per["mamba"] + 64 * per["expert"] + per["shared"]
                     + per["router"]) + per["attention"] + 2 * 65536 * 2688)
    assert cfg.num_params() == matrices == 3_166_076_928
    assert kind.num_params(doc) == matrices + 4 * (
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
