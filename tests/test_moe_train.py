"""Latent attention and dropless expert layers on the training path (PR 39):
the grouped matmul's backward against ``jax.lax.ragged_dot``'s own
derivative, the gathers' transposes, the router's gradient, the train step's
loss and gradients against ``jax.grad`` of the block kind's plain reference
(``benchmark/models/kimi_vl.py``), the shares of a layer adding up to the
uncut layer, the direct query projection, and the refusals.  Tiny sizes, the
CPU: numerics and control flow, never speeds."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kinds
from ray_tpu.models import latent, transformer
from ray_tpu.models.config import TransformerConfig
from ray_tpu.ops import moe
from ray_tpu.parallel import MeshSpec, make_optimizer, make_train_step
from ray_tpu.parallel.train_step import TrainState, state_shardings

ROW, F32 = kinds.KINDS["kimi_vl"], jnp.float32


@pytest.fixture(scope="module")
def tiny(tiny_doc):
    """(the tiny configuration's file, the program's configuration, seeded
    float32 parameters)."""
    return (tiny_doc, *kinds.tiny(ROW.name))


# ------------------------------------------- the grouped matmul's backward

E, K, N, T, TOPK = 4, 32, 48, 37, 2


def _sorted_rows(tile):
    """37 tokens x 2 assignments over 4 experts of which expert 2 has no
    token and the others' rows end mid-tile; a tenth not held."""
    rng = np.random.default_rng(0)
    idx = rng.integers(0, E, size=(T, TOPK))
    idx = jnp.asarray(np.where(idx == 2, 1, idx), jnp.int32)
    held = jnp.asarray(rng.random((T, TOPK)) < 0.9)
    plan = moe.sort_by_expert(idx, held, E, tile)
    sizes = np.asarray(plan[4])
    assert sizes[2] == 0 and all(s % tile for s in sizes if s)
    w = lambda *shape: jnp.asarray(                       # noqa: E731
        rng.normal(size=shape) * 0.2, F32)
    return (jnp.asarray(rng.normal(size=(T, K)), F32), held, plan,
            (w(2, E, K, N), w(2, E, K, N), w(2, E, N, K)))


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "plain"])
@pytest.mark.parametrize("tile", [16, 32])
def test_gmm_custom_vjp_is_ragged_dots_derivative(tile, gated):
    """The kernels (interpreted) under their ``custom_vjp`` against plain
    autodiff of the twin: the rows' gradient, the weights' (zero for the
    expert without a token and for the layer that was not indexed), through
    the sort's gather and the combine's."""
    x, held, (dest, source, tile_expert, tiles, _), ws = _sorted_rows(tile)
    layer = jnp.int32(1)
    gates = jnp.where(held, 0.3 + 0.2 * jnp.arange(TOPK)[None] + x[:, :TOPK],
                      0.0)

    def experts(xs, wg, wu, wo, gmm):
        act = gmm(xs, (wg, wu)) if gated else gmm(xs, (wg,))
        return gmm(act, (wo,))

    def kernels(x, *ws):
        gmm = lambda a, w: moe.moe_gmm(                   # noqa: E731
            a, w, layer, tile_expert, tiles, tile, interpret=True)
        ys = experts(moe._rows_in(x, source, dest), *ws, gmm)
        return (moe._combine(ys, gates, held, source, dest) ** 2).sum()

    def twin(x, *ws):
        gmm = lambda a, w: moe._gmm_jnp(a, w, layer, tile_expert,  # noqa
                                        tile)
        xs = jnp.take(x, source, axis=0, mode="fill", fill_value=0)
        picked = jnp.take(experts(xs, *ws, gmm), dest, axis=0, mode="fill",
                          fill_value=0)
        return (jnp.einsum("tkh,tk->th", picked, gates) ** 2).sum()

    with jax.default_matmul_precision("highest"):
        got, g_got = jax.jit(jax.value_and_grad(
            kernels, argnums=(0, 1, 2, 3)))(x, *ws)
        want, g_want = jax.jit(jax.value_and_grad(
            twin, argnums=(0, 1, 2, 3)))(x, *ws)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(b).max()))
    for dw in g_got[1:] if gated else (g_got[1], g_got[3]):
        assert not dw[0].any() and not dw[1, 2].any() and dw[1, 1].any()


def test_a_layer_without_one_held_assignment_has_zero_gradients():
    """Every assignment on experts held elsewhere (a router that has drifted
    away from a share, as the chip showed within 45 steps: PERF.md, PR 39):
    no tile holds anything, the output is zero and so is every gradient,
    the weights' among them (on the chip the first tile's block was written
    back as it stood, NaN)."""
    x, _, _, ws = _sorted_rows(16)
    idx = jnp.zeros((T, TOPK), jnp.int32)
    dest, source, tile_expert, tiles, _ = moe.sort_by_expert(
        idx, jnp.zeros((T, TOPK), bool), E, 16)
    assert int(tiles) == 0

    def f(x, wg, wu, wo):
        gmm = lambda a, w: moe.moe_gmm(                   # noqa: E731
            a, w, jnp.int32(0), tile_expert, tiles, 16, interpret=True)
        ys = gmm(gmm(moe._rows_in(x, source, dest), (wg, wu)), (wo,))
        return moe._combine(ys, jnp.zeros((T, TOPK)),
                            jnp.zeros((T, TOPK), bool), source, dest).sum()

    out, grads = jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3)))(x, *ws)
    assert float(out) == 0.0
    for g in grads:
        assert np.isfinite(np.asarray(g)).all() and not np.asarray(g).any()


def test_the_gates_gradient_reaches_the_router_and_not_the_bias():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(9, 16)), F32)
    router = jnp.asarray(rng.normal(size=(16, 8)), F32)
    bias = jnp.asarray(rng.normal(size=(8,)) * 0.1, F32)

    def f(x, router, bias):
        _, gates = moe.route_sigmoid(x, router, bias, 3, 2.0)
        return (gates * jnp.arange(3.0)).sum()

    dx, dr, db = jax.grad(f, argnums=(0, 1, 2))(x, router, bias)
    assert dx.any() and dr.any() and not db.any()


# ------------------------------ the row movements walk what is live (PR 59)

def _plain_moves():
    """The layer's row movements as the layout-sized gathers they were to
    PR 58 (``jnp.take`` by ``source`` over every row, by ``dest`` for every
    assignment, mine or not), with their transposes: the oracle."""
    take = lambda a, at: jnp.take(a, at, axis=0, mode="fill",  # noqa: E731
                                  fill_value=0)

    @jax.custom_vjp
    def rows_in(x, source, dest):
        return take(x, source)

    def rows_in_bwd(res, g):
        source, dest = res
        dx = sum(take(g, dest[:, j]).astype(F32)
                 for j in range(dest.shape[1]))
        return dx.astype(g.dtype), moe._no_grad(source), moe._no_grad(dest)

    rows_in.defvjp(lambda x, s, d: (rows_in(x, s, d), (s, d)), rows_in_bwd)

    @jax.custom_vjp
    def combine(ys, gates, mine, source, dest):
        gates = jnp.where(mine, gates, 0.0)
        return sum(gates[:, j, None] * take(ys, dest[:, j]).astype(F32)
                   for j in range(dest.shape[1]))

    def combine_bwd(res, g):
        ys, gates, mine, source, dest = res
        gate_row = jnp.zeros((ys.shape[0],), F32).at[dest.reshape(-1)].set(
            gates.reshape(-1), mode="drop")
        d_ys = (take(g.astype(ys.dtype), source)
                * gate_row[:, None]).astype(ys.dtype)
        d_gates = jnp.stack([(take(ys, dest[:, j]).astype(F32) * g).sum(-1)
                             for j in range(dest.shape[1])], axis=1)
        return (d_ys, jnp.where(mine, d_gates, 0.0), moe._no_grad(mine),
                moe._no_grad(source), moe._no_grad(dest))

    combine.defvjp(lambda ys, gates, mine, s, d: (
        combine(ys, gates, mine, s, d),
        (ys, jnp.where(mine, gates, 0.0), mine, s, d)), combine_bwd)
    return rows_in, combine


WT, WK, WH, WM, WE, WHELD = 96, 4, 32, 48, 16, 2
#: how the router's choice falls on the two experts held here, by name
SHARES = ("all", "quarter", "eighth", "none", "every_one_here")


def _walk_case(share, by_token, masked, dtype):
    """(x, idx, gates, live, stacks, expert_start, experts): 96 tokens x 4
    choices among the router's 16 experts (2 where the layer holds them
    all), 2 held from ``expert_start`` on."""
    rng = np.random.default_rng(len(share) + 2 * by_token + masked)
    experts = WHELD if share == "all" else WE
    start = 0 if share == "all" else 6
    if share in ("all", "every_one_here"):
        # the case the layout is sized for: T x k rows of it filled
        idx = start + rng.integers(0, WHELD, size=(WT, WK))
    elif share == "none":
        idx = (start + WHELD + rng.integers(0, WE - WHELD, size=(WT, WK))) % WE
    else:
        pool = WE if share == "eighth" else 2 * WK     # 2 of 16, 2 of 8
        idx = np.stack([rng.permutation(pool)[:WK] for _ in range(WT)])
        idx = (idx + start) % WE
    if by_token:
        # every token its own first expert, its choices moved along with it
        shift = rng.integers(0, WE // WHELD, size=(WT,)) * WHELD
        idx = (idx + shift[:, None]) % experts
        start = jnp.asarray((start + shift) % experts, jnp.int32)
    w = lambda *shape: jnp.asarray(                       # noqa: E731
        rng.normal(size=shape) * 0.3, dtype)
    # gates that are powers of two: a gate times a row is then exact, and
    # the CPU compiler's choice of where to fuse a product into a sum (one
    # rounding for two) cannot tell two programs apart that add in one order
    gates = jnp.asarray(2.0 ** -rng.integers(0, 4, size=(WT, WK)), F32)
    live = jnp.asarray(rng.random(WT) < 0.8) if masked else None
    stacks = {"w_gate": w(2, WHELD, WH, WM), "w_in": w(2, WHELD, WH, WM),
              "w_out": w(2, WHELD, WM, WH)}
    return (w(WT, WH), jnp.asarray(idx, jnp.int32), gates, live, stacks,
            start, experts)


def _held_part_grads(case, kernels, monkeypatch, plain):
    """``_held_part``'s value and its gradients in x, the gates and the three
    stacks, weighted so that every element counts; ``plain``: through the
    oracle's movements."""
    x, idx, gates, live, stacks, start, experts = case
    if plain:
        rows_in, combine = _plain_moves()
        monkeypatch.setattr(moe, "walks", lambda *a: False)
        monkeypatch.setattr(moe, "_rows_in", rows_in)
        monkeypatch.setattr(moe, "_combine", combine)
    weights = jnp.cos(jnp.arange(WT * WH, dtype=F32)).reshape(WT, WH)

    def f(x, gates, stacks):
        out, sizes = moe._held_part(
            x, idx, gates, live, stacks, jnp.int32(1), start, WK,
            True if kernels else None, True if kernels else None, experts)
        return (out * weights).sum(), (out, sizes)

    return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True))(
        x, gates, stacks)


@pytest.fixture
def short_trips(monkeypatch):
    """The walk's trips at a size the tiny layout has several of."""
    monkeypatch.setattr(moe, "WALK_ROWS", 64)
    monkeypatch.setattr(moe, "WALK_TOKENS", 16)


@pytest.mark.parametrize("kernels", [False, True], ids=["twin", "kernels"])
@pytest.mark.parametrize("by_token,masked", [
    (False, False), (True, False), (False, True), (True, True)],
    ids=["start_int", "start_by_token", "start_int-live",
         "start_by_token-live"])
@pytest.mark.parametrize("share", SHARES)
def test_held_part_walking_live_rows_is_the_plain_gathers_bit_for_bit(
        share, by_token, masked, kernels, short_trips, monkeypatch):
    """The walk over live tiles and held assignments against the
    layout-sized gathers: the layer's part, its sizes and every gradient
    bit for bit (the gates' gradient, whose sum over H is taken where the
    row lies and not at the token, to 1e-6 of its largest), at every share
    of the block's assignments held here, the whole block among them: the
    layout has no capacity and the walk drops nothing."""
    case = _walk_case(share, by_token, masked, F32)
    tile = moe.tile_rows(WT * WK, WHELD)
    rows = moe.layout_rows(WT * WK, WHELD, tile)
    assert moe.walks(rows, WT, WK, WHELD, case[-1], tile) == (share != "all")
    got = _held_part_grads(case, kernels, monkeypatch, plain=False)
    want = _held_part_grads(case, kernels, monkeypatch, plain=True)
    (_, (out, sizes)), (dx, dgates, dws) = got
    (_, (out_w, sizes_w)), (dx_w, dgates_w, dws_w) = want
    held = int(sizes.sum())
    assert {"none": held == 0, "all": held >= 0.75 * WT * WK,
            "every_one_here": held >= 0.75 * WT * WK}.get(share, held > 0)
    if share in ("all", "every_one_here") and not masked:
        assert held == WT * WK
    np.testing.assert_array_equal(sizes, sizes_w)
    np.testing.assert_array_equal(out, out_w)
    np.testing.assert_array_equal(dx, dx_w)
    for name in dws:
        np.testing.assert_array_equal(dws[name], dws_w[name])
    np.testing.assert_allclose(dgates, dgates_w, rtol=0, atol=1e-6 * max(
        float(jnp.abs(dgates_w).max()), 1e-30))
    assert bool(out.any()) == (held > 0)


def test_rows_no_assignment_sits_in_are_never_read(short_trips, monkeypatch):
    """Under the kernels: NaN in every row of ``xs`` and of the rows'
    gradient past the live tiles (what ``_blank`` leaves there on the chip
    is anything), NaN in ``ys`` wherever no assignment sits (the padding
    rows of live tiles too): the part and every gradient come out finite
    and as they were.  And the padding rows INSIDE a live tile of ``xs`` are
    zeros: ``moe_gmm_dw`` sums ``x^T dy`` over the whole tile."""
    case = _walk_case("quarter", False, False, F32)
    clean = _held_part_grads(case, True, monkeypatch, plain=False)
    seen = {}

    def sort(idx, held, experts, tile, real=moe.sort_by_expert):
        seen["plan"] = plan = real(idx, held, experts, tile)
        return plan

    def blank(shape, dtype, after, kernel):
        assert kernel
        return jnp.full(shape, jnp.nan, dtype)

    def gmm(x, weights, real=moe.moe_gmm, **kw):
        out = real(x, weights, **kw)
        if x.shape[1] == WH:
            seen["xs"] = x
            return out
        # (the poison is no function of ys: its gradient passes through)
        poison = jax.custom_vjp(lambda a: jnp.where(
            (seen["plan"][1] == WT)[:, None], jnp.nan, a))
        poison.defvjp(lambda a: (poison(a), None), lambda _, g: (g,))
        return poison(out)

    monkeypatch.setattr(moe, "sort_by_expert", sort)
    monkeypatch.setattr(moe, "_blank", blank)
    monkeypatch.setattr(moe, "moe_gmm", gmm)
    x, idx, gates, live, stacks, start, experts = case
    with jax.disable_jit():
        moe._held_part(x, idx, gates, live, stacks, jnp.int32(1), start, WK,
                       True, True, experts)
    _, source, _, tiles, _ = seen["plan"]
    tile = moe.tile_rows(WT * WK, WHELD)
    upto = int(tiles) * tile
    xs, source = np.asarray(seen["xs"]), np.asarray(source)
    assert 0 < upto < len(source) and np.isnan(xs[upto:]).all()
    padding = source[:upto] == WT
    assert padding.any() and not xs[:upto][padding].any()
    np.testing.assert_array_equal(xs[:upto][~padding],
                                  np.asarray(x)[source[:upto][~padding]])
    poisoned = _held_part_grads(case, True, monkeypatch, plain=False)
    for a, b in zip(jax.tree.leaves(poisoned), jax.tree.leaves(clean)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("rows,tokens,k,held,experts,want", [
    (69632, 8192, 8, 16, 64, True),      # the four-chip train cell's block
    (100352, 16384, 6, 8, 64, True),     # the share train cell's
    (34816, 4096, 8, 8, 128, True),      # K-EXAONE's admit, a sixteenth
    (24576, 2048, 6, 64, 128, False),    # a half held: the plain gathers
    (34816, 4096, 8, 8, 8, False),       # every expert held
    (1792, 128, 8, 8, 128, False),       # a decode step
], ids=["ep4", "share", "exaone_admit", "half", "whole", "decode"])
def test_the_walk_is_chosen_from_the_shapes(rows, tokens, k, held, experts,
                                            want):
    tile = moe.tile_rows(tokens * k, held)
    assert moe.walks(rows, tokens, k, held, experts, tile) is want


def test_rows_live_share_is_the_live_tiles_over_the_layout():
    rng = np.random.default_rng(3)
    for held, assignments in ((16, 65536), (8, 98304), (2, 384)):
        sizes = rng.multinomial(assignments // 4, np.ones(held) / held)
        tile = moe.tile_rows(assignments, held)
        rows = moe.layout_rows(assignments, held, tile)
        want = (-(-sizes // tile)).sum() * tile / rows
        got = moe.rows_live_share(jnp.asarray(sizes, jnp.int32), assignments)
        assert got.dtype == F32 and float(got) == pytest.approx(want,
                                                                rel=1e-6)
    assert float(moe.rows_live_share(jnp.zeros((4,), jnp.int32), 64)) == 0.0


# ------------------------------- the train step against the kind's reference

def _batch(doc, seed=0):
    tr = doc["train"]
    toks = np.random.default_rng(seed).integers(
        0, doc["vocab_size"], size=(tr["global_batch"],
                                    tr["sequence_length"] + 1),
        dtype=np.int32)
    return toks


def test_loss_and_gradients_are_the_references(kind, tiny):
    """``causal_lm_loss`` computed in float32 against ``jax.grad`` of the
    plain reference on the same seeded weights: the loss, and every leaf of
    the gradient (the selection bias's is zero on both sides)."""
    doc, cfg, params = tiny
    toks = _batch(doc)

    def program(p):
        return transformer.causal_lm_loss(
            p, {"tokens": toks[:, :-1], "targets": toks[:, 1:]}, cfg,
            compute_dtype=F32, remat="save_acts")[0]

    def reference(p):
        return jax.vmap(lambda s: kind.loss(p, s, doc))(toks).mean()

    with jax.default_matmul_precision("highest"):
        got, g_got = jax.jit(jax.value_and_grad(program))(params)
        want, g_want = jax.jit(jax.value_and_grad(reference))(params)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(g_got))
    for path, b in jax.tree_util.tree_leaves_with_path(g_want):
        a = flat_got[path]
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=2e-5 * scale + 1e-9,
                                   err_msg=jax.tree_util.keystr(path))
    assert not g_got["blocks"]["moe"]["bias"].any()
    assert g_got["blocks"]["moe"]["router"].any()
    assert g_got["blocks"]["moe"]["w_gate"].any()


def test_bf16_step_reads_the_references_loss_and_falls(kind, tiny):
    """The train step as the benchmark's runner builds it (``MeshSpec`` ->
    ``state_shardings`` -> ``make_train_step``, bf16 compute): its first
    loss beside the reference's, its counters, and a loss that falls."""
    doc, cfg, _ = tiny
    tr = doc["train"]
    mesh = MeshSpec(**tr["mesh"]).build(jax.devices()[:1])
    opt = make_optimizer(**tr["optimizer"])

    def init_fn(key):
        params = kind.init_params(key, cfg, F32)
        return TrainState(params=params, opt_state=opt.init(params),
                          step=jnp.zeros((), jnp.int32))

    key = jax.random.PRNGKey(5)
    sh = state_shardings(cfg, mesh, opt, jax.eval_shape(init_fn, key))
    state = jax.jit(init_fn, out_shardings=sh)(key)
    step = make_train_step(cfg, mesh, opt, sh, remat=tr["remat"])
    assert step.opt_state_bytes == 2 * cfg.num_params() * 4 + 8
    toks = _batch(doc, 1)
    want = float(jax.jit(lambda p: jax.vmap(
        lambda s: kind.loss(p, s, doc))(toks).mean())(state.params))
    losses = []
    for _ in range(6):
        state, m = step(state, {"tokens": toks[:, :-1],
                                "targets": toks[:, 1:]})
        losses.append(float(m["loss"]))
    assert abs(losses[0] - want) < doc["train"]["check"]["tol_loss_abs"]
    assert losses[-1] < losses[0] - 0.5
    layers = cfg.expert_layers
    assert 0 < int(m["moe_assignments_held"]) <= (
        toks[:, 1:].size * cfg.experts_per_token * layers)
    assert int(m["moe_expert_load_min"]) <= int(m["moe_expert_load_max"])
    assert float(m["moe_aux_loss"]) == 0.0
    # the selection bias: no gradient reaches it and no rule moves it
    assert not np.asarray(state.params["blocks"]["moe"]["bias"]).any()


def test_the_steps_rows_live_share_is_a_count_made_in_numpy(tiny):
    """``moe_rows_live_share`` of ``causal_lm_loss``'s metrics, a mean over
    the expert layers of the sorted layout's rows in tiles that hold an
    assignment over the rows it is sized for, against the same count made
    from the routers' choices (a share by position: the held experts stand
    for another group of the router's outputs at each position)."""
    from unittest import mock
    doc, cfg, params = tiny
    toks = _batch(doc, 3)
    seen = []

    def spy(aux, real=transformer._trunk_aux):
        seen.append(np.asarray(aux["moe_choices"]))      # [layers, B, S, k]
        return real(aux)

    with mock.patch.object(transformer, "_trunk_aux", spy):
        metrics = transformer.causal_lm_loss(
            params, {"tokens": toks[:, :-1], "targets": toks[:, 1:]}, cfg,
            compute_dtype=F32)[1]
    choices, = seen
    held, k = cfg.experts_held, cfg.experts_per_token
    b, s = choices.shape[1:3]
    assert cfg.share_by_position
    start = ((cfg.expert_start // held + np.arange(s))
             % (cfg.num_experts // held) * held)[None, :, None]
    tile = moe.tile_rows(b * s * k, held)
    rows = moe.layout_rows(b * s * k, held, tile)
    shares = []
    for layer in choices:
        local = layer - start
        sizes = np.array([(local == e).sum() for e in range(held)])
        shares.append((-(-sizes // tile)).sum() * tile / rows)
    assert 0 < np.mean(shares) < 1
    assert float(metrics["moe_rows_live_share"]) == pytest.approx(
        np.mean(shares), rel=1e-6)


# ------------------------------------------------------ the shares add up

def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(kind, tiny):
    """Four holders of four experts each, attention, router and shared
    experts counted once: their parts of an expert layer sum to the uncut
    reference's layer (all sixteen experts on one holder), forward and the
    gradient of the input."""
    doc, cfg, _ = tiny
    whole = dataclasses.replace(cfg, experts_held=0, expert_start=0)
    mp = jax.tree.map(lambda a: a[0], latent.init_params(
        jax.random.PRNGKey(7), whole, F32)["blocks"]["moe"])
    assert mp["w_gate"].shape[0] == mp["router"].shape[1] == 16
    x = jax.random.normal(jax.random.PRNGKey(8), (40, cfg.hidden_size), F32)
    routed = ("w_gate", "w_in", "w_out")
    small = {k: v for k, v in mp.items() if k not in routed}

    def share(x, start):
        out, counts, *_ = moe.moe_dropless(
            x, small, {k: mp[k][None, start:start + 4] for k in routed}, 0,
            experts_per_token=cfg.experts_per_token,
            scaling=cfg.routed_scaling_factor, expert_start=start,
            shared=start == 0)
        return out, counts[0]

    def summed(x):
        parts = [share(x, s) for s in (0, 4, 8, 12)]
        return sum(p[0] for p in parts), sum(p[1] for p in parts)

    uncut_doc = dict(doc, n_routed_experts=16, share={"expert_start": 0})
    uncut = lambda x: kind._expert_layer(x, mp, uncut_doc)   # noqa: E731
    with jax.default_matmul_precision("highest"):
        (got, assignments), want = jax.jit(summed)(x), jax.jit(uncut)(x)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
        # every assignment was some holder's, and one holder's alone
        assert int(assignments) == 40 * cfg.experts_per_token
        weights = jax.random.normal(jax.random.PRNGKey(9), want.shape, F32)
        g_got = jax.jit(jax.grad(
            lambda x: (summed(x)[0] * weights).sum()))(x)
        g_want = jax.jit(jax.grad(lambda x: (uncut(x) * weights).sum()))(x)
    np.testing.assert_allclose(g_got, g_want, rtol=1e-3, atol=1e-4)
    # the reference's own share is the program's share
    fixed = dict(doc, share=dict(doc["share"], by_position=False))
    one = kind._expert_layer(
        x, dict(small, **{k: mp[k][4:8] for k in routed}), fixed,
        shared=False)
    np.testing.assert_allclose(share(x, 4)[0], one, rtol=1e-4, atol=1e-5)


def test_shares_by_position_add_up_and_keep_their_share_of_any_routing(
        kind, tiny):
    """``share_by_position``: the four held weights stand at position p for
    the router's group ``start / 4 + p`` modulo 4.  Over an uncut layer whose
    sixteen experts are those four, four times over, the four holders' parts
    add up to the layer, forward and the gradient of the input; and a router
    that sends every token to one group still leaves each holder a quarter
    of the assignments, where a holder of one fixed group has all or none."""
    doc, cfg, _ = tiny
    assert cfg.share_by_position and doc["share"]["by_position"]
    whole = dataclasses.replace(cfg, experts_held=0, expert_start=0,
                                share_by_position=False)
    mp = jax.tree.map(lambda a: a[0], latent.init_params(
        jax.random.PRNGKey(7), whole, F32)["blocks"]["moe"])
    routed = ("w_gate", "w_in", "w_out")
    small = {k: v for k, v in mp.items() if k not in routed}
    four = {k: mp[k][:4] for k in routed}
    x = jax.random.normal(jax.random.PRNGKey(8), (40, cfg.hidden_size), F32)

    def share(x, small, start):
        first = (start // 4 + jnp.arange(x.shape[0])) % 4 * 4
        out, counts, *_ = moe.moe_dropless(
            x, small, {k: v[None] for k, v in four.items()}, 0,
            experts_per_token=cfg.experts_per_token,
            scaling=cfg.routed_scaling_factor, expert_start=first,
            shared=start == 0)
        return out, counts[0]

    def summed(x):
        return sum(share(x, small, s)[0] for s in (0, 4, 8, 12))

    uncut_doc = dict(doc, n_routed_experts=16, share={"expert_start": 0})
    tiled = dict(small, **{k: jnp.tile(v, (4, 1, 1)) for k, v in four.items()})
    uncut = lambda x: kind._expert_layer(x, tiled, uncut_doc)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(jax.jit(summed)(x), jax.jit(uncut)(x),
                                   rtol=1e-4, atol=1e-5)
        weights = jax.random.normal(jax.random.PRNGKey(9), x.shape, F32)
        np.testing.assert_allclose(
            jax.jit(jax.grad(lambda x: (summed(x) * weights).sum()))(x),
            jax.jit(jax.grad(lambda x: (uncut(x) * weights).sum()))(x),
            rtol=1e-3, atol=1e-4)
        # the reference's share by position is the program's
        one = jax.jit(lambda x: kind._expert_layer(
            x, dict(small, **four), doc, shared=False))(x)
        np.testing.assert_allclose(jax.jit(lambda x: share(x, small, 4)[0])(x),
                                   one, rtol=1e-4, atol=1e-5)
    # a router that has left group 0 for group 2 (its bias says so)
    gone = dict(small, bias=jnp.where(jnp.arange(16) // 4 == 2, 10.0, -10.0))
    k = cfg.experts_per_token
    for start in (0, 4, 8, 12):
        assert int(share(x, gone, start)[1]) == 10 * k
    fixed = moe.moe_dropless(
        x, gone, {k_: v[None] for k_, v in four.items()}, 0,
        experts_per_token=k, scaling=1.0, expert_start=0)[1][0]
    assert int(fixed) == 0


def test_the_direct_query_projection_is_the_references(kind, tiny):
    doc, cfg, params = tiny
    ap = jax.tree.map(lambda a: a[0], params["prefix"]["attn"])
    assert set(ap) == {"wq", "w_dkv", "kv_norm", "w_ukv", "wo"}
    assert ap["wq"].shape == (cfg.hidden_size,
                              cfg.num_heads * cfg.qk_head_dim)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 48, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda x: latent.attention(
            x, ap, cfg, jnp.arange(48)[None]))(x)
        want = jax.jit(jax.vmap(lambda s: kind._attention(s, ap, doc)))(x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


# ------------------------------------------------- counts and refusals

def test_the_counts_are_of_what_a_share_holds(kind, tiny):
    doc, cfg, params = tiny
    leaves = sum(a.size for a in jax.tree.leaves(params))
    assert kind.num_params(doc) == leaves
    small = leaves - cfg.num_params()           # norm scales and biases
    assert 0 < small < 0.01 * leaves
    whole = dataclasses.replace(cfg, experts_held=0, expert_start=0)
    per_expert = 3 * cfg.hidden_size * cfg.expert_mlp_size
    assert whole.num_params() - cfg.num_params() == (
        cfg.expert_layers * (16 - 4) * per_expert)
    # a token meets k * held / E routed experts here
    assert cfg.flops_per_token(64) == pytest.approx(
        kind.train_flops_per_token(doc, 64))
    assert whole.flops_per_token(64) - cfg.flops_per_token(64) == \
        pytest.approx(6.0 * cfg.expert_layers * per_expert
                      * cfg.experts_per_token * (1 - 4 / 16))


def _tiny_mesh(n):
    return MeshSpec(fsdp=-1).build(jax.devices()[:n])


def test_a_mesh_of_several_devices_is_refused_with_the_reason(tiny):
    _, cfg, _ = tiny
    if len(jax.devices()) < 2:
        pytest.skip("one device")
    with pytest.raises(NotImplementedError, match="no sharding rule") as e:
        make_train_step(cfg, _tiny_mesh(2), make_optimizer(), None)
    assert "kv_lora_rank" in str(e.value) and "ep" in str(e.value)


@pytest.mark.parametrize("kw,match", [
    (dict(hc_mult=4), "hc_mult is served"),
    (dict(layer_pattern=("linear", "full"), norm_on_output=True,
          num_layers=2, linear_num_heads=2, linear_key_dim=8,
          linear_value_dim=8, kv_lora_rank=0, qk_nope_head_dim=0,
          qk_rope_head_dim=0, v_head_dim=0, moe_dropless=False,
          num_experts=1, expert_mlp_size=0, shared_experts=0,
          dense_prefix_layers=0, experts_held=0, expert_start=0,
          share_by_position=False),
     "layer_pattern"),
])
def test_the_train_step_still_refuses(tiny, kw, match):
    _, cfg, _ = tiny
    refused = dataclasses.replace(cfg, **kw)
    with pytest.raises(NotImplementedError, match=match):
        make_train_step(refused, _tiny_mesh(1), make_optimizer(), None)
    with pytest.raises(NotImplementedError, match=match):
        transformer.apply_trunk({}, jnp.zeros((1, 4), jnp.int32), refused)


@pytest.mark.parametrize("kw,match", [
    (dict(expert_start=14), "not among the router's"),
    (dict(expert_start=2), "share_by_position"),
    (dict(experts_held=3, expert_start=0), "share_by_position"),
    (dict(q_lora_rank=-1), "q_lora_rank"),
    (dict(kv_lora_rank=0, qk_nope_head_dim=0, qk_rope_head_dim=0,
          v_head_dim=0, q_lora_rank=8), "latent attention only"),
])
def test_config_refuses_a_share_that_is_none(tiny, kw, match):
    _, cfg, _ = tiny
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(cfg, **kw)


def test_the_chips_gradient_check_runs_at_the_tiny_size():
    """``tests/chip_moe_grad_check.py`` is run on the chip at the share
    cell's sizes (PERF.md section 6, PR 39); here its tiny sizes, the
    kernels interpreted: the sound readings under its limit, the control
    (one held expert left out of the float32 layer) far over it."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chip_moe_grad_check", os.path.join(kinds.REPO, "tests",
                                            "chip_moe_grad_check.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = mod.check(tiny=True)
    assert out["ok"], out
    assert out["worst_sound"] < mod.LIMIT < mod.CONTROL_FLOOR \
        < out["least_control"]
