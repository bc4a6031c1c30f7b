"""A pattern of window and full attention layers with softmax-routed
dropless experts on the training path, the experts exchanged over ``ep``
(PR 58): the bf16 train step beside the block kind's plain reference
(``benchmark/models/mellum.py``; its loss and every gradient leaf in float32
on one device and four meshes: ``tests/test_mellum_grads.py``), the holders'
parts of an expert layer adding
up to the one-device layer and to the uncut reference through the exchange,
YaRN by kind, the softmax router and its balance term against the family's
own functions, the scopes, and the refusals.  Tiny sizes, the CPU: numerics
and control flow, never speeds.  (The band's backward kernel:
``tests/test_flash_window_bwd.py``.)"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import kinds
from ray_tpu.models import transformer
from ray_tpu.ops import moe
from ray_tpu.parallel import MeshSpec, make_optimizer, make_train_step
from ray_tpu.parallel.train_step import TrainState, state_shardings

ROW, F32 = kinds.KINDS["mellum"], jnp.float32
FAMILY = "transformers.models.qwen3_moe.modeling_qwen3_moe"


@pytest.fixture(scope="module")
def tiny(tiny_doc):
    """(the tiny configuration's file, the program's configuration, seeded
    float32 parameters)."""
    return (tiny_doc, *kinds.tiny(ROW.name))


def _mesh(**axes):
    n = math.prod(axes.values())
    if len(jax.devices()) < n:
        pytest.skip(f"{n} devices")
    return MeshSpec(**{"fsdp": 1, **axes}).build(jax.devices()[:n])


def _batch(doc, seed=0):
    tr = doc["train"]
    return np.random.default_rng(seed).integers(
        0, doc["vocab_size"], size=(tr["global_batch"],
                                    tr["sequence_length"] + 1),
        dtype=np.int32)


# ------------------------------- the train step against the kind's reference
# (the loss and every gradient leaf on one device and four meshes:
# ``tests/test_mellum_grads.py``, a file apart so that ``--dist loadfile``
# can part them)

def test_bf16_step_on_four_devices_reads_the_references_loss_and_falls(
        kind, tiny):
    """The train step as the benchmark's runner builds it (``MeshSpec`` ->
    ``state_shardings`` -> ``make_train_step``, bf16 compute, the
    configuration's own mesh of ``ep`` = 4): its first loss beside the
    reference's, its counters, and a loss that falls."""
    doc, cfg, _ = tiny
    tr = doc["train"]
    assert tr["mesh"] == {"fsdp": 1, "ep": 4}
    mesh = _mesh(ep=4)
    opt = make_optimizer(**tr["optimizer"])

    def init_fn(key):
        params = kind.init_params(key, cfg, F32)
        return TrainState(params=params, opt_state=opt.init(params),
                          step=jnp.zeros((), jnp.int32))

    key = jax.random.PRNGKey(5)
    sh = state_shardings(cfg, mesh, opt, jax.eval_shape(init_fn, key))
    state = jax.jit(init_fn, out_shardings=sh)(key)
    # the experts over ep and whole otherwise; the dense leaves, the
    # embedding and the head a quarter a device, as fsdp splits them; the
    # optimizer's moments as the parameters; a sequence a device
    assert sh.params["blocks"]["experts"]["w_out"].spec == P(None, "ep", None,
                                                            None)
    assert sh.params["blocks"]["window"]["attn"]["wq"].spec == P(
        None, None, ("fsdp", "ep"), None)
    assert sh.params["embed"]["tokens"].spec == P(("fsdp", "ep"), None)
    assert sh.opt_state[1][0].mu["lm_head"].spec == sh.params["lm_head"].spec
    step = make_train_step(cfg, mesh, opt, sh, remat=tr["remat"])
    assert step.batch_sharding.spec == P(("dp", "fsdp", "ep"), "sp")
    toks = _batch(doc, 1)
    want = float(jax.jit(lambda p: jax.vmap(
        lambda s: kind.loss(p, s, doc))(toks).mean())(state.params))
    losses = []
    for _ in range(6):
        state, m = step(state, {"tokens": toks[:, :-1],
                                "targets": toks[:, 1:]})
        losses.append(float(m["loss"]))
    assert abs(losses[0] - want) < tr["check"]["tol_loss_abs"]
    assert losses[-1] < losses[0] - 0.5
    every = toks[:, 1:].size * cfg.experts_per_token * cfg.num_layers
    assert int(m["moe_assignments_held"]) == every
    assert int(m["moe_chip_load_min"]) <= every // 16 <= int(
        m["moe_chip_load_max"])
    assert int(m["moe_expert_load_min"]) <= int(m["moe_expert_load_max"])
    assert 0 < float(m["moe_rows_live_share"]) <= 1
    assert float(m["moe_aux_loss"]) == 0.0 and float(m["moe_balance"]) > 1.9
    assert float(m["total_loss"]) == pytest.approx(
        float(m["loss"]) + cfg.moe_balance_weight * float(m["moe_balance"]))
    # what a chip sends a step, as the kind counts it for a chip's tokens
    assert float(m["moe_exchange_bytes"]) == kind.moe_ep_exchange_bytes(
        doc, tr["global_batch"] * tr["sequence_length"] / 4)


# ------------------------------------- the holders' parts through the exchange

@pytest.mark.parametrize("walked", [False, True], ids=["plain", "walked"])
@pytest.mark.parametrize("kernels", [False, True], ids=["twin", "kernels"])
def test_the_four_holders_parts_add_up_through_the_exchange(
        kind, tiny, kernels, walked, monkeypatch):
    """``moe_dropless_ep`` over four holders of two experts each, a block of
    tokens a holder: its output is the one-device layer's (all eight experts
    on one holder) and the uncut reference's, forward and the gradients of
    the tokens, the router and the experts; every assignment was one
    holder's.  ``walked``: with trips short enough for a block of 24 tokens,
    so that every holder's part walks its layout's live rows (PR 59; the
    one-device layer holds every expert and stays the plain gathers).  The
    share of the layout that was live, a holder, against a count made in
    numpy from the routers' choices."""
    doc, cfg, params = tiny
    mesh = _mesh(ep=4)
    if walked:
        monkeypatch.setattr(moe, "WALK_ROWS", 16)
        monkeypatch.setattr(moe, "WALK_TOKENS", 8)
    k, held, block = cfg.experts_per_token, 2, 24
    tile = moe.tile_rows(block * k, held)
    rows = moe.layout_rows(block * k, held, tile)
    assert moe.walks(rows, block, k, held, 8, tile) == walked
    mp = jax.tree.map(lambda a: a[0, 0], params["blocks"]["window"]["moe"])
    stacks = jax.tree.map(lambda a: a[1], params["blocks"]["experts"])
    x = jax.random.normal(jax.random.PRNGKey(8), (4 * 24, cfg.hidden_size),
                          F32)
    weights = jax.random.normal(jax.random.PRNGKey(9), x.shape, F32)
    kw = dict(experts_per_token=cfg.experts_per_token, scaling=1.0,
              router="softmax", interpret=True if kernels else None)

    def holder(x, small, stacks):
        out, load, idx, rows_live = moe.moe_dropless_ep(
            x, small, jax.tree.map(lambda a: a[None], stacks), 0, axis="ep",
            **kw)
        return out, load, idx, rows_live[None]

    def exchanged(x, mp, stacks):
        return jax.shard_map(
            holder, mesh=mesh, in_specs=(P("ep"), P(), P("ep")),
            out_specs=(P("ep"),) * 4, check_vma=False)(x, mp, stacks)

    def one_device(x, mp, stacks):
        out, _, _, load = moe.moe_dropless(
            x, mp, jax.tree.map(lambda a: a[None], stacks), 0, **kw)
        return out, load

    def uncut(x, mp, stacks):
        return kind._experts(x, mp["router"], stacks, doc)

    def scalar(f):
        return lambda *a: (f(*a)[0] * weights).sum()

    with jax.default_matmul_precision("highest"):
        got, load, idx, rows_live = jax.jit(exchanged)(x, mp, stacks)
        # holder h lays out the block of holder h - s at step s: the rows in
        # tiles that hold one of the block's assignments to its two experts
        blocks = np.asarray(idx).reshape(4, block, k)
        want_live = [np.mean([sum(
            -(-(blocks[(h - s) % 4] == e).sum() // tile) * tile
            for e in (2 * h, 2 * h + 1)) / rows for s in range(4)])
            for h in range(4)]
        np.testing.assert_allclose(rows_live, want_live, rtol=1e-6)
        one, load_one = jax.jit(one_device)(x, mp, stacks)
        want = jax.jit(uncut)(x, mp, stacks)
        np.testing.assert_allclose(got, one, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
        np.testing.assert_array_equal(load, load_one)
        assert int(load.sum()) == x.shape[0] * cfg.experts_per_token
        g_got = jax.jit(jax.grad(scalar(exchanged), (0, 1, 2)))(x, mp, stacks)
        if walked:
            # the walk drops and rounds nothing the plain gathers keep
            monkeypatch.setattr(moe, "walks", lambda *a: False)
            g_plain = jax.jit(jax.grad(scalar(exchanged), (0, 1, 2)))(
                x, mp, stacks)
            for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_plain)):
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6 * float(
                    jnp.abs(b).max()) + 1e-12)
        g_want = jax.jit(jax.grad(
            lambda *a: (uncut(*a) * weights).sum(), (0, 1, 2)))(x, mp, stacks)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(a, b, rtol=1e-3,
                                   atol=1e-4 * float(jnp.abs(b).max()) + 1e-9)


def test_exchange_bytes_count_what_the_walk_sends():
    """A block on ``n - 1`` hops with its choices and gates, a float32 block
    of results back from as many steps."""
    assert moe.exchange_bytes(8192, 2304, 8, 4, 2) == 3 * (
        8192 * (2304 * 2 + 8 * 8) + 8192 * 2304 * 4)
    assert moe.exchange_bytes(8192, 2304, 8, 1, 2) == 0


# ------------------------------------ the family's own formulas (transformers)

def test_yarn_by_kind_is_the_familys(kind, tiny):
    """``rope_table``: a window layer's table plain, a full layer's YaRN's
    with cos and sin times the published ``attention_factor``, against the
    kind's own arithmetic and against ``transformers``' ``yarn`` and
    ``default`` functions at the published sizes."""
    torch = pytest.importorskip("torch")
    from transformers.modeling_rope_utils import ROPE_INIT_FUNCTIONS
    cell = kinds.cell_doc(ROW.name)
    cfg = kinds.cell_cfg(ROW.name)
    assert cfg.rope_yarn_kinds == ("full",) and cfg.head_dim == 128
    for layer_type, k in (("sliding_attention", "window"),
                          ("full_attention", "full")):
        inv, mag = transformer.rope_table(cfg, k)
        ref_inv, ref_mag = kind.rope_inverse_frequencies(cell, layer_type)
        np.testing.assert_allclose(inv, ref_inv, rtol=1e-6)
        assert mag == pytest.approx(ref_mag, rel=1e-12)
        rp = cell["rope_parameters"][layer_type]
        hf = type("C", (), dict(
            rope_theta=rp["rope_theta"], head_dim=128, hidden_size=2304,
            num_attention_heads=32, partial_rotary_factor=1.0,
            max_position_embeddings=cell["max_position_embeddings"],
            rope_scaling=dict(rp), rope_parameters=dict(rp)))()
        hf_inv, hf_mag = ROPE_INIT_FUNCTIONS[rp["rope_type"]](hf, "cpu")
        np.testing.assert_allclose(inv, hf_inv.numpy(), rtol=1e-5)
        assert mag == pytest.approx(float(hf_mag), rel=1e-6)
    assert transformer.rope_table(cfg, "full")[1] == pytest.approx(
        1.2772588722239782)
    assert transformer.rope_table(cfg, "window")[1] == 1.0
    # static: the blend does not wait for a sequence past the original
    # context, and the two kinds' tables differ at any length
    assert not np.allclose(transformer.rope_table(cfg, "full")[0],
                           transformer.rope_table(cfg, "window")[0])
    del torch


def test_the_softmax_router_and_its_balance_term_are_the_familys(kind, tiny):
    """``route_softmax`` against the lines of ``Qwen3MoeSparseMoeBlock.
    forward`` and ``balance_term`` against ``load_balancing_loss_func`` (one
    layer's logits, no mask), in torch on the same numbers."""
    torch = pytest.importorskip("torch")
    import importlib
    family = importlib.import_module(FAMILY)
    rng = np.random.default_rng(3)
    x = rng.normal(size=(40, 16)).astype(np.float32)
    router = rng.normal(size=(16, 8)).astype(np.float32)
    idx, gates = moe.route_softmax(jnp.asarray(x), jnp.asarray(router), 3)
    logits = torch.tensor(x) @ torch.tensor(router)
    weights = torch.nn.functional.softmax(logits, dim=1, dtype=torch.float)
    weights, chosen = torch.topk(weights, 3, dim=-1)
    weights /= weights.sum(dim=-1, keepdim=True)
    np.testing.assert_array_equal(idx, chosen.numpy())
    np.testing.assert_allclose(gates, weights.numpy(), rtol=1e-5)
    want = family.load_balancing_loss_func((logits,), 8, 3)
    got = moe.balance_term(jnp.asarray(x), jnp.asarray(router), idx)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    # the gates' gradient reaches the router; the choice carries none
    dr = jax.grad(lambda r: (moe.route_softmax(
        jnp.asarray(x), r, 3)[1] * jnp.arange(3.0)).sum())(
            jnp.asarray(router))
    assert np.asarray(dr).any()
    # the reference's own router and term are the same numbers
    doc = dict(tiny[0], num_experts_per_tok=3)
    r_idx, r_gates, probs = kind.route(jnp.asarray(x), jnp.asarray(router),
                                       doc)
    np.testing.assert_array_equal(r_idx, idx)
    np.testing.assert_allclose(r_gates, gates, rtol=1e-6)


# ------------------------------------------------ names, counts and refusals

def test_the_train_step_carries_the_exchanges_and_the_layers_scopes(tiny):
    """``jit_train_step`` on a mesh of four: the scopes around the
    exchange's two walks and around the layers of each kind, forward and
    backward, with the expert layer's own; and the exchange as
    ``collective_permute``, which nothing else in the step is."""
    import re
    doc, cfg, _ = tiny
    mesh = _mesh(ep=4)
    opt = make_optimizer()

    def init():
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        return TrainState(params=params, opt_state=opt.init(params),
                          step=jnp.zeros((), jnp.int32))

    shapes = jax.eval_shape(init)
    sh = state_shardings(cfg, mesh, opt, shapes)
    step = make_train_step(cfg, mesh, opt, sh, remat="save_acts")
    tok = jax.ShapeDtypeStruct((4, 64), jnp.int32)
    lowered = step._jitted.lower(shapes, {"tokens": tok, "targets": tok})
    text = lowered.as_text(debug_info=True)
    scopes = set()
    for name in re.findall(r'loc\("([^"]+)"', text):
        scopes.update(re.findall(r"\w+", name))
    assert (moe.SCOPE_EXCHANGE_OUT, moe.SCOPE_EXCHANGE_BACK) == (
        "moe_exchange_tokens_out", "moe_exchange_results_back")
    assert {"moe_exchange_tokens_out", "moe_exchange_results_back",
            "layer_window", "layer_full", "attn", "norm", "loss",
            "optimizer", "moe_route", "moe_sort", "moe_experts",
            "moe_combine", "transpose", "jvp"} <= scopes
    # three hops of (tokens, choices, gates) and three results back a layer
    # forward, their transposes, and the tokens' walk again in the replay
    permutes = len(re.findall(r"collective_permute", lowered.as_text()))
    assert permutes >= 4 * (9 + 3), permutes
    assert "all_to_all" not in lowered.as_text()


def test_the_counts_are_of_the_configuration(kind, tiny):
    doc, cfg, params = tiny
    leaves = sum(a.size for a in jax.tree.leaves(params))
    assert kind.num_params(doc) == leaves
    cell = kinds.cell_doc(ROW.name)
    per = kind.layer_matrix_params(cell)
    # ISSUE 58's arithmetic: 21.23M of attention, 0.147M of router, 6.19M an
    # expert, 417.8M a layer; 3.06 GFLOP a token in matrices and 0.34 of
    # scores at 8,192 (a full layer's 0.20, a sliding layer's 0.047)
    assert per == {"attention": 21_233_664, "expert": 6_193_152,
                   "router": 147_456}
    assert per["attention"] + per["router"] + 64 * per["expert"] == 417_742_848
    assert kind.num_params(cell) == cell["params"]["whole"]
    flops = kind.train_flops_per_token(cell, 8192)
    matrices = 6.0 * (4 * (21_233_664 + 147_456 + 8 * 6_193_152)
                      + 98304 * 2304)
    assert matrices == pytest.approx(3.06e9, rel=2e-3)
    assert kind.band_mean(cell, 8192) == pytest.approx(1024 - 64 + 0.5 / 8)
    assert flops - matrices == pytest.approx(
        12 * 32 * 128 * (4096 + 3 * kind.band_mean(cell, 8192)))
    assert flops * 8192 == pytest.approx(27.9e12, rel=2e-3)
    # a chip's mean assignments are exactly its tokens' (every expert held)
    assert kind.moe_gmm_train_flops(cell, 8192) == pytest.approx(
        2.0 * per["expert"] * 8192 * 8 * 4 * 4)
    assert kind.moe_ep_exchange_bytes(cell, 8192) == pytest.approx(
        4 * (2 + 1) * 3 * 8192 * (2304 * 2 + 64)
        + 4 * 2 * 3 * 8192 * 2304 * 4)
    assert kind.flash_window_train_flops(cell, 1, 8192) == pytest.approx(
        3 * 7 * 2.0 * 8192 * kind.band_mean(cell, 8192) * 128 * 32)
    assert kind.flash_attention_flops(cell, 1, 8192, True) == pytest.approx(
        7 * 2.0 * 8192 * 8192 * 128 * 32 / 2)


def test_the_cells_first_loss_limit_stands_five_standard_errors_off():
    """The cell's first-step loss against the reference's is the mean over a
    step's tokens of per-token differences that uniform random targets make
    zero-mean and independent: a draw, whose standard error the chips read
    from the harness's own state PER TOKEN (PR 58, third round, calls i and
    j, ``chiprun_out/pr58i`` / ``pr58j``: seeds 1539225420 and 3100000037,
    per-token std below, lag-1 correlation 0.004 / -0.002).  The limit is
    five of them: the driver's draw that refused the first round's 0.004
    (4.858e-3, 1.8 standard errors; the program in full float32 reads
    -2.07e-6 on that seed) and the same seed here (4.950e-3) pass, and a
    sound run in a million does not."""
    tr = kinds.cell_doc(ROW.name)["train"]
    per_token_std = (0.49897873401641846, 0.5006446838378906)
    stderr = max(per_token_std) / math.sqrt(
        tr["global_batch"] * tr["sequence_length"])
    assert stderr == pytest.approx(2.766e-3, rel=1e-3)
    tol = tr["check"]["tol_loss_abs"]
    assert 5.0 * stderr <= tol <= 5.5 * stderr
    assert max(4.858e-3, 4.950e-3) < tol / 2
    assert math.erfc(tol / stderr / math.sqrt(2)) < 1e-6
    # the first round's limit refused one sound run in seven
    assert math.erfc(0.004 / stderr / math.sqrt(2)) == pytest.approx(
        1 / 7, rel=0.05)


def test_a_mesh_the_exchange_has_no_form_for_is_refused(tiny):
    _, cfg, _ = tiny
    mesh = MeshSpec(fsdp=1, tp=2, ep=2).build(jax.devices()[:4]) \
        if len(jax.devices()) >= 4 else pytest.skip("4 devices")
    with pytest.raises(NotImplementedError, match="no tp, sp or pp form"):
        make_train_step(cfg, mesh, make_optimizer(), None)
    three = dataclasses.replace(cfg, num_experts=9)
    with pytest.raises(NotImplementedError, match="a whole number"):
        make_train_step(three, _mesh(ep=4), make_optimizer(), None)
    with pytest.raises(NotImplementedError, match="knows no ep axis"):
        make_train_step(cfg, _mesh(ep=4), make_optimizer(), None,
                        zero_sharded_update=True)
    held = dataclasses.replace(cfg, experts_held=2, expert_start=2)
    with pytest.raises(NotImplementedError, match="no exchange"):
        transformer._dropless_block(
            jnp.zeros((4, 8, cfg.hidden_size)), {}, held,
            transformer.ParallelContext(mesh=_mesh(ep=4),
                                        batch_axes=("dp", "fsdp", "ep")))


@pytest.mark.parametrize("kw,match", [
    (dict(layer_pattern=("linear", "full"), sliding_window=0,
          rope_yarn_kinds=(), rope_yarn_factor=0.0, linear_num_heads=2,
          linear_key_dim=8, linear_value_dim=8), "linear"),
    (dict(norm_on_output=True), "norm_on_output"),
    (dict(mtp_layers=1), "mtp_layers"),
])
def test_the_train_step_still_refuses_what_has_no_backward(tiny, kw, match):
    _, cfg, _ = tiny
    refused = dataclasses.replace(cfg, **kw)
    assert refused.pattern_untrained and not cfg.pattern_untrained
    mesh = MeshSpec(fsdp=-1).build(jax.devices()[:1])
    with pytest.raises(NotImplementedError, match=match) as e:
        make_train_step(refused, mesh, make_optimizer(), None)
    assert "layer_pattern" in str(e.value) and "no backward" in str(e.value)
    with pytest.raises(NotImplementedError, match=match):
        transformer.apply_trunk({}, jnp.zeros((1, 4), jnp.int32), refused)


@pytest.mark.parametrize("kw,match", [
    (dict(moe_router="top1"), "'sigmoid' or 'softmax'"),
    (dict(moe_router="sigmoid"), "moe_balance_weight weighs the softmax"),
    (dict(rope_yarn_kinds=()), "rope_yarn_kinds"),
    (dict(rope_yarn_kinds=("linear",)), "rope_yarn_kinds"),
    (dict(rope_yarn_factor=0.0), "rope_yarn_kinds"),
    (dict(moe_dropless=False, num_experts=1, expert_mlp_size=0,
          mlp_size=64), "belong to moe_dropless"),
])
def test_config_refuses_a_router_or_a_table_it_cannot_wire(tiny, kw, match):
    _, cfg, _ = tiny
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(cfg, **kw)


def test_serving_refuses_yarn_by_kind_and_takes_the_softmax_router(tiny):
    """The serving path's rotary table by kind is plain, so the engine
    refuses ``rope_yarn_kinds``; the softmax router it serves (the decode
    step's expert layer is told the router's kind)."""
    from ray_tpu.serve.llm import LLMEngine
    _, cfg, params = tiny
    with pytest.raises(ValueError, match="rope_yarn_kinds"):
        LLMEngine(cfg, params=params, num_slots=2, max_len=32)
