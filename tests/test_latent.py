"""Latent attention, dropless expert layers behind a dense prefix and
hyper-connected residual streams on the serving path (models/latent.py,
ops/decode_attention.py ``mla_decode_attn``, ops/moe.py): a tiny model of 1
dense + 2 expert layers, hidden 128, 4 heads of 16 + 8 / 16, 8 experts top
2 and a shared one, 4 streams, seeded random weights, on the CPU.  The
independent side of every comparison is the block kind's plain float32
reference (benchmark/models/xing4_0.py: expanded attention over the whole
sequence, every expert on every token, no cache, nothing imported from
ray_tpu.models or ray_tpu.ops).  Numbers here are about results, never
speed."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import contract
import kinds
from ray_tpu.models import decode, latent, transformer
from ray_tpu.models.config import TransformerConfig
from ray_tpu.ops import decode_attention as da

ROW = kinds.KINDS["xing4_0"]


class TestXing40(contract.OnlyServed):
    row = ROW


def _prefill(cfg, params, cache, toks, slot, pad=0, **kw):
    return kinds.programs(cfg, **kw).prefill(
        params, cache, np.pad(toks, (0, pad))[None],
        np.array([len(toks)], np.int32), np.array([slot], np.int32))


# ------------------------------------------------ (a) the decode kernel

@pytest.mark.parametrize("max_len,block", [(128, 32), (64, 64)])
def test_latent_kernel_interpreted_equals_its_twin(monkeypatch, max_len,
                                                   block):
    """Several blocks a slot and one; an idle slot, one position, a full
    row; a layer in the middle of the stack."""
    monkeypatch.setattr(da, "BLOCK_LEN", block)
    ks = jax.random.split(jax.random.PRNGKey(max_len), 4)
    layers, slots, c, r, nh = 3, 5, 32, 8, 4
    rows = jax.random.normal(ks[0], (layers, slots, max_len, c))
    keys = jax.random.normal(ks[1], (layers, slots, r, max_len))
    q_lat = jax.random.normal(ks[2], (slots, nh, c))
    q_rope = jax.random.normal(ks[3], (slots, nh, r))
    live = jnp.array([0, 1, 17, max_len, 33])
    twin = da.mla_decode_attn(q_lat, q_rope, rows, keys, jnp.int32(1), live,
                              0.2, use_kernel=False)
    kernel = da.mla_decode_attn(q_lat, q_rope, rows, keys, jnp.int32(1),
                                live, 0.2, interpret=True)
    np.testing.assert_allclose(kernel, twin, atol=2e-6)
    assert not np.asarray(twin[0]).any()        # nothing live: zeros
    # one live position: the output is that position's latent row
    np.testing.assert_allclose(twin[1], jnp.broadcast_to(
        rows[1, 1, 0], (nh, c)), atol=1e-6)


# --------------------------- (b) prefill then decode against the reference

def test_the_parity_runs_rows_counts_and_choices(tiny):
    """What the contract's parity run (the expanded form over a prompt, then
    the absorbed form a token at a time) left in the cache."""
    cfg, params = tiny
    (toks,), _, cache = kinds.parity_run(ROW.name)
    n, steps = ROW.parity["lens"][0], ROW.parity["steps"]
    # an idle slot writes at its own stale length and nowhere else (as K
    # and V do: its length does not move, so the row is never read); the
    # counts are the live slot's
    assert not np.asarray(cache["latent"][:, 0, 1:]).any()
    assert not np.asarray(cache["rope_key"][:, 2, :, 1:]).any()
    assert cache["moe_counts"].tolist() == [
        steps * cfg.experts_per_token * cfg.expert_layers] * 2
    # every token's experts by the expanded form and by the absorbed form
    # alike: a prefill of the whole sequence chose the same
    whole, _ = _prefill(cfg, params, decode.init_kv_cache(
        cfg, 3, 64, jnp.float32, expert_choices=True), toks, slot=1)
    np.testing.assert_array_equal(
        np.asarray(cache[decode.CHOICES])[:, 1, :n + steps],
        whole[decode.CHOICES][:, 1, :n + steps])


@pytest.mark.parametrize("case", ["tie", "far", "nothing"])
def test_the_reference_follows_a_tie_break_and_nothing_more(kind, tiny_doc,
                                                            case):
    """The reference's router told another's choice: its k-th expert swapped
    for its next one, which scores within ``FOLLOW_MARGIN`` of it, is
    followed (either set is the equations' answer up to rounding); swapped
    for its worst one, it is not; -1 (nothing was routed) is not.  The gates
    are the router's own scores of whatever set it takes."""
    k, e = tiny_doc["num_experts_per_tok"], tiny_doc["n_routed_experts"]
    x = jax.random.normal(jax.random.PRNGKey(5),
                          (200, tiny_doc["hidden_size"]))
    router = jax.random.normal(jax.random.PRNGKey(6),
                               (tiny_doc["hidden_size"], e)) * 0.02
    bias = jnp.zeros((e,))
    own, gates, short = kind.route(x, router, bias, tiny_doc)
    assert not np.asarray(short).any()
    scores = jax.nn.sigmoid(x @ router)
    order = jnp.argsort(-scores, axis=-1)
    swap = {"tie": order[:, k], "far": order[:, -1],
            "nothing": jnp.full((200,), -1)}[case]
    told = own.at[:, -1].set(swap)
    idx, gates_told, short = kind.route(x, router, bias, tiny_doc, follow=told)
    gap = np.asarray(jnp.take_along_axis(scores, order[:, k - 1:k + 1], -1))
    if case == "tie":
        taken = gap[:, 0] - gap[:, 1] <= kind.FOLLOW_MARGIN
        assert taken.any()
        np.testing.assert_allclose(short, gap[:, 0] - gap[:, 1], atol=1e-6)
    else:
        taken = np.zeros(200, bool)
        assert (np.asarray(short) > kind.FOLLOW_MARGIN).all()
    np.testing.assert_array_equal(idx, np.where(taken[:, None], told, own))
    picked = jnp.take_along_axis(scores, idx, -1)
    np.testing.assert_allclose(
        gates_told, picked / picked.sum(-1, keepdims=True)
        * tiny_doc["routed_scaling_factor"], rtol=1e-6)


def _route_bf16(x, router_w, bias, k, scaling):
    """The lower-precision control of the configuration's ``gate_in_float32``:
    ``ops.moe.route_sigmoid`` with scores, top k and gates in bf16."""
    bf = jnp.bfloat16
    scores = jax.nn.sigmoid(x.astype(bf) @ router_w.astype(bf))
    _, idx = jax.lax.top_k(scores + bias.astype(bf), k)
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    gates = gates / gates.sum(-1, keepdims=True) * jnp.asarray(scaling, bf)
    return idx.astype(jnp.int32), gates.astype(jnp.float32)


@pytest.mark.parametrize("router", ["bf16", "wrong_at_a_few"])
def test_a_router_that_is_not_float32_is_not_followed(kind, tiny_doc,
                                                      monkeypatch, router):
    """The reference asks the program's router about its own input and
    follows the program's choice only where the answer is its own float32
    set.  A router wrong at a few tokens is refused at those, whatever it
    was told, and followed at the rest as before; one that scores in bf16
    ties or swaps at more than ``1 - ROUTER_TRUSTED`` of the tokens, and is
    followed nowhere.  The program's own router passes at every token."""
    from ray_tpu.ops import moe
    k, e = tiny_doc["num_experts_per_tok"], tiny_doc["n_routed_experts"]
    x = jax.random.normal(jax.random.PRNGKey(7),
                          (2000, tiny_doc["hidden_size"]))
    router_w = jax.random.normal(jax.random.PRNGKey(8),
                                 (tiny_doc["hidden_size"], e)) * 0.02
    bias = jnp.zeros((e,))
    own, _, _ = kind.route(x, router_w, bias, tiny_doc)
    scores = jax.nn.sigmoid(x @ router_w)
    order = jnp.argsort(-scores, axis=-1)
    told = own.at[:, -1].set(order[:, k])           # the tie-break, everywhere
    _, _, sound = kind.route(x, router_w, bias, tiny_doc, follow=told)
    assert np.isfinite(np.asarray(sound)).all()

    def wrong_at_a_few(x, router_w, bias, k, scaling):
        idx, gates = moe_route(x, router_w, bias, k, scaling)
        return idx.at[:5, -1].set(order[:5, -1].astype(idx.dtype)), gates

    moe_route = moe.route_sigmoid
    patched = {"bf16": _route_bf16, "wrong_at_a_few": wrong_at_a_few}[router]
    asked, _ = patched(x, router_w, bias, k, tiny_doc["routed_scaling_factor"])
    low = jnp.take_along_axis(scores, asked, -1).min(-1)
    inexact = np.asarray(jnp.take_along_axis(
        scores, order[:, k - 1:k], -1)[:, 0] - low > kind.ROUTER_EXACT)
    monkeypatch.setattr(moe, "route_sigmoid", patched)
    idx, _, short = kind.route(x, router_w, bias, tiny_doc, follow=told)
    if router == "bf16":
        assert 1 - kind.ROUTER_TRUSTED < inexact.mean() < 0.5
        inexact = np.ones_like(inexact)
    else:
        assert inexact.sum() == 5
    assert np.isinf(np.asarray(short)[inexact]).all()
    np.testing.assert_array_equal(np.asarray(idx)[inexact],
                                  np.asarray(own)[inexact])
    np.testing.assert_array_equal(np.asarray(short)[~inexact],
                                  np.asarray(sound)[~inexact])


def test_the_followed_reference_is_the_plain_one_where_the_sets_agree(
        kind, tiny_doc, tiny):
    """``logits`` by default runs the program as the harness's comparison
    does (a prefill, then decode steps) and follows its routers' choices:
    at float32 parameters and tiny sizes nearly every set is the
    reference's own, and where all of a sequence's are, the two references
    are one; told nothing (-1 everywhere), they always are."""
    cfg, params = tiny
    n, steps = 17, 4
    toks = jnp.asarray(np.random.default_rng(8).integers(
        1, cfg.vocab_size, n + steps), jnp.int32)
    at = jnp.arange(n - 1, n + steps)
    # (eager, as its twin at the end is: the two are held equal to the bit,
    # which two programs compiled apart need not be)
    plain = kind.logits(params, toks, tiny_doc, at, follow=None)
    chosen = jax.jit(lambda p, t: kind.program_choices(p, t, tiny_doc, n))(
        params, toks)
    assert chosen.shape == (cfg.expert_layers, n + steps,
                            cfg.experts_per_token)
    assert int(chosen.min()) >= 0
    _, short = jax.jit(lambda p, t, c: kind.hidden_states(p, t, tiny_doc, c))(
        params, toks, chosen)
    followed = jax.jit(lambda p, t: kind.logits(p, t, tiny_doc, at))(
        params, toks)
    if not np.asarray(short).any():
        np.testing.assert_allclose(followed, plain, atol=1e-5)
    assert np.abs(np.asarray(followed - plain)).max() < 0.5
    np.testing.assert_array_equal(
        kind.logits(params, toks, tiny_doc, at,
                    follow=jnp.full_like(chosen, -1)), plain)


def test_absorbed_form_equals_expanded_form(tiny):
    """A decode step through the cache (the query carried into the latent
    space) against a prefill of the same tokens (keys and values rebuilt
    per head): the same logits up to float32 rounding."""
    cfg, params = tiny
    toks = np.random.default_rng(2).integers(1, cfg.vocab_size,
                                             21).astype(np.int32)
    cache = decode.init_kv_cache(cfg, 2, 32, jnp.float32)
    _, expanded = _prefill(cfg, params, cache, toks, slot=0)
    # (padded to the whole prompt's shape: one program for both prefills)
    cache, _ = _prefill(cfg, params, cache, toks[:-1], slot=0, pad=1)
    _, absorbed = kinds.programs(cfg).step(
        params, cache, np.array([toks[-1], 0], np.int32),
        np.array([True, False]))
    np.testing.assert_allclose(absorbed[0], expanded[0], atol=5e-5)


# ------------- (c') a row walked in counted chunks (PR 47): the expanded
# form over what the slot holds so far, a chunk as long as the experts ask

BUCKET, SHORTEST, TILE = 32, 4, 2      # 8 experts, 2 a token: a chunk of 8


def _chunked_prefill(cfg, params, cache, toks, slot):
    """``_prefill`` at the bucket of 32 with a row walked in chunks of 8."""
    from unittest import mock
    with mock.patch.object(decode, "EXPERT_TILE", TILE):
        assert decode.prefill_width(cache, BUCKET, cfg, SHORTEST) == 8
        return _prefill(cfg, params, cache, toks, slot, BUCKET - len(toks),
                        chunk=SHORTEST)


# prompts that end inside the first chunk, a middle one and the last
@pytest.mark.parametrize("n", [5, 19, 29])
def test_chunks_then_decode_equal_the_reference(kind, tiny_doc, tiny, n):
    """What the chunks leave in the slot is what the whole row leaves
    (latent rows, rotary keys, the length, every token's experts), and the
    absorbed form continues it a token at a time to the reference's
    logits."""
    cfg, params = tiny
    steps = 3
    toks = np.random.default_rng(10 + n).integers(
        1, cfg.vocab_size, n + steps).astype(np.int32)
    ref = kinds.reference(ROW.name, params, toks, n - 1, follow=None)
    empty = decode.init_kv_cache(cfg, 3, 64, jnp.float32,
                                 expert_choices=True)
    whole, lg_whole = _prefill(cfg, params, empty, toks[:n], slot=1,
                               pad=BUCKET - n)
    cache, lg = _chunked_prefill(cfg, params, empty, toks[:n], slot=1)
    np.testing.assert_allclose(lg, lg_whole, atol=2e-5)
    np.testing.assert_allclose(cache["latent"][:, 1, :n],
                               whole["latent"][:, 1, :n], atol=1e-5)
    np.testing.assert_allclose(cache["rope_key"][:, 1, :, :n],
                               whole["rope_key"][:, 1, :, :n], atol=1e-5)
    assert cache["length"].tolist() == whole["length"].tolist() == [0, n, 0]
    np.testing.assert_array_equal(cache[decode.CHOICES][:, 1, :n],
                                  whole[decode.CHOICES][:, 1, :n])
    # no expert for a padded position, no row past the last chunk walked
    assert (np.asarray(cache[decode.CHOICES])[:, 1, n:] == -1).all()
    assert not np.asarray(cache["latent"][:, 1, -(-n // 8) * 8:]).any()
    assert not np.asarray(cache["latent"][:, [0, 2]]).any()
    got = [np.asarray(lg)[0]]
    step = kinds.programs(cfg).step
    for i in range(steps):
        fed = np.zeros(3, np.int32)
        fed[1] = toks[n + i]
        cache, lg = step(params, cache, fed, np.array([False, True, False]))
        got.append(np.asarray(lg)[1])
    np.testing.assert_allclose(np.stack(got), ref, atol=2e-4)


@pytest.mark.parametrize("row", [100, 384], ids=["one-chunk", "three-chunks"])
def test_the_continued_mixer_is_the_whole_rows_at_published_head_sizes(
        tiny, monkeypatch, row):
    """One layer's ``continued_attention`` a chunk of 128 at a time, its
    kernel interpreted at a latent head's 128 + 64 / 128 (keys of 192 and
    values of 128 as they are, a head's rows apart), against
    ``prefill_attention`` over the whole row: the same attention and the
    same cached rows, and nothing written past the chunks walked."""
    import functools
    from ray_tpu.ops import flash_attention as fa
    cfg = dataclasses.replace(tiny[0], num_heads=2, num_kv_heads=2,
                              qk_nope_head_dim=128, qk_rope_head_dim=64,
                              v_head_dim=128)
    ap = jax.tree.map(lambda a: a[0], latent._init_group(
        jax.random.PRNGKey(5), cfg, 1, False, jnp.float32)["attn"])
    w, span, layers, slots = 128, 512, 2, 3
    y = jax.random.normal(jax.random.PRNGKey(row), (1, span, cfg.hidden_size))
    stacks = (jnp.zeros((layers, slots, span, cfg.kv_lora_rank)),
              jnp.zeros((layers, slots, cfg.qk_rope_head_dim, span)))
    want, *held = latent.prefill_attention(
        y, ap, cfg, *stacks, 1, 2, jnp.arange(span)[None])
    monkeypatch.setattr(fa, "flash_attention_rows", functools.partial(
        fa.flash_attention_rows, interpret=True))
    before = fa.INTERPRET_TRACES.get("flash", 0)
    chunk = jax.jit(lambda y, lat, rope, at: latent.continued_attention(
        y, ap, cfg, lat, rope, 1, 2, at, span))
    got, chunks = [], -(-row // w)
    for c in range(chunks):
        out, *stacks = chunk(y[:, c * w:(c + 1) * w], *stacks,
                             jnp.int32(c * w))
        got.append(out)
    assert fa.INTERPRET_TRACES["flash"] == before + 1     # one program
    np.testing.assert_allclose(jnp.concatenate(got, axis=1)[:, :row],
                               want[:, :row], atol=2e-5)
    walked = chunks * w
    np.testing.assert_allclose(stacks[0][:, :, :walked],
                               held[0][:, :, :walked], atol=1e-6)
    np.testing.assert_allclose(stacks[1][..., :walked],
                               held[1][..., :walked], atol=1e-6)
    assert not np.asarray(stacks[0][:, :, walked:]).any()
    assert not np.asarray(stacks[1][..., walked:]).any()


@pytest.mark.parametrize("kw", [
    dict(moe_dropless=True, num_experts=8, experts_per_token=2,
         expert_mlp_size=64, shared_experts=1, routed_scaling_factor=2.0,
         dense_prefix_layers=1, hc_mult=2),
    dict(hc_mult=3),
    dict(moe_dropless=True, num_experts=4, experts_per_token=2,
         expert_mlp_size=32),
], ids=["gqa-prefix-experts-streams", "gqa-streams", "gqa-experts"])
def test_the_mechanisms_combine_with_kv_rows(kw):
    """Each mechanism is its own switch: over plain K/V rows (grouped-query
    attention) a decode step continues a prefill as under latent rows, with
    a dense prefix's rows stacked before the expert layers'."""
    cfg = TransformerConfig(
        vocab_size=256, num_layers=3, hidden_size=64, num_heads=4,
        num_kv_heads=2, mlp_size=128, max_seq_len=64, norm_eps=1e-6, **kw)
    params = kinds.init(transformer.init_params, cfg)
    cache = decode.init_kv_cache(cfg, 2, 32, jnp.float32)
    toks = np.random.default_rng(0).integers(1, 256, 20).astype(np.int32)
    _, whole = _prefill(cfg, params, cache, toks, slot=0)
    # (padded to the whole prompt's shape: one program for both prefills)
    cache, _ = _prefill(cfg, params, cache, toks[:-1], slot=0, pad=1)
    _, step = kinds.programs(cfg).step(
        params, cache, np.array([toks[-1], 0], np.int32),
        np.array([True, False]))
    np.testing.assert_allclose(step[0], whole[0], atol=2e-5)


def test_yarn_frequencies_and_scale_are_the_references(kind):
    """At the published sizes, against the kind's own arithmetic."""
    doc, cfg = kinds.cell_doc(ROW.name), kinds.cell_cfg(ROW.name)
    np.testing.assert_allclose(latent.rope_inv_freq(cfg),
                               kind.yarn_inv_freq(doc), rtol=1e-6)
    assert latent.rope_magnitude(cfg) == 1.0
    assert latent.softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)
    assert latent.softmax_scale(cfg) == pytest.approx(
        kind.attention_scale(doc))
    # the slowest dimensions are divided by the factor, the fastest kept
    plain = 10000.0 ** -(np.arange(0, 64, 2) / 64)
    freq = latent.rope_inv_freq(cfg)
    assert freq[0] == pytest.approx(plain[0])
    assert freq[-1] == pytest.approx(plain[-1] / 64)


# ------------------------------------------------ (c) hyper-connections

def test_h_res_is_doubly_stochastic(tiny):
    cfg, params = tiny
    x = jax.random.normal(jax.random.PRNGKey(0),
                          (2, 9, cfg.hc_mult, cfg.hidden_size)) * 3.0
    hp = jax.tree.map(lambda a: a[0], params["blocks"]["hc_attn"])
    pre, post, res = latent.hc_coeff(x, hp, cfg)
    np.testing.assert_allclose(res.sum(-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-5)
    assert float(res.min()) > 0 and res.dtype == jnp.float32
    assert float(pre.min()) > 0 and float(pre.max()) < 1
    assert float(post.min()) > 0 and float(post.max()) < 2
    # the coefficients are the token's own: they differ from row to row
    assert float(jnp.std(res[..., 0, 0])) > 1e-3


def test_sinkhorn_holds_at_the_clamp():
    logits = jnp.array([[30.0, -30.0, 0.0], [-30.0, 30.0, 5.0],
                        [1.0, 2.0, -30.0]])
    m = latent.sinkhorn(logits, 20, 1e-6)
    assert bool(jnp.isfinite(m).all())
    np.testing.assert_allclose(m.sum(-1), 1.0, atol=1e-4)


def test_one_stream_would_be_the_plain_residual(tiny):
    """``hc_write`` with the identity mix and unit gains is ``x + f``."""
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 3, 4, 8))
    out = jax.random.normal(jax.random.PRNGKey(2), (1, 3, 8))
    ones = jnp.ones((1, 3, 4))
    eye = jnp.broadcast_to(jnp.eye(4), (1, 3, 4, 4))
    np.testing.assert_allclose(latent.hc_write(x, out, ones, eye),
                               x + out[:, :, None], atol=1e-6)
    np.testing.assert_allclose(latent.hc_read(x, ones), x.sum(2), atol=1e-6)


# ------------------------------------------------------ (d) the engine

@pytest.fixture(scope="module")
def engine(tiny):
    """The contract's engine, started once a process."""
    return kinds.engine(*tiny, compute_dtype=jnp.float32,
                        **ROW.engine["kw"])


def test_the_engine_counts_what_the_experts_did(tiny, engine):
    """Cumulative counters from the two sums that ride each dispatch's
    tokens: assignments are live tokens x experts a token x expert layers,
    and no step touches more experts than it has."""
    from ray_tpu.serve.llm import _FLUSH
    cfg, _ = tiny
    c0 = engine.counters()
    outs = [engine.submit(list(range(1, 9 + i)), max_tokens=7)
            for i in range(3)]
    for r in outs:
        while r.out.get() is not _FLUSH:
            pass
    while engine._unfetched:        # the dispatches still in flight
        time.sleep(0.01)
    c1 = engine.counters()
    d = {k: c1[k] - c0[k] for k in c1 if k.startswith("moe_")}
    per = cfg.experts_per_token * cfg.expert_layers
    assert d["moe_assignments"] == 3 * 6 * per      # 6 decoded tokens each
    assert d["moe_assignments_prefill"] == (8 + 9 + 10) * per
    assert 0 < d["moe_experts_touched"] <= d["moe_assignments"]
    assert d["moe_experts_touched"] <= (d["moe_expert_layer_steps"]
                                        * cfg.num_experts)
    assert d["moe_expert_layer_steps"] >= 6 * cfg.expert_layers
    g = engine.breakdown()
    assert g["cache_latent_bytes"] == (
        cfg.num_layers * 4 * 64 * cfg.latent_row * 4)
    assert (g["cache_kv_bytes"], g["cache_state_bytes"]) == (0, 0)
    assert (g["experts_held"], g["expert_layers"]) == (8, 2)
    counted = engine.counters()
    assert 0 < counted["kv_positions_live"] <= counted["kv_positions_read"]


def test_a_dense_engine_has_no_expert_counters():
    from ray_tpu.models import config as mcfg
    from ray_tpu.serve.llm import LLMEngine
    eng = LLMEngine(mcfg.tiny(), num_slots=2, max_len=32, buckets=(16,))
    try:
        assert not [k for k in eng.counters() if k.startswith("moe_")]
        g = eng.breakdown()
    finally:
        eng.shutdown()
    assert (g["cache_latent_bytes"], g["experts_held"],
            g["expert_layers"]) == (0, 0, 0)


# --------------------------------------------------- (e) the refusals

def test_a_window_of_several_tokens_is_refused(tiny):
    cfg, params = tiny
    cache = decode.init_kv_cache(cfg, 2, 32, jnp.float32)
    with pytest.raises(ValueError, match="one token a step"):
        decode.window_step(params, cache, jnp.zeros((2, 3), jnp.int32),
                           jnp.ones((2,), bool), cfg, jnp.float32)


def test_config_names_what_is_only_served(tiny):
    cfg, _ = tiny
    # the tree and the cache of models/latent.py; of these only the
    # residual streams have no train block (PR 39)
    assert cfg.latent_tree == ("kv_lora_rank", "moe_dropless",
                               "dense_prefix_layers", "hc_mult")
    assert cfg.served_only == ("hc_mult",)
    assert TransformerConfig.__dataclass_fields__["hc_mult"].default == 0
    with pytest.raises(AttributeError, match="qk_head_dim"):
        cfg.head_dim
    with pytest.raises(NotImplementedError, match="served, not trained"):
        cfg.flops_per_token()


# ------------------------------------------------------- (f) the counts

def test_counts_of_the_l7_configurations_layers_and_kernels(kind):
    """(The tree to the parameter: the contract's.)  The program's tree, the
    kind's counts and ISSUE 35's arithmetic agree: 5.54B parameters, an
    expert layer of 745M of which 40M lie beside the experts (the catalog's
    figure), 8,064 B a token."""
    doc, cfg = kinds.cell_doc(ROW.name), kinds.cell_cfg(ROW.name)
    n = kind.num_params(doc)
    assert round(n / 1e9, 2) == 5.54
    assert abs(cfg.num_params() - n) < 1e-4 * n      # matrices alone
    per = kind.layer_matrix_params(doc)
    beside = per["attention"] + per["shared"] + per["router"] + per["hc"]
    assert round(beside / 1e6) == 40
    assert round((beside + 64 * per["expert"]) / 1e6) == 745
    assert round(per["attention"] / 1e6, 1) == 28.4
    assert kind.kv_bytes_per_token(doc) == 7 * 1152 == 8064
    cache = jax.eval_shape(lambda: kind.init_cache(cfg, 33, 8192,
                                                   jnp.bfloat16))
    assert sum(int(np.prod(a.shape)) * 2 for k, a in cache.items()
               if k in decode.LATENT) == 33 * 8192 * 8064
    # a step reads the experts its tokens reach, never all 64 where fewer
    # can be touched
    assert round(kind.experts_touched(doc, 32), 1) == 55.9
    assert round(kind.experts_touched(doc, 26), 1) == 52.0
    step = kind.decode_step_bytes(doc, 26, 26 * 4600)
    whole = kind.decode_step_bytes(doc, 1e9, 26 * 4600)
    assert 9.3e9 < step < 9.7e9 and whole - step > 6 * 11 * per["expert"]
    assert kind.moe_gmm_flops(doc, 128) == 128 * 6 * 3584 * 1024
    assert kind.moe_gmm_bytes(doc, 0, 56) == 56 * per["expert"] * 2
    assert kind.mla_decode_attn_bytes(doc, 1000) == 1000 * 8064
    assert kind.mla_decode_attn_flops(doc, 1) == 7 * 32 * 2 * (576 + 512)
