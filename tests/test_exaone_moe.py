"""The "window" kind of layer on its ring beside full layers on rows, a dense
layer ahead of a pattern's expert layers, an RMSNorm a head on q and k,
rotary positions by kind, and the multi-token-prediction block that drafts
for a two-token verify step (models/decode.py, models/speculative.py,
ops/decode_attention.py, ops/flash_attention.py): a tiny model of K-EXAONE's
first two periods (window 8, hidden 64, 16 routed experts of which a share of
8 is held), seeded random weights, on the CPU.  The independent side of every
comparison is the block kind's plain float32 reference
(benchmark/models/exaone_moe.py: a whole-sequence banded mask, no cache, no
kernel, nothing imported from ray_tpu.models or ray_tpu.ops), or one program
against itself.  Numbers here are about results, never speed."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import contract
import kinds
from ray_tpu.models import decode, speculative
from ray_tpu.ops import decode_attention as da
from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops.attention import attend

ROW = kinds.KINDS["exaone_moe"]
F32 = jnp.float32


class TestExaoneMoe(contract.OnlyServed, contract.Shares):
    row = ROW


# ----------------------------------------- the kernels against their twins
# At the cell's head sizes: 64 query heads over 8 KV heads of 128, a ring of
# 256 under a window of 128, one token a slot and a verify step's two.

def _rows(layers, slots, span, nkv=8, hd=128, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (layers, slots, span, nkv * hd)),
            jax.random.normal(ks[1], (layers, slots, span, nkv * hd)), ks[2])


@pytest.mark.parametrize("tokens", [1, 2])
def test_ring_kernel_interpreted_equals_its_twin_at_the_cells_heads(tokens):
    """Lengths short of the window, between the window and the ring, past
    several wraps, and an idle slot."""
    k_all, v_all, key = _rows(2, 5, 256)
    q = jax.random.normal(key, (5, tokens * 64, 128))
    live = jnp.array([3, 130, 0, 256, 1000], jnp.int32)
    args = (q, k_all, v_all, jnp.int32(1), live, 8, 128, tokens)
    twin = da.window_decode_attn(*args, use_kernel=False)
    kernel = jax.jit(lambda *a: da.window_decode_attn(
        *a[:5], 8, 128, tokens, interpret=True))(*args[:5])
    np.testing.assert_allclose(kernel, twin, atol=2e-5)
    assert not np.asarray(kernel[2]).any()          # the idle slot: zeros
    assert float(jnp.abs(twin[0]).mean()) > 0.1


def test_ring_twin_reads_each_querys_own_window():
    """By hand: query j of a step of two at position t = length - 2 + j reads
    positions t - window + 1 .. t, each in row position mod ring."""
    ring, window, nkv, hd, nh = 16, 8, 2, 16, 4
    k_all, v_all, key = _rows(1, 3, ring, nkv, hd, seed=1)
    q = jax.random.normal(key, (3, 2 * nh, hd))
    live = np.array([5, 0, 37])
    got = np.asarray(da.window_decode_attn(
        q, k_all, v_all, jnp.int32(0), jnp.asarray(live, jnp.int32), nkv,
        window, 2, use_kernel=False))
    for s in (0, 2):
        for j in range(2):
            t = live[s] - 2 + j
            rows = [p % ring for p in range(max(0, t - window + 1), t + 1)]
            for h in range(nh):
                g = slice(h // 2 * hd, (h // 2 + 1) * hd)
                kk, vv = (np.asarray(a[0, s, rows, g])
                          for a in (k_all, v_all))
                p = np.exp(kk @ np.asarray(q[s, j * nh + h]) * hd ** -0.5)
                np.testing.assert_allclose(got[s, j * nh + h],
                                           p / p.sum() @ vv, atol=1e-5)


@pytest.mark.parametrize("tokens", [1, 2])
def test_rows_kernel_of_several_tokens_equals_its_twin(tokens):
    """``decode_attn`` with a step's tokens as query rows, each masked at
    its own position: blocks of 512, lengths in the first block, across
    blocks and at the end, and an idle slot."""
    k_all, v_all, key = _rows(2, 4, 1024)
    q = jax.random.normal(key, (4, tokens * 64, 128))
    live = jnp.array([2, 700, 0, 1024], jnp.int32)
    twin = da.decode_attn(q, k_all, v_all, jnp.int32(1), live, 8,
                          use_kernel=False, tokens=tokens)
    kernel = jax.jit(lambda q, k, v, n: da.decode_attn(
        q, k, v, jnp.int32(1), n, 8, interpret=True, tokens=tokens))(
            q, k_all, v_all, live)
    np.testing.assert_allclose(kernel, twin, atol=2e-5)
    # the second token of a step reads one position more than the first
    if tokens == 2:
        alone = da.decode_attn(q[:, :64], k_all, v_all, jnp.int32(1),
                               live - 1, 8, use_kernel=False)
        np.testing.assert_allclose(twin[1, :64], alone[1], atol=2e-5)


@pytest.mark.parametrize("seq", [256, 1024])
def test_banded_flash_forward_equals_the_plain_band(seq):
    """The forward kernel with a band of 128 (interpreted, query blocks of
    512, KV blocks of 128) against the plain path's mask, at the cell's
    heads; a band is not the whole causal row."""
    ks = jax.random.split(jax.random.PRNGKey(seq), 3)
    q = jax.random.normal(ks[0], (1, seq, 16, 128))
    k = jax.random.normal(ks[1], (1, seq, 2, 128))
    v = jax.random.normal(ks[2], (1, seq, 2, 128))
    want = attend(q, k, v, causal=True, window=128)
    got = jax.jit(lambda q, k, v: fa.flash_attention(
        q, k, v, window=128, interpret=True))(q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.abs(want - attend(q, k, v, causal=True)).max()) > 0.1


def test_the_band_skips_the_blocks_it_does_not_touch():
    """A 4,096-token row under a band of 128: a query block of 512 loops
    over 5 KV blocks of 128 (4 for the first), not the diagonal's 8 x 4; the
    kind's count is that count."""
    kind, doc = kinds.load("exaone_moe"), kinds.cell_doc("exaone_moe")
    assert kind._band_blocks(doc, 4096) == 4 + 7 * 5
    assert kind._band_blocks(doc, 512) == 4
    assert (fa.WINDOW_BLOCK_KV, 512) == (kind.BAND_BLOCK_KV,
                                         kind.BAND_BLOCK_Q)


# ------------------------------------------- the verify window on the ring

def _prefilled(ring, n=23, slots=3, seed=4):
    """(cfg, params, a cache with one prompt of ``n`` in slot 1, tokens)."""
    cfg, params = kinds.tiny("exaone_moe")
    toks = np.random.default_rng(seed).integers(1, 256, n + 8).astype(
        np.int32)
    cache = decode.init_kv_cache(cfg, slots, 64, F32, ring=ring)
    cache, _ = jax.jit(lambda p, c, t, ln, s: decode.prefill(
        p, c, t, ln, s, cfg, F32))(
            params, cache, kinds.padded([toks[:n]], 32),
            np.array([n], np.int32), np.array([1], np.int32))
    return cfg, params, cache, toks


@functools.lru_cache(maxsize=None)
def _step(cfg):
    """``decode.window_step`` of ``cfg`` under one ``jit`` (a step of one
    token and a step of two are two traces of it)."""
    return jax.jit(lambda p, c, t, a: decode.window_step(p, c, t, a, cfg,
                                                         F32))


def _window(cfg, params, cache, fed, w):
    """A step of ``w`` tokens for slot 1: (cache, logits [w, V])."""
    tokens = np.zeros((cache["length"].shape[0], w), np.int32)
    tokens[1] = fed
    cache, logits = _step(cfg)(params, cache, tokens,
                               np.arange(tokens.shape[0]) == 1)
    return cache, np.asarray(logits[1])


def _slot(cache, slot=1):
    return {n: np.asarray(cache[n][:, slot]) for n in
            decode.RING + ("k", "v")}


def test_a_window_of_two_equals_two_steps_of_one():
    """Over ring and rows, to the tolerance of one program against itself;
    the slot's cache is the same either way."""
    cfg, params, cache, toks = _prefilled(ring=16)
    two, l2 = _window(cfg, params, cache, toks[23:25], 2)
    one, first = _window(cfg, params, cache, toks[23:24], 1)
    one, second = _window(cfg, params, one, toks[24:25], 1)
    np.testing.assert_allclose(l2, np.concatenate([first, second]),
                               rtol=1e-5, atol=1e-5)
    assert two["length"].tolist() == one["length"].tolist() == [0, 25, 0]
    for name, rows in _slot(two).items():
        np.testing.assert_allclose(rows, _slot(one)[name],
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("ring,sound", [(9, True), (8, False)],
                         ids=["window-plus-one", "exactly-the-window"])
def test_a_rolled_back_draft_leaves_no_trace(ring, sound):
    """A verify step of [token, draft] whose draft is rejected is rolled
    back by resetting ``length``; what the slot then decodes equals never
    having written the draft: on a ring of ``window + 1``.  On a ring of
    exactly the window the draft's row has replaced position ``t - 7``,
    which the steps after still read: the control, which has to differ."""
    cfg, params, cache, toks = _prefilled(ring=ring)
    clean, first = _window(cfg, params, cache, toks[23:24], 1)
    drafted, both = _window(cfg, params, cache, [toks[23], 200], 2)
    rolled = dict(drafted, length=clean["length"])
    assert rolled["length"].tolist() == [0, 24, 0]
    if sound:       # the kept token's logits never saw the draft
        np.testing.assert_allclose(both[:1], first, atol=1e-5)
    gaps = []
    for fed in toks[24:30]:
        clean, want = _window(cfg, params, clean, [fed], 1)
        rolled, got = _window(cfg, params, rolled, [fed], 1)
        gaps.append(float(np.abs(got - want).max()))
    if sound:
        assert max(gaps) < 1e-5, gaps
        for name, rows in _slot(rolled).items():
            np.testing.assert_allclose(rows, _slot(clean)[name], atol=1e-5)
    else:
        assert max(gaps) > 1e-2, gaps


def test_ring_len_has_the_windows_margin():
    cfg = kinds.cell_cfg("exaone_moe")
    assert (decode.ring_len(cfg, 1), decode.ring_len(cfg, 2)) == (128, 256)
    tiny, _ = kinds.tiny("exaone_moe")
    assert (decode.ring_len(tiny, 1), decode.ring_len(tiny, 2)) == (16, 16)


# -------------------------------------------- the block against the reference

def test_the_blocks_logits_equal_the_references():
    """The prefill's pass of the block leaves the draft the reference's
    block logits choose, and the block's rows; then the kind's decode step
    (a verify step of two with the next token forced, the draft rolled
    back, the block's pass of two) keeps the model's logits the
    reference's and the block's logits the reference's block's."""
    kind, doc = kinds.load("exaone_moe"), kinds.doc("exaone_moe")
    cfg, params = kinds.tiny("exaone_moe")
    n, steps = 23, 20
    toks = np.random.default_rng(7).integers(1, 256, n + steps).astype(
        np.int32)
    cache = kind.init_cache(cfg, 2, 64, F32)
    assert cache["wk"].shape[2] == 16
    cache, lg = jax.jit(lambda p, c, t, ln, s: decode.prefill(
        p, c, t, ln, s, cfg, F32))(
            params, cache, kinds.padded([toks[:n]], 32),
            np.array([n], np.int32), np.array([1], np.int32))
    first = int(np.argmax(lg[0]))
    seq = np.concatenate([toks[:n], [first]]).astype(np.int32)
    block = np.asarray(jax.jit(lambda p, t: kind.mtp_logits(p, t, doc))(
        params, seq))
    assert block.std() > 0.5
    assert int(cache["draft"][1]) == int(block[n - 1].argmax())
    # the model's logits with the next token forced (the harness's
    # comparison): the block then pairs a hidden state with a token the row
    # did not take, so its own rows are not the reference's here
    want = kinds.reference("exaone_moe", params, toks, n - 1)
    step = jax.jit(lambda p, c, t, a: kind.decode_step(p, c, t, a, cfg, F32))
    after = cache
    for s in range(steps):
        fed = np.zeros(2, np.int32)
        fed[1] = toks[n + s]
        cache, lg = step(params, cache, fed, np.array([False, True]))
        np.testing.assert_allclose(lg[1], want[s + 1], atol=2e-4)
    # the row fed its own greedy tokens, as the engine feeds it: the draft
    # a round leaves is the block on (h_t, the model's greedy token) over
    # rows 0 .. t of its own, the reference's block's choice on that row
    seq, cache2 = seq.tolist(), after
    for s in range(6):
        fed = np.zeros(2, np.int32)
        fed[1] = seq[-1]
        cache2, lg = step(params, cache2, fed, np.array([False, True]))
        seq.append(int(np.argmax(lg[1])))
        block = np.asarray(jax.jit(lambda p, t: kind.mtp_logits(
            p, t, doc))(params, np.asarray(seq, np.int32)))
        assert int(cache2["draft"][1]) == int(block[-1].argmax()), s
    assert cache["length"].tolist() == [0, n + steps]


# ------------------------------------------- the seeded weights' routing

def test_the_routers_load_is_level_on_rows_the_balancing_never_saw():
    """``balanced`` sets every expert layer's router (its scores' spread)
    and selection bias from many seeded rows at once.  On rows it has not
    seen every expert's load stays near the mean; the draw it started from
    (bias zero) loads them by the weights' accident.  ``sharpened`` is what
    ``init_params`` hands it: every q norm's scale at 4."""
    from ray_tpu.models import transformer
    kind = kinds.load("exaone_moe")
    cfg, params = kinds.tiny("exaone_moe")
    doc = kind._doc_of(cfg)
    fresh = jax.random.randint(       # rows like the sample's, half as long
        jax.random.PRNGKey(11),
        (kind.BALANCE_ROWS, kind.BALANCE_TOKENS // 2), 1, cfg.vocab_size)

    def spread(p):
        """Relative load of the most and the least loaded expert, worst
        layer, over the fresh rows' tokens."""
        said = jax.jit(jax.vmap(lambda t: jnp.stack(
            kind._walk(p, t, doc)[1])))(fresh)        # [rows, L, S, k]
        said = said[:, :, kind.BALANCE_FROM:]
        loads = [np.bincount(np.asarray(said[:, layer]).reshape(-1),
                             minlength=cfg.num_experts)
                 for layer in range(said.shape[1])]
        return (max(ld.max() / ld.mean() for ld in loads),
                min(ld.min() / ld.mean() for ld in loads))

    hi, lo = spread(params)
    assert hi < 1.35 and lo > 0.7, (hi, lo)
    drawn = kind.sharpened(kinds.init(transformer.init_params, cfg, F32, 3))
    for name in ("window", "full"):
        scale = drawn["blocks"][name]["attn"]["q_norm"]["scale"]
        assert float(scale.min()) == float(scale.max()) == 4.0
    assert float(drawn["mtp"]["blocks"]["full"]["attn"]["q_norm"]["scale"]
                 .mean()) == 4.0
    assert float(drawn["blocks"]["full"]["attn"]["k_norm"]["scale"]
                 .mean()) == 1.0
    hi0, lo0 = spread(drawn)
    assert hi0 > 1.4 and lo0 < 0.7, (hi0, lo0)


# ------------------------------------- the engine: draft on equals draft off

def _agreeing(cfg, params, scale=1e-4):
    """A target damped to near-identity (its sublayers' output norms scaled
    down: the residual stream stays the embedding) and a block that passes
    the next token's normed embedding on: the block's logits are then nearly
    the model's own for that token, and some drafts are right."""
    damped = speculative.damp_block_outputs(params, scale, output_norms=True)
    block = speculative.damp_block_outputs(params["mtp"], scale,
                                           output_norms=True)
    h = cfg.hidden_size
    block = dict(block, proj=jnp.concatenate(
        [jnp.eye(h), jnp.zeros((h, h))]).astype(params["mtp"]["proj"].dtype))
    return dict(damped, mtp=block)


@pytest.mark.parametrize("weights", ["random", "agreeing"])
def test_engine_emits_the_same_tokens_draft_on_or_off(weights):
    """Greedy requests through ``LLMEngine`` with the block drafting (a
    verify window of two, fixed) and with plain decode: the tokens are the
    model's own either way.  Random weights reject every draft; the agreeing
    weights accept some, and the counters say so."""
    cfg, params = kinds.tiny("exaone_moe")
    if weights == "agreeing":
        params = _agreeing(cfg, params)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in (5, 23, 40)]
    outs, stats = {}, {}
    for spec in (False, True):
        eng = kinds.engine(cfg, params, num_slots=3, max_len=128,
                           buckets=(32, 64), compute_dtype=F32,
                           steps_per_dispatch=4, spec_decode_enabled=spec,
                           spec_adaptive=False)
        reqs = [eng.submit(p, max_tokens=30) for p in prompts]
        outs[spec] = []
        for r in reqs:
            out = []
            while isinstance(item := r.out.get(timeout=300), int):
                out.append(item)
            assert not isinstance(item, BaseException), item
            outs[spec].append(out)
        stats[spec] = eng.counters()
    assert outs[True] == outs[False]
    assert [len(o) for o in outs[True]] == [30, 30, 30]
    on = stats[True]
    assert "spec_rounds" not in stats[False]
    assert on["spec_drafted"] == on["spec_rounds"] > 0
    assert on["spec_rolled_back_rows"] == (on["spec_drafted"]
                                           - on["spec_accepted"])
    if weights == "random":
        assert on["spec_accepted"] == 0
    else:
        assert 0 < on["spec_accepted"] < on["spec_drafted"]
        assert on["spec_rounds"] < 3 * 29
    # the experts' counts ride the speculative dispatch too (the block's
    # expert layer among the layers a round runs)
    assert on["moe_assignments"] > 0 and on["moe_experts_touched"] > 0
    assert on["moe_expert_layer_steps"] % (cfg.expert_layers + 1) == 0


def test_the_engine_refuses_a_cut_out_draft_under_a_pattern():
    """Speculation under a pattern is the model's own block's; a pattern
    without one, or with a recurrent kind, is refused with the reason."""
    import dataclasses
    from ray_tpu.serve.llm import LLMEngine
    cfg, params = kinds.tiny("exaone_moe")
    with pytest.raises(ValueError, match="multi-token-prediction"):
        LLMEngine(dataclasses.replace(cfg, mtp_layers=0), params=params,
                  num_slots=2, max_len=32, spec_decode_enabled=True)


# ---------------------------------- one walk over the layers, whatever the MLP

def test_the_dense_layer_and_the_experts_lie_by_layer():
    """``params["blocks"]``: the attention by kind [periods, layers a
    period, ...], the MLPs by layer (one dense, seven expert layers); one
    scan over the periods after the first, which is walked ahead of it."""
    cfg, params = kinds.tiny("exaone_moe")
    blocks = params["blocks"]
    assert blocks["window"]["attn"]["wq"].shape[:2] == (2, 3)
    assert blocks["full"]["attn"]["wq"].shape[:2] == (2, 1)
    assert blocks["window"]["attn"]["q_norm"]["scale"].shape == (2, 3, 16)
    assert blocks["dense"]["w_in"].shape == (1, 64, 96)
    assert blocks["moe"]["router"].shape == (7, 64, 16)
    assert blocks["experts"]["w_in"].shape == (7, 8, 64, 32)
    assert "moe" not in blocks["window"] and "mlp" not in blocks["window"]
    cache = decode.init_kv_cache(cfg, 2, 32, F32)
    step = lambda p, c: decode.decode_step(  # noqa: E731
        p, c, jnp.ones((2,), jnp.int32), jnp.ones((2,), bool), cfg, F32)
    scans = [e for e in kinds._scans(jax.make_jaxpr(step)(params, cache).jaxpr)
             if e.params["length"] == 1]
    assert len(scans) == 1
