"""K-EXAONE's kernels and its verify window on the ring, apart from the
kind's contract and its block (tests/test_exaone_moe.py) so that a
``--dist loadfile`` run can give the two halves to two workers: the
interpreted ring and rows kernels against their twins at the cell's heads,
the banded flash forward against the plain band, a window of two against two
steps of one, and the rolled-back draft (ops/decode_attention.py,
ops/flash_attention.py, models/decode.py).  The tiny model is the process's
one (``kinds.tiny``).  Numbers here are about results, never speed."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kinds
from ray_tpu.models import decode
from ray_tpu.ops import decode_attention as da
from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops.attention import attend

F32 = jnp.float32

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
