"""SambaY's block on the serving path (Phi-4-mini-flash-reasoning, PR 60): a
stack of three segments (models/config.py ``layer_segments``; models/decode.py
``layer_stack``), the "ssm1", "gmu" and "cross" kinds (models/hybrid.py), the
memory handed down the stack, differential attention over values two heads
wide (ops/decode_attention.py, ops/flash_attention.py) and a prefill whose
cross-decoder walks a prompt's last token alone: a tiny model of 12 layers
(three Mamba / window pairs, the Mamba / full pair, two unit / cross pairs),
hidden 64, a window of 16, seeded random weights, on the CPU.  The
independent side of every comparison is the block kind's plain float32
reference (benchmark/models/phi4flash.py: every layer over every position,
nothing imported from ray_tpu.models or ray_tpu.ops).  The scan's kernels
are tests/test_selective_scan.py's.  Numbers here are about results, never
speed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import contract
import kinds
from ray_tpu.models import decode
from ray_tpu.ops import attention, decode_attention, flash_attention

ROW = kinds.KINDS["phi4flash"]


class TestPhi4Flash(contract.OnlyServed):
    row = ROW


def test_the_stack_is_three_segments_each_scanned_once():
    """One ``lax.scan`` a segment, a period traced once each: three scans
    over layers in a decode step (3, 1 and 2 periods), two in the admit's
    self-decoder and one in its cross-decoder."""
    cfg, params = kinds.tiny(ROW.name)
    assert cfg.segments == ((("ssm1", "window"), 3), (("ssm1", "full"), 1),
                            (("gmu", "cross"), 2))
    assert cfg.cross_segment == 2 and cfg.depths("cross") == (9, 11)
    assert cfg.layer_pattern == sum((seg * n for seg, n in cfg.segments), ())
    cache = decode.init_kv_cache(cfg, 2, 32, jnp.float32)
    step = lambda p, c: decode.decode_step(   # noqa: E731
        p, c, jnp.ones((2,), jnp.int32), jnp.ones((2,), bool), cfg,
        jnp.float32)
    lengths = sorted(e.params["length"] for e in kinds._scans(
        jax.make_jaxpr(step)(params, cache).jaxpr))
    assert lengths == [1, 2, 3]
    # the cache tree: rows for ONE layer, rings for the window layers, the
    # state with channels on the lanes, a tail a Mamba layer; no memory
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (1, 2, 32, 32), "v": (1, 2, 32, 32), "length": (2,),
        "wk": (3, 2, 16, 32), "wv": (3, 2, 16, 32),
        "state": (4, 2, 16, 1, 128), "conv": (4, 2, 3, 128)}
    assert cache["state"].dtype == jnp.float32


def test_a_prefills_last_token_logits_equal_the_whole_stack_over_the_prompt():
    """The admit walks the cross-decoder over a prompt's last token alone;
    the reference walks every layer over every position: the two agree at
    that token, for a row that ends inside its bucket and past the
    window."""
    cfg, params = kinds.tiny(ROW.name)
    toks = np.random.default_rng(5).integers(1, 256, (1, 45)).astype(np.int32)
    for n in (45, 13):
        cache = decode.init_kv_cache(cfg, 2, 64, jnp.float32)
        _, lg = kinds.programs(cfg).prefill(
            params, cache, kinds.padded([toks[0, :n]], 64),
            np.array([n], np.int32), np.array([1], np.int32))
        want = kinds.reference(ROW.name, params, toks[0, :n], n - 1)
        np.testing.assert_allclose(lg, want, atol=2e-4)


def test_the_engine_counts_what_each_decoder_took_and_the_shared_rows():
    """A prompt of 11 and six tokens out: 11 through the self-decoder, its
    last through the cross-decoder, and five steps' live positions (12 ..
    16) each read by the full layer and the two cross layers; a prompt past
    the largest bucket is refused with the reason."""
    cfg, params = kinds.tiny(ROW.name)
    eng = kinds.engine(cfg, params, compute_dtype=jnp.float32,
                       **ROW.engine["kw"])
    before = eng.counters()
    assert len(eng.generate(list(range(1, 12)), max_tokens=6)) == 6
    after = eng.counters()
    grew = {k: after[k] - before[k] for k in (
        "prefill_self_tokens", "prefill_cross_tokens",
        "shared_kv_positions_read", "kv_positions_live")}
    assert grew == {"prefill_self_tokens": 11, "prefill_cross_tokens": 1,
                    "kv_positions_live": sum(range(12, 17)),
                    "shared_kv_positions_read": 3 * sum(range(12, 17))}
    short = kinds.engine(cfg, params, num_slots=2, max_len=64, buckets=(32,))
    with pytest.raises(ValueError, match="past the largest bucket"):
        short.submit(list(range(1, 40)))


# ----------------------- values two heads wide, in every attention kernel

def _plain_wide(q, k, v, seen, scale):
    """q [S, NH, D], k [M, NKV, D], v [M, NKV, D], seen [S, M] -> [S, NH, 2
    D]: head n scores key head n // reps and weighs the values of the pair
    that key head lies in, side by side."""
    nh, nkv = q.shape[1], k.shape[1]
    reps = nh // nkv
    wide = v.reshape(v.shape[0], nkv // 2, -1)
    hi = jax.lax.Precision.HIGHEST
    out = []
    for n in range(nh):
        s = jnp.einsum("sd,md->sm", q[:, n], k[:, n // reps],
                       precision=hi) * scale
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        out.append(jnp.einsum("sm,mc->sc", p, wide[:, n // (2 * reps)],
                              precision=hi))
    return jnp.stack(out, 1)


FORMS = {"twin": dict(use_kernel=False), "kernel": dict(interpret=True)}


@pytest.mark.parametrize("form", list(FORMS))
def test_decode_attn_weighs_a_pairs_two_value_heads(form):
    """40 query heads over 20 K/V heads of 64 (the published heads; rows of
    1,280 lanes) at lengths that are no whole blocks, with an idle slot."""
    slots, nh, nkv, hd, max_len = 3, 40, 20, 64, 96
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (slots, nh, hd))
    k_all, v_all = (jax.random.normal(k, (2, slots, max_len, nkv * hd))
                    for k in ks[1:])
    live = jnp.array([37, 0, 96])
    got = decode_attention.decode_attn(q, k_all, v_all, jnp.int32(1), live,
                                       nkv, wide=2, **FORMS[form])
    assert got.shape == (slots, nh, 2 * hd)
    for s, n in enumerate(live.tolist()):
        if not n:
            assert not np.asarray(got[s]).any()
            continue
        want = _plain_wide(q[s][None], k_all[1, s, :n].reshape(n, nkv, hd),
                           v_all[1, s, :n].reshape(n, nkv, hd),
                           jnp.ones((1, n), bool), hd ** -0.5)[0]
        np.testing.assert_allclose(got[s], want, atol=2e-5)


@pytest.mark.parametrize("form", list(FORMS))
def test_window_decode_attn_weighs_a_pairs_two_value_heads(form):
    """A ring of 32 rows under a window of 20: a slot short of the window, a
    slot that has wrapped, an idle slot."""
    slots, nh, nkv, hd, ring, window = 3, 8, 4, 16, 32, 20
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (slots, nh, hd))
    rows = [jax.random.normal(k, (slots, 70, nkv * hd)) for k in ks[1:]]
    live = jnp.array([50, 0, 7])
    # position p's row is p mod ring: the last ``ring`` positions of each
    at = (jnp.arange(70)[None] < live[:, None])
    k_all, v_all = (jnp.zeros((1, slots, ring, nkv * hd)) for _ in rows)
    for p in range(70):
        put = at[:, p][:, None]
        k_all = k_all.at[0, :, p % ring].set(
            jnp.where(put, rows[0][:, p], k_all[0, :, p % ring]))
        v_all = v_all.at[0, :, p % ring].set(
            jnp.where(put, rows[1][:, p], v_all[0, :, p % ring]))
    got = decode_attention.window_decode_attn(
        q, k_all, v_all, jnp.int32(0), live, nkv, window, wide=2,
        **FORMS[form])
    for s, n in enumerate(live.tolist()):
        if not n:
            assert not np.asarray(got[s]).any()
            continue
        lo = max(n - window, 0)
        want = _plain_wide(q[s][None],
                           rows[0][s, lo:n].reshape(n - lo, nkv, hd),
                           rows[1][s, lo:n].reshape(n - lo, nkv, hd),
                           jnp.ones((1, n - lo), bool), hd ** -0.5)[0]
        np.testing.assert_allclose(got[s], want, atol=2e-5)


@pytest.mark.parametrize("window", [0, 40])
def test_the_flash_forward_reads_value_heads_two_keys_wide(window):
    """The forward kernel (interpreted) and the plain path with half as many
    value heads twice as wide, causal and under a band."""
    s, nh, nkv, hd = 256, 8, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, s, nh, hd))
    k = jax.random.normal(ks[1], (1, s, nkv, hd))
    v = jax.random.normal(ks[2], (1, s, nkv, hd))
    wide = v.reshape(1, s, nkv // 2, 2 * hd)
    pos = jnp.arange(s)
    seen = pos[None] <= pos[:, None]
    if window:
        seen &= pos[:, None] - pos[None] < window
    want = _plain_wide(q[0], k[0], v[0], seen, hd ** -0.5)
    np.testing.assert_allclose(
        attention.attend(q, k, wide, window=window)[0], want, atol=2e-5)
    got = flash_attention.flash_attention(q, k, wide, block_q=128,
                                          block_kv=128, interpret=True,
                                          window=window)
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    with pytest.raises(ValueError, match="whole number of them a value head"):
        flash_attention.flash_attention(q, k, v[:, :, :3], interpret=True)
