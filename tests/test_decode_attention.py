"""Decode attention over the stacked cache (``ops/decode_attention.py``): the
kernel, interpreted, against its twin and against a per-slot softmax written
out in numpy, at both head arrangements the cells run (32 query heads over 8
KV heads, 30 over 30), lengths at the blocks' edges, inactive slots and the
logit softcap.  The chip's compiler sees the kernel in
``tests/test_chip_compile_kernels.py``; here the arithmetic and the index
maps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import decode_attention as da

HD, LAYERS, LAYER, MAX_LEN, BLOCK = 16, 3, 1, 64, 16
HEADS = [pytest.param(32, 8, id="32q-8kv"),
         pytest.param(30, 30, id="30q-30kv")]


@pytest.fixture(autouse=True)
def four_blocks_a_slot(monkeypatch):
    monkeypatch.setattr(da, "BLOCK_LEN", BLOCK)
    assert da.block_len(MAX_LEN, 8 * HD * 2) == BLOCK


def _inputs(nh, nkv, slots, seed=0, dtype=jnp.bfloat16):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (LAYERS, slots, MAX_LEN, nkv * HD)
    return (jax.random.normal(kq, (slots, nh, HD), dtype),
            jax.random.normal(kk, shape, dtype),
            jax.random.normal(kv, shape, dtype))


def _plain(q, k_all, v_all, layer, live, nkv, softcap):
    """Softmax attention, one slot and one head at a time, in float64."""
    q, k, v = (np.asarray(a, np.float64) for a in
               (q, k_all[layer], v_all[layer]))
    slots, nh, hd = q.shape
    out = np.zeros((slots, nh, hd))
    for s in range(slots):
        n = int(live[s])
        for h in range(nh if n else 0):
            g = h // (nh // nkv)
            scores = k[s, :n, g * hd:(g + 1) * hd] @ q[s, h] * hd ** -0.5
            if softcap:
                scores = softcap * np.tanh(scores / softcap)
            p = np.exp(scores - scores.max())
            out[s, h] = (p / p.sum()) @ v[s, :n, g * hd:(g + 1) * hd]
    return out


def _f32(a):
    return np.asarray(a, np.float32)


@pytest.mark.parametrize("nh,nkv", HEADS)
@pytest.mark.parametrize("length", [0, BLOCK - 1, BLOCK, MAX_LEN - 1],
                         ids=lambda n: f"len{n}")
@pytest.mark.parametrize("case", ["all-active", "some-inactive", "softcap"])
def test_kernel_equals_twin_and_plain_attention(nh, nkv, length, case):
    """``length`` positions cached in slot 1 before the token's own, so
    ``length + 1`` count; the other slots hold other lengths."""
    slots = 4
    q, k_all, v_all = _inputs(nh, nkv, slots)
    lengths = np.array([37, length, 5, MAX_LEN - 1])
    active = np.array([True, True, case != "some-inactive",
                       case != "some-inactive"])
    live = jnp.asarray(np.where(active, lengths + 1, 0), jnp.int32)
    softcap = 5.0 if case == "softcap" else 0.0
    args = (q, k_all, v_all, jnp.int32(LAYER), live, nkv, softcap)
    kernel = _f32(da.decode_attn(*args, interpret=True))
    twin = _f32(da.decode_attn(*args, use_kernel=False))
    plain = _plain(q, k_all, v_all, LAYER, live, nkv, softcap)
    # bf16 operands and a bf16 result: three digits
    np.testing.assert_allclose(kernel, twin, atol=1e-2)
    np.testing.assert_allclose(kernel, plain, atol=1e-2)
    np.testing.assert_allclose(twin, plain, atol=1e-2)
    assert not kernel[~active].any() and not twin[~active].any()


@pytest.mark.parametrize("nh,nkv", HEADS)
@pytest.mark.parametrize("path", ["kernel", "twin"])
def test_a_call_for_one_layer_reads_no_other(nh, nkv, path):
    q, k_all, v_all = _inputs(nh, nkv, 3, dtype=jnp.float32)
    live = jnp.asarray([9, 40, 64], jnp.int32)
    other = jnp.arange(LAYERS)[:, None, None, None] != LAYER
    poisoned = (jnp.where(other, jnp.nan, k_all),
                jnp.where(other, jnp.nan, v_all))
    kw = dict(interpret=True) if path == "kernel" else dict(use_kernel=False)
    clean = da.decode_attn(q, k_all, v_all, jnp.int32(LAYER), live, nkv, **kw)
    out = da.decode_attn(q, *poisoned, jnp.int32(LAYER), live, nkv, **kw)
    assert np.isfinite(_f32(out)).all()
    np.testing.assert_array_equal(_f32(out), _f32(clean))


@pytest.mark.parametrize("nh,nkv", HEADS)
def test_nothing_past_a_slots_length_is_read(nh, nkv):
    """Rows at or past a slot's live length, and every row of an inactive
    slot, may hold anything: the kernel fetches none of the blocks that
    hold only such rows and masks the rest of the last live block."""
    q, k_all, v_all = _inputs(nh, nkv, 4, dtype=jnp.float32)
    live = jnp.asarray([0, 17, 0, 33], jnp.int32)
    dead = jnp.arange(MAX_LEN)[None, None, :, None] >= live[None, :, None,
                                                           None]
    out = da.decode_attn(q, jnp.where(dead, jnp.nan, k_all),
                         jnp.where(dead, 0.0, v_all), jnp.int32(LAYER), live,
                         nkv, interpret=True)
    ref = _plain(q, k_all, v_all, LAYER, live, nkv, 0.0)
    np.testing.assert_allclose(_f32(out), ref, atol=1e-4)


def test_no_slot_active_gives_zeros():
    q, k_all, v_all = _inputs(32, 8, 3)
    out = da.decode_attn(q, k_all, v_all, jnp.int32(LAYER),
                         jnp.zeros(3, jnp.int32), 8, interpret=True)
    assert not _f32(out).any()


def test_the_plan_lists_the_live_blocks_and_nothing_else():
    live = jnp.asarray([0, 0, 17, 0, 64, 0, 1], jnp.int32)
    slot_of, block_of, total = (
        np.asarray(a) for a in da._plan(live, BLOCK, MAX_LEN // BLOCK))
    assert total == 7
    items = list(zip(slot_of.tolist(), block_of.tolist()))
    assert len(items) == 7 * (MAX_LEN // BLOCK)
    assert items[:7] == [(2, 0), (2, 1), (4, 0), (4, 1), (4, 2), (4, 3),
                         (6, 0)]
    # past the list nothing new is named (the grid ends with the list)
    assert set(items[7:]) == {(6, 0)}
    # nothing live at all: one block named, none counted
    slot_of, block_of, total = (
        np.asarray(a) for a in da._plan(jnp.zeros(3, jnp.int32), BLOCK, 4))
    assert total == 0 and set(zip(slot_of.tolist(), block_of.tolist())) == {
        (2, 0)}


def test_block_length_follows_the_row_width(monkeypatch):
    assert da.block_len(40, 32 * 2) == 40             # no divisor: one block
    monkeypatch.undo()                                # the module's BLOCK_LEN
    assert da.block_len(2048, 1024 * 2) == 512        # 8 KV heads of 128
    assert da.block_len(4096, 3840 * 2) == 256        # 30 KV heads of 128
    assert da.block_len(1536, 1024 * 2) == 512
    assert da.block_len(64, 32 * 2) == 64


def test_an_inactive_slots_stale_length_changes_no_active_slots_output():
    """Through ``decode.decode_step``: a retired slot keeps its length."""
    import kinds
    from ray_tpu.models import config as mcfg
    from ray_tpu.models import decode, transformer

    cfg = mcfg.tiny()
    params, run = kinds.init(transformer.init_params, cfg), kinds.programs(cfg)
    cache = decode.init_kv_cache(cfg, 3, 32, dtype=jnp.float32)
    toks = jnp.asarray([[5, 6, 7, 8, 9, 10], [11, 12, 13, 0, 0, 0],
                        [1, 2, 3, 4, 5, 6]], jnp.int32)
    cache, _ = run.prefill(params, cache, toks, jnp.asarray([6, 3, 6]),
                           jnp.arange(3))
    active = jnp.asarray([True, True, False])
    step = jnp.asarray([3, 4, 5], jnp.int32)
    _, logits = run.step(params, cache, step, active)
    stale = dict(cache, length=cache["length"].at[2].set(31))
    _, logits_stale = run.step(params, stale, step, active)
    np.testing.assert_array_equal(np.asarray(logits[:2]),
                                  np.asarray(logits_stale[:2]))
