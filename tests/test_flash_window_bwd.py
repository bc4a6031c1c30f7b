"""The banded flash attention's backward (PR 58): ``flash_attention(window=
...)`` differentiated, its kernel ``flash_window_bwd`` interpreted on the
CPU, against ``jax.grad`` of plain attention under the band's mask.  Tiny
sizes: numerics and control flow, never speeds."""

import jax
import numpy as np
import pytest

from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops.attention import attend


def _case(s, h, kv, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (2, s, h, d)),
            jax.random.normal(ks[1], (2, s, kv, d)),
            jax.random.normal(ks[2], (2, s, kv, d)),
            jax.random.normal(ks[3], (2, s, h, d)))


@pytest.mark.parametrize("s,h,kv,d,window,block", [
    (512, 8, 1, 64, 200, 128),      # no multiple of a block; GQA 8 to 1
    (384, 4, 2, 64, 128, 128),      # one block exactly: the band ends a block
    (256, 4, 2, 64, 1024, 128),     # a sequence shorter than the window
    (512, 2, 2, 128, 288, 128),     # 1,152 for 1,024's ratio: a fourth block
    (384, 2, 1, 128, 1, 128),       # a query reads itself alone
    (64, 2, 1, 64, 24, 512),        # under 128 positions: the scan's mask
], ids=["gqa8-w200", "w-one-block", "short-sequence", "four-blocks",
        "w1", "blockwise"])
def test_the_bands_backward_is_plain_attentions(s, h, kv, d, window, block):
    q, k, v, w = _case(s, h, kv, d)
    traced = fa.INTERPRET_TRACES.get("flash", 0)

    def kernel(q, k, v):
        return (fa.flash_attention(q, k, v, window=window, block_q=block,
                                   block_kv=block, interpret=True) * w).sum()

    def plain(q, k, v):
        return (attend(q, k, v, window=window) * w).sum()

    with jax.default_matmul_precision("highest"):
        got, g_got = jax.value_and_grad(kernel, (0, 1, 2))(q, k, v)
        want, g_want = jax.value_and_grad(plain, (0, 1, 2))(q, k, v)
    assert fa.INTERPRET_TRACES["flash"] > traced
    # (a sum of thousands of terms of either sign: absolute)
    np.testing.assert_allclose(got, want, atol=1e-3)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5)


def test_the_band_takes_its_own_backward_kernel_and_full_keeps_its_own():
    """By name: a band's gradient runs ``flash_window_bwd`` over the band's
    blocks (3 of 512 for 1,024: ``window_band_blocks``), the full layer's
    ``flash_dkv`` untouched; under 128 positions neither (the scan)."""
    q, k, v, _ = _case(256, 4, 2, 64)
    grad = lambda **kw: str(jax.make_jaxpr(jax.grad(  # noqa: E731
        lambda q, k, v: fa.flash_attention(q, k, v, interpret=True,
                                           block_q=128, block_kv=128,
                                           **kw).sum(), (0, 1, 2)))(q, k, v))
    band, full = grad(window=100), grad()
    assert fa.KERNEL_FLASH_WINDOW_BWD == "flash_window_bwd"
    assert "flash_window_bwd" in band and "flash_dkv" not in band
    assert "flash_window_prefill" in band
    assert "flash_dkv" in full and "flash_window" not in full
    assert fa.window_band_blocks(1024, 512) == 3
    assert fa.window_band_blocks(1152, 512) == 4
    assert fa.window_band_blocks(1, 128) == 1
    assert fa.window_band_blocks(129, 128) == 2


def test_the_band_saves_what_the_replay_keeps():
    """The forward keeps ``attn_out`` and ``attn_lse`` under the names
    ``save_acts`` saves, as the full layer's does."""
    q, k, v, _ = _case(256, 4, 2, 64)
    text = str(jax.make_jaxpr(jax.grad(lambda q: fa.flash_attention(
        q, k, v, window=64, interpret=True).sum()))(q))
    assert "name=attn_out" in text and "name=attn_lse" in text
