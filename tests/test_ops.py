"""Kernel-level op tests: Pallas flash attention (interpret mode on the CPU
mesh) and the chunked cross-entropy the train step uses.

Mirrors the reference's kernel-adjacent unit testing style (its C++ gtest
layer, SURVEY §4.1) at the op granularity that matters here: numerics vs the
plain XLA path, forward and backward.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kinds
from ray_tpu.models import transformer, tiny
from ray_tpu.ops.attention import attend
from ray_tpu.ops.flash_attention import flash_attention


def _qkv(B=2, S=128, H=4, KV=2, D=64, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, D), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_forward_matches_plain(causal):
    q, k, v = _qkv()
    ref = attend(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_kv=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_forward_mha_no_gqa():
    q, k, v = _qkv(H=4, KV=4)
    ref = attend(q, k, v)
    out = flash_attention(q, k, v, block_q=64, block_kv=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_gradients_match_plain(causal):
    q, k, v = _qkv(S=64)

    def lf(q, k, v):
        return (flash_attention(q, k, v, causal=causal,
                                block_q=32, block_kv=32) ** 2).sum()

    def lr(q, k, v):
        return (attend(q, k, v, causal=causal) ** 2).sum()

    gf = jax.jit(jax.grad(lf, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(lr, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gf, gr):
        scale = float(jnp.max(jnp.abs(b))) + 1e-9
        assert float(jnp.max(jnp.abs(a - b))) / scale < 1e-4


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("kv_heads", [2, 4])
def test_flash_pallas_backward_matches_plain(causal, kv_heads):
    """Blocks >= 128 take the Pallas dq/dkv kernels (not the scan fallback)."""
    q, k, v = _qkv(S=256, KV=kv_heads)
    gup = jax.random.normal(jax.random.PRNGKey(7), q.shape, q.dtype)

    def lf(q, k, v):
        return (flash_attention(q, k, v, causal=causal,
                                block_q=128, block_kv=128) * gup).sum()

    def lr(q, k, v):
        return (attend(q, k, v, causal=causal) * gup).sum()

    gf = jax.jit(jax.grad(lf, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(lr, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gf, gr):
        scale = float(jnp.max(jnp.abs(b))) + 1e-9
        assert float(jnp.max(jnp.abs(a - b))) / scale < 1e-4


def test_flash_uneven_seq_raises_and_auto_takes_plain(monkeypatch):
    """A shape that does not tile: the kernel asked for by name raises and
    says why; the ``mha`` dispatcher, which chooses, takes the plain path
    (also where the backend is a TPU) and is still correct."""
    from ray_tpu.ops.attention import mha
    from ray_tpu.ops.flash_attention import flash_supported

    q, k, v = _qkv(S=48)
    with pytest.raises(ValueError, match="not a multiple of the blocks"):
        flash_attention(q, k, v, block_q=32, block_kv=32)
    assert flash_supported(1536, 1536, 4, 2) is None
    assert "seq" in flash_supported(1100, 1100, 4, 2)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    long_q = jnp.zeros((1, 1100, 4, 64))    # >= 1024 but no multiple of 512
    long_kv = jnp.zeros((1, 1100, 2, 64))
    out = mha(long_q, long_kv, long_kv)     # would raise if it chose flash
    assert out.shape == long_q.shape
    np.testing.assert_allclose(np.asarray(mha(q, k, v)),
                               np.asarray(attend(q, k, v)), atol=2e-5)


def test_chunked_cross_entropy_matches_full():
    cfg = tiny(vocab=512, layers=2, hidden=64, heads=4, seq=128)
    params = kinds.init(transformer.init_params, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 129), 0, 512)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    whole, chunked = (jax.jit(jax.value_and_grad(
        lambda p: transformer.causal_lm_loss(p, batch, cfg,
                                             loss_chunk=chunk)[0]))
        for chunk in (None, 32))
    (l1, g1), (l2, g2) = whole(params), chunked(params)
    assert abs(float(l1) - float(l2)) < 1e-4

    # bf16 compute: reduction-order differences are ~bf16 eps on O(1) grads
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        assert float(jnp.max(jnp.abs(a - b))) < 6e-3


def test_chunked_cross_entropy_with_mask():
    cfg = tiny(vocab=512, layers=2, hidden=64, heads=4, seq=128)
    params = kinds.init(transformer.init_params, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 129), 0, 512)
    mask = (jax.random.uniform(jax.random.PRNGKey(2), (2, 128)) > 0.3)
    mask = mask.astype(jnp.float32)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:], "loss_mask": mask}
    l1, l2 = (jax.jit(lambda p: transformer.causal_lm_loss(
        p, batch, cfg, loss_chunk=chunk)[0])(params) for chunk in (None, 64))
    assert abs(float(l1) - float(l2)) < 5e-4
