"""The flash kernels at two head widths (PR 45): queries and keys ``d_qk``
lanes wide, values, the output and its gradient ``d_v``, as a latent head's
192 / 128 (here 24 / 16 at tiny sizes; Pallas in interpret mode on the CPU).

Every case holds one path to the plain ``attend``: the forward kernel, the
scan fallback of the backward (``_bwd_blockwise``, blocks under 128) and the
Pallas backward kernel (``_flash_bwd_pallas``, blocks of 128), causal
and not, one kv head under all query heads and a kv head a query head, at
one width (the parent's cases, ``tests/test_ops.py``) beside two.  Then the
dispatcher and ``models/latent.py``'s expanded attention, result and
gradient, against the block kind's float32 reference.

A file of its own, after ``tests/test_ops.py`` and ``tests/test_latent.py``
in the order the suite is handed to its workers, so that those two keep the
seconds they took: CHANGES.md, PR 45, says why.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kinds
from ray_tpu.models import latent
from ray_tpu.ops.attention import attend, mha
from ray_tpu.ops.flash_attention import flash_attention


#: (query and key width, value width): one width, and a latent head's two
WIDTHS = pytest.mark.parametrize("widths", [(64, 64), (24, 16)],
                                 ids=["64", "24-16"])
CAUSAL = pytest.mark.parametrize("causal", [True, False])
#: of 4 query heads: all under one kv head, and a kv head each
KV_HEADS = pytest.mark.parametrize("kv_heads", [1, 4])


def _qkv(widths, B=2, S=128, H=4, KV=2):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return (jax.random.normal(ks[0], (B, S, H, widths[0])),
            jax.random.normal(ks[1], (B, S, KV, widths[0])),
            jax.random.normal(ks[2], (B, S, KV, widths[1])))


def _assert_same_gradients(causal, operands, **blocks):
    """Of ``sum(out * g)`` for a random ``g``, by q, k and v."""
    q, _, v = operands
    g = jax.random.normal(jax.random.PRNGKey(7), q.shape[:3] + v.shape[3:])

    def through(fn):
        return jax.jit(jax.grad(lambda *a: (fn(*a) * g).sum(),
                                argnums=(0, 1, 2)))(*operands)

    got = through(lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                                  **blocks))
    want = through(lambda q, k, v: attend(q, k, v, causal=causal))
    for a, b in zip(got, want):
        assert a.shape == b.shape
        assert float(jnp.abs(a - b).max()) < 1e-4 * float(jnp.abs(b).max())


@WIDTHS
@CAUSAL
@KV_HEADS
def test_flash_forward_matches_plain(causal, kv_heads, widths):
    q, k, v = _qkv(widths, KV=kv_heads)
    ref = attend(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_kv=32)
    assert out.shape == ref.shape == q.shape[:3] + (widths[1],)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@WIDTHS
@CAUSAL
@KV_HEADS
def test_flash_scan_backward_matches_plain(causal, kv_heads, widths):
    """Blocks under 128 take the scan fallback (``_bwd_blockwise``)."""
    _assert_same_gradients(causal, _qkv(widths, S=64, KV=kv_heads),
                           block_q=32, block_kv=32)


@WIDTHS
@CAUSAL
@KV_HEADS
def test_flash_pallas_backward_matches_plain(causal, kv_heads, widths):
    """Blocks of 128 take the Pallas backward kernel."""
    _assert_same_gradients(causal, _qkv(widths, B=1, S=256, KV=kv_heads),
                           block_q=128, block_kv=128)


def test_flash_refuses_keys_that_fit_neither_side():
    q, k, v = _qkv((24, 16))
    with pytest.raises(ValueError, match="not as wide as queries"):
        flash_attention(q, v, v, block_q=32, block_kv=32)
    with pytest.raises(ValueError, match="not as many as values"):
        flash_attention(q, k, v[:, :64], block_q=32, block_kv=32)


@pytest.mark.parametrize("use_flash", [None, True], ids=["chosen", "flash"])
def test_mha_takes_a_latent_layers_two_widths(use_flash):
    """Keys of 192 and values of 128, a kv head a query head, as
    ``models/latent.py`` hands them over: what the dispatcher chooses on the
    CPU (the plain path) and the kernel asked for by name (interpreted) both
    give ``attend``'s result, 128 wide."""
    q, k, v = _qkv((192, 128), B=1, H=2, KV=2)
    out = mha(q, k, v, use_flash=use_flash)
    assert out.shape == (1, 128, 2, 128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(attend(q, k, v)),
                               atol=2e-5)


# ------------------------------------------- the latent kind's expanded form

@pytest.fixture(scope="module")
def tiny_latent():
    """(the block kind, its tiny configuration file, the program's
    configuration, one attention layer's float32 parameters): 4 heads of
    16 + 8 lanes for queries and keys and 16 for values."""
    kind, doc = kinds.load("xing4_0"), kinds.doc("xing4_0")
    cfg, params = kinds.tiny("xing4_0")
    return kind, doc, cfg, jax.tree.map(lambda a: a[0],
                                        params["prefix"]["attn"])


@pytest.mark.parametrize("seq,use_flash", [(48, None), (256, True)],
                         ids=["plain", "flash-kernels"])
def test_expanded_attention_and_its_gradient_are_the_references(
        tiny_latent, monkeypatch, seq, use_flash):
    """``latent.attention``, which hands its heads over at their own two
    widths, against the kind's float32 reference: the result and the
    gradient by the input and by every matrix of the layer, through the
    plain path the dispatcher takes on the CPU, and through the flash
    kernels interpreted, the forward and the backward kernel (256 positions
    are two blocks of 128)."""
    from ray_tpu.ops import attention as ops_attention

    kind, doc, cfg, ap = tiny_latent
    x = jax.random.normal(jax.random.PRNGKey(5), (2, seq, cfg.hidden_size))
    weights = jax.random.normal(jax.random.PRNGKey(6), x.shape)
    handed = []
    if use_flash:
        def kernels(q, k, v, causal=True):
            handed.append((q.shape[-1], k.shape[-1], v.shape[-1]))
            return flash_attention(q, k, v, causal=causal, block_q=128,
                                   block_kv=128)

        monkeypatch.setattr(ops_attention, "mha", kernels)

    def program(x, ap):
        return (latent.attention(x, ap, cfg, jnp.arange(seq)[None])
                * weights).sum()

    def reference(x, ap):
        return (jax.vmap(lambda s: kind._attention(s, ap, doc))(x)
                * weights).sum()

    with jax.default_matmul_precision("highest"):
        got, got_grads = jax.jit(jax.value_and_grad(
            program, argnums=(0, 1)))(x, ap)
        want, want_grads = jax.jit(jax.value_and_grad(
            reference, argnums=(0, 1)))(x, ap)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for a, b in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=2e-3,
                                   atol=2e-4 * float(jnp.abs(b).max()))
    assert set(handed) == ({(24, 24, 16)} if use_flash else set())
