"""Chip-speed plane exactness gates (ISSUE 20).

Three knobs — ``attention_impl="splash"``, ``grad_quant_enabled``,
``zero_sharded_update`` — each pinned on CPU before any TPU window sees
them:

* splash interpret-mode output/grad parity vs ``ops/flash_attention`` on
  GQA + causal shapes (the shapes the 1B bench runs),
* int8 block-scaled quantized reduce: error inside the declared
  analytical bound, bitwise deterministic, stochastic rounding unbiased
  in expectation,
* ZeRO-sharded update allclose to the replicated update over 10 steps
  (same seed, fp32) — AdamW is elementwise, so sharding the update must
  not change the math,
* an explicit ``attention_impl`` that cannot run raises (no degrading), and
  interpret mode is taken only on the CPU backend, visibly.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import config as mcfg  # noqa: E402
from ray_tpu.parallel import (OptimizerSpec, init_sharded_state,  # noqa: E402
                              init_zero_state, make_mesh, make_train_step)
from ray_tpu.parallel.quant_collectives import (  # noqa: E402
    dequantize_int8_block, quantize_int8_block)



def _qkv(b=2, s=256, h=4, kv=2, d=128, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, s, h, d), jnp.float32),
            jax.random.normal(ks[1], (b, s, kv, d), jnp.float32),
            jax.random.normal(ks[2], (b, s, kv, d), jnp.float32))


# ------------------------------------------------------------- splash parity

@pytest.mark.parametrize("causal", [True, False])
def test_splash_interpret_parity_with_flash(causal):
    """Forward + all three grads match ops/flash_attention on a GQA shape
    (head_dim=128, the kernel's minimum lane tile)."""
    from ray_tpu.ops.flash_attention import flash_attention
    from ray_tpu.ops.splash_attention import splash_mha

    q, k, v = _qkv()
    ref = flash_attention(q, k, v, causal=causal)
    out = splash_mha(q, k, v, causal=causal)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-4

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=causal) ** 2)

    g_ref = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(loss(splash_mha), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_ref, g_out):
        err = float(jnp.max(jnp.abs(a - b)))
        scale = float(jnp.max(jnp.abs(a))) + 1e-6
        assert err / scale < 1e-3, (name, err, scale)


def test_splash_through_model_and_fallback_warning():
    """attention_impl="splash" matches the default impl through the full
    model (logits-level) in interpret mode, which the CPU backend takes and
    records; an explicit impl that cannot run the shape (head_dim 16)
    raises and names the reason — it never degrades to another path."""
    from ray_tpu.models import transformer
    from ray_tpu.ops.flash_attention import INTERPRET_TRACES

    base = mcfg.TransformerConfig(
        vocab_size=128, num_layers=2, hidden_size=512, num_heads=4,
        num_kv_heads=2, mlp_size=256, max_seq_len=128)
    splash_cfg = mcfg.TransformerConfig(
        **{**base.__dict__, "attention_impl": "splash"})
    params = transformer.init_params(jax.random.PRNGKey(0), base,
                                     dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 128)
    ref = transformer.apply(params, toks, base, compute_dtype=jnp.float32)[0]
    before = INTERPRET_TRACES.get("splash", 0)
    out = transformer.apply(params, toks, splash_cfg,
                            compute_dtype=jnp.float32)[0]
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-3
    assert INTERPRET_TRACES["splash"] > before  # the CPU run said so

    t2 = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 256)
    for impl, why in (("splash", "head_dim=16"), ("flash", "softcap")):
        extra = {"attn_logit_softcap": 30.0} if impl == "flash" else {}
        cfg = mcfg.TransformerConfig(
            **{**mcfg.tiny().__dict__, "attention_impl": impl, **extra})
        p2 = transformer.init_params(jax.random.PRNGKey(0), cfg,
                                     dtype=jnp.float32)
        with pytest.raises(ValueError, match=why):
            transformer.apply(p2, t2, cfg, compute_dtype=jnp.float32)


def test_interpret_mode_is_never_taken_on_a_tpu(monkeypatch):
    """The kernels pick interpret mode by themselves only on the CPU
    backend; where the backend says "tpu" an interpreted run is refused
    and the automatic choice is the compiled kernel."""
    from ray_tpu.ops import flash_attention as fa

    assert fa.resolve_interpret(None, "flash") is True      # CPU here
    assert fa.resolve_interpret(False, "flash") is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert fa.resolve_interpret(None, "flash") is False
    with pytest.raises(ValueError, match="interpret=True on a TPU"):
        fa.resolve_interpret(True, "splash")
    q, k, v = _qkv(s=192)   # 192 is no multiple of the 128-wide tiles
    with pytest.raises(ValueError, match="seq"):
        fa.flash_attention(q[:, :, :, :64], k[:, :, :, :64], v[:, :, :, :64],
                           block_q=128, block_kv=128)


# --------------------------------------------------------------- quant reduce

def test_quantize_roundtrip_error_bound_and_determinism():
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 4096), jnp.float32) * 10
    q, scale = quantize_int8_block(x, block=256)
    q2, scale2 = quantize_int8_block(x, block=256)
    assert jnp.array_equal(q, q2) and jnp.array_equal(scale, scale2)
    back = dequantize_int8_block(q, scale, block=256)
    # per-block bound: |err| <= scale/2 = amax/254 elementwise
    amax = jnp.max(jnp.abs(x.reshape(4, 16, 256)), -1, keepdims=True)
    bound = jnp.broadcast_to(amax / 254.0 + 1e-7, (4, 16, 256)).reshape(4, 4096)
    assert bool(jnp.all(jnp.abs(back - x) <= bound))
    # all-zero blocks dequantize exactly
    z = jnp.zeros((512,), jnp.float32)
    qz, sz = quantize_int8_block(z, block=256)
    assert bool(jnp.all(dequantize_int8_block(qz, sz, 256) == 0.0))


def test_stochastic_rounding_unbiased():
    """E[dequant(quant_stochastic(x))] -> x: the mean over many keys lands
    far inside the deterministic half-step bound."""
    x = jnp.full((256,), 0.3, jnp.float32)  # worst case: mid-step value
    _, scale = quantize_int8_block(x, block=256)
    step = float(scale[0])
    acc = jnp.zeros_like(x)
    n = 64
    for i in range(n):
        q, s = quantize_int8_block(x, block=256, stochastic=True,
                                   key=jax.random.PRNGKey(i))
        acc = acc + dequantize_int8_block(q, s, 256)
    bias = float(jnp.max(jnp.abs(acc / n - x)))
    assert bias < step / 4, (bias, step)


def test_quantized_psum_scatter_bounded_and_deterministic():
    """The wire collective inside a real dp=4 shard_map: result within the
    declared bound of the exact fp32 reduce-scatter, chunk placement
    identical to lax.psum_scatter, and bitwise repeatable."""
    mesh = make_mesh(4, dp=4, fsdp=1)
    dp, n = 4, 4096
    x = jax.random.normal(jax.random.PRNGKey(7), (dp, n), jnp.float32)

    def body(xs):
        from ray_tpu.parallel.quant_collectives import quantized_psum_scatter
        flat = xs.reshape(-1)
        exact = jax.lax.psum_scatter(flat, "dp", scatter_dimension=0,
                                     tiled=True)
        quant = quantized_psum_scatter(flat, "dp", dp, block=256)
        return exact[None], quant[None]

    from jax.sharding import PartitionSpec as P
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=P(("dp",), None),
                       out_specs=(P(("dp",), None), P(("dp",), None)),
                       check_vma=False)
    exact1, quant1 = fn(x)
    _, quant2 = fn(x)
    assert jnp.array_equal(quant1, quant2)
    # bound: dp ranks each contribute <= amax/254 per element
    amax = float(jnp.max(jnp.abs(x)))
    bound = dp * amax / 254.0 + 1e-6
    assert float(jnp.max(jnp.abs(quant1 - exact1))) <= bound


# ------------------------------------------------------------------ ZeRO step

def _run_arm(cfg, mesh, spec, steps=10, batch=8, **knobs):
    opt = spec.build()
    if knobs.get("zero_sharded_update"):
        state, sh = init_zero_state(cfg, mesh, spec)
    else:
        state, sh = init_sharded_state(cfg, mesh, opt)
    step = make_train_step(cfg, mesh, opt, sh, compute_dtype=jnp.float32,
                           opt_spec=spec, **knobs)
    rng = np.random.RandomState(0)
    losses = []
    for _ in range(steps):
        b = {"tokens": rng.randint(0, cfg.vocab_size,
                                   (batch, cfg.max_seq_len + 1))}
        state, m = step(state, b)
        losses.append(float(m["total_loss"]))
    return state, losses, m, step


def test_zero_sharded_update_allclose_replicated():
    """The acceptance gate: 10 fp32 steps, same seed/batches — the
    ZeRO-sharded arm's params and losses match the replicated arm."""
    cfg = mcfg.tiny()
    mesh = make_mesh(4, dp=4, fsdp=1)
    spec = OptimizerSpec(total_steps=50, warmup_steps=5)
    s_ref, l_ref, m_ref, _ = _run_arm(cfg, mesh, spec)
    s_zero, l_zero, m_zero, step = _run_arm(cfg, mesh, spec,
                                            zero_sharded_update=True)
    np.testing.assert_allclose(l_zero, l_ref, rtol=1e-5, atol=1e-5)
    for (pa, a), (pb, bv) in zip(
            jax.tree_util.tree_leaves_with_path(s_ref.params),
            jax.tree_util.tree_leaves_with_path(s_zero.params)):
        assert str(pa) == str(pb)
        np.testing.assert_allclose(np.asarray(bv), np.asarray(a),
                                   rtol=2e-5, atol=2e-6, err_msg=str(pa))
    # the dp-manual step reports the same global metrics as the auto step
    assert float(m_zero["tokens"]) == float(m_ref["tokens"])
    assert abs(float(m_zero["grad_norm"]) - float(m_ref["grad_norm"])) < 1e-4
    # ZeRO shards the resident Adam state ~dp x
    rep_bytes = 2 * 4 * sum(
        int(np.prod(l.shape)) for l in jax.tree.leaves(s_ref.params))
    assert step.opt_state_bytes < rep_bytes / 2


def test_grad_quant_arm_tracks_and_is_deterministic():
    """int8 gradient wire: losses stay within quantization distance of the
    fp32 arm over 6 steps, reruns are bitwise identical, and the wire
    accounting moves the payload to int8."""
    cfg = mcfg.tiny()
    mesh = make_mesh(4, dp=4, fsdp=1)
    spec = OptimizerSpec(total_steps=50, warmup_steps=5)
    _, l_ref, _, st_ref = _run_arm(cfg, mesh, spec, steps=6)
    s_q1, l_q1, _, st_q = _run_arm(cfg, mesh, spec, steps=6,
                                   grad_quant_enabled=True)
    s_q2, l_q2, _, _ = _run_arm(cfg, mesh, spec, steps=6,
                                grad_quant_enabled=True)
    assert l_q1 == l_q2
    for a, b in zip(jax.tree.leaves(s_q1.params),
                    jax.tree.leaves(s_q2.params)):
        assert jnp.array_equal(a, b)
    np.testing.assert_allclose(l_q1, l_ref, rtol=5e-3, atol=5e-3)
    wire_q = sum(v for (op, dt), v in st_q.collective_bytes.items()
                 if dt == "int8")
    wire_f = sum(v for (op, dt), v in st_q.collective_bytes.items()
                 if dt == "float32")
    wire_ref = sum(st_ref.collective_bytes.values())
    assert wire_q > 0 and (wire_q + wire_f) < wire_ref / 3


def test_quant_plus_zero_composes():
    """Both knobs on: still trains (losses finite, tracking the fp32 arm)
    with the params all-gather kept lossless fp32."""
    cfg = mcfg.tiny()
    mesh = make_mesh(4, dp=4, fsdp=1)
    spec = OptimizerSpec(total_steps=50, warmup_steps=5)
    _, l_ref, _, _ = _run_arm(cfg, mesh, spec, steps=5)
    _, l_both, _, step = _run_arm(cfg, mesh, spec, steps=5,
                                  grad_quant_enabled=True,
                                  zero_sharded_update=True,
                                  quant_stochastic=True)
    assert all(np.isfinite(l_both))
    np.testing.assert_allclose(l_both, l_ref, rtol=1e-2, atol=1e-2)
    assert ("all_gather", "float32") in step.collective_bytes
    assert ("reduce_scatter", "int8") in step.collective_bytes
