"""Pipeline parallelism tests on the virtual 8-device CPU mesh: GPipe
microbatching must be numerically equivalent to the plain layer scan
(SURVEY §2.3 PP row — no reference analogue; greenfield)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import tiny, transformer
from ray_tpu.parallel import (MeshSpec, init_pp_state, init_sharded_state,
                              make_mesh, make_optimizer, make_pp_train_step,
                              make_train_step, merge_layers, partition_layers)
from ray_tpu.parallel.pipeline import pipeline_loss_fn


def _cfg():
    return tiny(vocab=128, layers=4, hidden=32, heads=4, seq=32)


def test_partition_merge_roundtrip():
    cfg = _cfg()
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    staged = partition_layers(params, 2)
    assert staged["blocks"]["attn"]["wq"].shape[0] == 2
    merged = merge_layers(staged)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(merged)):
        np.testing.assert_array_equal(a, b)


def test_pipeline_loss_matches_plain():
    """pp=2 pipeline loss == single-device loss on identical f32 params."""
    cfg = _cfg()
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}

    ref_loss, _ = transformer.causal_lm_loss(params, batch, cfg,
                                             compute_dtype=jnp.float32,
                                             loss_chunk=None)

    mesh = make_mesh(4, pp=2, dp=2)
    loss_fn = pipeline_loss_fn(cfg, mesh, num_microbatches=2,
                               compute_dtype=jnp.float32, loss_chunk=None)
    staged = partition_layers(params, 2)
    pp_loss, metrics = jax.jit(loss_fn)(staged, batch)
    assert abs(float(ref_loss) - float(metrics["loss"])) < 1e-5, (
        float(ref_loss), float(metrics["loss"]))


def test_pipeline_gradients_match_plain():
    cfg = _cfg()
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}

    ref_grads = jax.grad(lambda p: transformer.causal_lm_loss(
        p, batch, cfg, compute_dtype=jnp.float32, loss_chunk=None)[0])(params)

    mesh = make_mesh(2, pp=2)
    loss_fn = pipeline_loss_fn(cfg, mesh, num_microbatches=2,
                               compute_dtype=jnp.float32, loss_chunk=None)
    staged = partition_layers(params, 2)
    pp_grads = jax.grad(lambda p: loss_fn(p, batch)[0])(staged)
    pp_grads = merge_layers(pp_grads)

    for (ka, a), (kb, b) in zip(
            jax.tree_util.tree_leaves_with_path(ref_grads),
            jax.tree_util.tree_leaves_with_path(pp_grads)):
        scale = float(jnp.max(jnp.abs(a))) + 1e-8
        assert float(jnp.max(jnp.abs(a - b))) / scale < 1e-4, (ka, scale)


def test_pipeline_train_step_decreases_loss():
    cfg = _cfg()
    mesh = make_mesh(pp=2, dp=2, fsdp=2)
    opt = make_optimizer(learning_rate=1e-3, warmup_steps=2, total_steps=50)
    state, sh = init_pp_state(cfg, mesh, opt)
    step = make_pp_train_step(cfg, mesh, opt, sh, num_microbatches=2)
    toks = jax.random.randint(jax.random.PRNGKey(2), (8, 33), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks}
    state, m0 = step(state, batch)
    first = float(m0["loss"])
    for _ in range(10):
        state, m = step(state, batch)
    assert np.isfinite(float(m["loss"]))
    assert float(m["loss"]) < first, (first, float(m["loss"]))


def test_interleaved_partition_merge_roundtrip():
    cfg = _cfg()
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    staged = partition_layers(params, 2, virtual_stages=2)
    # 4 layers, P=2, V=2 -> each device holds V*Lc = 2 layer rows
    assert staged["blocks"]["attn"]["wq"].shape[:2] == (2, 2)
    merged = merge_layers(staged, virtual_stages=2)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(merged)):
        np.testing.assert_array_equal(a, b)


def test_interleaved_pipeline_loss_matches_plain():
    """pp=2, V=2 interleaved schedule == single-device loss."""
    from ray_tpu.parallel.pipeline import interleaved_pipeline_loss_fn
    cfg = _cfg()
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}

    ref_loss, _ = transformer.causal_lm_loss(params, batch, cfg,
                                             compute_dtype=jnp.float32,
                                             loss_chunk=None)

    mesh = make_mesh(4, pp=2, dp=2)
    loss_fn = interleaved_pipeline_loss_fn(
        cfg, mesh, num_microbatches=4, virtual_stages=2,
        compute_dtype=jnp.float32, loss_chunk=None)
    staged = partition_layers(params, 2, virtual_stages=2)
    _, metrics = jax.jit(loss_fn)(staged, batch)
    assert abs(float(ref_loss) - float(metrics["loss"])) < 1e-5, (
        float(ref_loss), float(metrics["loss"]))


def test_interleaved_pipeline_gradients_match_plain():
    from ray_tpu.parallel.pipeline import interleaved_pipeline_loss_fn
    cfg = _cfg()
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}

    ref_grads = jax.grad(lambda p: transformer.causal_lm_loss(
        p, batch, cfg, compute_dtype=jnp.float32, loss_chunk=None)[0])(params)

    mesh = make_mesh(2, pp=2)
    loss_fn = interleaved_pipeline_loss_fn(
        cfg, mesh, num_microbatches=2, virtual_stages=2,
        compute_dtype=jnp.float32, loss_chunk=None)
    staged = partition_layers(params, 2, virtual_stages=2)
    pp_grads = jax.grad(lambda p: loss_fn(p, batch)[0])(staged)
    pp_grads = merge_layers(pp_grads, virtual_stages=2)

    for (ka, a), (kb, b) in zip(
            jax.tree_util.tree_leaves_with_path(ref_grads),
            jax.tree_util.tree_leaves_with_path(pp_grads)):
        scale = float(jnp.max(jnp.abs(a))) + 1e-8
        assert float(jnp.max(jnp.abs(a - b))) / scale < 1e-4, (ka, scale)


def test_interleaved_train_step_decreases_loss():
    cfg = _cfg()
    mesh = make_mesh(pp=2, dp=2)
    opt = make_optimizer(learning_rate=1e-3, warmup_steps=2, total_steps=50)
    state, sh = init_pp_state(cfg, mesh, opt, virtual_stages=2)
    step = make_pp_train_step(cfg, mesh, opt, sh, num_microbatches=2,
                              virtual_stages=2)
    toks = jax.random.randint(jax.random.PRNGKey(2), (8, 33), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    losses = []
    for _ in range(8):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses


def test_pipeline_fsdp_loss_matches_plain():
    """pp x fsdp (ZeRO param/opt sharding inside the pipeline, fsdp left to
    the compiler) == single-device loss on identical f32 params."""
    cfg = _cfg()
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}

    ref_loss, _ = transformer.causal_lm_loss(params, batch, cfg,
                                             compute_dtype=jnp.float32,
                                             loss_chunk=None)

    mesh = make_mesh(4, pp=2, fsdp=2)
    loss_fn = pipeline_loss_fn(cfg, mesh, num_microbatches=2,
                               compute_dtype=jnp.float32, loss_chunk=None)
    staged = partition_layers(params, 2)
    _pp_loss, metrics = jax.jit(loss_fn)(staged, batch)
    assert abs(float(ref_loss) - float(metrics["loss"])) < 1e-5, (
        float(ref_loss), float(metrics["loss"]))


def test_pipeline_sp_loss_matches_plain():
    """pp x sp (ring attention across the sequence shards inside each
    pipeline stage) == single-device loss on identical f32 params."""
    cfg = _cfg()
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}

    ref_loss, _ = transformer.causal_lm_loss(params, batch, cfg,
                                             compute_dtype=jnp.float32,
                                             loss_chunk=None)

    mesh = make_mesh(4, pp=2, sp=2)
    loss_fn = pipeline_loss_fn(cfg, mesh, num_microbatches=2,
                               compute_dtype=jnp.float32, loss_chunk=None)
    staged = partition_layers(params, 2)
    _pp_loss, metrics = jax.jit(loss_fn)(staged, batch)
    assert abs(float(ref_loss) - float(metrics["loss"])) < 1e-5, (
        float(ref_loss), float(metrics["loss"]))


def test_pipeline_fsdp_sp_train_steps():
    """pp x fsdp and pp x sp full train steps: state stays sharded, loss
    decreases (the historical sharding-rule bug sites — VERDICT r4 weak #6)."""
    cfg = _cfg()
    opt = make_optimizer(learning_rate=1e-3, warmup_steps=2, total_steps=50)
    for kw in (dict(pp=2, fsdp=2, dp=2), dict(pp=2, sp=2, dp=2)):
        mesh = MeshSpec(**kw).build(jax.devices()[:8])
        state, sh = init_pp_state(cfg, mesh, opt)
        step = make_pp_train_step(cfg, mesh, opt, sh, num_microbatches=2)
        toks = jax.random.randint(jax.random.PRNGKey(2), (8, 33), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
        losses = []
        for _ in range(6):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0], (kw, losses)
        if "fsdp" in kw and kw.get("fsdp", 1) > 1:
            w = state.params["blocks"]["attn"]["wq"]
            assert "fsdp" in str(w.sharding.spec), w.sharding.spec
