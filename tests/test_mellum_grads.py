"""The train step of a window / full pattern with experts over ``ep`` against
``jax.grad`` of the block kind's plain reference, on one device and on four
meshes, and the chip's gradient check at the tiny size (PR 58).  A file
apart from ``tests/test_mellum_train.py``, whose helpers it takes, so that
``--dist loadfile`` can part the two."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kinds
from ray_tpu.models import sharding as shard_rules
from ray_tpu.models import transformer
from ray_tpu.parallel.mesh import named_sharding
from test_mellum_train import F32, ROW, _batch, _mesh, tiny  # noqa: F401
# (ROW: the kind's row, which ``kinds``' fixtures read off the module)


@pytest.mark.parametrize("axes", [None, dict(ep=4), dict(dp=2, ep=2),
                                  dict(fsdp=2, ep=2), dict(fsdp=4, ep=1)],
                         ids=["one-device", "ep4", "dp2-ep2", "fsdp2-ep2",
                              "fsdp4-ep1"])
def test_loss_and_gradients_are_the_references(kind, tiny, axes):
    """``causal_lm_loss`` in float32 (its total: the loss and the weighed
    balance term) against ``jax.grad`` of the plain reference on the same
    seeded weights, the parameters laid over the mesh as the train state
    lays them and the experts exchanged over ``ep``: the loss, and every
    leaf of the gradient (the unused selection bias's is zero)."""
    doc, cfg, params = tiny
    toks = _batch(doc)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    pctx = transformer.ParallelContext()
    if axes:
        mesh = _mesh(**axes)
        pctx = transformer.ParallelContext(
            mesh=mesh, batch_axes=shard_rules.batch_axes(cfg))
        params = jax.device_put(params, named_sharding(
            mesh, shard_rules.logical_param_specs(cfg)))
        batch = jax.device_put(batch, named_sharding(
            mesh, shard_rules.batch_spec(cfg)))
        held = params["blocks"]["experts"]["w_gate"].sharding.shard_shape(
            params["blocks"]["experts"]["w_gate"].shape)[1]
        assert held == cfg.num_experts // axes["ep"]

    def program(p):
        total, metrics = transformer.causal_lm_loss(
            p, batch, cfg, pctx, compute_dtype=F32, remat="save_acts")
        return total, metrics

    def reference(p):
        return jax.vmap(lambda s: kind.total_loss(p, s, doc))(toks).mean()

    with jax.default_matmul_precision("highest"):
        (got, metrics), g_got = jax.jit(jax.value_and_grad(
            program, has_aux=True))(params)
        want, g_want = jax.jit(jax.value_and_grad(reference))(
            jax.device_get(params))
        plain = jax.jit(lambda p: jax.vmap(
            lambda s: kind.loss(p, s, doc))(toks).mean())(
                jax.device_get(params))
    np.testing.assert_allclose(got, want, rtol=2e-6)
    np.testing.assert_allclose(metrics["loss"], plain, rtol=2e-6)
    np.testing.assert_allclose(
        got - metrics["loss"], cfg.moe_balance_weight * metrics["moe_balance"],
        rtol=1e-3)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(g_got))
    for path, b in jax.tree_util.tree_leaves_with_path(g_want):
        a = flat_got[path]
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=2e-5 * scale + 1e-9,
                                   err_msg=jax.tree_util.keystr(path))
    blocks = g_got["blocks"]
    assert not blocks["window"]["moe"]["bias"].any()
    for leaf in (blocks["window"]["moe"]["router"],
                 blocks["full"]["attn"]["q_norm"]["scale"],
                 blocks["experts"]["w_gate"]):
        assert np.asarray(leaf).any()
    # every assignment was some holder's, and one holder's alone
    every = toks[:, 1:].size * cfg.experts_per_token * cfg.num_layers
    assert int(metrics["moe_assignments_held"]) == every
    holders = axes["ep"] if axes else 1
    assert int(metrics["moe_chip_load_min"]) <= every // (
        holders * cfg.num_layers) <= int(metrics["moe_chip_load_max"])


def test_the_chips_gradient_check_runs_at_the_tiny_size(capsys):
    """``tests/chip_mellum_check.py`` is run on the four chips at the cell's
    sizes (PERF.md section 6, PR 58); here its tiny sizes in float32 on four
    virtual devices, the reference told the run's routing: the sound step
    and the sound layer under their limits on two seeds, a holder's part
    left out, a wider band and YaRN on every layer far over them, the
    combine rounded to bf16 over the layer's (the grouped products' control
    is mute where the CPU takes the kernel's twin, and is not run)."""
    import importlib.util
    import json
    import os
    if len(jax.devices()) < 4:
        pytest.skip("4 devices")
    spec = importlib.util.spec_from_file_location(
        "chip_mellum_check", os.path.join(kinds.REPO, "tests",
                                          "chip_mellum_check.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main(["tiny", "3", "4"]) == 0
    out = json.loads(capsys.readouterr().out.split("MELLUMCHECK ")[1])
    read, tiny = out["readings"], mod.TINY_LIMITS
    assert out["limits"] == tiny and out["seeds"] == [3, 4]
    assert set(read) == set(mod.VARIANTS) - {"layer_gmm_bf16"}
    for name, rows in read.items():
        assert len(rows) == (1 if name in mod.MUST_FAIL else 2), name
        assert all(r["passes"] != (name in mod.MUST_FAIL) for r in rows)
    assert all(r["leaf"] < 1e-4 < tiny["leaf"] for r in read["sound"])
    for name in ("part_left_out", "band_1152", "yarn_all"):
        assert read[name][0]["leaf"] > 100 * tiny["leaf"], name
    assert read["layer_combine_bf16"][0]["layer_out"] > 10 * tiny["layer_out"]
    # a gradient of zeros reads 1 on every leaf, and fails
    assert not mod.passes({"loss": 0.0, "leaf": 1.0}, mod.LIMITS)
    # the chips' own rows (``chiprun_out/pr58h/check.out``, PR 58, second
    # round) under the limits set from them: the sound program passes on
    # every seed read, each lower precision fails, with a twentieth of the
    # reading to spare on either side
    chip = {"sound": [{"loss": 1.9073486328125e-06, "leaf": 0.0273217242},
                      {"loss": 1.811981201171875e-05, "leaf": 0.0286210310}],
            "layer_sound": [
                {"layer_out": 0.0045529669, "layer_grad": 0.0053317747},
                {"layer_out": 0.0045532538, "layer_grad": 0.0053238668},
                {"layer_out": 0.0045532645, "layer_grad": 0.0053284047}],
            "layer_gmm_bf16": [
                {"layer_out": 0.0080658058, "layer_grad": 0.0081133023}],
            "layer_combine_bf16": [
                {"layer_out": 0.0053150607, "layer_grad": 0.0053317747}]}
    for name, rows in chip.items():
        for held in rows:
            near = {k: v * (0.95 if name in mod.MUST_FAIL else 1.05)
                    for k, v in held.items()}
            assert mod.passes(near, mod.LIMITS) != (name in mod.MUST_FAIL)
    assert not mod.passes({"loss": 5.7e-3, "leaf": 0.0}, mod.LIMITS)
    assert set(mod.MUST_FAIL) == {
        "part_left_out", "band_1152", "yarn_all", "layer_gmm_bf16",
        "layer_combine_bf16"}
    assert set(mod.VARIANTS) == set(mod.MUST_FAIL) | {"sound", "layer_sound"}
