"""The delta rule with a decay a channel (ops/kda.py), the KDA variant of
the linear mixer and the gated full layer (models/hybrid.py), dropless
experts under a layer pattern and a served share of them (models/decode.py
``layer_stack``): a tiny model of 2 periods (gated GQA + 3 KDA), hidden 64,
16 routed experts of which a share of 4 is held, seeded random weights, on
the CPU.  The independent side of every comparison is the block kind's plain
float32 reference (benchmark/models/solar_open2.py: the rule one token at a
time, no chunks, no cache, nothing imported from ray_tpu.models or
ray_tpu.ops) or ``kda.kda_recurrence``.  Numbers here are about results,
never speed."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import contract
import kinds
from ray_tpu.models import decode
from ray_tpu.ops import kda

ROW = kinds.KINDS["solar_open2"]


class TestSolarOpen2(contract.OnlyServed, contract.Shares):
    row = ROW


class TestKdaKernels(contract.DeltaRule):
    """A decay of its own for every channel."""
    ops, name, per_channel, heads = kda, "kda", True, (3, 2, 4)
    kernel_sizes, low = (("128-8-16", 128, 8, 16),
                         ("100-128-128", 100, 128, 128)), 0

    @pytest.mark.parametrize("form", ["twin", "kernel"])
    def test_a_channel_that_forgets_at_once_stays_finite_and_equal(self,
                                                                   form):
        """alpha 0.05 in one channel for 128 steps: the cumulative log decay
        reaches -383 there, and exp(+192) inside a chunk would be infinite.
        Every exponent the tile takes is a difference <= 0."""
        q, k, v, g, beta = self.inputs(1, 128, 2, 8, 16, seed=11, low=3)
        assert float(jnp.cumsum(g, axis=1).min()) < -380
        o_ref, h_ref = kda.kda_recurrence(q, k, v, g, beta)
        if form == "twin":
            o, h = jax.jit(kda.kda_chunk_fwd_jnp)(q, k, v, g, beta)
        else:
            o, h = self.fn("_chunk_fwd", interpret=True)(q, k, v, g, beta)
        assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(h).all())
        np.testing.assert_allclose(o, o_ref, atol=2e-5)
        np.testing.assert_allclose(h, h_ref, atol=2e-5)


# ------------------------------------ (b) the reference and the program

def test_the_reference_is_told_the_compared_runs_own_routing(kind, tiny,
                                                             tiny_doc):
    """``program_run`` (the program run a second time, inside the
    reference's jit) gives what the compared run gave, choices and logits,
    run as the harness's comparison runs it (a jitted prefill, a jitted step
    a token, in bf16); each of its two programs is fenced in, and its logits
    are read by ``logits``, so that nothing of it is pruned: on the chip the
    unfenced step rounded at other points than the compared run's, and the
    pruned one fell the other way at a near-tie about once a run (PERF.md,
    PR 44)."""
    cfg, params = tiny
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    toks = np.random.default_rng(5).integers(1, 256, size=40).astype(np.int32)
    cache = kind.init_cache(cfg, 1, 128, jnp.bfloat16)
    cache, lg = jax.jit(lambda p, c, t, ln, sl: kind.prefill(
        p, c, t, ln, sl, cfg))(params, cache, toks[None, :29],
                               np.array([29], np.int32),
                               np.array([0], np.int32))
    got = [np.asarray(lg)[0]]
    step = jax.jit(lambda p, c, t, a: kind.decode_step(p, c, t, a, cfg))
    for i in range(29, 40):
        cache, lg = step(params, cache, toks[i:i + 1], np.ones((1,), bool))
        got.append(np.asarray(lg)[0])
    second = jax.jit(lambda p, t: kind.program_run(p, t, tiny_doc, 29)).trace(
        params, toks)
    told, ran = second.lower().compile()(params, toks)
    np.testing.assert_array_equal(told, cache["expert_choices"][:, 0, :40])
    np.testing.assert_array_equal(ran, np.stack(got))
    # around the prefill and around the step
    assert str(second.jaxpr).count("optimization_barrier") == 4


def test_the_reference_reads_the_second_runs_logits(kind, tiny, tiny_doc):
    """Not finite, no number: nothing of the second run is pruned."""
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), tiny[1])
    toks = np.random.default_rng(5).integers(1, 256, size=40).astype(np.int32)
    pos = jnp.arange(28, 40)

    @jax.jit
    def logits(p, t, spoiled):
        """The reference, its second run's steps giving NaN where
        ``spoiled`` (data: one program serves the control and the case)."""
        with mock.patch.object(kind, "decode_step", lambda *a: (
                lambda cache, lg: (cache, jnp.where(spoiled, jnp.nan, lg)))(
                    *decode.decode_step(*a))):
            return kind.logits(p, t, tiny_doc, pos)

    assert np.isfinite(logits(params, toks, False)).all()
    assert np.isnan(logits(params, toks, True)).all()


# ------------------------ one walk, with or without experts or a pattern

WALKS = {
    "pattern-with-experts": dict(
        layer_pattern=("full", "linear"), num_layers=6, moe_dropless=True,
        num_experts=8, experts_per_token=2, expert_mlp_size=16,
        shared_experts=1, linear_decay_per_channel=True, linear_gate_rank=4,
        attn_output_gate=True, attn_head_dim=16),
    "pattern-without": dict(
        layer_pattern=("linear", "full"), num_layers=6, norm_on_output=True,
        qk_norm=True),
    "experts-without-a-pattern": dict(
        num_layers=3, moe_dropless=True, num_experts=8, experts_per_token=2,
        expert_mlp_size=16, shared_experts=1, use_rope=True),
}


def _walked(cfg, params, new):
    """The experts' counts and choices come back a layer."""
    if cfg.moe_dropless:
        layers = cfg.expert_layers
        assert new["expert_choices"].shape[0] == layers
        # every layer's router chose for both slots' tokens
        assert int((new["expert_choices"][:, :, 0] >= 0).all())
        assert int(new["moe_counts"][0]) == 2 * 2 * layers


# a pattern with experts, a pattern without and dropless experts without a
# pattern
test_every_tree_walks_the_same_layer_stack = contract.walks(
    WALKS, lambda name: dict(
        vocab_size=64, hidden_size=32, num_heads=2, num_kv_heads=1,
        mlp_size=48, max_seq_len=64, use_rope=False, no_positions=True,
        linear_num_heads=2, linear_key_dim=8, linear_value_dim=8), _walked)


# ------------------------------------------------------------- refusals

def test_a_pattern_carries_experts_in_the_pre_norm_wiring():
    from ray_tpu.models.config import TransformerConfig
    base, _ = ROW.config_refusals
    TransformerConfig(**base, **kinds._EXPERTS)


def test_a_share_by_position_is_not_served_under_a_pattern_either(tiny):
    import dataclasses
    from ray_tpu.serve.llm import LLMEngine
    cfg, params = tiny
    by_position = dataclasses.replace(cfg, share_by_position=True)
    with pytest.raises(ValueError, match="share_by_position"):
        LLMEngine(by_position, params=params, num_slots=2, max_len=32)


# ------------------------------------------ the kind's counts (l4-e40 file)

def test_counts_of_the_l4_e40_configurations_step_and_kernels(kind):
    """(The tree, the matrices a layer and the cache's gauges: the
    contract's.)  The decode step's four byte terms: weights outside the
    experts once, the held experts the live tokens reach, the state read and
    written per active slot per KDA layer at 4 bytes, K/V per live token for
    the one GQA layer; and the kernels' counts."""
    doc, cfg = kinds.cell_doc(ROW.name), kinds.cell_cfg(ROW.name)
    assert cfg.layer_pattern == ("full", "linear", "linear", "linear")
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_start) == (320, 40,
                                                                     0)
    assert cfg.num_params() == 3_307_995_136      # the matrices alone
    per = kind.layer_matrix_params(doc)
    # ISSUE 44's check of the reading: 154.8M and 126.1M beside the experts
    assert round((per["kda"] + per["shared"] + per["router"]) / 1e6, 1) \
        == 154.7
    assert round((per["gqa"] + per["shared"] + per["router"]) / 1e6, 1) \
        == 126.1
    outside = 3 * per["kda"] + per["gqa"] + 4 * (per["shared"]
                                                 + per["router"])
    weights = (outside + 24576 * 4096) * 2
    assert kind.decode_step_bytes(doc, 0, 0) == weights
    assert kind.state_bytes_per_slot(doc) == 3 * 64 * 128 * 128 * 4
    assert kind.kv_bytes_per_token(doc) == 4096
    touched = 40 * (1 - (1 - 8 / 320) ** 64)
    assert 32 < touched < 32.2 == pytest.approx(
        kind.experts_touched(doc, 64), abs=0.2)
    assert kind.decode_step_bytes(doc, 64, 1000) == pytest.approx(
        weights + 4 * touched * per["expert"] * 2
        + 2 * 64 * 3 * 64 * 128 * 128 * 4 + 1000 * 4096)
    assert kind.kda_recurrent_step_bytes(doc, 65) == 65 * (
        2 * 3 * 64 * 128 * 128 * 4 + 3 * 64 * (4 * 128 * 2 + 4 * 128 + 4))
    assert kind.kda_recurrent_step_flops(doc, 1) == 3 * 64 * 7 * 128 * 128
    assert kind.kda_chunk_fwd_bytes(doc, 1000) == 3 * 64 * 1540 * 1000
    assert kind.CHUNK == kda.CHUNK == 64     # the counts' chunk is the kernel's
    assert kind.kda_chunk_fwd_flops(doc, 1) == pytest.approx(
        3 * 64 * (10 * 64 * 128 + 6 * 128 * 128 + 2 * 64 * 64 / 3))
    assert kind.moe_gmm_flops(doc, 10) == 2 * 15_728_640 * 10
    assert kind.moe_gmm_bytes(doc, 10, 3) == (
        3 * 15_728_640 + 10 * 2 * (4096 + 1280)) * 2
    assert kind.decode_attn_bytes(doc, 7) == 7 * 4096
