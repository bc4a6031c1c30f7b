"""Granite 4.0-H's block on the serving path: the "ssm" kind of layer with a
dense MLP beneath it, the tied head under a layer pattern and the four
published multipliers (models/config.py, models/decode.py ``layer_stack``,
``transformer.lm_head_logits``, the attention's scale through
ops/decode_attention.py and ops/flash_attention.py): a tiny model of two
periods of ``M M * M``, hidden 64, one group of 8 state-space heads, the
multipliers no powers of two, seeded random weights, on the CPU.  The
independent side of every comparison is the block kind's plain float32
reference (benchmark/models/granitemoehybrid.py: the recurrence one token at
a time, nothing imported from ray_tpu.models or ray_tpu.ops).  The kernels'
cases at one group wider than a grid step are tests/test_ssd.py's.  Numbers
here are about results, never speed."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import contract
import kinds
from ray_tpu.models import decode
from ray_tpu.models.config import TransformerConfig
from ray_tpu.ops import attention, decode_attention, flash_attention

ROW = kinds.KINDS["granitemoehybrid"]


class TestGraniteMoeHybrid(contract.OnlyServed):
    row = ROW


# ------------------------------------------- each multiplier, in the program

@pytest.mark.parametrize("field", TransformerConfig.MULTIPLIERS)
def test_a_dropped_multiplier_fails_the_parity(field):
    """The program with one multiplier left out (the field at 0: absent,
    skipped in Python) against the reference with all four: the first
    logits after the prompt stand further apart than a hundred times the
    parity test's tolerance."""
    cfg, params = kinds.tiny(ROW.name)
    assert all(getattr(cfg, f) > 0 for f in cfg.MULTIPLIERS)
    p = ROW.parity
    toks, got, _ = kinds.parity_run(ROW.name)
    run = kinds.programs(dataclasses.replace(cfg, **{field: 0.0}))
    cache = decode.init_kv_cache(cfg, p["n_slots"], p["max_len"],
                                 jnp.float32)
    _, lg = run.prefill(
        params, cache,
        kinds.padded([t[:n] for t, n in zip(toks, p["lens"])], p["bucket"]),
        np.array(p["lens"], np.int32), np.array(p["slots"], np.int32))
    sound = np.stack([g[0] for g in got])
    assert np.abs(np.asarray(lg) - sound).max() > 100 * p["atol"]


def test_an_absent_multiplier_is_skipped_not_multiplied_by_one():
    """A configuration without the four scalars traces to the program it
    traced to before they had a field: setting every one to what it stands
    for (1, 1, ``head_dim ** -0.5``, 1) adds the multiplications back."""
    cfg, params = kinds.tiny(ROW.name)
    absent = dataclasses.replace(cfg, **dict.fromkeys(cfg.MULTIPLIERS, 0.0))
    ones = dataclasses.replace(
        cfg, embedding_multiplier=1.0, residual_multiplier=1.0,
        attention_multiplier=cfg.head_dim ** -0.5, logits_scaling=1.0)
    assert absent.attn_scale == ones.attn_scale == cfg.head_dim ** -0.5
    cache = decode.init_kv_cache(cfg, 2, 32, jnp.float32)

    def muls(c):
        step = lambda p, k: decode.decode_step(   # noqa: E731
            p, k, jnp.ones((2,), jnp.int32), jnp.ones((2,), bool), c,
            jnp.float32)
        text = str(jax.make_jaxpr(step)(params, cache))
        return text.count(" mul ") + text.count(" div ")

    # the embedding's, two a layer of a period's four (one trace a period),
    # the logits'
    assert muls(ones) - muls(absent) == 1 + 2 * 4 + 1


# ------------------------------------ one attention scale, every attention

def _plain(q, k, v, scale):
    """softmax(scale q k^T) v, causal, one head group: q [S, H, D], k, v
    [S, KV, D]."""
    reps = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(a, reps, axis=1) for a in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * scale
    seen = jnp.arange(k.shape[0])[None] <= jnp.arange(q.shape[0])[:, None]
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
    return jnp.einsum("hqk,khd->qhd", p, v,
                      precision=jax.lax.Precision.HIGHEST)


FORMS = {"twin": dict(use_kernel=False), "kernel": dict(interpret=True)}


@pytest.mark.parametrize("form", list(FORMS))
def test_decode_attn_takes_the_scale_at_heads_of_64_lanes(form):
    """32 query heads over 8 K/V heads of 64 (rows of 512 lanes, two heads
    to a 128-lane tile) with ``1 / head_dim`` for a scale: against plain
    attention over each slot's live rows."""
    slots, nh, nkv, hd, max_len = 3, 32, 8, 64, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (slots, nh, hd))
    k_all, v_all = (jax.random.normal(k, (2, slots, max_len, nkv * hd))
                    for k in ks[1:])
    live = jnp.array([37, 0, 64])
    got = decode_attention.decode_attn(q, k_all, v_all, jnp.int32(1), live,
                                       nkv, scale=1 / hd, **FORMS[form])
    for s, n in enumerate(live.tolist()):
        if not n:
            assert not np.asarray(got[s]).any()
            continue
        want = _plain(jnp.zeros((n, nh, hd)).at[-1].set(q[s]),
                      k_all[1, s, :n].reshape(n, nkv, hd),
                      v_all[1, s, :n].reshape(n, nkv, hd), 1 / hd)[-1]
        np.testing.assert_allclose(got[s], want, atol=2e-5)
    # and the scale is read: the default is another result
    other = decode_attention.decode_attn(q, k_all, v_all, jnp.int32(1), live,
                                         nkv, **FORMS[form])
    assert float(jnp.abs(other - got).max()) > 1e-2


@pytest.mark.parametrize("form", ["plain", "flash"])
def test_the_prefill_attention_takes_the_scale_at_heads_of_64_lanes(form):
    s, nh, nkv, hd = 128, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, s, nh, hd))
    k, v = (jax.random.normal(key, (1, s, nkv, hd)) for key in ks[1:])
    if form == "flash":
        got = flash_attention.flash_attention(q, k, v, interpret=True,
                                              scale=1 / hd)
    else:
        got = attention.mha(q, k, v, use_flash=False, scale=1 / hd)
    np.testing.assert_allclose(got[0], _plain(q[0], k[0], v[0], 1 / hd),
                               atol=2e-5)
    assert float(jnp.abs(attention.mha(q, k, v, use_flash=False)
                         - got).max()) > 1e-2


# ------------------------------------------------- the kind's counts (l40)

def test_counts_of_the_l40_configurations_step_and_kernels(kind):
    """(The tree, the matrices a layer and the cache's gauges: the
    contract's.)  ISSUE 53's arithmetic: nothing is cut; the decode step's
    three byte terms and the state's share of them; the kernels' counts
    with one group."""
    doc, cfg = kinds.cell_doc(ROW.name), kinds.cell_cfg(ROW.name)
    assert doc["reduced"] == {} and cfg.tied_embeddings
    assert cfg.layer_pattern == ("ssm",) * 5 + ("full",) + ("ssm",) * 4
    assert (cfg.num_periods, cfg.ssm_layers, cfg.full_layers, cfg.ssm_groups,
            cfg.head_dim, cfg.mlp_layers) == (4, 36, 4, 1, 64, 40)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.attn_scale,
            cfg.logits_scaling) == (12, 0.22, 1 / 64, 8)
    per = kind.layer_matrix_params(doc)
    matrices = (36 * per["mamba"] + 4 * per["attention"] + 40 * per["mlp"]
                + 100352 * 2048)
    assert cfg.num_params() == matrices == 3_190_292_480
    assert kind.num_params(doc) == matrices + 36 * (
        5 * 4352 + 3 * 64 + 4096) + 2 * 40 * 2048 + 2048
    assert round(kind.num_params(doc) / 1e9, 2) == 3.19
    # a token's FLOPs: the head is the embedding's table, met once
    assert cfg.flops_per_token(1024) == (
        6 * matrices + 12 * 4 * 1024 * 2048 + 3 * 36 * 64 * 4 * 64 * 128)
    assert kind.train_flops_per_token(doc, 1024) == (
        6 * matrices + 6 * 4 * 1024 * 2048 + 3 * 36 * 64 * 5 * 64 * 128)
    assert kind.decode_step_bytes(doc, 0, 0) == matrices * 2
    assert kind.state_bytes_per_slot(doc) == 36 * 64 * 64 * 128 * 4
    assert kind.kv_bytes_per_token(doc) == 4 * 2 * 512 * 2
    assert kind.decode_state_bytes(doc, 64) == 2 * 64 * 75_497_472
    step = kind.decode_step_bytes(doc, 64, 64 * 1800)
    assert step == matrices * 2 + 2 * 64 * 75_497_472 + 64 * 1800 * 8192
    # ISSUE 53: the recurrent state is 57% of a step at 64 live slots
    assert round(100 * kind.decode_state_bytes(doc, 64) / step) == 57
    assert kind.ssd_recurrent_step_bytes(doc, 65) == 65 * (
        2 * 75_497_472 + 36 * ((2 * 4096 + 2 * 128) * 2 + 2 * 64 * 4))
    assert kind.ssd_recurrent_step_flops(doc, 1) == 36 * 64 * 5 * 64 * 128
    from ray_tpu.ops import ssd
    assert kind.CHUNK == ssd.CHUNK == 128   # the counts' chunk is the kernel's
    # ``C B^T`` once a group, whatever blocks of heads the kernel walks
    assert kind.ssd_chunk_fwd_flops(doc, 1) == 36 * (
        1 * 2 * 128 * 128 + 64 * (2 * 128 * 64 + 4 * 64 * 128))
    assert kind.ssd_chunk_fwd_bytes(doc, 1000) == 36 * (
        (2 * 4096 + 2 * 128) * 2 + 3 * 64 * 4) * 1000
    assert kind.decode_attn_bytes(doc, 7) == 7 * 8192
    assert kind.decode_attn_flops(doc, 7) == 4 * 4 * 32 * 64 * 7
    assert kind.flash_attention_flops(doc, 1, 1024) == (
        4 * 2 * 1024 * 1024 * 64 * 32)
