"""Layers of two kinds on the serving path (models/hybrid.py, ops/
gated_delta.py): a tiny hybrid of 2 periods (3 gated-delta-rule layers + 1
full-attention layer each), hidden 64, key / value heads 4 x 8 / 4 x 16,
seeded random weights, on the CPU.  The independent side of every
comparison is the block kind's plain float32 reference
(benchmark/models/olmo_hybrid.py: the delta rule one token at a time, no
chunks, no cache, nothing imported from ray_tpu.models or ray_tpu.ops) or
``gated_delta.gdn_recurrence``.  Numbers here are about results, never
speed."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import contract
import kinds
from kinds import REPO, _scans, padded as _padded
from ray_tpu.models import decode, transformer
from ray_tpu.ops import gated_delta as gd

ROW = kinds.KINDS["olmo_hybrid"]


class TestOlmoHybrid(contract.OnlyServed):
    row = ROW


class TestGdnKernels(contract.DeltaRule):
    ops, name = gd, "gdn"
    kernel_sizes = (("128-8-16", 128, 8, 16), ("100-96-192", 100, 96, 192))


def test_unit_lower_inverse_against_linalg():
    """Entries up to 2 (beta 2, aligned keys): the worst case of the solve."""
    low = jnp.tril(2.0 * jax.random.uniform(jax.random.PRNGKey(0), (64, 64),
                                            minval=-1.0), -1)
    want = np.linalg.inv(np.eye(64) + np.asarray(low, np.float64))
    got = np.asarray(jax.jit(gd.inv_unit_lower)(low))
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


# ------------------------------------ (b) the program against itself

def test_a_decode_step_continues_a_prefill(tiny):
    """Prefill of n tokens == prefill of n - 1 and one decode step: logits,
    recurrent state and convolution tail."""
    cfg, params = tiny
    run = kinds.programs(cfg)
    toks = np.random.default_rng(4).integers(1, 256, size=(1, 37)).astype(
        np.int32)

    def prefill(n):
        cache = decode.init_kv_cache(cfg, 2, 64, jnp.float32)
        return run.prefill(params, cache, toks[:, :n],
                           np.array([n], np.int32), np.array([1], np.int32))

    whole, lg_whole = prefill(37)
    part, _ = prefill(36)
    part, lg = run.step(params, part, np.array([0, toks[0, 36]], np.int32),
                        np.array([False, True]))
    np.testing.assert_allclose(lg[1], lg_whole[0], atol=2e-4)
    for name in ("state", "conv"):
        np.testing.assert_allclose(part[name][:, 1], whole[name][:, 1],
                                   atol=5e-4)
    assert part["length"].tolist() == [0, 37]


# ----------------------- (c) a slot re-admitted carries nothing over

def test_a_readmitted_slot_and_the_scratch_slot_carry_nothing(tiny):
    cfg, params = tiny
    run = kinds.programs(cfg)
    rng = np.random.default_rng(1)
    old = rng.integers(1, 256, size=50).astype(np.int32)
    new = rng.integers(1, 256, size=21).astype(np.int32)

    def admit(cache, rows, slots):
        return run.prefill(
            params, cache, _padded(rows, 64),
            np.array([len(r) for r in rows], np.int32),
            np.array(slots, np.int32))

    def step(cache, token):
        return run.step(params, cache, np.array([token, 0, 0], np.int32),
                        np.array([True, False, False]))

    fresh, lg_fresh = admit(decode.init_kv_cache(cfg, 3, 128, jnp.float32),
                            [new, [1]], [0, 2])
    used, _ = admit(decode.init_kv_cache(cfg, 3, 128, jnp.float32),
                    [old, [1]], [0, 2])
    for _ in range(3):                   # the old request decodes a while
        used, _ = step(used, 7)
    used, lg_used = admit(used, [new, [1]], [0, 2])   # slot 0 again
    np.testing.assert_array_equal(lg_used[0], lg_fresh[0])
    for name in ("state", "conv"):
        np.testing.assert_array_equal(used[name][:, 0], fresh[name][:, 0])
        # slot 1 was never admitted; the padding row went to scratch slot 2
        assert float(jnp.abs(used[name][:, 1]).max()) == 0.0
    np.testing.assert_array_equal(step(used, 9)[1][0], step(fresh, 9)[1][0])


# ------------------------------------------- (d) a dense engine's gauges

def test_a_dense_engine_reports_the_same_gauges():
    from ray_tpu.models import config as mcfg
    from ray_tpu.serve.llm import LLMEngine
    cfg = mcfg.tiny()
    eng = LLMEngine(cfg, num_slots=2, max_len=32, buckets=(16,))
    try:
        g = eng.breakdown()
    finally:
        eng.shutdown()
    assert g["linear_layers"] == 0 and g["full_layers"] == cfg.num_layers
    assert g["cache_state_bytes"] == 0
    assert g["cache_kv_bytes"] == decode.cache_bytes(cfg, 3, 32)


# ------------------------------------------ the kind's counts (l12 file)

def test_counts_of_the_l12_configurations_step_and_kernels(kind):
    """(The tree, the matrices a layer and the cache's gauges: the
    contract's.)  The decode step's three byte terms: weights once, the
    state read and written per active slot per linear layer at 4 bytes, K/V
    per live token for the full layers only; and the kernels' counts."""
    doc = kinds.cell_doc(ROW.name)
    assert round(kind.num_params(doc) / 1e9, 2) == 3.27
    per = kind.layer_matrix_params(doc)
    weights = (9 * per["linear"] + 3 * per["full"] + 100352 * 3840) * 2
    assert kind.decode_step_bytes(doc, 0, 0) == weights
    assert kind.state_bytes_per_slot(doc) == 9 * 30 * 96 * 192 * 4
    assert kind.decode_step_bytes(doc, 1, 0) - weights \
        == 2 * 9 * 30 * 96 * 192 * 4
    assert kind.kv_bytes_per_token(doc) == 46_080
    assert kind.decode_step_bytes(doc, 0, 1) - weights == 46_080
    assert kind.decode_step_flops(doc, 1, 0) == weights + 9 * 30 * 6 * 96 * 192
    assert kind.gdn_recurrent_step_bytes(doc, 24) == 24 * (
        2 * 9 * 30 * 96 * 192 * 4 + 9 * 30 * (2 * 96 + 2 * 192) * 2)
    assert kind.gdn_chunk_fwd_bytes(doc, 1000) == 9 * 30 * 1160 * 1000
    assert kind.CHUNK == gd.CHUNK == 64      # the counts' chunk is the kernel's
    assert kind.gdn_chunk_fwd_flops(doc, 1) == pytest.approx(
        9 * 30 * (6 * 64 * 96 + 4 * 64 * 192 + 6 * 96 * 192 + 2 * 64 * 64 / 3))


# ------------- nothing in benchmark/ outside models/ and tests/ names a model

def test_only_a_kinds_file_names_the_programs_models():
    names = re.compile(r"ray_tpu\.models|TransformerConfig")
    bench = os.path.join(REPO, "benchmark")
    hits = []
    for d, _, files in os.walk(bench):
        if os.path.relpath(d, bench).split(os.sep)[0] in ("models", "tests"):
            continue
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(d, f)
                hits += [f"{path}:{n}: {line}" for n, line in
                         enumerate(open(path), 1) if names.search(line)]
    assert not hits, "".join(hits)


# ------------- one walk over the layers, and no cache through a scan (PR 31)

def test_one_walk_over_the_layers_and_no_cache_through_a_scan(tiny):
    """In ``ray_tpu/models/`` two functions loop over the layers' weights:
    ``decode.layer_stack`` (serving, every kind of cache) and
    ``transformer.apply_trunk`` (training).  And whichever tree the serving
    programs are traced on, no scan takes or returns a cache array as
    xs / ys: that slices every layer out and restacks all of it (PR 26)."""
    import ast

    from ray_tpu.models import paged_decode, speculative
    from ray_tpu.models.config import TransformerConfig

    loops = {"scan", "fori_loop", "while_loop", "map"}
    models = os.path.join(REPO, "ray_tpu", "models")
    over_layers = set()
    for name in sorted(os.listdir(models)):
        if not name.endswith(".py"):
            continue
        src = open(os.path.join(models, name)).read()
        for fn in ast.parse(src).body:
            if isinstance(fn, ast.FunctionDef) and any(
                    isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Attribute)
                    and n.func.attr in loops
                    and ast.unparse(n.func.value).endswith("lax")
                    for n in ast.walk(fn)) and '"blocks"' in ast.get_source_segment(src, fn):
                over_layers.add((name, fn.name))
    assert over_layers == {("decode.py", "layer_stack"),
                           ("transformer.py", "apply_trunk")}

    hybrid_cfg, hybrid_params = tiny
    dense_cfg = TransformerConfig(
        vocab_size=128, num_layers=3, hidden_size=64, num_heads=4,
        num_kv_heads=2, mlp_size=128, max_seq_len=96)
    dense_params = kinds.init(transformer.init_params, dense_cfg)
    draft_cfg = TransformerConfig(**{**dense_cfg.__dict__, "num_layers": 1})
    slots, max_len, rows, bucket = 5, 96, 2, 16
    trees = {
        "rows": (dense_cfg, dense_params,
                 decode.init_kv_cache(dense_cfg, slots, max_len)),
        "pages": (dense_cfg, dense_params, paged_decode.init_paged_cache(
            dense_cfg, 20, 8, slots, max_len // 8)),
        "recurrent": (hybrid_cfg, hybrid_params,
                      decode.init_kv_cache(hybrid_cfg, slots, max_len)),
    }
    state = decode.init_decode_state(slots, jax.random.PRNGKey(1))
    admit = (jnp.zeros((rows, bucket), jnp.int32),) + tuple(
        jnp.ones((rows,), dt) for dt in (jnp.int32, jnp.int32, jnp.float32,
                                         jnp.int32, jnp.int32, jnp.bool_))
    for name, (cfg, params, cache) in trees.items():
        held = {a.shape for k, a in cache.items()
                if k not in ("length", "block_table")}
        programs = [
            (lambda p, c, st: decode.decode_state_loop(p, c, st, 2, cfg),
             (params, cache, state)),
            (lambda p, c, st, *a: decode.prefill_admit(p, c, st, *a, cfg),
             (params, cache, state) + admit),
        ]
        if name != "recurrent":
            draft = speculative.make_draft_params(params, 1)
            programs.append((
                lambda p, c, dp, dc, st: speculative.spec_decode_state_loop(
                    p, c, dp, dc, st, 3, 2, cfg, draft_cfg),
                (params, cache, draft,
                 decode.init_kv_cache(draft_cfg, slots, max_len), state)))
        for fn, args in programs:
            found = list(_scans(jax.make_jaxpr(fn)(*args).jaxpr))
            assert found
            for eqn in found:
                skip = eqn.params["num_consts"] + eqn.params["num_carry"]
                through = ([v.aval.shape for v in eqn.invars[skip:]]
                           + [v.aval.shape for v in
                              eqn.outvars[eqn.params["num_carry"]:]])
                assert not held & set(through), (name, through)
