"""The "window" kind of layer on its ring beside full layers on rows, a dense
layer ahead of a pattern's expert layers, an RMSNorm a head on q and k,
rotary positions by kind, and the multi-token-prediction block that drafts
for a two-token verify step (models/decode.py, models/speculative.py,
ops/decode_attention.py, ops/flash_attention.py): a tiny model of K-EXAONE's
first two periods (window 8, hidden 64, 16 routed experts of which a share of
8 is held), seeded random weights, on the CPU.  The independent side of every
comparison is the block kind's plain float32 reference
(benchmark/models/exaone_moe.py: a whole-sequence banded mask, no cache, no
kernel, nothing imported from ray_tpu.models or ray_tpu.ops), or one program
against itself.  The kernels against their twins and the verify window on
the ring are ``tests/test_exaone_kernels.py``, a file of their own so that a
``--dist loadfile`` run can part the two.  Numbers here are about results,
never speed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import contract
import kinds
from ray_tpu.models import decode, speculative

ROW = kinds.KINDS["exaone_moe"]
F32 = jnp.float32


class TestExaoneMoe(contract.OnlyServed, contract.Shares):
    row = ROW


# -------------------------------------------- the block against the reference

def test_the_blocks_logits_equal_the_references():
    """The prefill's pass of the block leaves the draft the reference's
    block logits choose, and the block's rows; then the kind's decode step
    (a verify step of two with the next token forced, the draft rolled
    back, the block's pass of two) keeps the model's logits the
    reference's and the block's logits the reference's block's."""
    kind, doc = kinds.load("exaone_moe"), kinds.doc("exaone_moe")
    cfg, params = kinds.tiny("exaone_moe")
    n, steps = 23, 20
    toks = np.random.default_rng(7).integers(1, 256, n + steps).astype(
        np.int32)
    cache = kind.init_cache(cfg, 2, 64, F32)
    assert cache["wk"].shape[2] == 16
    cache, lg = jax.jit(lambda p, c, t, ln, s: decode.prefill(
        p, c, t, ln, s, cfg, F32))(
            params, cache, kinds.padded([toks[:n]], 32),
            np.array([n], np.int32), np.array([1], np.int32))
    first = int(np.argmax(lg[0]))
    seq = np.concatenate([toks[:n], [first]]).astype(np.int32)
    block = np.asarray(jax.jit(lambda p, t: kind.mtp_logits(p, t, doc))(
        params, seq))
    assert block.std() > 0.5
    assert int(cache["draft"][1]) == int(block[n - 1].argmax())
    # the model's logits with the next token forced (the harness's
    # comparison): the block then pairs a hidden state with a token the row
    # did not take, so its own rows are not the reference's here
    want = kinds.reference("exaone_moe", params, toks, n - 1)
    step = jax.jit(lambda p, c, t, a: kind.decode_step(p, c, t, a, cfg, F32))
    after = cache
    for s in range(steps):
        fed = np.zeros(2, np.int32)
        fed[1] = toks[n + s]
        cache, lg = step(params, cache, fed, np.array([False, True]))
        np.testing.assert_allclose(lg[1], want[s + 1], atol=2e-4)
    # the row fed its own greedy tokens, as the engine feeds it: the draft
    # a round leaves is the block on (h_t, the model's greedy token) over
    # rows 0 .. t of its own, the reference's block's choice on that row
    seq, cache2 = seq.tolist(), after
    for s in range(6):
        fed = np.zeros(2, np.int32)
        fed[1] = seq[-1]
        cache2, lg = step(params, cache2, fed, np.array([False, True]))
        seq.append(int(np.argmax(lg[1])))
        block = np.asarray(jax.jit(lambda p, t: kind.mtp_logits(
            p, t, doc))(params, np.asarray(seq, np.int32)))
        assert int(cache2["draft"][1]) == int(block[-1].argmax()), s
    assert cache["length"].tolist() == [0, n + steps]


# ------------------------------------------- the seeded weights' routing

def test_the_routers_load_is_level_on_rows_the_balancing_never_saw():
    """``balanced`` sets every expert layer's router (its scores' spread)
    and selection bias from many seeded rows at once.  On rows it has not
    seen every expert's load stays near the mean; the draw it started from
    (bias zero) loads them by the weights' accident.  ``sharpened`` is what
    ``init_params`` hands it: every q norm's scale at 4."""
    from ray_tpu.models import transformer
    kind = kinds.load("exaone_moe")
    cfg, params = kinds.tiny("exaone_moe")
    doc = kind._doc_of(cfg)
    fresh = jax.random.randint(       # rows like the sample's, half as long
        jax.random.PRNGKey(11),
        (kind.BALANCE_ROWS, kind.BALANCE_TOKENS // 2), 1, cfg.vocab_size)

    def spread(p):
        """Relative load of the most and the least loaded expert, worst
        layer, over the fresh rows' tokens."""
        said = jax.jit(jax.vmap(lambda t: jnp.stack(
            kind._walk(p, t, doc)[1])))(fresh)        # [rows, L, S, k]
        said = said[:, :, kind.BALANCE_FROM:]
        loads = [np.bincount(np.asarray(said[:, layer]).reshape(-1),
                             minlength=cfg.num_experts)
                 for layer in range(said.shape[1])]
        return (max(ld.max() / ld.mean() for ld in loads),
                min(ld.min() / ld.mean() for ld in loads))

    hi, lo = spread(params)
    assert hi < 1.35 and lo > 0.7, (hi, lo)
    drawn = kind.sharpened(kinds.init(transformer.init_params, cfg, F32, 3))
    for name in ("window", "full"):
        scale = drawn["blocks"][name]["attn"]["q_norm"]["scale"]
        assert float(scale.min()) == float(scale.max()) == 4.0
    assert float(drawn["mtp"]["blocks"]["full"]["attn"]["q_norm"]["scale"]
                 .mean()) == 4.0
    assert float(drawn["blocks"]["full"]["attn"]["k_norm"]["scale"]
                 .mean()) == 1.0
    hi0, lo0 = spread(drawn)
    assert hi0 > 1.4 and lo0 < 0.7, (hi0, lo0)


# ------------------------------------- the engine: draft on equals draft off

def _agreeing(cfg, params, scale=1e-4):
    """A target damped to near-identity (its sublayers' output norms scaled
    down: the residual stream stays the embedding) and a block that passes
    the next token's normed embedding on: the block's logits are then nearly
    the model's own for that token, and some drafts are right."""
    damped = speculative.damp_block_outputs(params, scale, output_norms=True)
    block = speculative.damp_block_outputs(params["mtp"], scale,
                                           output_norms=True)
    h = cfg.hidden_size
    block = dict(block, proj=jnp.concatenate(
        [jnp.eye(h), jnp.zeros((h, h))]).astype(params["mtp"]["proj"].dtype))
    return dict(damped, mtp=block)


@pytest.mark.parametrize("weights", ["random", "agreeing"])
def test_engine_emits_the_same_tokens_draft_on_or_off(weights):
    """Greedy requests through ``LLMEngine`` with the block drafting (a
    verify window of two, fixed) and with plain decode: the tokens are the
    model's own either way.  Random weights reject every draft; the agreeing
    weights accept some, and the counters say so."""
    cfg, params = kinds.tiny("exaone_moe")
    if weights == "agreeing":
        params = _agreeing(cfg, params)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in (5, 23, 40)]
    outs, stats = {}, {}
    for spec in (False, True):
        eng = kinds.engine(cfg, params, num_slots=3, max_len=128,
                           buckets=(32, 64), compute_dtype=F32,
                           steps_per_dispatch=4, spec_decode_enabled=spec,
                           spec_adaptive=False)
        reqs = [eng.submit(p, max_tokens=30) for p in prompts]
        outs[spec] = []
        for r in reqs:
            out = []
            while isinstance(item := r.out.get(timeout=300), int):
                out.append(item)
            assert not isinstance(item, BaseException), item
            outs[spec].append(out)
        stats[spec] = eng.counters()
    assert outs[True] == outs[False]
    assert [len(o) for o in outs[True]] == [30, 30, 30]
    on = stats[True]
    assert "spec_rounds" not in stats[False]
    assert on["spec_drafted"] == on["spec_rounds"] > 0
    assert on["spec_rolled_back_rows"] == (on["spec_drafted"]
                                           - on["spec_accepted"])
    if weights == "random":
        assert on["spec_accepted"] == 0
    else:
        assert 0 < on["spec_accepted"] < on["spec_drafted"]
        assert on["spec_rounds"] < 3 * 29
    # the experts' counts ride the speculative dispatch too (the block's
    # expert layer among the layers a round runs)
    assert on["moe_assignments"] > 0 and on["moe_experts_touched"] > 0
    assert on["moe_expert_layer_steps"] % (cfg.expert_layers + 1) == 0


def test_the_engine_refuses_a_cut_out_draft_under_a_pattern():
    """Speculation under a pattern is the model's own block's; a pattern
    without one, or with a recurrent kind, is refused with the reason."""
    import dataclasses
    from ray_tpu.serve.llm import LLMEngine
    cfg, params = kinds.tiny("exaone_moe")
    with pytest.raises(ValueError, match="multi-token-prediction"):
        LLMEngine(dataclasses.replace(cfg, mtp_layers=0), params=params,
                  num_slots=2, max_len=32, spec_decode_enabled=True)


# ---------------------------------- one walk over the layers, whatever the MLP

def test_the_dense_layer_and_the_experts_lie_by_layer():
    """``params["blocks"]``: the attention by kind [periods, layers a
    period, ...], the MLPs by layer (one dense, seven expert layers); one
    scan over the periods after the first, which is walked ahead of it."""
    cfg, params = kinds.tiny("exaone_moe")
    blocks = params["blocks"]
    assert blocks["window"]["attn"]["wq"].shape[:2] == (2, 3)
    assert blocks["full"]["attn"]["wq"].shape[:2] == (2, 1)
    assert blocks["window"]["attn"]["q_norm"]["scale"].shape == (2, 3, 16)
    assert blocks["dense"]["w_in"].shape == (1, 64, 96)
    assert blocks["moe"]["router"].shape == (7, 64, 16)
    assert blocks["experts"]["w_in"].shape == (7, 8, 64, 32)
    assert "moe" not in blocks["window"] and "mlp" not in blocks["window"]
    cache = decode.init_kv_cache(cfg, 2, 32, F32)
    step = lambda p, c: decode.decode_step(  # noqa: E731
        p, c, jnp.ones((2,), jnp.int32), jnp.ones((2,), bool), cfg, F32)
    scans = [e for e in kinds._scans(jax.make_jaxpr(step)(params, cache).jaxpr)
             if e.params["length"] == 1]
    assert len(scans) == 1
