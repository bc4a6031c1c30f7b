"""Speculative decoding: verify-window math + exact greedy equivalence
(models/speculative.py — beyond-reference TPU-native serve addition)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import decode, paged_decode, speculative  # noqa: E402
from ray_tpu.models.config import TransformerConfig  # noqa: E402
from ray_tpu.models.transformer import init_params  # noqa: E402

TARGET_CFG = TransformerConfig(vocab_size=96, num_layers=2, hidden_size=64,
                               num_heads=4, num_kv_heads=2, mlp_size=128,
                               max_seq_len=96)
DRAFT_CFG = TransformerConfig(vocab_size=96, num_layers=1, hidden_size=32,
                              num_heads=2, num_kv_heads=2, mlp_size=64,
                              max_seq_len=96)
PROMPT = np.array([3, 14, 15, 92, 6], np.int32)


PAGE = 8


def _prefilled(cfg, params, num_slots=2, paged=False):
    if paged:    # slot 0 owns pages 1.., enough for max_seq_len
        pages = cfg.max_seq_len // PAGE
        cache = paged_decode.init_paged_cache(
            cfg, 2 * pages + 1, PAGE, num_slots, pages, dtype=jnp.float32)
        cache["block_table"] = cache["block_table"].at[0].set(
            jnp.arange(1, pages + 1))
    else:
        cache = decode.init_kv_cache(cfg, num_slots=num_slots,
                                     max_len=cfg.max_seq_len,
                                     dtype=jnp.float32)
    toks = np.zeros((1, 8), np.int32)
    toks[0, :len(PROMPT)] = PROMPT
    cache, logits = decode.prefill(
        params, cache, jnp.asarray(toks),
        jnp.array([len(PROMPT)], jnp.int32), jnp.array([0], jnp.int32),
        cfg, compute_dtype=jnp.float32)
    return cache, int(jnp.argmax(logits[0]))


def _state(first, budget, eos=-1):
    """The engine's decode state with slot 0 live: greedy, ``budget`` tokens
    to go, ``first`` the token it feeds next."""
    state = decode.init_decode_state(2, jax.random.PRNGKey(0))
    return dict(state,
                tokens=state["tokens"].at[0].set(first),
                active=state["active"].at[0].set(True),
                budget=state["budget"].at[0].set(budget),
                eos=state["eos"].at[0].set(eos))


def _vanilla_greedy(params, cache, first, cfg, n_steps):
    cache, _, emitted = decode.decode_state_loop(
        params, cache, _state(first, n_steps), n_steps, cfg,
        compute_dtype=jnp.float32)
    return [first] + [int(t) for t in np.asarray(emitted)[:, 0]]


def _spec_loop(tparams, tcache, dparams, dcache, first, k, rounds, tcfg,
               dcfg, eos=-1):
    """``rounds`` rounds of the engine's speculative loop, the budget never
    the limit: the result with the per-round emit counts of slot 0."""
    out = jax.jit(
        lambda tp, tc, dp, dc, st: speculative.spec_decode_state_loop(
            tp, tc, dp, dc, st, k, rounds, tcfg, dcfg,
            compute_dtype=jnp.float32))(
        tparams, tcache, dparams, dcache, _state(first, rounds * k + 1, eos))
    return out, [int(x) for x in np.asarray(out["emit_counts"])[:, 0]]


def _rows_of(cache, slot, n):
    """K of a slot's first ``n`` positions, [L, n, NKV * D], rows or pages."""
    if "block_table" not in cache:
        return np.asarray(cache["k"])[:, slot, :n]
    table = np.asarray(cache["block_table"][slot])
    k = np.asarray(cache["k"])
    return np.stack([k[:, table[p // PAGE], p % PAGE].reshape(k.shape[0], -1)
                     for p in range(n)], 1)


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_verify_window_matches_sequential_decode_steps(paged):
    """verify_window(k) is decode_step generalized: same logits, same
    cache contents as k sequential single-token steps."""
    params = init_params(jax.random.PRNGKey(0), TARGET_CFG,
                         dtype=jnp.float32)
    cache_a, first = _prefilled(TARGET_CFG, params, paged=paged)
    cache_b = jax.tree_util.tree_map(lambda x: x, cache_a)
    window = jnp.array([[first, 7, 21, 3], [0, 0, 0, 0]], jnp.int32)
    active = jnp.array([True, False])

    cache_a, wlogits = speculative.verify_window(
        params, cache_a, window, active, TARGET_CFG,
        compute_dtype=jnp.float32)

    step_logits = []
    for j in range(4):
        cache_b, lg = decode.decode_step(
            params, cache_b, window[:, j], active, TARGET_CFG,
            compute_dtype=jnp.float32)
        step_logits.append(np.asarray(lg))
    np.testing.assert_allclose(np.asarray(wlogits)[0],
                               np.stack(step_logits)[:, 0], rtol=2e-4,
                               atol=2e-4)
    assert int(cache_a["length"][0]) == int(cache_b["length"][0])
    np.testing.assert_allclose(
        _rows_of(cache_a, 0, int(cache_a["length"][0])),
        _rows_of(cache_b, 0, int(cache_b["length"][0])),
        rtol=2e-4, atol=2e-4)


def test_spec_decode_equals_vanilla_greedy():
    """The whole point: with a DIFFERENT (weaker) draft model, greedy
    speculative output is token-identical to vanilla greedy decode."""
    tparams = init_params(jax.random.PRNGKey(0), TARGET_CFG,
                          dtype=jnp.float32)
    dparams = init_params(jax.random.PRNGKey(7), DRAFT_CFG,
                          dtype=jnp.float32)
    tcache, first = _prefilled(TARGET_CFG, tparams)
    dcache, _ = _prefilled(DRAFT_CFG, dparams)
    vcache, vfirst = _prefilled(TARGET_CFG, tparams)
    assert vfirst == first
    vanilla = _vanilla_greedy(tparams, vcache, first, TARGET_CFG, 24)

    k, rounds = 4, 6
    out, accs = _spec_loop(tparams, tcache, dparams, dcache, first, k, rounds,
                           TARGET_CFG, DRAFT_CFG)
    n = int(out["counts"][0])
    assert rounds <= n <= rounds * k   # >=1 token per round, <=k
    spec_seq = [first] + [int(t) for t in np.asarray(out["tokens"])[0, :n]]
    assert spec_seq == vanilla[:len(spec_seq)], (spec_seq, vanilla)
    # inactive slot untouched
    assert int(out["counts"][1]) == 0
    # per-round emission accounting is consistent
    assert sum(accs) == n


def test_self_draft_accepts_every_token():
    """Draft == target: every draft token matches the target argmax, so
    each round emits the maximum k tokens ((k-1 drafts + bonus))."""
    params = init_params(jax.random.PRNGKey(0), TARGET_CFG,
                         dtype=jnp.float32)
    tcache, first = _prefilled(TARGET_CFG, params)
    dcache, _ = _prefilled(TARGET_CFG, params)
    _out, accs = _spec_loop(params, tcache, params, dcache, first, 4, 3,
                            TARGET_CFG, TARGET_CFG)
    assert accs == [4, 4, 4]


def test_eos_deactivates_slot():
    tparams = init_params(jax.random.PRNGKey(0), TARGET_CFG,
                          dtype=jnp.float32)
    dparams = init_params(jax.random.PRNGKey(7), DRAFT_CFG,
                          dtype=jnp.float32)
    tcache, first = _prefilled(TARGET_CFG, tparams)
    dcache, _ = _prefilled(DRAFT_CFG, dparams)
    vcache, _ = _prefilled(TARGET_CFG, tparams)
    vanilla = _vanilla_greedy(tparams, vcache, first, TARGET_CFG, 24)
    eos = vanilla[3]  # force an eos hit a few tokens in

    out, accs = _spec_loop(tparams, tcache, dparams, dcache, first, 4, 6,
                           TARGET_CFG, DRAFT_CFG, eos=eos)
    assert not bool(out["state"]["active"][0])
    n = int(out["counts"][0])
    emitted = [int(t) for t in np.asarray(out["tokens"])[0, :n]]
    assert eos in emitted
    # rounds after the eos round emit nothing
    eos_round = next(i for i, _ in enumerate(accs)
                     if eos in emitted[:sum(accs[:i + 1])])
    assert all(a == 0 for a in accs[eos_round + 1:])
