"""Speculative decode under continuous batching + prefix-cache-aware routing.

Three contracts pinned here (no cluster needed):

* greedy EXACTNESS — a spec-enabled engine streams byte-identical tokens
  to the plain engine, dense and paged, through the real scheduler thread;
* acceptance ACCOUNTING — every spec counter is derived from per-round
  emit counts alone and must sum to exactly the tokens that reached the
  client streams;
* paged ROLLBACK — after rejected drafts roll the cache length back, the
  pages hold exactly what a fresh prefill of the verified sequence writes;
* router digest lockstep + scoring — the router-side block hash matches
  the replica digest byte-for-byte, and p2c×prefix scoring degrades to
  pure p2c on ties / absent digests.
"""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models.config import TransformerConfig  # noqa: E402
from ray_tpu.serve.llm import LLMEngine  # noqa: E402

TINY = TransformerConfig(vocab_size=128, num_layers=2, hidden_size=64,
                         num_heads=4, num_kv_heads=2, mlp_size=128,
                         max_seq_len=128)

PROMPTS = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8], [5, 5, 5],
           [9, 8, 7, 6, 5, 4]]
MAX_TOKENS = [12, 5, 9, 1]


def _drain(req):
    from ray_tpu.serve.llm import _FLUSH
    out = []
    while True:
        item = req.out.get(timeout=120)
        if item is _FLUSH:
            return out
        if isinstance(item, BaseException):
            raise item
        out.append(item)


def _run_engine(spec: bool, paged: bool):
    kw = dict(num_slots=4, max_len=64, buckets=(16,), seed=7,
              steps_per_dispatch=4)
    if paged:
        kw.update(paged=True, page_size=8)
    if spec:
        kw.update(spec_decode_enabled=True, spec_k=4, spec_draft_layers=1)
    eng = LLMEngine(TINY, **kw)
    reqs = [eng.submit(list(p), max_tokens=m)
            for p, m in zip(PROMPTS, MAX_TOKENS)]
    outs = [_drain(r) for r in reqs]
    bd = eng.breakdown()
    eng.shutdown()
    return outs, bd


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_spec_engine_matches_vanilla_greedy(paged):
    """Greedy acceptance keeps the output EXACTLY equal to the plain
    engine — including budget-clamped (max_tokens=1) and mid-window EOS
    slots — while the accounting identities hold: every streamed token is
    a spec-emitted token, rollback = drafted - accepted."""
    base, _ = _run_engine(False, paged)
    spec, bd = _run_engine(True, paged)
    assert [len(o) for o in base] == MAX_TOKENS
    assert spec == base
    sp = bd["spec"]
    assert sp["draft_errors"] == 0
    assert sp["rounds"] > 0
    # every token the clients saw was emitted by a spec round, EXCEPT each
    # request's first token (that one comes from the prefill sample)
    assert sp["tokens"] == sum(MAX_TOKENS) - len(PROMPTS)
    assert 0 <= sp["accepted"] <= sp["drafted"]
    assert sp["rollback_tokens"] == sp["drafted"] - sp["accepted"]
    assert 0.0 <= sp["acceptance_rate"] <= 1.0
    assert sp["tokens_per_round"] >= 1.0  # >= 1 token per verify, always


# --------------------------------------------------------------- rollback


def _paged_admit(params, cache, slot, prompt, next_free, max_pages, cfg):
    """Host-side stand-in for the engine's admit: point the slot's block
    table at fresh pages and prefill the whole prompt from position 0."""
    from ray_tpu.models import decode
    bt = np.zeros((max_pages,), np.int32)
    bt[:] = range(next_free, next_free + max_pages)
    cache = dict(cache, block_table=cache["block_table"].at[slot].set(
        jnp.asarray(bt)))
    toks = np.zeros((1, 64), np.int32)
    toks[0, :len(prompt)] = prompt
    cache, logits = decode.prefill(
        params, cache, jnp.asarray(toks),
        jnp.asarray([len(prompt)], jnp.int32),
        jnp.asarray([slot], jnp.int32), cfg, jnp.float32)
    return cache, int(jnp.argmax(logits[0])), next_free + max_pages


def _gather_kv(cache, slot, n_pos, page):
    """Per-position K/V rows through the slot's block table."""
    bt = np.asarray(cache["block_table"][slot])
    ks = [np.asarray(cache["k"][:, bt[p // page], p % page])
          for p in range(n_pos)]
    vs = [np.asarray(cache["v"][:, bt[p // page], p % page])
          for p in range(n_pos)]
    return np.stack(ks, 1), np.stack(vs, 1)  # [L, n_pos, NKV, D]


def test_spec_paged_rollback_matches_fresh_prefill():
    """After spec rounds (with rejections AND a budget clamp mid-window),
    the paged cache is indistinguishable from a fresh prefill of the
    verified sequence: same lengths, same K/V in every live position.

    Contract: the cache covers prompt + all streamed tokens EXCEPT the
    last one (whose KV lands next round when it is fed back)."""
    from ray_tpu.models import decode as dec, paged_decode as pd
    from ray_tpu.models import speculative as spec

    page, max_pages, slots = 8, 12, 2
    params = transformer_params()
    dcfg = dataclasses.replace(TINY, num_layers=1)
    dparams = spec.make_draft_params(params, 1)

    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5]
    cache = pd.init_paged_cache(TINY, num_pages=64, page_size=page,
                                num_slots=slots, max_pages_per_slot=max_pages,
                                dtype=jnp.float32)
    cache, first, nf = _paged_admit(params, cache, 0, prompt, 1, max_pages,
                                    TINY)
    # draft cache is always dense and ingests the FULL prompt
    dcache = dec.init_kv_cache(dcfg, slots, 128, jnp.float32)
    toks = np.zeros((1, 64), np.int32)
    toks[0, :len(prompt)] = prompt
    dcache, _ = dec.prefill(dparams, dcache, jnp.asarray(toks),
                            jnp.asarray([len(prompt)], jnp.int32),
                            jnp.asarray([0], jnp.int32), dcfg, jnp.float32)

    budget = 10
    state = dec.init_decode_state(slots, jax.random.PRNGKey(5))
    state = dict(state,
                 tokens=state["tokens"].at[0].set(first),
                 active=state["active"].at[0].set(True),
                 budget=state["budget"].at[0].set(budget))
    k, rounds = 4, 5  # rounds*k > budget => the budget clamp path runs
    res = spec.spec_decode_state_loop(params, cache, dparams, dcache, state,
                                      k, rounds, TINY, dcfg, top_k=0,
                                      compute_dtype=jnp.float32)
    cnt = int(res["counts"][0])
    emitted = [int(t) for t in np.asarray(res["tokens"][0])[:cnt]]
    assert cnt == budget  # clamp stopped emission exactly at the budget
    assert int(np.asarray(res["emit_counts"])[:, 0].sum()) == cnt

    tcache = res["target_cache"]
    assert int(tcache["length"][0]) == len(prompt) + cnt
    verified = prompt + [first] + emitted[:cnt - 1]
    assert len(verified) == len(prompt) + cnt

    fresh = pd.init_paged_cache(TINY, num_pages=64, page_size=page,
                                num_slots=slots, max_pages_per_slot=max_pages,
                                dtype=jnp.float32)
    fresh, _, _ = _paged_admit(params, fresh, 0, verified, 1, max_pages, TINY)
    k_got, v_got = _gather_kv(tcache, 0, len(verified), page)
    k_want, v_want = _gather_kv(fresh, 0, len(verified), page)
    # float32 tolerance, not bit equality: the verify window computes K/V
    # for k+1 tokens in one [slots, k+1, H] matmul, the fresh prefill in one
    # [1, S, H] matmul, and XLA's CPU backend (jax 0.9.0) picks a different
    # reduction order for the two shapes -- 1 element of 1,344 differs by
    # 1.3e-6.  A wrong rollback would differ by O(1).
    np.testing.assert_allclose(k_got, k_want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(v_got, v_want, rtol=1e-5, atol=1e-5)


def transformer_params():
    from ray_tpu.models import transformer
    from ray_tpu.models import speculative as spec
    params = transformer.init_params(jax.random.PRNGKey(0), TINY,
                                     dtype=jnp.float32)
    # damped tail => the 1-layer draft agrees with the target often enough
    # that both the accept and the reject/rollback paths run
    return spec.damp_block_outputs(params, 0.05, from_layer=1)


# ------------------------------------------------- routing digest + scoring


def test_router_block_hash_matches_replica_digest():
    """The router's truncated first-page hash MUST match what the replica
    digest advertises — a drift turns every routing decision into a miss."""
    from ray_tpu.models.paged_decode import PageAllocator, PrefixCache
    from ray_tpu.serve.router import _block_hash

    page = 8
    alloc = PageAllocator(num_pages=16)
    cache = PrefixCache(alloc, page)
    tokens = [11, 22, 33, 44, 55, 66, 77, 88, 99, 101]  # 1 full page + tail
    pages = alloc.alloc(2)
    cache.insert(tokens, pages)
    digest = cache.first_page_digest(cap=4)
    assert _block_hash(tokens, page) in digest
    # a different first page is NOT in the digest
    assert _block_hash([1] + tokens[1:], page) not in digest
    # shorter-than-a-page prompts registered nothing
    assert len(digest) == 1


def test_choose_replica_scoring_prefers_prefix_hit():
    """_score_candidates: a digest hit wins against equal load, falls back
    to pure p2c when no candidate has a digest, and weight semantics keep
    ties on the p2c pick."""
    from ray_tpu.serve.router import Router, _block_hash

    page = 8
    tokens = list(range(1, 17))
    h = _block_hash(tokens, page)
    r = Router()
    r._digests = {"rep-a": (page, frozenset({h})),
                  "rep-b": (page, frozenset({"00000000"}))}
    # equal load: the hit (rep-a) must win even when p2c picked rep-b
    got = r._score_candidates("d", ("rep-a", 3), ("rep-b", 3), "rep-b",
                              tokens)
    assert got == "rep-a"
    # hit loses to a big enough load gap: (9+1)*(1-0.5) > (1+1)*1
    got = r._score_candidates("d", ("rep-a", 9), ("rep-b", 1), "rep-b",
                              tokens)
    assert got == "rep-b"
    # no digests at all -> fallback keeps the p2c pick
    r._digests = {}
    assert r._score_candidates("d", ("rep-a", 3), ("rep-b", 0), "rep-b",
                               tokens) == "rep-b"
    # prompt shorter than one page -> nothing reusable -> scores tie on
    # load alone; equal load keeps the p2c pick
    r._digests = {"rep-a": (page, frozenset({h}))}
    assert r._score_candidates("d", ("rep-a", 2), ("rep-b", 2), "rep-b",
                               tokens[:4]) == "rep-b"


def test_hint_tokens_extraction():
    """Only LLM-shaped payloads produce a routing hint."""
    from ray_tpu.serve.router import _hint_tokens

    assert _hint_tokens(({"tokens": [1, 2, 3]},), {}) == [1, 2, 3]
    assert _hint_tokens((), {"tokens": (4, 5)}) == [4, 5]
    assert _hint_tokens(({"tokens": "abc"},), {}) is None
    assert _hint_tokens(("not a dict",), {}) is None
    assert _hint_tokens((), {}) is None
