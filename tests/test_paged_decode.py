"""Paged KV cache: equivalence with the dense slot cache, allocator and
prefix-cache bookkeeping (greenfield TPU inference — no reference analogue;
SURVEY §2.7 note)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models.config import TransformerConfig  # noqa: E402
from ray_tpu.models import decode, paged_decode  # noqa: E402
from ray_tpu.models.transformer import init_params  # noqa: E402

CFG = TransformerConfig(vocab_size=128, num_layers=2, hidden_size=64,
                        num_heads=4, num_kv_heads=2, mlp_size=128,
                        max_seq_len=64)
PAGE = 8


def _setup():
    params = init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
    return params


def test_paged_matches_dense_greedy():
    """Same prompt, same params: paged and dense greedy decode agree."""
    params = _setup()
    prompt = np.array([3, 14, 15, 92, 6, 5], np.int32)
    B, S = 1, 8  # bucket
    toks = np.zeros((B, S), np.int32)
    toks[0, :len(prompt)] = prompt
    lengths = jnp.array([len(prompt)], jnp.int32)
    slot_ids = jnp.array([0], jnp.int32)
    n_steps = 10

    # dense
    dcache = decode.init_kv_cache(CFG, num_slots=2, max_len=64,
                                  dtype=jnp.float32)
    dcache, dlogits = decode.prefill(params, dcache, jnp.asarray(toks),
                                     lengths, slot_ids, CFG,
                                     compute_dtype=jnp.float32)
    def greedy(cache, logits):
        """The engine's decode loop on either tree, slot 0 live."""
        first = jnp.argmax(logits, -1).astype(jnp.int32)[0]
        state = decode.init_decode_state(2, jax.random.PRNGKey(1))
        state = dict(state, tokens=state["tokens"].at[0].set(first),
                     active=state["active"].at[0].set(True),
                     budget=state["budget"].at[0].set(n_steps))
        _, _, emitted = decode.decode_state_loop(
            params, cache, state, n_steps, CFG, compute_dtype=jnp.float32)
        return [int(first)] + [int(t) for t in np.asarray(emitted)[:, 0]]

    dense_seq = greedy(dcache, dlogits)

    # paged
    pcache = paged_decode.init_paged_cache(
        CFG, num_pages=16, page_size=PAGE, num_slots=2, max_pages_per_slot=8,
        dtype=jnp.float32)
    alloc = paged_decode.PageAllocator(16)
    pages = alloc.alloc(4)  # room for prompt + 10 new tokens
    bt = np.zeros((2, 8), np.int32)
    bt[0, :4] = pages
    pcache["block_table"] = jnp.asarray(bt)
    pcache, plogits = decode.prefill(
        params, pcache, jnp.asarray(toks), lengths, slot_ids, CFG,
        compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(dlogits), np.asarray(plogits),
                               rtol=2e-4, atol=2e-4)
    assert greedy(pcache, plogits) == dense_seq


def test_prefix_reuse_matches_cold_prefill():
    """Prefill of (shared prefix + suffix) via reused pages == cold prefill."""
    params = _setup()
    full = np.arange(1, 21, dtype=np.int32)  # 20 tokens = 2 full pages + 4

    def cold():
        cache = paged_decode.init_paged_cache(
            CFG, 32, PAGE, 2, 8, dtype=jnp.float32)
        alloc = paged_decode.PageAllocator(32)
        pages = alloc.alloc(4)
        bt = np.zeros((2, 8), np.int32)
        bt[0, :4] = pages
        cache["block_table"] = jnp.asarray(bt)
        toks = np.zeros((1, 24), np.int32)
        toks[0, :20] = full
        cache, logits = decode.prefill(
            params, cache, jnp.asarray(toks), jnp.array([20], jnp.int32),
            jnp.array([0], jnp.int32), CFG, compute_dtype=jnp.float32,
            start_pos=jnp.array([0], jnp.int32))
        return cache, logits, pages

    cache, logits_cold, pages = cold()
    # register the 2 full pages in the prefix cache, then admit a second
    # sequence with the same prompt into slot 1, reusing them
    alloc = paged_decode.PageAllocator(32)
    pages2 = alloc.alloc(4)
    prefix = paged_decode.PrefixCache(alloc, PAGE)
    prefix.insert(full.tolist(), pages2)
    # (copy the cold K/V pages into the positions pages2 point at, emulating
    # that the first admit filled them)
    k = np.asarray(cache["k"])
    v = np.asarray(cache["v"])
    k2, v2 = k.copy(), v.copy()
    for src, dst in zip(pages[:2], pages2[:2]):
        k2[:, dst] = k[:, src]
        v2[:, dst] = v[:, src]
    reused, rpages = prefix.match_prefix(full.tolist())
    assert reused == 16 and rpages == pages2[:2]
    tail = alloc.alloc(2)  # pages for the 4-token suffix + decode room
    bt = np.zeros((2, 8), np.int32)
    bt[1, :2] = rpages
    bt[1, 2:4] = tail
    cache2 = {
        "k": jnp.asarray(k2), "v": jnp.asarray(v2),
        "block_table": jnp.asarray(bt),
        "length": jnp.zeros((2,), jnp.int32),
    }
    toks = np.zeros((1, 8), np.int32)
    toks[0, :4] = full[16:]
    cache2, logits_warm = decode.prefill(
        params, cache2, jnp.asarray(toks), jnp.array([4], jnp.int32),
        jnp.array([1], jnp.int32), CFG, compute_dtype=jnp.float32,
        start_pos=jnp.array([16], jnp.int32))
    np.testing.assert_allclose(np.asarray(logits_cold),
                               np.asarray(logits_warm), rtol=2e-4, atol=2e-4)


def test_page_allocator_refcounts():
    a = paged_decode.PageAllocator(8)  # pages 1..7 usable
    p = a.alloc(7)
    assert a.available() == 0 and a.alloc(1) is None
    a.incref(p[:2])
    a.release(p)          # first 2 still held by the extra ref
    assert a.available() == 5
    a.release(p[:2])
    assert a.available() == 7


def test_prefix_cache_hash_and_eviction():
    a = paged_decode.PageAllocator(16)
    pc = paged_decode.PrefixCache(a, 4)
    toks = list(range(12))
    pages = a.alloc(3)
    pc.insert(toks, pages)
    n, hit = pc.match_prefix(toks)
    assert n == 12 and hit == pages
    a.release(hit)
    # divergent prompt shares only the agreeing prefix pages
    toks2 = toks[:8] + [99, 98, 97, 96]
    n2, hit2 = pc.match_prefix(toks2)
    assert n2 == 8 and hit2 == pages[:2]
    a.release(hit2)
    # retire the sequence (drop the admit-time refs); pages survive on the
    # prefix cache's refs alone until eviction returns them
    a.release(pages)
    before = a.available()
    pc.evict_some(3)
    assert a.available() == before + 3
