"""Paged KV cache + prefix caching — vLLM-style block tables, TPU-first.

The reference has no LLM inference engine (SURVEY §2.7: ``@serve.batch`` is
the primitive); this extends ``models/decode.py``'s slot cache with paging so
HBM scales with *actual* sequence lengths instead of ``slots x max_len``
worst case, and identical prompt prefixes share cache pages.

TPU-first shape choices:

* The cache is one static HBM tensor ``[L, num_pages, page, NKV, D]``; a
  sequence's cache is the pages its **block table** row points at
  (``[slots, max_pages]`` int32).  Shapes never change -> jit compiles one
  prefill per length bucket and one decode step, forever — the same
  static-shape discipline as the dense cache.
* Decode gathers each slot's pages with ``jnp.take`` (XLA lowers to dynamic
  slices); attention reads the whole gathered row anyway, so the gather is
  bandwidth-equivalent to the dense cache read.
* Page allocation/refcounting/prefix hashing is **host-side Python** in the
  engine (it is O(pages) per admit/retire, not per token) — the device
  program never sees the free list, only the block table array.
* Prefix caching: full pages of a prompt (page-aligned chunks) are keyed by
  a rolling content hash; an admit that hits reuses those pages read-only
  (refcount++) and prefills only the uncached suffix.  Decode always writes
  to pages at index >= ceil-boundary of the reused prefix, which are
  private by construction — no copy-on-write path is ever needed.
"""

from __future__ import annotations

import hashlib
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .config import TransformerConfig
from .transformer import Params, _norm, lm_head_logits

from .decode import (_mlp, _proj_out, _qkv, sample_per_slot)

PagedKVCache = Dict[str, jnp.ndarray]


def init_paged_cache(cfg: TransformerConfig, num_pages: int, page_size: int,
                     num_slots: int, max_pages_per_slot: int,
                     dtype=jnp.bfloat16) -> PagedKVCache:
    """Allocate the paged HBM cache + block tables.

    Page 0 is reserved as the null page (block tables point unused entries
    at it); allocators hand out pages 1..num_pages-1.
    """
    shape = (cfg.num_layers, num_pages, page_size, cfg.num_kv_heads,
             cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "block_table": jnp.zeros((num_slots, max_pages_per_slot), jnp.int32),
        "length": jnp.zeros((num_slots,), jnp.int32),
    }


def paged_cache_bytes(cfg: TransformerConfig, num_pages: int, page_size: int,
                      dtype_bytes: int = 2) -> int:
    return (2 * cfg.num_layers * num_pages * page_size * cfg.num_kv_heads
            * cfg.head_dim * dtype_bytes)


# ---------------------------------------------------------------------------
# Device programs
# ---------------------------------------------------------------------------

def paged_prefill(params: Params, cache: PagedKVCache, tokens: jnp.ndarray,
                  lengths: jnp.ndarray, slot_ids: jnp.ndarray,
                  start_pos: jnp.ndarray, cfg: TransformerConfig,
                  compute_dtype=jnp.bfloat16
                  ) -> Tuple[PagedKVCache, jnp.ndarray]:
    """Causal forward over right-padded prompt suffixes; K/V land in pages.

    tokens:   [B, S] suffix tokens (positions start_pos .. start_pos+len)
    lengths:  [B] true suffix lengths (<= S)
    slot_ids: [B] slot whose block table routes the writes
    start_pos:[B] absolute position of tokens[:, 0] (0 unless a cached
              prefix was reused; reused pages are NOT written here)
    Returns (cache, last-real-token logits [B, V] f32).

    Attention inside the suffix is pure causal self-attention PLUS reads of
    the reused prefix pages (positions < start_pos) via the block table.
    """
    b, s = tokens.shape
    page = cache["k"].shape[2]
    max_pages = cache["block_table"].shape[1]
    cast = compute_dtype
    x = params["embed"]["tokens"][tokens].astype(cast)
    positions = start_pos[:, None] + jnp.arange(s)[None]        # [B, S]
    if cfg.learned_positions:
        x = x + params["embed"]["pos"][
            jnp.minimum(positions, cfg.max_seq_len - 1)].astype(cast)
    bt = cache["block_table"][slot_ids]                          # [B, MP]
    # scatter coordinates for every suffix position
    page_idx = bt[jnp.arange(b)[:, None],
                  jnp.minimum(positions // page, max_pages - 1)]  # [B, S]
    page_off = positions % page                                  # [B, S]
    scale = cfg.head_dim ** -0.5
    reps = cfg.num_heads // cfg.num_kv_heads
    kv_span = max_pages * page
    # gathered-cache positions each query may read: absolute pos < q pos
    abs_kv_pos = jnp.arange(kv_span)[None]                       # [1, MP*page]
    valid_write = (jnp.arange(s)[None] < lengths[:, None])       # [B, S]

    def body(x, layer):
        lp, k_pages, v_pages = layer    # [P, page, NKV, D]
        y = _norm(x, lp["attn_norm"], cfg)
        q, k, v = _qkv(y, lp["attn"], cfg, positions)
        # write suffix K/V into pages first, then attend over the gathered
        # row (prefix pages + own suffix) with a causal mask on absolute
        # positions — one code path covers both.
        flat_pi = page_idx.reshape(-1)
        flat_po = page_off.reshape(-1)
        keep = valid_write.reshape(-1)
        safe_pi = jnp.where(keep, flat_pi, 0)  # dump padding into null page
        k_pages = k_pages.at[safe_pi, flat_po].set(
            k.reshape(b * s, cfg.num_kv_heads, -1).astype(k_pages.dtype),
            mode="drop")
        v_pages = v_pages.at[safe_pi, flat_po].set(
            v.reshape(b * s, cfg.num_kv_heads, -1).astype(v_pages.dtype),
            mode="drop")
        kg = jnp.take(k_pages, bt, axis=0)   # [B, MP, page, NKV, D]
        vg = jnp.take(v_pages, bt, axis=0)
        kg = kg.reshape(b, kv_span, cfg.num_kv_heads, cfg.head_dim)
        vg = vg.reshape(b, kv_span, cfg.num_kv_heads, cfg.head_dim)
        qh = q.reshape(b, s, cfg.num_kv_heads, reps, cfg.head_dim)
        scores = jnp.einsum("bsgrd,bmgd->bgrsm", qh.astype(jnp.float32),
                            kg.astype(jnp.float32)) * scale
        if cfg.attn_logit_softcap:
            c = cfg.attn_logit_softcap
            scores = c * jnp.tanh(scores / c)
        # causal on ABSOLUTE positions: [B, S, span] -> [B, 1, 1, S, span]
        causal = abs_kv_pos[:, None, :] <= positions[:, :, None]
        scores = jnp.where(causal[:, None, None, :, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bgrsm,bmgd->bsgrd", probs, vg.astype(jnp.float32))
        attn = attn.reshape(b, s, cfg.num_heads * cfg.head_dim)
        x = x + _proj_out(attn.astype(cast), lp["attn"], cast)
        x = x + _mlp(_norm(x, lp["mlp_norm"], cfg), lp, cfg)
        return x, (k_pages, v_pages)

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["blocks"], cache["k"], cache["v"]))
    x = _norm(x, params["final_norm"], cfg)
    last = jnp.take_along_axis(
        x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]
    logits = lm_head_logits(params, last, cfg)
    new_len = start_pos + lengths
    cache = {
        "k": k_new, "v": v_new,
        "block_table": cache["block_table"],
        "length": cache["length"].at[slot_ids].set(new_len),
    }
    return cache, logits


def paged_decode_step(params: Params, cache: PagedKVCache,
                      tokens: jnp.ndarray, active: jnp.ndarray,
                      cfg: TransformerConfig, compute_dtype=jnp.bfloat16
                      ) -> Tuple[PagedKVCache, jnp.ndarray]:
    """One token per active slot, attention over block-table pages."""
    n_slots = tokens.shape[0]
    page = cache["k"].shape[2]
    max_pages = cache["block_table"].shape[1]
    kv_span = max_pages * page
    cast = compute_dtype
    lengths = cache["length"]
    bt = cache["block_table"]                                    # [S, MP]
    x = params["embed"]["tokens"][tokens][:, None].astype(cast)
    if cfg.learned_positions:
        x = x + params["embed"]["pos"][
            jnp.minimum(lengths, cfg.max_seq_len - 1)][:, None].astype(cast)
    positions = lengths[:, None]
    scale = cfg.head_dim ** -0.5
    reps = cfg.num_heads // cfg.num_kv_heads
    write_page = bt[jnp.arange(n_slots),
                    jnp.minimum(lengths // page, max_pages - 1)]  # [S]
    write_off = lengths % page
    pos_mask = (jnp.arange(kv_span)[None] <= lengths[:, None])   # [S, span]

    def body(x, layer):
        lp, k_pages, v_pages = layer
        y = _norm(x, lp["attn_norm"], cfg)
        q, k, v = _qkv(y, lp["attn"], cfg, positions)
        safe_page = jnp.where(active, write_page, 0)
        k_pages = k_pages.at[safe_page, write_off].set(
            k[:, 0].astype(k_pages.dtype), mode="drop")
        v_pages = v_pages.at[safe_page, write_off].set(
            v[:, 0].astype(v_pages.dtype), mode="drop")
        kg = jnp.take(k_pages, bt, axis=0).reshape(
            n_slots, kv_span, cfg.num_kv_heads, cfg.head_dim)
        vg = jnp.take(v_pages, bt, axis=0).reshape(
            n_slots, kv_span, cfg.num_kv_heads, cfg.head_dim)
        qh = q[:, 0].reshape(n_slots, cfg.num_kv_heads, reps, cfg.head_dim)
        scores = jnp.einsum("sgrd,smgd->sgrm", qh.astype(jnp.float32),
                            kg.astype(jnp.float32)) * scale
        if cfg.attn_logit_softcap:
            c = cfg.attn_logit_softcap
            scores = c * jnp.tanh(scores / c)
        scores = jnp.where(pos_mask[:, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("sgrm,smgd->sgrd", probs, vg.astype(jnp.float32))
        attn = attn.reshape(n_slots, 1, cfg.num_heads * cfg.head_dim)
        x = x + _proj_out(attn.astype(cast), lp["attn"], cast)
        x = x + _mlp(_norm(x, lp["mlp_norm"], cfg), lp, cfg)
        return x, (k_pages, v_pages)

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["blocks"], cache["k"], cache["v"]))
    x = _norm(x, params["final_norm"], cfg)
    logits = lm_head_logits(params, x[:, 0], cfg)
    cache = {
        "k": k_new, "v": v_new,
        "block_table": cache["block_table"],
        "length": jnp.where(active, lengths + 1, lengths),
    }
    return cache, logits


def paged_verify_window(params: Params, cache: PagedKVCache,
                        tokens: jnp.ndarray, active: jnp.ndarray,
                        cfg: TransformerConfig, compute_dtype=jnp.bfloat16
                        ) -> Tuple[PagedKVCache, jnp.ndarray]:
    """Speculative-decode verify: a k-token window per slot over the paged
    cache (``speculative.verify_window`` generalized to block tables).

    tokens: [slots, k] int32 — token j sits at absolute position
    ``length[s] + j``, scattered through slot s's block-table row.
    Returns (cache, logits [slots, k, V] f32); ``length`` advances by k
    for active slots.  Callers roll ``length`` back to the accepted
    prefix afterwards — rollback is a length reset ONLY, and it is
    page-exact by construction: every window position lands in a page
    the slot's block table already owns (private pages at index >= the
    shared-prefix boundary), so rejected positions become unread garbage
    the next round overwrites.  Writes for inactive slots and positions
    past the block-table span are dumped into the reserved null page 0
    (same discipline as ``paged_decode_step``) — an inactive slot's old
    pages may already belong to another sequence.
    """
    n_slots, kwin = tokens.shape
    page = cache["k"].shape[2]
    max_pages = cache["block_table"].shape[1]
    kv_span = max_pages * page
    cast = compute_dtype
    lengths = cache["length"]                                    # [slots]
    bt = cache["block_table"]                                    # [S, MP]
    x = params["embed"]["tokens"][tokens].astype(cast)           # [S,k,H]
    positions = lengths[:, None] + jnp.arange(kwin)[None]        # [S,k]
    if cfg.learned_positions:
        x = x + params["embed"]["pos"][
            jnp.minimum(positions, cfg.max_seq_len - 1)].astype(cast)
    scale = cfg.head_dim ** -0.5
    reps = cfg.num_heads // cfg.num_kv_heads
    row = jnp.arange(n_slots)[:, None]
    page_idx = bt[row, jnp.minimum(positions // page, max_pages - 1)]
    page_off = positions % page
    valid = active[:, None] & (positions < kv_span)              # [S,k]
    safe_pi = jnp.where(valid, page_idx, 0).reshape(-1)
    flat_po = page_off.reshape(-1)
    # query j may read absolute positions <= length+j (its own position)
    causal = (jnp.arange(kv_span)[None, None]
              <= positions[:, :, None])            # [slots, k, span]

    def body(x, layer):
        lp, k_pages, v_pages = layer
        y = _norm(x, lp["attn_norm"], cfg)
        q, kk, vv = _qkv(y, lp["attn"], cfg, positions)  # [S,k,N*,D]
        k_pages = k_pages.at[safe_pi, flat_po].set(
            kk.reshape(n_slots * kwin, cfg.num_kv_heads,
                       -1).astype(k_pages.dtype), mode="drop")
        v_pages = v_pages.at[safe_pi, flat_po].set(
            vv.reshape(n_slots * kwin, cfg.num_kv_heads,
                       -1).astype(v_pages.dtype), mode="drop")
        kg = jnp.take(k_pages, bt, axis=0).reshape(
            n_slots, kv_span, cfg.num_kv_heads, cfg.head_dim)
        vg = jnp.take(v_pages, bt, axis=0).reshape(
            n_slots, kv_span, cfg.num_kv_heads, cfg.head_dim)
        qh = q.reshape(n_slots, kwin, cfg.num_kv_heads, reps, cfg.head_dim)
        scores = jnp.einsum("skgrd,smgd->skgrm", qh.astype(jnp.float32),
                            kg.astype(jnp.float32)) * scale
        if cfg.attn_logit_softcap:
            c = cfg.attn_logit_softcap
            scores = c * jnp.tanh(scores / c)
        scores = jnp.where(causal[:, :, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("skgrm,smgd->skgrd", probs,
                          vg.astype(jnp.float32))
        attn = attn.reshape(n_slots, kwin, cfg.num_heads * cfg.head_dim)
        x = x + _proj_out(attn.astype(cast), lp["attn"], cast)
        x = x + _mlp(_norm(x, lp["mlp_norm"], cfg), lp, cfg)
        return x, (k_pages, v_pages)

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["blocks"], cache["k"], cache["v"]))
    x = _norm(x, params["final_norm"], cfg)
    logits = lm_head_logits(params, x, cfg)
    cache = {
        "k": k_new, "v": v_new,
        "block_table": bt,
        "length": jnp.where(active,
                            jnp.minimum(lengths + kwin, kv_span), lengths),
    }
    return cache, logits


def paged_decode_loop(params: Params, cache: PagedKVCache,
                      tokens: jnp.ndarray, active: jnp.ndarray,
                      temperature: jnp.ndarray, key: jax.Array,
                      n_steps: int, cfg: TransformerConfig, top_k: int = 0,
                      compute_dtype=jnp.bfloat16
                      ) -> Tuple[PagedKVCache, jnp.ndarray, jnp.ndarray]:
    """``n_steps`` paged decode+sample steps in one compiled scan."""

    def body(carry, i):
        cache, toks = carry
        cache, logits = paged_decode_step(params, cache, toks, active, cfg,
                                          compute_dtype)
        nxt = sample_per_slot(logits, jax.random.fold_in(key, i),
                              temperature, top_k)
        nxt = jnp.where(active, nxt, toks)
        return (cache, nxt), nxt

    (cache, tokens), emitted = jax.lax.scan(
        body, (cache, tokens), jnp.arange(n_steps))
    return cache, tokens, emitted


def paged_prefill_admit(params: Params, cache: PagedKVCache, state,
                        tokens: jnp.ndarray, lengths: jnp.ndarray,
                        slot_ids: jnp.ndarray, start_pos: jnp.ndarray,
                        bt_rows: jnp.ndarray, temps: jnp.ndarray,
                        budgets: jnp.ndarray, eos: jnp.ndarray,
                        real_mask: jnp.ndarray, cfg: TransformerConfig,
                        top_k: int = 0, compute_dtype=jnp.bfloat16):
    """Paged admit in one program: write the admitted slots' block-table
    rows, prefill the uncached suffixes, sample, merge into the decode
    state (``decode.init_decode_state`` layout).  bt_rows: [B, MP] int32."""
    from .decode import _merge_admit

    cache = dict(cache)
    cache["block_table"] = cache["block_table"].at[slot_ids].set(bt_rows)
    cache, logits = paged_prefill(params, cache, tokens, lengths, slot_ids,
                                  start_pos, cfg, compute_dtype)
    first = sample_per_slot(logits, state["key"], temps, top_k)
    state = _merge_admit(state, first, slot_ids, temps, budgets, eos,
                         real_mask)
    return cache, state, first


def paged_decode_state_loop(params: Params, cache: PagedKVCache, state,
                            n_steps: int, cfg: TransformerConfig,
                            top_k: int = 0, compute_dtype=jnp.bfloat16):
    """Paged twin of ``decode.decode_state_loop`` (on-device active decay)."""
    temps, eos, key = state["temps"], state["eos"], state["key"]

    def body(carry, i):
        cache, toks, active, budget = carry
        cache, logits = paged_decode_step(params, cache, toks, active, cfg,
                                          compute_dtype)
        nxt = sample_per_slot(logits, jax.random.fold_in(key, i), temps,
                              top_k)
        nxt = jnp.where(active, nxt, toks)
        budget = jnp.where(active, budget - 1, budget)
        active = active & (budget > 0) & (nxt != eos)
        return (cache, nxt, active, budget), nxt

    carry = (cache, state["tokens"], state["active"], state["budget"])
    (cache, toks, active, budget), emitted = jax.lax.scan(
        body, carry, jnp.arange(n_steps))
    state = {"tokens": toks, "active": active, "budget": budget,
             "temps": temps, "eos": eos,
             "key": jax.random.fold_in(key, n_steps)}
    return cache, state, emitted


# ---------------------------------------------------------------------------
# Host-side page allocator + prefix cache
# ---------------------------------------------------------------------------

class PageAllocator:
    """Free-list page allocator with refcounts (page 0 = reserved null page).

    Prefix sharing gives pages refcount > 1; a page returns to the free list
    when its count hits zero.  Pure host Python — called per admit/retire,
    never per token."""

    def __init__(self, num_pages: int):
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._refs: Dict[int, int] = {}

    def available(self) -> int:
        return len(self._free)

    def used(self) -> int:
        """Pages currently referenced (the KV-utilization numerator; page 0
        is the reserved null page and counts as neither used nor free)."""
        return self.num_pages - 1 - len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        return pages

    def incref(self, pages: Sequence[int]):
        for p in pages:
            self._refs[p] += 1

    def release(self, pages: Sequence[int]):
        for p in pages:
            self._refs[p] -= 1
            if self._refs[p] == 0:
                del self._refs[p]
                self._free.append(p)


class PrefixCache:
    """Content-hash -> page mapping for full-page prompt prefixes.

    A chunk key is the rolling hash of ALL tokens up to the end of that page
    (so two prompts share page i only if they agree on every token before
    it).  Eviction: a cached page with refcount 1 (cache-only) is reclaimed
    lazily when the allocator runs dry."""

    def __init__(self, allocator: PageAllocator, page_size: int):
        self.alloc = allocator
        self.page = page_size
        self._map: Dict[bytes, int] = {}        # chunk hash -> page id
        self._lru: List[bytes] = []
        # FIRST-page chunk keys (insertion-ordered): the bounded routing
        # digest reads these — a request can only start reusing at page 0,
        # so deeper chunks add no routing signal
        self._first: Dict[bytes, None] = {}
        # lookup accounting (serve observability + bench_llm read these):
        # a lookup is a hit when >= 1 page was reused
        self.lookups = 0
        self.hits = 0
        self.tokens_reused = 0
        self.evictions = 0

    @staticmethod
    def _hash(tokens: Sequence[int]) -> bytes:
        return hashlib.blake2b(
            b"".join(int(t).to_bytes(4, "little") for t in tokens),
            digest_size=16).digest()

    def match_prefix(self, tokens: Sequence[int],
                     max_pages: Optional[int] = None
                     ) -> Tuple[int, List[int]]:
        """Longest reusable page-aligned prefix.  Returns (n_tokens_reused,
        page_ids) with refcounts already taken.  ``max_pages`` caps the
        reuse (the LLM engine must leave >= 1 prompt token to prefill for
        logits) — capping HERE keeps the hit/tokens_reused counters in
        agreement with what the caller actually reuses."""
        pages: List[int] = []
        n_full = len(tokens) // self.page
        if max_pages is not None:
            n_full = min(n_full, max_pages)
        reused = 0
        for i in range(n_full):
            key = self._hash(tokens[:(i + 1) * self.page])
            pid = self._map.get(key)
            if pid is None:
                break
            pages.append(pid)
            reused += self.page
        if pages:
            self.alloc.incref(pages)
        return reused, pages

    def count_lookup(self, tokens_reused: int):
        """Account one admission's prefix reuse — called once per ADMITTED
        request, not inside match_prefix: an arena-full backpressure retry
        re-runs the lookup and must not double-count, or hit_rate inflates
        exactly when the engine is under KV memory pressure."""
        self.lookups += 1
        if tokens_reused > 0:
            self.hits += 1
            self.tokens_reused += tokens_reused

    def stats(self) -> Dict[str, float]:
        return {"lookups": self.lookups, "hits": self.hits,
                "hit_rate": self.hits / self.lookups if self.lookups else 0.0,
                "tokens_reused": self.tokens_reused,
                "cached_pages": len(self._map),
                "evictions": self.evictions}

    def insert(self, tokens: Sequence[int], page_ids: Sequence[int]):
        """Register freshly-filled full pages for future reuse.  The cache
        holds one ref per registered page (released on eviction)."""
        n_full = min(len(tokens) // self.page, len(page_ids))
        for i in range(n_full):
            key = self._hash(tokens[:(i + 1) * self.page])
            if key in self._map:
                continue
            self._map[key] = page_ids[i]
            self.alloc.incref([page_ids[i]])
            self._lru.append(key)
            if i == 0:
                self._first[key] = None

    def evict_some(self, n: int = 8) -> int:
        """Drop up to n oldest cached chunks (returns pages whose only ref
        was the cache)."""
        dropped = 0
        while self._lru and dropped < n:
            key = self._lru.pop(0)
            pid = self._map.pop(key, None)
            self._first.pop(key, None)
            if pid is not None:
                self.alloc.release([pid])
                dropped += 1
        self.evictions += dropped
        return dropped

    def first_page_digest(self, cap: int = 32) -> List[str]:
        """Bounded digest of the hot first-page chunks for cache-aware
        routing: the NEWEST ``cap`` first-page keys as 8-hex-char (32-bit)
        prefixes of the chunk hash.  A router computes the same truncated
        hash over a request's first ``page`` tokens and scores replicas by
        membership — 32 bits keeps the heartbeat payload small while
        making a cross-prompt collision (a spurious routing *preference*,
        never a correctness issue) vanishingly rare at digest sizes."""
        keys = list(self._first)[-max(0, cap):]
        return [k.hex()[:8] for k in keys]
