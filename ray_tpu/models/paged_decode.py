"""Paged KV cache + prefix caching — vLLM-style block tables, TPU-first.

The reference has no LLM inference engine (SURVEY §2.7: ``@serve.batch`` is
the primitive); this extends ``models/decode.py``'s slot cache with paging so
HBM scales with *actual* sequence lengths instead of ``slots x max_len``
worst case, and identical prompt prefixes share cache pages.  A paged tree
(``init_paged_cache``) goes through the same calls as any other cache
(``decode.prefill``, ``decode_step``, ``window_step``, ``decode_state_loop``,
``prefill_admit``): they find its ``block_table`` and hand the walk over the
layers this file's one mixer, ``page_attention``.

TPU-first shape choices:

* The cache is one static HBM tensor ``[L, num_pages, page, NKV, D]``; a
  sequence's cache is the pages its **block table** row points at
  (``[slots, max_pages]`` int32).  Shapes never change -> jit compiles one
  prefill per length bucket and one decode step, forever — the same
  static-shape discipline as the dense cache.
* The arena is carried through the layers and written in place like every
  other cache; attention gathers each row's pages out of it (``k_all[layer,
  table]``, which XLA lowers to dynamic slices) and reads the whole gathered
  row in float32: no kernel reads pages yet, and what the gather costs
  beside the dense cache's read is not measured (no benchmark cell is paged).
* Page allocation/refcounting/prefix hashing is **host-side Python** in the
  engine (it is O(pages) per admit/retire, not per token) — the device
  program never sees the free list, only the block table array.
* Prefix caching: full pages of a prompt (page-aligned chunks) are keyed by
  a rolling content hash; an admit that hits reuses those pages read-only
  (refcount++) and prefills only the uncached suffix.  Decode always writes
  to pages at index >= ceil-boundary of the reused prefix, which are
  private by construction — no copy-on-write path is ever needed.  Rolling a
  speculative window back is a length reset for the same reason: every
  window position lands in a page the slot already owns, and what was
  rejected is unread garbage the next round overwrites.
"""

from __future__ import annotations

import hashlib
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .config import TransformerConfig
from .decode import _proj_out, _qkv, masked_attention

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
# The mixer of a paged tree
# ---------------------------------------------------------------------------

def page_attention(y, ap, cfg: TransformerConfig, k_all, v_all, i, table,
                   positions, valid):
    """One layer's attention for W new tokens a row through block tables:
    what a prefill of uncached suffixes (rows: the admitted slots, W: the
    bucket), a decode step (every slot, 1) and a speculative verify (every
    slot, k) all do.

    y: [rows, W, H], token j of a row at absolute position ``positions[row,
    j]``; k_all, v_all: the page arena [L, P, page, NKV, D], of which this
    is layer ``i``, carried and updated in place; table: [rows, max_pages],
    the rows' block tables; valid: [rows, W] (or [rows, 1]) bool, the tokens
    whose K/V are kept.  The others' (padding, an inactive slot, whose old
    pages may already belong to another sequence) and those past the table's
    span are dumped into the reserved null page 0.

    The new K/V go into their pages first, then each query attends over its
    row's gathered pages (a reused prefix, what the slot decoded so far, the
    window itself) under a causal mask on absolute positions: one code path.
    Returns (attention after its output projection [rows, W, H], k_all,
    v_all)."""
    rows, w, _ = y.shape
    cast, page, max_pages = y.dtype, k_all.shape[2], table.shape[1]
    span = max_pages * page
    q, k, v = _qkv(y, ap, cfg, positions)
    page_of = jnp.take_along_axis(
        table, jnp.minimum(positions // page, max_pages - 1), axis=1)
    page_of = jnp.where(valid & (positions < span), page_of, 0).reshape(-1)
    offset = (positions % page).reshape(-1)
    heads = (rows * w, cfg.num_kv_heads, cfg.head_dim)
    with jax.named_scope("kv_write"):
        k_all = k_all.at[i, page_of, offset].set(
            k.reshape(heads).astype(k_all.dtype), mode="drop")
        v_all = v_all.at[i, page_of, offset].set(
            v.reshape(heads).astype(v_all.dtype), mode="drop")
    with jax.named_scope("kv_read"):
        gathered = (rows, span, cfg.num_kv_heads, cfg.head_dim)
        attn = masked_attention(q, k_all[i, table].reshape(gathered),
                                v_all[i, table].reshape(gathered), positions,
                                cfg)
    return _proj_out(attn.astype(cast), ap, cast), k_all, v_all


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
