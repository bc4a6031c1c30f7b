"""Autoregressive decoding with a slot-based KV cache — the inference side of
the transformer (training side: ``transformer.apply_trunk``).

The reference has no LLM inference engine (SURVEY §2.7 note: no vLLM in the
snapshot; ``@serve.batch`` is the primitive) — this is greenfield TPU-first
code backing ``ray_tpu.serve.llm``.

TPU-first design:
* **Static shapes.**  The cache is a fixed [L, slots, max_len, KV * D] HBM
  tensor (a position's KV heads side by side in one row: one layout for 8
  heads and for 30, ``ops/decode_attention.py``); a "slot" is one
  sequence's reserved cache row.  Continuous batching
  admits/retires sequences by slot index — tensor shapes never change, so jit
  compiles exactly two programs (one prefill per length bucket, one decode
  step) and reuses them forever.
* **Scan over layers**: compile time is depth-independent, matching
  ``apply_trunk``.  The stacked cache never goes through the scan as xs/ys
  (that slices every layer out and restacks all of it, three passes over the
  whole cache a step).  Decode carries it and scatters one row per slot at
  ``[layer, slot, length]``; prefill returns each layer's new K/V as ys and
  writes them after the scan.  Either way the donated buffer is updated in
  place and the only bytes written are the new rows.
* **Prefill** runs the normal causal forward over a right-padded [B, bucket]
  block and writes K/V for every position; padding beyond a sequence's length
  is never *read* because decode masks by per-slot length (causality makes
  the writes at pad positions harmless: real positions never attend to them).
* **Decode** is one token per active slot: q at position `len`, attention
  over the slot's rows up to it, read where they lie in the stack by one
  kernel that takes the layer index and the live lengths
  (``ops.decode_attention.decode_attn``): no layer's slab is sliced out,
  and blocks past a slot's length, or of an inactive slot, are not fetched.

No torch, no dynamic shapes, no per-request Python in the hot loop.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import TransformerConfig
from .transformer import Params, _norm, _rope, lm_head_logits

KVCache = Dict[str, jnp.ndarray]


def init_kv_cache(cfg: TransformerConfig, num_slots: int, max_len: int,
                  dtype=jnp.bfloat16) -> KVCache:
    """Allocate the HBM cache: K/V per layer per slot, plus per-slot lengths.
    A model with a ``layer_pattern`` keeps K/V for its full-attention layers
    only and a recurrent state for the others (``hybrid.init_cache``)."""
    if cfg.layer_pattern:
        from . import hybrid
        return hybrid.init_cache(cfg, num_slots, max_len, dtype)
    shape = (cfg.num_layers, num_slots, max_len,
             cfg.num_kv_heads * cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "length": jnp.zeros((num_slots,), jnp.int32),
    }


def cache_bytes(cfg: TransformerConfig, num_slots: int, max_len: int,
                dtype_bytes: int = 2) -> int:
    return (2 * cfg.num_layers * num_slots * max_len * cfg.num_kv_heads
            * cfg.head_dim * dtype_bytes)


def cache_gauges(cfg: TransformerConfig, cache: KVCache) -> Dict[str, int]:
    """What a cache tree holds, by kind of state: bytes of keys and values
    (per token) and of everything else a slot keeps (per sequence: a
    recurrent state, a convolution tail), and the layers of each kind."""
    def nbytes(a):
        return int(a.size) * jnp.dtype(a.dtype).itemsize

    control = ("length", "block_table")
    kv = sum(nbytes(a) for n, a in cache.items() if n in ("k", "v"))
    state = sum(nbytes(a) for n, a in cache.items()
                if n not in ("k", "v") + control)
    return {"cache_kv_bytes": kv, "cache_state_bytes": state,
            "linear_layers": cfg.linear_layers,
            "full_layers": cfg.full_layers}


# ---------------------------------------------------------------------------
# Shared per-layer attention pieces
# ---------------------------------------------------------------------------

@jax.named_scope("attn")
def _qkv(x, p, cfg: TransformerConfig, positions):
    """x: [B, S, H] -> q [B,S,NH,D], k/v [B,S,NKV,D] with RoPE applied."""
    b, s, _ = x.shape
    cast = x.dtype
    q = x @ p["wq"].astype(cast)
    k = x @ p["wk"].astype(cast)
    v = x @ p["wv"].astype(cast)
    if "bq" in p:
        q = q + p["bq"].astype(cast)
        k = k + p["bk"].astype(cast)
        v = v + p["bv"].astype(cast)
    if cfg.qk_norm:                 # over the whole row, before the heads
        q = _norm(q, p["q_norm"], cfg)
        k = _norm(k, p["k_norm"], cfg)
    q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    if cfg.use_rope:
        q = _rope_per_row(q, positions, cfg.rope_theta)
        k = _rope_per_row(k, positions, cfg.rope_theta)
    return q, k, v


def _rope_per_row(x: jnp.ndarray, positions: jnp.ndarray,
                  theta: float) -> jnp.ndarray:
    """RoPE with per-batch-row positions. x: [B, S, H, D]; positions: [B, S]."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


@jax.named_scope("mlp")
def _mlp(y, p, cfg: TransformerConfig):
    cast = y.dtype
    if cfg.num_experts > 1:
        from ..ops import moe as moe_ops
        out, _ = moe_ops.moe_mlp(
            y, p["moe"]["router"], p["moe"]["w_gate"], p["moe"]["w_in"],
            p["moe"]["w_out"], cfg.experts_per_token,
            cfg.expert_capacity_factor)
        return out
    mp = p["mlp"]
    if cfg.use_swiglu:
        return (jax.nn.silu(y @ mp["w_gate"].astype(cast))
                * (y @ mp["w_in"].astype(cast))) @ mp["w_out"].astype(cast)
    h = jax.nn.gelu(y @ mp["w_in"].astype(cast) + mp["b_in"].astype(cast))
    return h @ mp["w_out"].astype(cast) + mp["b_out"].astype(cast)


@jax.named_scope("attn")
def _proj_out(attn, p, cast):
    out = attn @ p["wo"].astype(cast)
    if "bo" in p:
        out = out + p["bo"].astype(cast)
    return out


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def prefill_attention(y, ap, cfg: TransformerConfig, positions):
    """One layer's causal attention over whole right-padded rows.  y: [B, S,
    H] -> (attention after its output projection [B, S, H], this layer's
    k and v [B, S, NKV, D] for the cache)."""
    from ..ops.attention import mha
    b, s, _ = y.shape
    q, k, v = _qkv(y, ap, cfg, positions)
    with jax.named_scope("attn"):
        attn = mha(q, k, v, causal=True, logit_softcap=cfg.attn_logit_softcap)
    return _proj_out(attn.reshape(b, s, -1), ap, y.dtype), k, v


def prefill(params: Params, cache: KVCache, tokens: jnp.ndarray,
            lengths: jnp.ndarray, slot_ids: jnp.ndarray,
            cfg: TransformerConfig,
            compute_dtype=jnp.bfloat16) -> Tuple[KVCache, jnp.ndarray]:
    """Run the causal forward over right-padded prompts, populate the cache.

    tokens: [B, S] int32 (right-padded to the bucket length S)
    lengths: [B] true prompt lengths; slot_ids: [B] cache rows to fill.
    Returns (cache, last-token logits [B, V] f32).
    """
    if cfg.layer_pattern:
        from . import hybrid
        return hybrid.prefill(params, cache, tokens, lengths, slot_ids, cfg,
                              compute_dtype)
    b, s = tokens.shape
    cast = compute_dtype
    x = params["embed"]["tokens"][tokens].astype(cast)
    if cfg.learned_positions:
        x = x + params["embed"]["pos"][:s][None].astype(cast)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    def body(x, lp):
        out, k, v = prefill_attention(_norm(x, lp["attn_norm"], cfg),
                                      lp["attn"], cfg, positions)
        x = x + out
        x = x + _mlp(_norm(x, lp["mlp_norm"], cfg), lp, cfg)
        return x, (k.reshape(b, s, -1).astype(cache["k"].dtype),
                   v.reshape(b, s, -1).astype(cache["v"].dtype))

    x, (k_rows, v_rows) = jax.lax.scan(body, x, params["blocks"])
    # write every layer's K/V into the slots, in place on the donated cache
    # (padded tail included; decode's length mask keeps it unread)
    with jax.named_scope("kv_write"):
        k_new = cache["k"].at[:, slot_ids, :s].set(k_rows)
        v_new = cache["v"].at[:, slot_ids, :s].set(v_rows)
    x = _norm(x, params["final_norm"], cfg)
    # logits of each prompt's *last real token* (next-token distribution)
    last = jnp.take_along_axis(
        x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]  # [B, H]
    logits = lm_head_logits(params, last, cfg)
    cache = {
        "k": k_new, "v": v_new,
        "length": cache["length"].at[slot_ids].set(lengths),
    }
    return cache, logits


# ---------------------------------------------------------------------------
# Decode step
# ---------------------------------------------------------------------------

def decode_attention(y, ap, cfg: TransformerConfig, k_all, v_all, i, lengths,
                     active):
    """One layer's attention for one new token a slot.  y: [slots, 1, H];
    k_all, v_all: the stacked cache [layers, slots, max_len, NKV * D], of
    which this is layer ``i``.  Appends the token's K/V at ``[i, slot,
    length]`` in place and attends over the layer's rows up to it, of the
    ``active`` slots only (an inactive slot keeps a stale length; it is
    read as length 0 and its output is zeros).  Returns (attention after
    its output projection [slots, 1, H], k_all, v_all)."""
    from ..ops.decode_attention import decode_attn
    n_slots, cast = y.shape[0], y.dtype
    max_len = k_all.shape[2]
    slot_idx = jnp.arange(n_slots)
    q, k, v = _qkv(y, ap, cfg, lengths[:, None])  # q:[S,1,NH,D] k/v:[S,1,NKV,D]
    # append at position `length` (one row per slot of this layer)
    with jax.named_scope("kv_write"):
        k_all = k_all.at[i, slot_idx, lengths].set(
            k.reshape(n_slots, -1).astype(k_all.dtype))
        v_all = v_all.at[i, slot_idx, lengths].set(
            v.reshape(n_slots, -1).astype(v_all.dtype))
    # positions that count: up to and with the new token's
    live = jnp.where(active, jnp.minimum(lengths + 1, max_len), 0)
    with jax.named_scope("kv_read"):
        attn = decode_attn(q[:, 0], k_all, v_all, i, live, cfg.num_kv_heads,
                           cfg.attn_logit_softcap)
    attn = attn.reshape(n_slots, 1, cfg.num_heads * cfg.head_dim)
    return _proj_out(attn.astype(cast), ap, cast), k_all, v_all


def decode_step(params: Params, cache: KVCache, tokens: jnp.ndarray,
                active: jnp.ndarray, cfg: TransformerConfig,
                compute_dtype=jnp.bfloat16) -> Tuple[KVCache, jnp.ndarray]:
    """One autoregressive step for every active slot.

    tokens: [slots] int32 — the last emitted token per slot
    active: [slots] bool — inactive slots compute garbage that is masked out
    Returns (cache, logits [slots, V] f32).  Appends K/V at position `length`
    and increments `length` for active slots.
    """
    if cfg.layer_pattern:
        from . import hybrid
        return hybrid.decode_step(params, cache, tokens, active, cfg,
                                  compute_dtype)
    max_len = cache["k"].shape[2]
    cast = compute_dtype
    lengths = cache["length"]                                  # [slots]
    x = params["embed"]["tokens"][tokens][:, None].astype(cast)  # [S,1,H]
    if cfg.learned_positions:
        x = x + params["embed"]["pos"][jnp.minimum(
            lengths, cfg.max_seq_len - 1)][:, None].astype(cast)

    def body(carry, layer):
        x, k_all, v_all = carry         # k/v_all: [L, slots, max_len, NKV*D]
        lp, i = layer
        out, k_all, v_all = decode_attention(
            _norm(x, lp["attn_norm"], cfg), lp["attn"], cfg, k_all, v_all,
            i, lengths, active)
        x = x + out
        x = x + _mlp(_norm(x, lp["mlp_norm"], cfg), lp, cfg)
        return (x, k_all, v_all), None

    (x, k_new, v_new), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["blocks"], jnp.arange(cache["k"].shape[0])))
    x = _norm(x, params["final_norm"], cfg)
    logits = lm_head_logits(params, x[:, 0], cfg)
    cache = {
        "k": k_new, "v": v_new,
        "length": jnp.where(active, jnp.minimum(lengths + 1, max_len),
                            lengths),
    }
    return cache, logits


def sample(logits: jnp.ndarray, key: jax.Array, temperature: float = 0.0,
           top_k: int = 0) -> jnp.ndarray:
    """Greedy (temperature 0) or temperature/top-k sampling. logits: [B, V]."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0:
        thresh = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < thresh, -1e30, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


def sample_per_slot(logits: jnp.ndarray, key: jax.Array,
                    temperature: jnp.ndarray, top_k: int = 0) -> jnp.ndarray:
    """Traceable mixed sampling: per-row temperature (0 = greedy).

    logits: [B, V]; temperature: [B] f32.  Rows with temperature 0 take the
    argmax; others sample categorically at their temperature.  Lives inside
    the jitted decode step so sampled tokens never leave the device.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / t
    if top_k > 0:
        thresh = jax.lax.top_k(scaled, top_k)[0][..., -1:]
        scaled = jnp.where(scaled < thresh, -1e30, scaled)
    drawn = jax.random.categorical(key, scaled).astype(jnp.int32)
    return jnp.where(temperature > 0.0, drawn, greedy)


def decode_and_sample(params: Params, cache: KVCache, tokens: jnp.ndarray,
                      active: jnp.ndarray, temperature: jnp.ndarray,
                      key: jax.Array, cfg: TransformerConfig,
                      top_k: int = 0,
                      compute_dtype=jnp.bfloat16
                      ) -> Tuple[KVCache, jnp.ndarray]:
    """One decode step with on-device sampling: the whole autoregressive
    recurrence (embed -> attend-over-cache -> sample -> feed back) stays on
    the device, so the host only reads tokens back lazily (the engine fetches
    with a pipelined lag, so a readback never stalls the next dispatch).
    Inactive slots keep their token."""
    cache, logits = decode_step(params, cache, tokens, active, cfg,
                                compute_dtype)
    nxt = sample_per_slot(logits, key, temperature, top_k)
    return cache, jnp.where(active, nxt, tokens)


def decode_loop(params: Params, cache: KVCache, tokens: jnp.ndarray,
                active: jnp.ndarray, temperature: jnp.ndarray,
                key: jax.Array, n_steps: int, cfg: TransformerConfig,
                top_k: int = 0, compute_dtype=jnp.bfloat16
                ) -> Tuple[KVCache, jnp.ndarray, jnp.ndarray]:
    """``n_steps`` decode steps in one compiled program (``lax.scan``).

    One host dispatch + one readback per *n_steps* tokens-per-slot instead of
    per token: a decode step of a small batch is short, so per-token host
    dispatch would leave the chip waiting on the host.  Returns
    (cache, final tokens [slots], emitted [n_steps, slots])."""

    def body(carry, i):
        cache, toks = carry
        cache, nxt = decode_and_sample(
            params, cache, toks, active, temperature,
            jax.random.fold_in(key, i), cfg, top_k, compute_dtype)
        return (cache, nxt), nxt

    (cache, tokens), emitted = jax.lax.scan(
        body, (cache, tokens), jnp.arange(n_steps))
    return cache, tokens, emitted


def prefill_and_sample(params: Params, cache: KVCache, tokens: jnp.ndarray,
                       lengths: jnp.ndarray, slot_ids: jnp.ndarray,
                       temperature: jnp.ndarray, key: jax.Array,
                       cfg: TransformerConfig, top_k: int = 0,
                       compute_dtype=jnp.bfloat16
                       ) -> Tuple[KVCache, jnp.ndarray]:
    """Prefill + sample each prompt's first output token on device."""
    cache, logits = prefill(params, cache, tokens, lengths, slot_ids, cfg,
                            compute_dtype)
    return cache, sample_per_slot(logits, key, temperature, top_k)


# ---------------------------------------------------------------------------
# Device-resident autoregressive state (zero host ops in the serving loop)
# ---------------------------------------------------------------------------
#
# Every EAGER op or small host->device transfer is its own dispatch and, where
# its result is read, a sync point; a jitted dispatch is async.  The serving
# engine therefore keeps the complete per-slot
# autoregressive state ON DEVICE and only ever calls two jitted programs:
#
#   decode_state_loop(params, cache, state, n)   — n steps, state evolves
#   prefill_admit(params, cache, state, <numpy admit batch>)
#
# `state` carries tokens/active/temps/budget/eos + the PRNG key; active slots
# DECAY on device (budget exhausted or EOS sampled) by the same predicate the
# host applies to the emitted tokens, so the host's scheduling mirror stays
# consistent without a single eager device write.

def init_decode_state(num_slots: int, key: jax.Array) -> Dict[str, Any]:
    """All-device per-slot autoregressive state (incl. the scratch slot)."""
    return {
        "tokens": jnp.zeros((num_slots,), jnp.int32),
        "active": jnp.zeros((num_slots,), bool),
        "temps": jnp.zeros((num_slots,), jnp.float32),
        "budget": jnp.zeros((num_slots,), jnp.int32),
        "eos": jnp.full((num_slots,), -1, jnp.int32),
        "key": key,
    }


def _merge_admit(state: Dict[str, Any], first: jnp.ndarray,
                 slot_ids: jnp.ndarray, temps: jnp.ndarray,
                 budgets: jnp.ndarray, eos: jnp.ndarray,
                 real_mask: jnp.ndarray) -> Dict[str, Any]:
    """Merge one admit batch into the decode state.  The sampled first token
    spends one unit of budget; a 1-token request (or an immediate EOS) is
    born inactive."""
    budgets = budgets - 1
    act = real_mask & (budgets > 0) & (first != eos)
    return {
        "tokens": state["tokens"].at[slot_ids].set(first),
        "active": state["active"].at[slot_ids].set(act),
        "temps": state["temps"].at[slot_ids].set(temps),
        "budget": state["budget"].at[slot_ids].set(budgets),
        "eos": state["eos"].at[slot_ids].set(eos),
        "key": jax.random.fold_in(state["key"], 0x5EED),
    }


def prefill_admit(params: Params, cache: KVCache, state: Dict[str, Any],
                  tokens: jnp.ndarray, lengths: jnp.ndarray,
                  slot_ids: jnp.ndarray, temps: jnp.ndarray,
                  budgets: jnp.ndarray, eos: jnp.ndarray,
                  real_mask: jnp.ndarray, cfg: TransformerConfig,
                  top_k: int = 0, compute_dtype=jnp.bfloat16):
    """Prefill + sample + merge into the decode state, one fixed-shape
    program.  Returns (cache, state, first_tokens [B])."""
    cache, logits = prefill(params, cache, tokens, lengths, slot_ids, cfg,
                            compute_dtype)
    first = sample_per_slot(logits, state["key"], temps, top_k)
    state = _merge_admit(state, first, slot_ids, temps, budgets, eos,
                         real_mask)
    return cache, state, first


def decode_state_loop(params: Params, cache: KVCache, state: Dict[str, Any],
                      n_steps: int, cfg: TransformerConfig, top_k: int = 0,
                      compute_dtype=jnp.bfloat16):
    """``n_steps`` decode+sample steps with on-device active decay.

    Returns (cache, state, emitted [n_steps, slots]).  A slot goes inactive
    the step its budget hits zero or it samples its EOS token; inactive
    slots repeat their last token (the host emits only to live requests)."""
    temps, eos, key = state["temps"], state["eos"], state["key"]

    def body(carry, i):
        cache, toks, active, budget = carry
        cache, logits = decode_step(params, cache, toks, active, cfg,
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
