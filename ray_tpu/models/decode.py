"""Autoregressive decoding on a device-resident cache: the inference side of
the transformer (training side: ``transformer.apply_trunk``).

The reference has no LLM inference engine (SURVEY §2.7 note: no vLLM in the
snapshot; ``@serve.batch`` is the primitive) — this is greenfield TPU-first
code backing ``ray_tpu.serve.llm``.

**One walk over the layers.**  ``layer_stack`` is the serving path's only
forward pass: embedding, one ``lax.scan`` over the layers, the block's
wiring, the MLP, the final norm and the head.  What differs between a
prefill, a decode step and a speculative window, and between kinds of cache,
is the *mixer* it is handed for each kind of layer: a plain function
``mixer(y, layer_weights, layer_index, carry) -> (out, carry, ys)`` closed
over what it needs, and the only code that knows a cache.  The cache tree
says which mixers apply (``prefill``, ``window_step``):

* rows, ``k`` and ``v`` [layers, slots, max_len, KV * D] (a position's KV
  heads side by side in one row: one layout for 8 heads and for 30,
  ``ops/decode_attention.py``; a "slot" is one sequence's reserved rows):
  ``prefill_attention`` over whole right-padded prompts, ``decode_attention``
  for the W tokens a slot appends;
* pages, a tree with a ``block_table`` (``paged_decode.py``):
  ``paged_decode.page_attention`` for both;
* a recurrent ``state`` and a ``conv`` tail beside the rows, where the
  configuration has a ``layer_pattern`` (``hybrid.py``):
  ``hybrid.linear_prefill`` / ``linear_step`` for its "linear" layers,
  ``hybrid.ssm_prefill`` / ``ssm_step`` for its "ssm" layers or
  ``hybrid.ssm1_prefill`` / ``ssm1_step`` for its "ssm1" layers (Mamba-1's
  selective scan, the state ``[layers, slots, N, inner / 128, 128]``);
* under a cross-decoder (``cfg.cross_segment``: a stack of segments whose
  last layers are "gmu" and "cross") the rows are those of ONE "full"
  layer, which every "cross" layer reads too (``cross_attention``), and the
  memory the last "ssm1" layer hands the "gmu" layers is an activation of
  the pass, carried with that kind's state and no cache.  A prefill walks
  the segments before it over the prompt and the cross-decoder over the
  prompt's last token alone (``_prefill_row``, ``cross_row``): nothing
  below the full layer's rows and the memory at a position is kept of it;
* rings, ``wk`` and ``wv`` [window layers, slots, ring, KV * D], beside the
  rows, where the pattern has "window" layers: a position's row is
  ``position mod ring``, keys stored rotated, so a row needs no position
  beside what the slot's ``length`` says (``ring_len``).  A prefill writes a
  row's last ``min(length, ring)`` positions (``_prefill_row``), a window of
  W tokens its W (``ring_attention``); the full layers keep their rows;
* ``mtp_k`` and ``mtp_v``, one layer's rows of its own for a
  multi-token-prediction block (``cfg.mtp_layers``; ``mtp_walk``,
  ``mtp_step``), and the token it drafted last a slot, ``draft``;
* ``latent`` rows and ``rope_key`` columns, one compressed row a token a
  layer shared by all heads, where the configuration has latent attention
  (``latent.py``): ``latent.prefill_attention`` / ``decode_attention``.

Rows alone (K/V or latent) may be continued: ``continued_attention``, this
file's and ``latent.py``'s, writes a chunk of one row after what its slot
holds and attends over the slot so far; a long row is walked that way in
counted chunks (``prefill_width``).

TPU-first design:
* **Static shapes.**  Continuous batching admits/retires sequences by slot
  index — tensor shapes never change, so jit compiles one prefill per length
  bucket and one decode program and reuses them forever.
* **The stacked cache is never a scan's xs/ys**, whatever its kind (that
  slices every layer out and restacks all of it, three passes over the whole
  cache a step).  A window carries the stack and scatters its rows at
  ``[layer, slot, position]``; a prefill over rows returns each layer's new
  K/V as ys and writes them after the scan, into the cache its loop over
  the admit's rows carries.  Either way the donated buffer is updated in
  place and the only bytes written are the new rows.
* **Prefill** is a fixed shape and counted rows: a [B, bucket] block of
  right-padded prompts is one program whatever an admit holds, and the
  program walks the rows that hold a prompt, one causal forward a row, in a
  loop whose trip count is data.  It writes K/V for every position of a row;
  padding beyond a sequence's length is never *read* because decode masks by
  per-slot length (causality makes the writes at pad positions harmless:
  real positions never attend to them).
* **Decode** is one token per active slot (a speculative verify step: W):
  q at position `len`, attention
  over the slot's rows up to it, read where they lie in the stack by one
  kernel that takes the layer index and the live lengths
  (``ops.decode_attention.decode_attn``; ``window_decode_attn`` over a
  ring): no layer's slab is sliced out,
  and blocks past a slot's length, or of an inactive slot, are not fetched.

No torch, no dynamic shapes, no per-request Python in the hot loop.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import TransformerConfig
from .transformer import Params, _norm, lm_head_logits

KVCache = Dict[str, jnp.ndarray]
#: the two arrays of a latent cache (``latent.py``)
LATENT = ("latent", "rope_key")
#: the two arrays of the window layers' rings, and of a
#: multi-token-prediction block's rows
RING = ("wk", "wv")
MTP_ROWS = ("mtp_k", "mtp_v")
#: the optional record of the routers' choices (``init_kv_cache``)
CHOICES = "expert_choices"


def ring_len(cfg: TransformerConfig, tokens: int = 1) -> int:
    """Rows of a window layer's ring that serve steps of ``tokens`` new
    tokens a slot: ``sliding_window + tokens - 1`` rounded up to what the
    kernel tiles (whole 128s; 16s under a window of 128).  The margin: in a
    step of W tokens the last token's row lands where position ``t - window
    + W - 1`` lay, which the first token still reads; with it a rejected
    draft is rolled back by resetting ``length``, as on rows and pages."""
    need = cfg.sliding_window + tokens - 1
    tile = 128 if need >= 128 else 16
    return -(-need // tile) * tile


def init_kv_cache(cfg: TransformerConfig, num_slots: int, max_len: int,
                  dtype=jnp.bfloat16, expert_choices: bool = False,
                  ring: Optional[int] = None) -> KVCache:
    """Allocate the HBM cache: K/V per full-attention layer per slot, plus
    per-slot lengths.  A model with recurrent layers keeps a state and a
    convolution tail for those beside it (``hybrid.init_state``); a model
    with latent attention keeps compressed rows instead of K/V
    (``latent.init_cache``) and, with dropless experts, two running counts
    of what they did (``moe_counts``: assignments, experts touched).
    ``expert_choices`` adds a record of every cached token's routing
    ([expert layers, slots, max_len, k] int32, -1 where nothing was routed):
    ``prefill`` and ``window_step`` write it where the tree has it, as they
    write the token's row.  No engine asks for it; a comparison with a
    reference does, because a choice between two experts that score alike is
    the one thing here a rounding can turn over.  ``ring``: the rows of a
    window layer's ring (``ring_len(cfg)``, a step of one token, where not
    given)."""
    length = jnp.zeros((num_slots,), jnp.int32)
    if cfg.kv_lora_rank:
        from . import latent
        cache = dict(latent.init_cache(cfg, num_slots, max_len, dtype),
                     length=length)
    else:
        cache = _init_rows(cfg, num_slots, max_len, dtype, length)
    if cfg.window_layers:
        rows = (cfg.window_layers, num_slots, ring or ring_len(cfg),
                cache["k"].shape[-1])
        cache.update(wk=jnp.zeros(rows, dtype), wv=jnp.zeros(rows, dtype))
    if cfg.mtp_layers:
        rows = (cfg.mtp_layers,) + cache["k"].shape[1:]
        cache.update(mtp_k=jnp.zeros(rows, dtype),
                     mtp_v=jnp.zeros(rows, dtype),
                     draft=jnp.zeros((num_slots,), jnp.int32))
    if cfg.moe_dropless:
        cache["moe_counts"] = jnp.zeros((2,), jnp.int32)
    if expert_choices:
        cache[CHOICES] = jnp.full(
            (cfg.expert_layers, num_slots, max_len, cfg.experts_per_token),
            -1, jnp.int32)
    return cache


def _init_rows(cfg: TransformerConfig, num_slots: int, max_len: int, dtype,
               length) -> KVCache:
    shape = (cfg.full_layers, num_slots, max_len,
             cfg.num_kv_heads * cfg.head_dim)
    cache = {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "length": length,
    }
    if cfg.recurrent_layers:
        from . import hybrid
        cache.update(hybrid.init_state(cfg, num_slots, dtype))
    return cache


def cache_bytes(cfg: TransformerConfig, num_slots: int, max_len: int,
                dtype_bytes: int = 2) -> int:
    """Bytes of the K/V rows ``init_kv_cache`` allocates: a row a layer
    that keeps rows (every layer without a pattern, the "full" layers under
    one; a "cross" layer keeps none, it reads the "full" layer's)."""
    return (2 * cfg.full_layers * num_slots * max_len * cfg.num_kv_heads
            * cfg.head_dim * dtype_bytes)


def cache_gauges(cfg: TransformerConfig, cache: KVCache) -> Dict[str, int]:
    """What a cache tree holds, by kind of state: bytes of keys and values
    and of latent rows (per token) and of everything else a slot keeps (per
    sequence: a recurrent state, a convolution tail), the layers of each
    kind (``ssm_layers``, and ``window_layers`` with their rings' bytes,
    where the model has them; a multi-token-prediction block's rows count
    among the keys and values; under a cross-decoder ``ssm1_layers``,
    ``cross_layers`` and ``cache_shared_kv_bytes``, the rows of the one
    "full" layer, which every "cross" layer reads besides), and the experts
    a layer holds.
    ``cache_state_hbm_bytes`` is ``cache_state_bytes`` with every array's
    minor dimension in whole tiles of 128 lanes, as the chip stores it: the
    two are equal where no lane holds nothing (a delta-rule state of 192
    lanes a head is stored in 256 unless ``gated_delta.pack_state`` packs
    two heads a tile)."""
    def nbytes(*names, lanes=1):    # the minor dimension in whole ``lanes``
        return sum(math.prod(a.shape[:-1]) * -(-a.shape[-1] // lanes) * lanes
                   * jnp.dtype(a.dtype).itemsize
                   for n, a in cache.items() if n in names)

    per_token = ("k", "v") + MTP_ROWS + RING + LATENT
    control = ("length", "block_table", "moe_counts", "draft", CHOICES)
    state = tuple(n for n in cache if n not in per_token + control)
    return {"cache_kv_bytes": nbytes("k", "v", *MTP_ROWS),
            "cache_state_bytes": nbytes(*state),
            "cache_state_hbm_bytes": nbytes(*state, lanes=128),
            "cache_latent_bytes": nbytes(*LATENT),
            "linear_layers": cfg.linear_layers,
            **({"ssm_layers": cfg.ssm_layers} if cfg.ssm_layers else {}),
            **({"ssm1_layers": cfg.ssm1_layers,
                "cross_layers": cfg.cross_layers,
                "cache_shared_kv_bytes": nbytes("k", "v")}
               if cfg.cross_segment else {}),
            **({"window_layers": cfg.window_layers,
                "cache_ring_bytes": nbytes(*RING)}
               if cfg.window_layers else {}),
            "full_layers": cfg.full_layers,
            "expert_layers": cfg.expert_layers,
            "experts_held": cfg.experts_held if cfg.moe_dropless else 0}


# ---------------------------------------------------------------------------
# Shared per-layer pieces
# ---------------------------------------------------------------------------

def _rotary(cfg: TransformerConfig, kind: str) -> bool:
    """Whether a layer of ``kind`` rotates its queries and keys."""
    return cfg.use_rope and (kind == "window" or not cfg.rope_window_only)


@jax.named_scope("attn")
def _qkv(x, p, cfg: TransformerConfig, positions, kind: str = "full"):
    """x: [B, S, H] -> q [B,S,NH,D], k/v [B,S,NKV,D] with RoPE applied
    where a layer of ``kind`` has it."""
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
    if cfg.qk_head_norm:            # over a head, one scale for all heads
        q = _norm(q, p["q_norm"], cfg)
        k = _norm(k, p["k_norm"], cfg)
    if _rotary(cfg, kind):
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


def _experts(y, lp, cfg: TransformerConfig, live, cast, layer, stacks):
    """The dropless expert layer (``ops.moe.moe_dropless``) on y: [rows, W,
    H], of the ``live`` tokens [rows, W], multiplied in ``cast`` (the router
    scores y as it comes); the experts' weights stay in ``stacks`` [layers,
    experts, ...], of which this is ``layer``.  Returns (out, (counts [2],
    the chosen experts [rows, W, k]))."""
    from ..ops import moe as moe_ops
    out, counts, idx, _ = moe_ops.moe_dropless(
        y.reshape(-1, y.shape[-1]), lp["moe"], stacks, layer,
        experts_per_token=cfg.experts_per_token,
        scaling=cfg.routed_scaling_factor, compute_dtype=cast,
        live=None if live is None else live.reshape(-1),
        expert_start=cfg.expert_start, router=cfg.moe_router)
    return out.reshape(y.shape), (counts, idx.reshape(y.shape[:2] + (-1,)))


@jax.named_scope("mlp")
def _mlp(y, p, cfg: TransformerConfig):
    cast = y.dtype
    if "moe" in p:       # with a capacity; a dense prefix layer has none
        from ..ops import moe as moe_ops
        out, _ = moe_ops.moe_mlp(
            y, p["moe"]["router"], p["moe"]["w_gate"], p["moe"]["w_in"],
            p["moe"]["w_out"], cfg.experts_per_token,
            cfg.expert_capacity_factor)
        return out
    mp = p["mlp"]
    if cfg.mlp_act:                 # "relu2": two matrices, no gate
        from ..ops.moe import relu2
        up = relu2(jnp.dot(y, mp["w_in"].astype(cast),
                            preferred_element_type=jnp.float32))
        return up.astype(cast) @ mp["w_out"].astype(cast)
    if cfg.use_swiglu:
        return (jax.nn.silu(y @ mp["w_gate"].astype(cast))
                * (y @ mp["w_in"].astype(cast))) @ mp["w_out"].astype(cast)
    h = jax.nn.gelu(y @ mp["w_in"].astype(cast) + mp["b_in"].astype(cast))
    return h @ mp["w_out"].astype(cast) + mp["b_out"].astype(cast)


@jax.named_scope("attn")
def _proj_out(attn, p, cast, x=None):
    """The output projection; where the layer has an output gate
    (``cfg.attn_output_gate``) the attention is first multiplied, element by
    element, by the sigmoid of a projection of the layer's input ``x``."""
    if "w_gate" in p:
        attn = attn * jax.nn.sigmoid(x @ p["w_gate"].astype(cast))
    out = attn @ p["wo"].astype(cast)
    if "bo" in p:
        out = out + p["bo"].astype(cast)
    return out


def _wide(cfg: TransformerConfig) -> int:
    """The K/V heads whose values a query head weighs side by side: 2 under
    differential attention (a pair's ``[v1 | v2]``), else its own."""
    return 2 if cfg.diff_attn else 1


def _depth(cfg: TransformerConfig, kind: str, index):
    """The depth in the stack of layer ``index`` (traced or not) of
    ``kind``, float32: what ``lam0`` is a function of."""
    return jnp.asarray(cfg.depths(kind), jnp.float32)[index]


@jax.named_scope("diff_attn")
def diff_combine(o, ap, cfg: TransformerConfig, depth):
    """Differential attention's second half.  o [..., NH, 2 D]: every query
    head's softmax map over the two value heads of its K/V pair, the heads
    laid ``(K/V pair g, member i of the pair, query r of the K/V head)`` so
    that head ``n`` scores K/V head ``n // reps`` as plain grouped-query
    attention does (which heads pair is a convention: any fixed pairing is
    the same arithmetic on drawn weights).  Returns ``(1 - lam0) rmsnorm(o[g,
    0, r] - lam o[g, 1, r])`` [..., NH * D], with ``lam = exp(lq1 . lk1) -
    exp(lq2 . lk2) + lam0`` and ``lam0 = 0.8 - 0.6 exp(-0.3 depth)``, in
    float32."""
    f32 = lambda name: ap[name].astype(jnp.float32)             # noqa: E731
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * depth)
    lam = (jnp.exp(jnp.sum(f32("lam_q1") * f32("lam_k1")))
           - jnp.exp(jnp.sum(f32("lam_q2") * f32("lam_k2"))) + lam0)
    lead, width = o.shape[:-2], o.shape[-1]
    pairs = o.astype(jnp.float32).reshape(
        lead + (cfg.num_kv_heads // 2, 2, -1, width))
    d = pairs[..., 0, :, :] - lam * pairs[..., 1, :, :]
    d = d * jax.lax.rsqrt(jnp.mean(d * d, -1, keepdims=True) + cfg.norm_eps)
    d = (1.0 - lam0) * d * ap["sub_norm"]["scale"].astype(jnp.float32)
    return d.reshape(lead + (-1,))


def _query_alone(y, ap, cfg: TransformerConfig):
    """A "cross" layer's projection, its only one before the output's: y
    [B, S, H] -> q [B, S, NH, D] (no positions, no bias, no norm: the
    configuration's check)."""
    with jax.named_scope("cross"):
        q = y @ ap["wq"].astype(y.dtype)
    return q.reshape(y.shape[:2] + (cfg.num_heads, cfg.head_dim))


def masked_attention(q, k, v, positions, cfg: TransformerConfig):
    """Plain float32 attention of W queries a row over the row's whole span:
    no kernel reads pages yet (rows and rings have theirs,
    ``decode_attention``, ``ring_attention``).  q: [R, W,
    NH, D] at absolute ``positions`` [R, W]; k, v: [R, span, NKV, D], row
    ``m`` holding position ``m``; query j reads positions <= its own.
    Returns [R, W, NH * D] float32."""
    r, w = positions.shape
    reps = cfg.num_heads // cfg.num_kv_heads
    qh = q.reshape(r, w, cfg.num_kv_heads, reps, cfg.head_dim)
    scores = jnp.einsum("rwgpd,rmgd->rwgpm", qh.astype(jnp.float32),
                        k.astype(jnp.float32)) * cfg.attn_scale
    if cfg.attn_logit_softcap:
        c = cfg.attn_logit_softcap
        scores = c * jnp.tanh(scores / c)
    causal = jnp.arange(k.shape[1])[None, None] <= positions[:, :, None]
    scores = jnp.where(causal[:, :, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("rwgpm,rmgd->rwgpd", probs, v.astype(jnp.float32))
    return attn.reshape(r, w, cfg.num_heads * cfg.head_dim)


# ---------------------------------------------------------------------------
# The layer stack
# ---------------------------------------------------------------------------

Mixer = Callable[..., Tuple[jnp.ndarray, Any, Any]]
# the norm of a kind's mixer branch, among its layer's weights (an "mlp"
# layer has no mixer)
_BRANCH_NORM = {"full": "attn_norm", "window": "attn_norm",
                "cross": "attn_norm", "linear": "mixer_norm",
                "ssm": "mixer_norm", "ssm1": "mixer_norm",
                "gmu": "mixer_norm"}
# the kind whose entry of the carry a kind's mixer is handed, where it is not
# its own: a "cross" layer reads the "full" layer's rows, a "gmu" layer the
# memory, which is the last of what the "ssm1" layers carry
_CARRY_OF = {"cross": "full", "gmu": "ssm1"}


def _memory_unit(y, lp, i, carry):
    """A "gmu" layer as a mixer: the gate of ``y`` on the memory, the last
    of what the "ssm1" kind carries."""
    from .hybrid import gmu
    return gmu(y, lp["mixer"], carry[-1]), carry, None


def _layer_weights(stack: Params, index, lead: int) -> Params:
    """Layer ``index`` (traced or not) of weights stacked over ``lead``
    leading dims ([layers, ...], or a kind's [periods, layers of the kind a
    period, ...]): one dynamic index of the stack flattened, a slice its
    matmul reads where it lies.  A period's slice taken first (the stack as
    a scan's xs) is copied out whole: every weight of the linear layers
    once a decode step, 35% of the hybrid cell's chip (PERF.md, PR 30)."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(
            a.reshape((-1,) + a.shape[lead:]), index, 0, keepdims=False),
        stack)


def _kv_mixer(attention, cfg: TransformerConfig, *closed) -> Mixer:
    """``attention(y, attn_weights, cfg, k_all, v_all, layer, *closed) ->
    (out, k_all, v_all)`` as the mixer of the "full" layers (or, over a
    ring, of the "window" layers), on the carried pair ``(k_all, v_all)``."""
    def mixer(y, lp, i, kv):
        out, *kv = attention(y, lp["attn"], cfg, *kv, i, *closed)
        return out, tuple(kv), None
    return mixer


def layer_stack(params: Params, tokens: jnp.ndarray, positions: jnp.ndarray,
                mixers: Dict[str, Mixer], carry: Dict[str, Any],
                cfg: TransformerConfig, compute_dtype,
                pick: Optional[jnp.ndarray] = None,
                live: Optional[jnp.ndarray] = None, head: bool = True,
                through: Optional[Tuple[int, int]] = None):
    """The serving forward pass: ``tokens`` [rows, W] (or, floating, what
    stands for their embeddings [rows, W, H]: a multi-token-prediction
    block's input) at absolute
    ``positions`` [rows, W] through every layer, each layer's mixing done by
    its kind's entry of ``mixers`` on its kind's entry of ``carry`` (the
    cache arrays a mixer updates in place; never scan xs/ys).

    One ``lax.scan`` over periods of ``cfg.layer_pattern`` with the kinds
    inside a period unrolled, so the trace is one period whatever the depth;
    a model without a pattern is the pattern ``("full",)``, one layer a
    period.  A stack of more than one pattern (``cfg.layer_segments``) is one
    such scan a segment, one after another, a kind's layers counted through
    them; ``through`` ``(first, after)`` walks those segments alone: from a
    later one on ``tokens`` are the hidden states [rows, W, H] the segments
    before left, and short of the last the result is those states (at
    ``pick`` where given), with no final norm and no head.  A mixer is
    handed its kind's entry of ``carry`` or, where ``_CARRY_OF`` names one,
    another kind's: what a layer above left for it.  A kind says which
    sublayers a layer has: a mixer and, under
    it, a dense MLP or, with ``cfg.moe_dropless``, the dropless experts,
    whose weights stay in their stacks; or, with ``cfg.sublayers_alone``,
    one of the two alone (an "mlp" layer is the feed-forward, every other
    kind its mixer), each behind its own norm.  A dense prefix
    (``cfg.dense_prefix_layers``) is walked before the scan, which is then
    over the expert layers (under a pattern: the first period, its first
    layers with a dense MLP, is walked before the scan over the others); a
    mixer is handed the layer's index among its
    kind's cache rows, an expert layer its rank among the expert layers.  A
    block is wired
    ``x + f(norm(x))`` (``x + m f(norm(x))`` with ``cfg.residual_multiplier``
    m), ``x + norm(f(x))`` under ``cfg.norm_on_output``, or
    with ``cfg.hc_mult`` residual streams, read, written and mixed around
    the sublayer by per-token coefficients (``latent.hc_coeff``), for the
    mixer and the MLP alike.  ``live`` [rows, W] are the tokens that count
    (None: all); only a dropless expert layer asks, so that a padded position
    or an idle slot is routed nowhere.  The configuration's other published
    scalars are applied here too, each where it is not 0: the embedding is
    multiplied by ``cfg.embedding_multiplier``, the head's logits are divided
    by ``cfg.logits_scaling`` (``lm_head_logits``), and the mixers read
    ``cfg.attn_scale``.

    Returns (logits float32, carry, ys): logits [rows, W, V], or [rows, V]
    of position ``pick`` [rows] of each row, or with ``head=False`` what the
    head would be given ([rows, W, H] or [rows, H], after the final norm: a
    caller that walks a row in pieces runs the head once); ys maps a kind
    to what its mixer returned a layer, stacked [layers of the kind, ...]
    (None where it returns none), ``"moe"`` to the expert layers' counts
    [expert layers, 2], ``"experts"`` to their routers' choices [expert
    layers, rows, W, k] and, where the model has a multi-token-prediction
    block, ``"hidden"`` to the last layer's output [rows, W, H], before the
    final norm."""
    cast = compute_dtype
    blocks = params["blocks"]
    segments = cfg.segments
    lo, hi = through or (0, len(segments))
    prefix = cfg.dense_prefix_layers
    if jnp.issubdtype(tokens.dtype, jnp.floating):
        x = tokens.astype(cast)
    else:
        x = params["embed"]["tokens"][tokens].astype(cast)
        if cfg.embedding_multiplier:
            x = x * cfg.embedding_multiplier
    if cfg.learned_positions and not lo:
        x = x + params["embed"]["pos"][
            jnp.minimum(positions, cfg.max_seq_len - 1)].astype(cast)
    if cfg.hc_mult:
        # the embedding fills every stream; the streams are float32.  Their
        # mixing is float32 either way (``latent.hc_write``), so it costs
        # nothing (tokens/s and temporaries equal on the chip), and carried
        # in ``cast`` the compiler rounds them at other points in one
        # program than in another: two compilations of one prefill then
        # chose other experts for 7-23% of (token, layer) pairs, and none
        # of 24,558 so (PERF.md, PR 35): a token's experts should not depend
        # on which program ran it.
        x = jnp.broadcast_to(x[:, :, None].astype(jnp.float32),
                             x.shape[:2] + (cfg.hc_mult,) + x.shape[2:])

    def layer(x, carry, kind, lp, index, experts=None):
        """One block on the streams ``x``; returns (x, carry, what the
        mixer returned, the expert layer's (counts, choices) or None)."""
        norm = lambda y, name: _norm(y, lp[name], cfg)           # noqa: E731

        # the block's wiring, decided here and nowhere else: what the
        # sublayer behind ``name``'s norm sees of x, and how its output
        # joins x
        def wire(x, name):
            if cfg.hc_mult:
                from . import latent
                pre, post, res = latent.hc_coeff(
                    x, lp["hc_attn" if name == branch else "hc_mlp"], cfg)
                return (norm(latent.hc_read(x, pre), name),
                        lambda out: latent.hc_write(x, out, post, res))
            if cfg.norm_on_output:
                return x, lambda out: x + norm(out, name)
            m = cfg.residual_multiplier     # 0: absent, and skipped
            return norm(x, name), lambda out: x + (out * m if m else out)

        branch = _BRANCH_NORM.get(kind)
        rows = routed = None
        # (what a sublayer multiplies is ``cast``; a router scores what it
        # is given)
        if branch:
            seen, join = wire(x, branch)
            out, carry, rows = mixers[kind](seen.astype(cast), lp, index,
                                            carry)
            x = join(out)
        if kind == "mlp" or not cfg.sublayers_alone:
            seen, join = wire(x, "mlp_norm")
            if experts is None:
                out = _mlp(seen.astype(cast), lp, cfg)
            else:
                out, routed = _experts(seen, lp, cfg, live, cast, *experts)
            x = join(out)
        return x, carry, rows, routed

    # Where a layer's weights lie.  A pattern's blocks are stacked by kind
    # [periods, layers of the kind a period, ...] with the experts of every
    # layer in one stack of their own; without a pattern the blocks are one
    # stack [layers, ...], the routed experts among a dropless layer's
    # ``moe``.  Both are indexed where they lie (``_layer_weights``) and the
    # experts never leave their stacks: the kernel takes the layer's index
    # among the expert layers.  Only a dense model's one-dimensional stack
    # is the scan's xs, whose one-layer slices always fused: indexed too,
    # Mistral's programs compiled to other code (prefill temporaries +97
    # KB) and the open-loop cells read 0.2-0.6% later first tokens on the
    # chip, four pairs of four (PERF.md, PR 31).
    routed = ("w_gate", "w_in", "w_out")
    stacks, small = None, blocks
    if cfg.moe_dropless and cfg.layer_pattern:
        stacks = blocks["experts"]
    elif cfg.moe_dropless:
        stacks = {k: blocks["moe"][k] for k in routed}
        small = dict(blocks, moe={k: v for k, v in blocks["moe"].items()
                                  if k not in routed})
    as_xs = not (cfg.layer_pattern or cfg.moe_dropless)

    def weights(kind, index):
        by_kind = bool(cfg.layer_pattern)
        return _layer_weights(small[kind] if by_kind else small, index,
                              1 + by_kind)

    # a pattern's dense prefix lies in its first period, whose layers are
    # counted with their kind's like every other's; without a pattern the
    # dense layers are walked apart and come first among the cache's rows
    ahead = prefix if cfg.layer_pattern else 0

    def period(walk, step, pattern, base):
        """One period of ``pattern``, its kinds unrolled; a layer's index
        is the one in its kind's stack of weights (a mixer's in its stack
        of cache rows: the layers before the scan come first there), past
        the ``base`` layers of its kind in the segments before."""
        (x, carry), (p, dense) = walk, step
        carry, at = dict(carry), dict.fromkeys(pattern, 0)
        per_period = [kind for kind in mixers if kind in pattern]
        ys = {kind: [] for kind in per_period}
        chosen = []
        # the layers of a period that have an MLP, the experts where the
        # model has them: the "mlp" layers, or every layer
        has_mlp = [kind == "mlp" or not cfg.sublayers_alone
                   for kind in pattern]
        for j, kind in enumerate(pattern):
            index = base.get(kind, 0) + p * pattern.count(kind) + at[kind]
            at[kind] += 1
            lp = weights(kind, index) if dense is None else dense
            experts = None
            if stacks and has_mlp[j]:
                rank = p * sum(has_mlp) + sum(has_mlp[:j]) - ahead
                # (the period walked ahead of the scan, and it alone, has a
                # Python index)
                if ahead and isinstance(p, int) and j < ahead:
                    lp = dict(lp, mlp=_layer_weights(blocks["dense"], j, 1))
                else:
                    experts = (rank, stacks)
                    if ahead:       # the small weights lie by layer too
                        lp = dict(lp, moe=_layer_weights(blocks["moe"],
                                                         rank, 1))
            held = _CARRY_OF.get(kind, kind)
            x, state, rows, said = layer(
                x, carry.get(held), kind, lp,
                index + (0 if ahead else prefix), experts)
            if kind in per_period:
                carry[held] = state
                ys[kind].append(rows)
            if said is not None:
                chosen.append(said)
        if chosen:
            ys["moe"], ys["experts"] = zip(*chosen)
        return (x, carry), {kind: jax.tree.map(lambda *a: jnp.stack(a), *outs)
                            for kind, outs in ys.items() if outs}

    carry, first, began = dict(carry), [], None
    for j in range(prefix - ahead):     # the dense layers before the experts
        x, carry["full"], rows, _ = layer(
            x, carry["full"], "full",
            _layer_weights(params["prefix"], j, 1), j)
        first.append(rows)
    pattern = segments[0][0]
    if ahead:                       # the period that holds the dense layers
        (x, carry), began = period((x, carry), (0, None), pattern, {})
    parts, base = [], {}
    for j, (pattern, periods) in enumerate(segments):
        if lo <= j < hi:
            # (less the dense layers walked apart, which no stack of
            # several segments has)
            (x, carry), ys = jax.lax.scan(
                functools.partial(period, pattern=pattern, base=dict(base)),
                (x, carry),
                (jnp.arange(bool(ahead), periods - (prefix - ahead)),
                 blocks if as_xs else None))
            # [periods, layers a period, ...] -> [layers, ...]
            parts.append(jax.tree.map(
                lambda a: a.reshape((-1,) + a.shape[2:]), ys))
        for kind in pattern:
            base[kind] = base.get(kind, 0) + periods
    ys = parts[0] if len(parts) == 1 else {
        kind: jax.tree.map(lambda *a: jnp.concatenate(a),
                           *(p[kind] for p in parts if kind in p))
        for kind in dict.fromkeys(k for p in parts for k in p)}
    if began:
        ys = {k: jax.tree.map(lambda a, b: jnp.concatenate([a, b]),
                              began[k], v) if k in began else v
              for k, v in ys.items()}
    if "full" in ys and first:
        ys["full"] = jax.tree.map(
            lambda *a: jnp.concatenate([jnp.stack(a[:-1]), a[-1]]),
            *first, ys["full"])
    if cfg.hc_mult:                  # the streams are summed before the norm
        x = x.sum(axis=2)
    if cfg.mtp_layers:
        ys["hidden"] = x
    if hi == len(segments):
        x = _norm(x, params["final_norm"], cfg).astype(cast)
    if pick is not None:
        x = jnp.take_along_axis(x, pick[:, None, None], axis=1)[:, 0]
    return (lm_head_logits(params, x, cfg)
            if head and hi == len(segments) else x), carry, ys


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def prefill_attention(y, ap, cfg: TransformerConfig, positions,
                      kind: str = "full", layer=None):
    """One layer's causal attention over whole right-padded rows, a "window"
    layer's over each query's last ``cfg.sliding_window`` positions (the
    flash forward with a band).  y: [B, S,
    H] -> (attention after its output projection [B, S, H], this layer's
    k and v [B, S, NKV, D] for the cache).  ``layer``: its index among its
    kind's, which differential attention's ``lam0`` goes by."""
    from ..ops.attention import mha
    b, s, _ = y.shape
    q, k, v = _qkv(y, ap, cfg, positions, kind)
    # (under differential attention a pair's two value heads side by side:
    # the same rows, read as half as many heads twice as wide)
    wide = v.reshape(b, s, -1, _wide(cfg) * cfg.head_dim)
    with jax.named_scope("window_attn" if kind == "window" else "attn"):
        attn = mha(q, k, wide, causal=True,
                   logit_softcap=cfg.attn_logit_softcap,
                   window=cfg.sliding_window if kind == "window" else 0,
                   scale=cfg.attn_scale)
    if cfg.diff_attn:
        attn = diff_combine(attn, ap, cfg, _depth(cfg, kind, layer))
    return _proj_out(attn.reshape(b, s, -1).astype(y.dtype), ap, y.dtype,
                     y), k, v


#: Positions a pass of the admit program walks of a row it walks in chunks.
#: 512 is the shortest row that costs its share of a 2,048 row on the chip:
#: 19 ms of 76, where a row of 256 is 10.6 ms, 11% over its share, and one of
#: 128 is 9.4, the 8.1 ms read of the weights being the floor under both
#: (PERF.md section 5, "The admit program alone").
PREFILL_CHUNK = 512
#: Rows of a chunk an expert is handed, under uniform routing, where the
#: layers have dropless experts: the MXU's tile.  The grouped matmul fetches
#: every touched expert's weights once a call whatever the rows, and a chunk
#: of a prompt touches them all: 10.3 ms a chunk of Xing4.0's six expert
#: layers, against 1.4 ms of multiplying at 32 rows an expert (a chunk of
#: 512); at 128 rows the two meet (PERF.md section 6, PR 47).
EXPERT_TILE = 128


def _rows_alone(cache: KVCache) -> bool:
    """Rows a prefill may start after, K/V or latent: what a position left
    in its slot is all a later one needs of it.  A paged tree starts after
    a prefix its own way (``page_attention``); a recurrent state and a ring
    take no start yet."""
    return not any(n in cache for n in ("block_table", "state", "wk"))


def prefill_width(cache: KVCache, bucket: int, cfg: TransformerConfig,
                  chunk: int = PREFILL_CHUNK) -> int:
    """How many positions of a row of ``bucket`` one pass of the admit
    program walks: a chunk where the row is walked in counted chunks, else
    the whole bucket.  Read off shapes alone.  The chunk is ``chunk``, or
    under dropless experts the shortest multiple of it that hands an expert
    ``EXPERT_TILE`` rows (Xing4.0's 64 experts, 4 a token: 2,048); a row is
    walked in chunks on a tree of rows alone at a bucket of at least four
    chunks (where buckets double, every length of a shorter one needs all of
    its chunks, and nothing could be saved)."""
    if cfg.moe_dropless:
        rows = -(-EXPERT_TILE * cfg.num_experts // cfg.experts_per_token)
        chunk *= -(-rows // chunk)
    chunked = (_rows_alone(cache) and bucket >= 4 * chunk
               and bucket % chunk == 0)
    return chunk if chunked else bucket


def continued_attention(y, ap, cfg: TransformerConfig, k_all, v_all, i, slot,
                        start, span: int):
    """One layer's attention for W tokens of one row that continue what its
    slot holds.  y: [1, W, H] at positions ``start ..``; k_all, v_all: the
    stacked cache [layers, slots, max_len, NKV * D], of which this is layer
    ``i``.  Writes the tokens' K/V at ``[i, slot, start : start + W]`` in
    place and attends, causally, over the slot's rows ``0 .. span`` (static;
    ``start + W <= span``) where they lie
    (``ops.flash_attention.flash_attention_rows``).  Returns (attention
    after its output projection [1, W, H], k_all, v_all)."""
    from ..ops.flash_attention import flash_attention_rows
    if cfg.diff_attn:
        raise NotImplementedError(
            "diff_attn: a row that continues what its slot holds has no "
            "kernel for values two heads wide (flash_attention_rows)")
    w = y.shape[1]
    q, k, v = _qkv(y, ap, cfg, start + jnp.arange(w)[None])
    with jax.named_scope("kv_write"):
        k_all, v_all = (
            jax.lax.dynamic_update_slice(
                a, rows.reshape(1, 1, w, -1).astype(a.dtype),
                (i, slot, start, 0))
            for a, rows in ((k_all, k), (v_all, v)))
    with jax.named_scope("attn"):
        attn = flash_attention_rows(q, k_all, v_all, i, slot, start, span,
                                    cfg.num_kv_heads, cfg.attn_logit_softcap,
                                    scale=cfg.attn_scale)
    return _proj_out(attn, ap, y.dtype, y), k_all, v_all


def _said(choices, ys, live, slot, at):
    """The record of the routers' choices with a walk's ``ys`` written at
    ``[:, slot, at ..]``: -1 at a position that is not ``live``."""
    return jax.lax.dynamic_update_slice(
        choices, jnp.where(live[None, ..., None], ys["experts"], -1),
        (0, slot, at, 0))


def _prefill_chunks(params: Params, cache: KVCache, tokens: jnp.ndarray,
                    length: jnp.ndarray, slot: jnp.ndarray,
                    start: jnp.ndarray, width: int, span: int,
                    cfg: TransformerConfig, compute_dtype
                    ) -> Tuple[KVCache, jnp.ndarray]:
    """``_prefill_row`` on a tree of rows alone in ``ceil(length / width)``
    passes of ``width`` positions, a loop whose trip count is data: each
    pass is the same walk over ``[1, width]`` tokens with a mixer that
    writes the chunk's rows into the slot in place and reads the slot's rows
    up to them (``continued_attention``, the tree's kind's), so a row costs
    the chunks its prompt fills and the chunks past them are left as they
    were.  The last pass holds the prompt's last token; the head runs on it
    once, after the loop."""
    last = jnp.maximum(length - 1, 0)      # the prompt's last real token
    attention, rows = continued_attention, ("k", "v")
    if "latent" in cache:
        from .latent import continued_attention as attention
        rows = LATENT
    kept = rows + ((CHOICES,) if CHOICES in cache else ())

    def one(c, walk):
        held, _ = walk
        at = c * width
        # a padded position is routed to no expert
        live = ((at + jnp.arange(width))[None] < length[:, None]
                if cfg.moe_dropless else None)
        x, carry, ys = layer_stack(
            params, jax.lax.dynamic_slice_in_dim(tokens, at, width, 1),
            (start + at)[:, None] + jnp.arange(width)[None],
            {"full": _kv_mixer(attention, cfg, slot, (start + at)[0], span)},
            {"full": tuple(held[n] for n in rows)}, cfg, compute_dtype,
            jnp.clip(last - at, 0, width - 1), live, head=False)
        held = dict(held, **dict(zip(rows, carry["full"])))
        if CHOICES in held:
            held[CHOICES] = _said(held[CHOICES], ys, live, slot,
                                  (start + at)[0])
        return held, x

    held, x = jax.lax.fori_loop(
        0, jnp.maximum(-(-length[0] // width), 1), one,
        ({n: cache[n] for n in kept},
         jnp.zeros((1, cfg.hidden_size), compute_dtype)))
    return (dict(cache, **held, length=cache["length"].at[slot].set(
        (start + length)[0])), lm_head_logits(params, x, cfg))


def _prefill_row(params: Params, cache: KVCache, tokens: jnp.ndarray,
                 length: jnp.ndarray, slot: jnp.ndarray,
                 start: Optional[jnp.ndarray], cfg: TransformerConfig,
                 compute_dtype, chunk: int) -> Tuple[KVCache, jnp.ndarray]:
    """One prompt through the layers and into its slot of ``cache``, which
    comes and goes in place.  tokens: [1, S]; length, start: [1] (start
    None: the row begins its slot); slot: a scalar.  A tree of rows alone,
    K/V or latent, walks a long row in chunks (``prefill_width``) and any
    row that has a start after what its slot holds, in one chunk if it is
    short.  Returns (cache, last-token logits [1, V] f32)."""
    s = tokens.shape[1]
    width = prefill_width(cache, s, cfg, chunk)
    if width < s or (start is not None and _rows_alone(cache)):
        # a start that is data may lie anywhere in the slot
        held = cache["latent" if "latent" in cache else "k"]
        span = s if start is None else held.shape[2]
        return _prefill_chunks(
            params, cache, tokens, length, slot,
            jnp.zeros_like(length) if start is None else start, width, span,
            cfg, compute_dtype)
    if start is None:
        start = jnp.zeros_like(length)
    last = jnp.maximum(length - 1, 0)      # the prompt's last real token
    positions = start[:, None] + jnp.arange(s)[None]
    new = dict(cache, length=cache["length"].at[slot].set(
        (start + length)[0]))
    if "block_table" in cache:
        from .paged_decode import page_attention
        table = jax.lax.dynamic_slice_in_dim(cache["block_table"], slot, 1)
        pages = _kv_mixer(page_attention, cfg, table, positions,
                          jnp.arange(s)[None] < length[:, None])
        logits, carry, _ = layer_stack(
            params, tokens, positions, {"full": pages},
            {"full": (cache["k"], cache["v"])}, cfg, compute_dtype, last)
        new["k"], new["v"] = carry["full"]
        return new, logits
    # a padded position is routed to no expert
    live = (jnp.arange(s)[None] < length[:, None] if cfg.moe_dropless
            else None)

    def choices(ys):
        """``new`` with the row's routing recorded, where the tree asks."""
        if CHOICES in cache:
            new[CHOICES] = _said(cache[CHOICES], ys, live, slot, start[0])
        return new

    if "latent" in cache:
        from .latent import prefill_attention as latent_rows
        logits, carry, ys = layer_stack(
            params, tokens, positions,
            {"full": _kv_mixer(latent_rows, cfg, slot, positions)},
            {"full": tuple(cache[n] for n in LATENT)}, cfg, compute_dtype,
            last, live)
        new.update(zip(LATENT, carry["full"]))
        return choices(ys), logits

    def rows(y, lp, i, carry):
        out, k, v = prefill_attention(y, lp["attn"], cfg, positions, layer=i)
        return out, carry, (k.reshape(1, s, -1).astype(cache["k"].dtype),
                            v.reshape(1, s, -1).astype(cache["v"].dtype))

    mixers, kind = {"full": rows}, None
    if "wk" in cache:
        from ..ops.decode_attention import ring_positions
        # of a row, the ring keeps the newest position congruent to each of
        # its rows: the last min(length, ring) positions (a row nothing has
        # reached yet takes position 0's and is masked by what it would hold)
        held = jnp.maximum(ring_positions(last, cache["wk"].shape[2]), 0)

        def band(y, lp, i, carry):
            out, k, v = prefill_attention(y, lp["attn"], cfg, positions,
                                          "window", i)
            return out, carry, tuple(
                jnp.take_along_axis(a.reshape(1, s, -1), held[..., None], 1)
                .astype(cache["wk"].dtype) for a in (k, v))

        mixers["window"] = band
    if "state" in cache:
        from . import hybrid
        kind, whole_rows, _ = hybrid.recurrent(cfg)

        def recurrent(y, lp, i, carry):
            # (an "ssm1" layer also hands on its memory: all it carries)
            out, state, tail, *handed = whole_rows(y, lp["mixer"], cfg,
                                                   length)
            return (out, tuple(handed) or carry,
                    (state, tail.astype(cache["conv"].dtype)))

        mixers[kind] = recurrent
    split, carry = cfg.cross_segment, dict.fromkeys(mixers)
    if split:
        carry[kind] = (jnp.zeros((1, s, cfg.ssm1_inner), compute_dtype),)
    logits, handed, ys = layer_stack(
        params, tokens, positions, mixers, carry, cfg, compute_dtype, last,
        live, through=(0, split) if split else None)
    if split:
        # the cross-decoder on the prompt's last token alone: what the
        # self-decoder left of it (``logits`` are its hidden state there),
        # the memory at that position and the full layer's keys and values
        # of the row, which are all its layers read of the positions before
        memory = jnp.take_along_axis(handed["ssm1"][-1],
                                     last[:, None, None], axis=1)
        own = tuple(a[0] for a in ys["full"])

        def cross(y, lp, i, carry):
            return (cross_row(y, lp["attn"], cfg, *carry, i, length), carry,
                    None)

        logits, _, _ = layer_stack(
            params, logits[:, None], last[:, None],
            {"gmu": _memory_unit, "cross": cross},
            {"ssm1": (memory,), "full": own},
            cfg, compute_dtype, jnp.zeros_like(last),
            through=(split, len(cfg.segments)))

    # every layer's rows [layers of the kind, 1, ...] into the slot, in place
    # on the donated cache (the K/V of the padded tail included; decode's
    # length mask keeps it unread)
    def put(name, rows):
        return jax.lax.dynamic_update_slice(
            cache[name], rows, (0, slot) + (0,) * (rows.ndim - 2))

    if "full" in ys:
        with jax.named_scope("kv_write"):
            new["k"], new["v"] = put("k", ys["full"][0]), put("v", ys["full"][1])
    if "window" in ys:
        with jax.named_scope("ring_write"):
            new["wk"] = put("wk", ys["window"][0])
            new["wv"] = put("wv", ys["window"][1])
    if kind in ys:
        with jax.named_scope("state_write"):
            new["state"] = put("state", ys[kind][0])
            new["conv"] = put("conv", ys[kind][1])
    if "hidden" in ys:
        # the block's pass over the row: position t pairs the model's
        # hidden state with token t + 1, the prompt's last with the token
        # the model's own logits choose (a sampled slot's draft is never
        # accepted, ``speculative.spec_state_round``)
        nxt = jnp.where(jnp.arange(s)[None] == last[:, None],
                        jnp.argmax(logits, -1).astype(tokens.dtype)[:, None],
                        jnp.roll(tokens, -1, 1))
        block_logits, _, block_ys = mtp_walk(
            params, ys["hidden"], nxt, positions, {"full": rows},
            {"full": None}, cfg, compute_dtype, last, live)
        with jax.named_scope("kv_write"):
            new["mtp_k"] = put("mtp_k", block_ys["full"][0])
            new["mtp_v"] = put("mtp_v", block_ys["full"][1])
        new["draft"] = cache["draft"].at[slot].set(
            jnp.argmax(block_logits[0], -1).astype(jnp.int32))
    return choices(ys), logits


def mtp_walk(params: Params, hidden, next_tokens, positions, mixers, carry,
             cfg: TransformerConfig, compute_dtype, pick=None, live=None):
    """The multi-token-prediction block on ``hidden`` [rows, W, H], the
    model's last hidden states (before its final norm), each paired with the
    token after it, ``next_tokens`` [rows, W]: ``W_eh [norm(E x_{t+1});
    norm(h_t)]`` through one "full" layer of the model's form on K/V rows of
    the block's own (``mixers``, ``carry``: ``layer_stack``'s), the block's
    norm and the model's head.  Returns ``layer_stack``'s three: logits for
    the token after ``next_tokens``."""
    mp = params["mtp"]
    with jax.named_scope("mtp_proj"):
        emb = params["embed"]["tokens"][next_tokens].astype(compute_dtype)
        u = jnp.concatenate(
            [_norm(emb, mp["embed_norm"], cfg),
             _norm(hidden.astype(compute_dtype), mp["hidden_norm"], cfg)],
            -1) @ mp["proj"].astype(compute_dtype)
    view = {k: v for k, v in params.items() if k != "mtp"}
    view.update(blocks=mp["blocks"], final_norm=mp["final_norm"])
    return layer_stack(view, u, positions, mixers, carry, cfg.mtp_cfg,
                       compute_dtype, pick, live)


def prefill(params: Params, cache: KVCache, tokens: jnp.ndarray,
            lengths: jnp.ndarray, slot_ids: jnp.ndarray,
            cfg: TransformerConfig, compute_dtype=jnp.bfloat16,
            start_pos: Optional[jnp.ndarray] = None,
            rows: Optional[jnp.ndarray] = None, chunk: int = PREFILL_CHUNK
            ) -> Tuple[KVCache, jnp.ndarray]:
    """Run the causal forward over right-padded prompts, populate the cache.

    A fixed shape, counted rows, counted chunks: the arrays are [B, ...]
    whatever an admit holds, so a bucket is one program, and the program
    walks the first ``rows`` of them one after another in a loop whose trip
    count is that number, data.  Each pass takes one row [1, S] through
    ``layer_stack`` and writes its slot of the cache, carried through the
    loop in place; on a tree of rows alone, K/V or latent, a row of four
    chunks or more (``prefill_width``: a chunk is ``chunk`` positions, 2,048
    under Xing4.0's experts) is itself a loop over the chunks its prompt
    fills, each written into the slot and attending over what the slot holds
    by then.  Rows past the count are not computed: their slots, lengths and
    states stay as they were and their logits read 0; nor are the chunks
    past a prompt's last token, and their rows of the slot stay as they
    were.  (A [8, 2048] admit of Mistral's 14 layers was 664 ms on the chip
    with one prompt in it or eight, PERF.md, PR 32; a row of 2,048 was 76 ms
    whether its prompt had 1,100 tokens or 2,000, PR 37; a latent row of
    8,192 was 272 ms whether its prompt had 4,100 tokens or 8,000, PR 47.)

    tokens: [B, S] int32 (right-padded to the bucket length S)
    lengths: [B] true prompt lengths; slot_ids: [B] cache rows to fill.
    start_pos: [B], the absolute position of ``tokens[:, 0]``, where the
      slot already holds what comes before: on a paged tree its block-table
      row points at pages with a reused prefix (read, never written here);
      on a tree of rows alone, K/V or latent, rows ``0 .. start_pos`` of
      the slot, which an earlier call wrote (``start_pos + S`` may not pass
      the slot's ``max_len``).
    rows: scalar int32, how many rows hold a prompt, real rows first; every
      row where it is not given.
    chunk: the shortest chunk (static; ``prefill_width`` lengthens it for
      dropless experts); the engine leaves it alone.
    Returns (cache, last-token logits [B, V] f32).
    """
    tokens, lengths, slot_ids = map(jnp.asarray, (tokens, lengths, slot_ids))
    b = tokens.shape[0]
    if start_pos is not None and not ("block_table" in cache
                                      or _rows_alone(cache)):
        raise ValueError("start_pos: a recurrent state takes no prefix to "
                         "start after")

    def one(r, walk):
        cache, logits = walk
        row = lambda a: jax.lax.dynamic_slice_in_dim(a, r, 1)   # noqa: E731
        cache, lg = _prefill_row(params, cache, row(tokens), row(lengths),
                                 slot_ids[r],
                                 None if start_pos is None else row(start_pos),
                                 cfg, compute_dtype, chunk)
        return cache, jax.lax.dynamic_update_slice_in_dim(logits, lg, r, 0)

    return jax.lax.fori_loop(
        0, b if rows is None else rows, one,
        (cache, jnp.zeros((b, cfg.vocab_size), jnp.float32)))


# ---------------------------------------------------------------------------
# A window of W new tokens a slot (decode: W = 1; speculative verify: W = k)
# ---------------------------------------------------------------------------

def decode_attention(y, ap, cfg: TransformerConfig, k_all, v_all, i, lengths,
                     active):
    """One layer's attention for W new tokens a slot.  y: [slots, W, H];
    k_all, v_all: the stacked cache [layers, slots, max_len, NKV * D], of
    which this is layer ``i``.  Appends the tokens' K/V at ``[i, slot,
    length + j]`` in place and attends over the layer's rows up to each,
    through the ``decode_attn`` kernel (the W tokens' queries its ``W * NH``
    query rows), of
    the ``active`` slots only (an inactive slot keeps a stale length; it is
    read as length 0 and its output is zeros).  Returns (attention
    after its output projection [slots, W, H], k_all, v_all)."""
    from ..ops.decode_attention import decode_attn
    return _step_attention(
        y, ap, cfg, k_all, v_all, i, lengths, active, "full",
        lambda q, k_all, v_all, live, w: decode_attn(
            q, k_all, v_all, i, live, cfg.num_kv_heads,
            cfg.attn_logit_softcap, tokens=w, scale=cfg.attn_scale,
            wide=_wide(cfg)))


def ring_attention(y, ap, cfg: TransformerConfig, k_all, v_all, i, lengths,
                   active):
    """``decode_attention`` for a "window" layer: k_all, v_all are the
    stacked rings [window layers, slots, ring, NKV * D]; token ``j``'s K/V
    land in row ``(length + j) mod ring`` and its query reads the
    ``cfg.sliding_window`` positions up to its own, each ring row masked by
    the position it holds (``ops.decode_attention.window_decode_attn``).
    The ring has to have the step's margin (``ring_len``: ``sliding_window +
    W - 1`` rows at least), which who allocates it sees to."""
    from ..ops.decode_attention import window_decode_attn
    return _step_attention(
        y, ap, cfg, k_all, v_all, i, lengths, active, "window",
        lambda q, k_all, v_all, live, w: window_decode_attn(
            q, k_all, v_all, i, live, cfg.num_kv_heads, cfg.sliding_window,
            w, scale=cfg.attn_scale, wide=_wide(cfg)))


def _step_attention(y, ap, cfg: TransformerConfig, k_all, v_all, i, lengths,
                    active, kind: str, attend):
    """What rows ("full") and rings ("window") share of a step of W tokens a
    slot: the projections, the tokens' rows written at ``[i, slot, length +
    j]`` (on a ring: modulo its rows), ``attend(q [slots, W * NH, D], k_all,
    v_all, live [slots], W)`` over them, the output projection."""
    n_slots, w, _ = y.shape
    cast, span, ring = y.dtype, k_all.shape[2], kind == "window"
    positions = lengths[:, None] + jnp.arange(w)[None]           # [slots, W]
    if kind == "cross":     # the rows are another layer's: nothing to write
        q = _query_alone(y, ap, cfg)
    else:
        q, k, v = _qkv(y, ap, cfg, positions, kind)
        # q: [S, W, NH, D], k/v: [S, W, NKV, D]; one row a token,
        # slot-major: [i, slot, length + j]
        slot, at = jnp.repeat(jnp.arange(n_slots), w), positions.reshape(-1)
        if ring:
            at = at % span
        with jax.named_scope("ring_write" if ring else "kv_write"):
            k_all = k_all.at[i, slot, at].set(
                k.reshape(n_slots * w, -1).astype(k_all.dtype))
            v_all = v_all.at[i, slot, at].set(
                v.reshape(n_slots * w, -1).astype(v_all.dtype))
    with jax.named_scope("ring_read" if ring else "kv_read"):
        # positions that count: up to and with the new tokens'
        live = lengths + w if ring else jnp.minimum(lengths + w, span)
        attn = attend(q.reshape(n_slots, w * cfg.num_heads, cfg.head_dim),
                      k_all, v_all, jnp.where(active, live, 0), w)
    if cfg.diff_attn:
        attn = diff_combine(attn.reshape(n_slots, w, cfg.num_heads, -1), ap,
                            cfg, _depth(cfg, kind, i))
    attn = attn.reshape(n_slots, w, cfg.num_heads * cfg.head_dim)
    return _proj_out(attn.astype(cast), ap, cast, y), k_all, v_all


def cross_attention(y, ap, cfg: TransformerConfig, k_all, v_all, i, lengths,
                    active):
    """``decode_attention`` for a "cross" layer, the ``i``-th: k_all, v_all
    are the rows of the model's one "full" layer, which wrote the new
    tokens' own above; the layer has a query and an output projection of its
    own and writes nothing."""
    from ..ops.decode_attention import decode_attn
    return _step_attention(
        y, ap, cfg, k_all, v_all, i, lengths, active, "cross",
        lambda q, k_all, v_all, live, w: decode_attn(
            q, k_all, v_all, 0, live, cfg.num_kv_heads, tokens=w,
            scale=cfg.attn_scale, wide=_wide(cfg)))


def cross_row(y, ap, cfg: TransformerConfig, k, v, i, length):
    """A "cross" layer, the ``i``-th, for the LAST token of each whole row a
    prefill walked: y [B, 1, H] at position ``length - 1``; k, v [B, S, NKV
    * D], the "full" layer's keys and values of the row, of which the first
    ``length`` [B] count.  Plain attention: one query a row."""
    b, s = k.shape[:2]
    q = _query_alone(y, ap, cfg)
    wide = _wide(cfg) * cfg.head_dim
    with jax.named_scope("cross"):
        reps = cfg.num_heads // cfg.num_kv_heads
        scores = jnp.einsum(
            "bgrd,bmgd->bgrm",
            q.reshape(b, cfg.num_kv_heads, reps, -1).astype(jnp.float32),
            k.reshape(b, s, cfg.num_kv_heads, -1).astype(jnp.float32)
        ) * cfg.attn_scale
        seen = (jnp.arange(s)[None] < length[:, None])[:, None, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        # a K/V head's queries weigh the values of its pair (or its own)
        values = jnp.repeat(v.reshape(b, s, -1, wide).astype(jnp.float32),
                            wide // cfg.head_dim, axis=2)
        attn = jnp.einsum("bgrm,bmgd->bgrd", probs, values)
    if cfg.diff_attn:
        attn = diff_combine(attn.reshape(b, 1, cfg.num_heads, wide), ap, cfg,
                            _depth(cfg, "cross", i))
    return _proj_out(attn.reshape(b, 1, -1).astype(y.dtype), ap, y.dtype, y)


def window_step(params: Params, cache: KVCache, tokens: jnp.ndarray,
                active: jnp.ndarray, cfg: TransformerConfig,
                compute_dtype=jnp.bfloat16, hidden: bool = False):
    """W new tokens for every slot in one forward, on any cache tree.

    tokens: [slots, W] int32 — token j sits at position ``length + j``
    active: [slots] bool — inactive slots compute garbage that is masked
    out; their lengths (and recurrent states) stay as they were
    Returns (cache, logits [slots, W, V] f32): K/V of all W positions are
    appended and ``length`` advances by W for active slots.  ``hidden``: a
    third result, the last layer's output [slots, W, H] (a model with a
    multi-token-prediction block).
    """
    w = tokens.shape[1]
    lengths = cache["length"]
    positions = lengths[:, None] + jnp.arange(w)[None]
    if "block_table" in cache:
        from .paged_decode import page_attention
        span = cache["block_table"].shape[1] * cache["k"].shape[2]
        attend = _kv_mixer(page_attention, cfg, cache["block_table"],
                           positions, active[:, None])
    elif "latent" in cache:
        from .latent import decode_attention as latent_step
        span = cache["latent"].shape[2]
        attend = _kv_mixer(latent_step, cfg, lengths, active)
    else:
        span = cache["k"].shape[2]
        attend = _kv_mixer(decode_attention, cfg, lengths, active)
    rows = LATENT if "latent" in cache else ("k", "v")
    mixers, carry = {"full": attend}, {"full": tuple(cache[n] for n in rows)}
    if "wk" in cache:
        mixers["window"] = _kv_mixer(ring_attention, cfg, lengths, active)
        carry["window"] = tuple(cache[n] for n in RING)
    kind = None
    if "state" in cache:
        from . import hybrid
        kind, _, one_step = hybrid.recurrent(cfg)
        if w != 1:
            raise ValueError("a recurrent state steps one token at a time: "
                             f"window of {w}")

        def recurrent(y, lp, i, sc):
            out, *sc = one_step(y, lp["mixer"], cfg, i, *sc, active)
            return out, tuple(sc), None

        mixers[kind] = recurrent
        carry[kind] = (cache["state"], cache["conv"])
    if cfg.cross_segment:
        # an activation of the pass, no cache: the memory the last "ssm1"
        # layer hands the "gmu" layers rides with what that kind carries
        carry[kind] += (jnp.zeros((tokens.shape[0], w, cfg.ssm1_inner),
                                  compute_dtype),)
        mixers["gmu"] = _memory_unit
        mixers["cross"] = _kv_mixer(cross_attention, cfg, lengths, active)
    logits, carry, ys = layer_stack(
        params, tokens, positions, mixers, carry, cfg, compute_dtype,
        live=jnp.broadcast_to(active[:, None], tokens.shape)
        if cfg.moe_dropless else None)
    new = dict(cache, length=jnp.where(
        active, jnp.minimum(lengths + w, span), lengths))
    new.update(zip(rows, carry["full"]))
    if "wk" in cache:
        new.update(zip(RING, carry["window"]))
    if "moe_counts" in cache:   # what the experts did, over layers and steps
        new["moe_counts"] = cache["moe_counts"] + ys["moe"].sum(axis=0)
    if CHOICES in cache:    # each token's at its position; an idle slot's
        # falls past the end and is dropped
        new[CHOICES] = cache[CHOICES].at[
            :, jnp.arange(tokens.shape[0])[:, None],
            jnp.where(active[:, None], positions, span)].set(
                ys["experts"], mode="drop")
    if kind:
        new["state"], new["conv"] = carry[kind][:2]
    return (new, logits, ys["hidden"]) if hidden else (new, logits)


def mtp_step(params: Params, cache: KVCache, hidden, next_tokens, lengths,
             active, cfg: TransformerConfig, compute_dtype=jnp.bfloat16):
    """The multi-token-prediction block for W positions a slot, ``lengths``
    [slots] on: ``hidden`` [slots, W, H] of the model's ``window_step`` over
    them, each paired with the token after it, ``next_tokens`` [slots, W].
    Writes the block's K/V rows of those positions; ``length`` is the
    caller's to set.  Returns (cache, logits [slots, W, V] f32: for the
    token after each ``next_tokens``)."""
    w = hidden.shape[1]
    logits, carry, ys = mtp_walk(
        params, hidden, next_tokens, lengths[:, None] + jnp.arange(w)[None],
        {"full": _kv_mixer(decode_attention, cfg.mtp_cfg, lengths, active)},
        {"full": tuple(cache[n] for n in MTP_ROWS)}, cfg, compute_dtype,
        live=jnp.broadcast_to(active[:, None], next_tokens.shape)
        if cfg.moe_dropless else None)
    new = dict(cache, **dict(zip(MTP_ROWS, carry["full"])))
    if "moe_counts" in cache:
        new["moe_counts"] = cache["moe_counts"] + ys["moe"].sum(axis=0)
    return new, logits


def decode_step(params: Params, cache: KVCache, tokens: jnp.ndarray,
                active: jnp.ndarray, cfg: TransformerConfig,
                compute_dtype=jnp.bfloat16) -> Tuple[KVCache, jnp.ndarray]:
    """One autoregressive step for every active slot.

    tokens: [slots] int32 — the last emitted token per slot
    active: [slots] bool — inactive slots compute garbage that is masked out
    Returns (cache, logits [slots, V] f32).  Appends K/V at position `length`
    and increments `length` for active slots.
    """
    cache, logits = window_step(params, cache, tokens[:, None], active, cfg,
                                compute_dtype)
    return cache, logits[:, 0]


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


# ---------------------------------------------------------------------------
# Device-resident autoregressive state (zero host ops in the serving loop)
# ---------------------------------------------------------------------------
#
# Every EAGER op or small host->device transfer is its own dispatch and, where
# its result is read, a sync point; a jitted dispatch is async.  The serving
# engine therefore keeps the complete per-slot
# autoregressive state ON DEVICE and only ever calls two jitted programs,
# whatever the kind of cache:
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
                  top_k: int = 0, compute_dtype=jnp.bfloat16,
                  start_pos: Optional[jnp.ndarray] = None,
                  table_rows: Optional[jnp.ndarray] = None):
    """Prefill + sample + merge into the decode state, one fixed-shape
    program that walks the rows ``real_mask`` counts (real rows first;
    ``prefill``) and samples and merges all B, so a row's first token does
    not depend on how many rows came with it.  A paged admit brings two
    more arrays: the admitted slots' block-table rows ``table_rows`` [B,
    max_pages], written first, and ``start_pos`` (``prefill``), so that only
    the uncached suffixes are prefilled.  Returns (cache, state,
    first_tokens [B])."""
    if table_rows is not None:
        cache = dict(cache, block_table=cache["block_table"].at[
            slot_ids].set(table_rows))
    cache, logits = prefill(params, cache, tokens, lengths, slot_ids, cfg,
                            compute_dtype, start_pos,
                            rows=jnp.sum(real_mask, dtype=jnp.int32))
    first = sample_per_slot(logits, state["key"], temps, top_k)
    state = _merge_admit(state, first, slot_ids, temps, budgets, eos,
                         real_mask)
    return cache, state, first


def decode_state_loop(params: Params, cache: KVCache, state: Dict[str, Any],
                      n_steps: int, cfg: TransformerConfig, top_k: int = 0,
                      compute_dtype=jnp.bfloat16):
    """``n_steps`` decode+sample steps with on-device active decay.

    One host dispatch + one readback per *n_steps* tokens-per-slot instead of
    per token: a decode step of a small batch is short, so per-token host
    dispatch would leave the chip waiting on the host.
    Returns (cache, state, emitted [n_steps, slots]).  A slot goes inactive
    the step its budget hits zero or it samples its EOS token; inactive
    slots repeat their last token (the host emits only to live requests).
    A cache with ``moe_counts`` gives ``emitted`` one row more: what the
    dropless expert layers did over these steps, [assignments, experts
    touched, 0...], so that the counts ride the tokens' one fetch
    (``split_moe_counts``)."""
    temps, eos, key = state["temps"], state["eos"], state["key"]
    if "moe_counts" in cache:
        cache = dict(cache, moe_counts=jnp.zeros_like(cache["moe_counts"]))

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
    if "moe_counts" in cache:
        emitted = jnp.concatenate([emitted, jnp.zeros_like(
            emitted[:1]).at[0, :2].set(cache["moe_counts"])])
    return cache, state, emitted


def split_moe_counts(emitted):
    """(tokens [n_steps, slots], (assignments, experts touched)) of what
    ``decode_state_loop`` returned for a cache with ``moe_counts``."""
    return emitted[:-1], (int(emitted[-1, 0]), int(emitted[-1, 1]))
