"""Latent attention, a dense prefix before dropless expert layers, and
residual streams mixed by hyper-connections.  This file is what is
particular to them: the parameter tree, the per-slot state and the attention
mixers for whole rows (``prefill_attention``, with its cache write for
serving and without for the train step: ``attention``) and for
one token a slot (``decode_attention``), and the hyper-connections'
coefficients.  Serving's walk over the layers and its block's wiring are
``decode.py``'s (``layer_stack``), training's ``transformer.py``'s
(``block_forward``; one residual stream), the expert layer is
``ops/moe.py``'s (``moe_dropless``); ``serve/llm.py`` runs the same calls on
this cache as on any.

**Latent attention** (DeepSeek-V2's MLA; ``N`` an RMSNorm with a learned
scale), for a layer's input ``x``::

    c_q = N(x W_dq);  q = c_q W_uq -> heads of [q_nope | q_rope]
    (``q_lora_rank`` 0:  q = x W_q, projected directly)
    [c_kv | k_r] = x W_dkv;  c_kv = N(c_kv);  k_r = rope(k_r)   one for all heads
    [k_nope | v] = c_kv W_ukv                                   per head
    score = (q_nope . k_nope + rope(q_rope) . k_r) * scale;  o = softmax(score) v W_o

A token caches ``c_kv`` after its norm and ``k_r`` after its rotation, for
all heads together: ``latent`` [layers, slots, max_len, kv_lora_rank] and
``rope_key`` [layers, slots, qk_rope_head_dim, max_len] (why two arrays:
``ops/decode_attention.py``), 576 numbers a token a layer at the published
sizes against 2 x 1,024 for Mistral's eight KV heads.  Prefill runs the
*expanded* form, keys and values rebuilt per head from the latent rows,
through the flash kernel the other kinds share; decode runs the *absorbed*
form: ``q' = q_nope W_uk^T`` carries the query into the latent space, scores
and values are read off the cached rows themselves (``mla_decode_attn``),
and ``o = (softmax c_kv) W_uv``.  The same numbers up to rounding.

**Hyper-connections** (manifold-constrained, arXiv 2512.24880): the residual
is ``X`` in ``R^{n x H}`` a token, ``n = hc_mult``.  Around each sublayer
``F`` (with its pre-norm), from the sublayer's own small parameters::

    z = N(vec(X));  [p_pre | p_post | p_res] = z phi
    h_pre = sigmoid(a_pre p_pre + b_pre)            (n)
    h_post = 2 sigmoid(a_post p_post + b_post)      (n)
    H_res = sinkhorn(clamp(a_res mat(p_res) + b_res))   (n x n, doubly stochastic)
    X <- H_res X + h_post^T F(h_pre X)

``sinkhorn`` is ``exp`` and then ``hc_sinkhorn_iters`` rounds of column and
row normalisation with ``hc_eps`` in the divisions, in float32.

Parameters: ``params["prefix"]`` holds the leading dense layers and
``params["blocks"]`` the expert layers, leaves stacked [layers of the group,
...]; a layer's weights are indexed where they lie, the experts' by the
kernel itself.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.ad_checkpoint import checkpoint_name

from .config import TransformerConfig
from .transformer import (Params, _norm, plain_inv_freq, yarn_inv_freq,
                          yarn_mscale)

F32 = jnp.float32
#: a prefill row is padded to whole flash blocks (causality keeps the
#: padding unread)
FLASH_BLOCK = 512


# ---------------------------------------------------------------------------
# Rotary positions (YaRN) and the softmax scale
# ---------------------------------------------------------------------------

def rope_inv_freq(cfg: TransformerConfig) -> np.ndarray:
    """Inverse frequencies of the ``qk_rope_head_dim`` rotary dimensions
    [R / 2], float32: plain, or YaRN's (``transformer.yarn_inv_freq``)."""
    dim = cfg.qk_rope_head_dim
    if not cfg.rope_yarn_factor:
        return plain_inv_freq(cfg.rope_theta, dim).astype(np.float32)
    return yarn_inv_freq(cfg, dim)


def rope_magnitude(cfg: TransformerConfig) -> float:
    """What YaRN multiplies cos and sin by (1 where ``mscale`` equals
    ``mscale_all_dim``)."""
    f = cfg.rope_yarn_factor
    if not f:
        return 1.0
    return (yarn_mscale(f, cfg.rope_yarn_mscale)
            / yarn_mscale(f, cfg.rope_yarn_mscale_all_dim))


def softmax_scale(cfg: TransformerConfig) -> float:
    scale = cfg.qk_head_dim ** -0.5
    if cfg.rope_yarn_factor and cfg.rope_yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.rope_yarn_factor,
                             cfg.rope_yarn_mscale_all_dim) ** 2
    return scale


def rope(x: jnp.ndarray, positions: jnp.ndarray,
         cfg: TransformerConfig) -> jnp.ndarray:
    """x: [B, S, heads, R] at ``positions`` [B, S], rotated in halves."""
    angles = positions[..., None].astype(F32) * rope_inv_freq(cfg)
    mag = rope_magnitude(cfg)
    cos = (jnp.cos(angles) * mag)[:, :, None, :]
    sin = (jnp.sin(angles) * mag)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Parameters and per-slot state
# ---------------------------------------------------------------------------

def hc_widths(cfg: TransformerConfig) -> Tuple[int, int]:
    """(width of the flattened streams, coefficients a sublayer: n for
    ``h_pre``, n for ``h_post``, n x n for ``H_res``)."""
    n = cfg.hc_mult
    return n * cfg.hidden_size, 2 * n + n * n


def expert_stack(key, cfg: TransformerConfig, layers: int, shape, fan_in,
                 dtype):
    """One matrix of every held expert of ``layers`` layers, [layers,
    experts held, *shape], drawn a layer at a time: one [layers, experts,
    ...] draw would hold its float32 bits beside the result.  Of the
    router's experts the ones this holder has (``cfg.experts_held``)."""
    return jax.lax.map(
        lambda k: (jax.random.normal(k, (cfg.experts_held,) + shape, dtype)
                   * fan_in ** -0.5).astype(dtype),
        jax.random.split(key, layers))


def _init_group(key, cfg: TransformerConfig, layers: int, sparse: bool,
                dtype) -> Params:
    """``layers`` layers of one group, leaves stacked [layers, ...]."""
    h, nh, m = cfg.hidden_size, cfg.num_heads, cfg.mlp_size
    keys = iter(jax.random.split(key, 24))
    lead = (layers,)

    def dense(shape, fan_in, gain=1.0):
        return (jax.random.normal(next(keys), lead + shape, dtype)
                * (gain * fan_in ** -0.5)).astype(dtype)

    def ones(n):
        return {"scale": jnp.ones(lead + (n,), dtype)}

    group: Params = {"attn_norm": ones(h), "mlp_norm": ones(h)}
    if cfg.kv_lora_rank:
        qr, cr = cfg.q_lora_rank, cfg.kv_lora_rank
        queries = ({"w_dq": dense((h, qr), h), "q_norm": ones(qr),
                    "w_uq": dense((qr, nh * cfg.qk_head_dim), qr)} if qr
                   else {"wq": dense((h, nh * cfg.qk_head_dim), h)})
        group["attn"] = {
            **queries,
            "w_dkv": dense((h, cfg.latent_row), h), "kv_norm": ones(cr),
            "w_ukv": dense((cr, nh * (cfg.qk_nope_head_dim
                                      + cfg.v_head_dim)), cr),
            "wo": dense((nh * cfg.v_head_dim, h), nh * cfg.v_head_dim),
        }
    else:
        hd, nkv = h // nh, cfg.num_kv_heads
        group["attn"] = {"wq": dense((h, nh * hd), h),
                         "wk": dense((h, nkv * hd), h),
                         "wv": dense((h, nkv * hd), h),
                         "wo": dense((nh * hd, h), nh * hd)}
    if sparse:
        e, em = cfg.num_experts, cfg.expert_mlp_size

        def experts(shape, fan_in):
            return expert_stack(next(keys), cfg, layers, shape, fan_in, dtype)

        group["moe"] = {"router": dense((h, e), h),
                        "bias": jnp.zeros(lead + (e,), dtype),
                        "w_gate": experts((h, em), h),
                        "w_in": experts((h, em), h),
                        "w_out": experts((em, h), em)}
        if cfg.shared_experts:
            sm = cfg.shared_experts * em
            group["moe"].update(shared_gate=dense((h, sm), h),
                                shared_in=dense((h, sm), h),
                                shared_out=dense((sm, h), sm))
    else:
        group["mlp"] = {"w_gate": dense((h, m), h), "w_in": dense((h, m), h),
                        "w_out": dense((m, h), m)}
    if cfg.hc_mult:
        n, (width, coeffs) = cfg.hc_mult, hc_widths(cfg)
        # the streams start equal; ``b_res`` leans to the identity, ``a``
        # lets a token move every coefficient about its bias
        bias = jnp.concatenate([jnp.zeros((2 * n,)),
                                2.0 * jnp.eye(n).reshape(-1)])
        for name in ("hc_attn", "hc_mlp"):
            group[name] = {
                "norm": ones(width), "phi": dense((width, coeffs), width),
                "a": jnp.full(lead + (3,), 0.5, dtype),
                "b": jnp.broadcast_to(bias.astype(dtype), lead + (coeffs,)),
            }
    return group


def init_params(key: jax.Array, cfg: TransformerConfig, dtype) -> Params:
    """The parameter tree of a configuration with any of latent attention,
    dropless experts, a dense prefix or hyper-connections."""
    h = cfg.hidden_size
    k_emb, k_pre, k_blk, k_head = jax.random.split(key, 4)
    prefix = cfg.dense_prefix_layers
    params: Params = {
        "embed": {"tokens": jax.random.normal(
            k_emb, (cfg.vocab_size, h), dtype) * 0.02},
        "blocks": _init_group(k_blk, cfg, cfg.num_layers - prefix,
                              cfg.moe_dropless, dtype),
        "final_norm": {"scale": jnp.ones((h,), dtype)},
    }
    if prefix:
        params["prefix"] = _init_group(k_pre, cfg, prefix, False, dtype)
    if not cfg.tied_embeddings:
        params["lm_head"] = (jax.random.normal(k_head, (h, cfg.vocab_size),
                                               dtype) * h ** -0.5
                             ).astype(dtype)
    return params


def init_cache(cfg: TransformerConfig, num_slots: int, max_len: int,
               dtype) -> Dict[str, jnp.ndarray]:
    """The latent kind of per-slot state (module docstring)."""
    layers = cfg.num_layers
    return {"latent": jnp.zeros((layers, num_slots, max_len,
                                 cfg.kv_lora_rank), dtype),
            "rope_key": jnp.zeros((layers, num_slots, cfg.qk_rope_head_dim,
                                   max_len), dtype)}


# ---------------------------------------------------------------------------
# The attention mixers
# ---------------------------------------------------------------------------

@jax.named_scope("mla_down")
def _down(y, ap, cfg: TransformerConfig, positions):
    """y: [B, S, H] -> (q_nope [B,S,NH,dn], q_rope [B,S,NH,R] rotated, c_kv
    [B,S,C] normed, k_r [B,S,R] rotated): the two compressions, the query's
    heads, and what a token caches."""
    b, s, _ = y.shape
    cast = y.dtype
    if "wq" in ap:                  # q_lora_rank 0: projected directly
        q = y @ ap["wq"].astype(cast)
    else:
        c_q = _norm(y @ ap["w_dq"].astype(cast), ap["q_norm"], cfg)
        q = c_q @ ap["w_uq"].astype(cast)
    q = q.reshape(b, s, cfg.num_heads, cfg.qk_head_dim)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    down = y @ ap["w_dkv"].astype(cast)
    c_kv = _norm(down[..., :cfg.kv_lora_rank], ap["kv_norm"], cfg)
    k_r = rope(down[..., None, cfg.kv_lora_rank:], positions, cfg)[:, :, 0]
    return q_nope, rope(q_rope, positions, cfg), c_kv, k_r


def _row_major(stack):
    """The stacked cache as the engine holds it and the decode kernel reads
    it.  A prefill program has no kernel on the stack to say so, and the
    compiler then carries it through the loops positions-minor-most, a copy
    of the whole cache in and out of every admit (2.2 GB among the
    temporaries; sandbox compile, PERF.md, PR 35)."""
    from jax.experimental.layout import Layout, with_layout_constraint
    return with_layout_constraint(
        stack, Layout(major_to_minor=tuple(range(stack.ndim))))


def _w_ukv(ap, cfg: TransformerConfig, cast):
    """``W_ukv`` by head: (W_uk [C, NH, dn], W_uv [C, NH, dv])."""
    w = ap["w_ukv"].astype(cast).reshape(
        cfg.kv_lora_rank, cfg.num_heads,
        cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def _query(q_nope, q_rope, cfg: TransformerConfig, cast):
    """The expanded form's queries [.., NH, dn + R].  The flash kernels
    scale by the query's width, so the query carries the difference to the
    kind's own scale."""
    q = jnp.concatenate([q_nope, q_rope], axis=-1).astype(F32)
    return (q * (softmax_scale(cfg) * cfg.qk_head_dim ** 0.5)).astype(cast)


def _write(latent_all, rope_all, c_kv, k_r, i, slot, at):
    """One row's ``c_kv`` [1, W, C] and ``k_r`` [1, W, R] at ``[i, slot, at
    : at + W]`` of the stacked cache, in place."""
    with jax.named_scope("latent_write"):
        return (_row_major(jax.lax.dynamic_update_slice(
            latent_all, c_kv.astype(latent_all.dtype)[None],
            (i, slot, at, 0))),
                _row_major(jax.lax.dynamic_update_slice(
                    rope_all, k_r.astype(rope_all.dtype).swapaxes(1, 2)[None],
                    (i, slot, 0, at))))


def _expanded(q_nope, q_rope, c_kv, k_r, ap, cfg: TransformerConfig):
    """Causal latent attention over whole rows in the expanded form, from
    what ``_down`` returns: keys and values rebuilt per head from the latent
    rows, through the flash kernel the other kinds share
    (``ops.attention.mha``; it has a backward), then the output projection.
    Returns [B, S, H]."""
    from ..ops.attention import mha
    b, s, nh, _ = q_nope.shape
    cast = c_kv.dtype
    with jax.named_scope("mla_up"):
        w_uk, w_uv = _w_ukv(ap, cfg, cast)
        k_nope = jnp.einsum("bsc,chd->bshd", c_kv, w_uk)
        v = jnp.einsum("bsc,chd->bshd", c_kv, w_uv)
    # the heads at their own two widths, the sequence in whole blocks
    seq = -(-s // FLASH_BLOCK) * FLASH_BLOCK if s >= 2 * FLASH_BLOCK else s

    def fit(a):
        return jnp.pad(a, ((0, 0), (0, seq - s), (0, 0), (0, 0)))

    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r[:, :, None], (b, s, nh, k_r.shape[-1]))],
        axis=-1)
    with jax.named_scope("attn"):
        attn = mha(fit(_query(q_nope, q_rope, cfg, cast)), fit(k), fit(v),
                   causal=True)
    attn = attn[:, :s].reshape(b, s, -1)
    with jax.named_scope("attn"):
        return attn @ ap["wo"].astype(cast)


def attention(y, ap, cfg: TransformerConfig, positions):
    """One layer's causal latent attention over whole sequences, no cache:
    the train step's mixer.  What a layer's replay needs carries the names
    ``transformer.REMAT_SAVE_NAMES`` has: the queries and what a token would
    cache (keys and values are rebuilt from that, a small matmul).  y: [B,
    S, H]; positions: [1, S] or [B, S].  Returns [B, S, H]."""
    parts = (checkpoint_name(a, "mla_" + n) for a, n in zip(
        _down(y, ap, cfg, positions), ("q_nope", "q_rope", "c_kv", "k_r")))
    return _expanded(*parts, ap, cfg)


def prefill_attention(y, ap, cfg: TransformerConfig, latent_all, rope_all,
                      i, slot, positions):
    """One layer's causal latent attention over one right-padded row, the
    expanded form.  y: [1, S, H]; writes the row's ``c_kv`` and ``k_r`` at
    ``[i, slot]`` of the stacked cache, in place.  Returns (attention after
    its output projection [1, S, H], latent_all, rope_all)."""
    q_nope, q_rope, c_kv, k_r = _down(y, ap, cfg, positions)
    return (_expanded(q_nope, q_rope, c_kv, k_r, ap, cfg),
            *_write(latent_all, rope_all, c_kv, k_r, i, slot, 0))


def _up(c_kv, k_r, ap, cfg: TransformerConfig):
    """The expanded form's keys and values of one sequence's cached rows,
    a head's rows apart.  c_kv: [S, C]; k_r: [S, R] -> (keys [NH, S, dn + R],
    every head's rotary part the one ``k_r``, values [NH, S, dv])."""
    with jax.named_scope("mla_up"):
        w_uk, w_uv = _w_ukv(ap, cfg, c_kv.dtype)
        k_nope = jnp.einsum("sc,chd->hsd", c_kv, w_uk)
        v = jnp.einsum("sc,chd->hsd", c_kv, w_uv)
    return jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r[None], (cfg.num_heads,) + k_r.shape)],
        axis=-1), v


def continued_attention(y, ap, cfg: TransformerConfig, latent_all, rope_all,
                        i, slot, start, span: int):
    """One layer's latent attention for W tokens of one row that continue
    what its slot holds, the expanded form (``decode.continued_attention``
    is the dense kind's).  y: [1, W, H] at positions ``start ..``; writes
    the tokens' ``c_kv`` and ``k_r`` at ``[i, slot, start : start + W]`` of
    the stacked cache in place and attends, causally, over the row so far:
    keys and values [NH, span, ..] (``span`` static, ``start + W <= span``)
    rebuilt from the slot's latent rows ``0 .. start`` in blocks of W, a
    loop whose trip count is data, so the work goes by what the slot holds
    and not by the bucket, and the tokens' own from what ``_down`` gave;
    what lies past them stays zero and is masked.  They are one layer's
    temporaries, not a cache.  The absorbed form would read the latent rows
    as they lie, at 576-wide keys and 512-wide values for 192 / 128: 3.4
    times the kernel's work.  Returns (attention after its output
    projection [1, W, H], latent_all, rope_all)."""
    from ..ops.flash_attention import flash_attention_rows
    w, cast = y.shape[1], y.dtype
    q_nope, q_rope, c_kv, k_r = _down(y, ap, cfg,
                                      start + jnp.arange(w)[None])
    latent_all, rope_all = _write(latent_all, rope_all, c_kv, k_r, i, slot,
                                  start)

    def put(kv, rows, at):
        # (as the kernel reads them: left alone the compiler carries the
        # keys positions-minor-most and transposes them once a call)
        return tuple(_row_major(jax.lax.dynamic_update_slice_in_dim(
            a, r, at, 1)) for a, r in zip(kv, rows))

    def block(b, kv):
        """Rows ``b * W ..`` of the prefix, read back from the slot."""
        c = jax.lax.dynamic_slice(
            latent_all, (i, slot, b * w, 0), (1, 1, w, cfg.kv_lora_rank))
        r = jax.lax.dynamic_slice(
            rope_all, (i, slot, 0, b * w), (1, 1, cfg.qk_rope_head_dim, w))
        with jax.named_scope("latent_read"):
            c, r = c[0, 0].astype(cast), r[0, 0].T.astype(cast)
        return put(kv, _up(c, r, ap, cfg), b * w)

    kv = jax.lax.fori_loop(
        0, -(-start // w), block,
        (jnp.zeros((cfg.num_heads, span, cfg.qk_head_dim), cast),
         jnp.zeros((cfg.num_heads, span, cfg.v_head_dim), cast)))
    # the tokens' own rows last: a start that is no whole number of blocks
    # leaves the last block reaching into them
    k, v = put(kv, _up(c_kv[0], k_r[0], ap, cfg), start)
    with jax.named_scope("attn"):
        attn = flash_attention_rows(_query(q_nope, q_rope, cfg, cast),
                                    k[None, None], v[None, None], 0, 0,
                                    start, span, cfg.num_heads)
        return attn @ ap["wo"].astype(cast), latent_all, rope_all


def decode_attention(y, ap, cfg: TransformerConfig, latent_all, rope_all, i,
                     lengths, active):
    """One layer's latent attention for one new token a slot, the absorbed
    form.  y: [slots, 1, H]; appends the token's ``c_kv`` and ``k_r`` at
    ``[i, slot, length]`` in place and reads the layer's rows up to it
    through ``mla_decode_attn``, of the ``active`` slots only.  Returns
    (attention after its output projection [slots, 1, H], latent_all,
    rope_all)."""
    from ..ops.decode_attention import mla_decode_attn
    n_slots, w, _ = y.shape
    if w != 1:
        raise ValueError("a latent cache is decoded one token a step: "
                         f"window of {w}")
    cast, max_len = y.dtype, latent_all.shape[2]
    q_nope, q_rope, c_kv, k_r = _down(y, ap, cfg, lengths[:, None])
    slot = jnp.arange(n_slots)
    with jax.named_scope("latent_write"):
        latent_all = latent_all.at[i, slot, lengths].set(
            c_kv[:, 0].astype(latent_all.dtype))
        rope_all = rope_all.at[i, slot, :, lengths].set(
            k_r[:, 0].astype(rope_all.dtype))
    w_uk, w_uv = _w_ukv(ap, cfg, cast)
    with jax.named_scope("mla_up"):
        q_lat = jnp.einsum("shd,chd->shc", q_nope[:, 0], w_uk)
    with jax.named_scope("latent_read"):
        live = jnp.where(active, jnp.minimum(lengths + 1, max_len), 0)
        o_lat = mla_decode_attn(q_lat, q_rope[:, 0], latent_all, rope_all, i,
                                live, softmax_scale(cfg))
    with jax.named_scope("mla_up"):
        attn = jnp.einsum("shc,chd->shd", o_lat, w_uv)
    with jax.named_scope("attn"):
        out = attn.reshape(n_slots, 1, -1) @ ap["wo"].astype(cast)
    return out, latent_all, rope_all


# ---------------------------------------------------------------------------
# Hyper-connections
# ---------------------------------------------------------------------------

def sinkhorn(logits, iters: int, eps: float):
    """[..., n, n] float32 -> doubly stochastic: ``exp``, then ``iters``
    rounds of column and row normalisation."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
    return m


@jax.named_scope("hc_coeff")
def hc_coeff(x, hp, cfg: TransformerConfig):
    """The streams x: [rows, W, n, H] -> (h_pre [rows, W, n], h_post [rows,
    W, n], H_res [rows, W, n, n]), float32, from one sublayer's parameters
    ``hp``."""
    n = cfg.hc_mult
    z = _norm(x.reshape(x.shape[:2] + (-1,)), hp["norm"], cfg)
    # in the weights' own type on the MXU, accumulated in float32: the
    # coefficients are continuous in z, a rounding of it moves them little
    phi = hp["phi"]
    p = jnp.einsum("rwz,zc->rwc", z.astype(phi.dtype), phi,
                   preferred_element_type=F32)
    a, b = hp["a"].astype(F32), hp["b"].astype(F32)
    pre = jax.nn.sigmoid(a[0] * p[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * p[..., n:2 * n] + b[n:2 * n])
    res = (a[2] * p[..., 2 * n:] + b[2 * n:]).reshape(p.shape[:2] + (n, n))
    res = jnp.clip(res, -cfg.hc_res_clamp, cfg.hc_res_clamp)
    return pre, post, sinkhorn(res, cfg.hc_sinkhorn_iters, cfg.hc_eps)


@jax.named_scope("hc_mix")
def hc_read(x, pre):
    """What the sublayer sees: ``h_pre X`` [rows, W, H], as X is kept."""
    return (pre[..., None] * x.astype(F32)).sum(axis=2).astype(x.dtype)


@jax.named_scope("hc_mix")
def hc_write(x, out, post, res):
    """``H_res X + h_post^T out``: the streams after the sublayer."""
    x32 = x.astype(F32)
    mixed = sum(res[..., n, None] * x32[:, :, None, n]
                for n in range(x.shape[2]))
    return (mixed + post[..., None] * out.astype(F32)[:, :, None, :]
            ).astype(x.dtype)
