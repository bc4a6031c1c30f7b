"""Decoder-only transformer: one implementation for GPT-2 / Llama-3 / Mixtral.

TPU-first design choices:
* **Stacked layer params + lax.scan** — compile time independent of depth; XLA sees one
  block body (the reference's torch models unroll layers in Python).
* **bf16 compute, fp32 params/optimizer** — matmuls hit the MXU in bf16; the cast sits
  next to each einsum so XLA fuses it.
* **Static shapes everywhere** — no data-dependent control flow inside jit.
* Attention dispatches to plain XLA / Pallas flash / ring attention (`sp` axis)
  based on a `ParallelContext`.

Params are a plain pytree (dict) so sharding rules (models/sharding.py) are specs over
the same tree structure.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..ops import moe as moe_ops
from ..ops.attention import attend, mha
from .config import TransformerConfig

Params = Dict[str, Any]

# Tensors tagged with checkpoint_name inside the block: the big matmul outputs
# whose recompute dominates the remat replay.  "save_acts" keeps all of them —
# the backward then replays only norms/elementwise — at ~(3*h + 2*m) bf16
# bytes/token/layer of HBM.  "save_mlp" keeps just the MLP half (the FLOP bulk)
# when the full set doesn't fit.
REMAT_SAVE_NAMES = ("attn_q", "attn_k", "attn_v", "attn_out", "attn_lse",
                    "mlp_gate", "mlp_up", "mlp_pre",
                    # latent attention (models/latent.py ``attention``): the
                    # queries and what a token would cache; keys and values
                    # are rebuilt from that.  Nothing of a dropless expert
                    # layer's sorted rows is kept: they are sized for every
                    # assignment (ops/moe.py), and its replay is three
                    # grouped products
                    "mla_q_nope", "mla_q_rope", "mla_c_kv", "mla_k_r")


def remat_policy(remat: Union[bool, str, None]):
    """Map a remat spec to (enabled, jax.checkpoint policy).

    - False/None: no rematerialization (fastest when activations fit HBM)
    - True / "full": save nothing, replay the whole block (min memory)
    - "save_acts": save the named matmul outputs above (replay ~= norms only)
    - "save_mlp": save only the MLP intermediates
    - "dots": XLA-style save-all-matmul-outputs policy
    """
    if remat is None or remat is False:
        return False, None
    if remat is True or remat == "full":
        return True, jax.checkpoint_policies.nothing_saveable
    if remat == "save_acts":
        return True, jax.checkpoint_policies.save_only_these_names(
            *REMAT_SAVE_NAMES)
    if remat == "save_mlp":
        return True, jax.checkpoint_policies.save_only_these_names(
            "mlp_gate", "mlp_up", "mlp_pre")
    if remat == "dots":
        return True, jax.checkpoint_policies.dots_saveable
    raise ValueError(f"unknown remat policy {remat!r}")


@dataclasses.dataclass(frozen=True)
class ParallelContext:
    """How to run attention/MoE under a mesh. None mesh = single device."""
    mesh: Optional[Any] = None
    sp_axis: Optional[str] = None     # sequence-parallel axis name (ring attn)
    batch_axes: Tuple[str, ...] = ("dp",)
    # True when the caller is ALREADY inside a shard_map where sp_axis is
    # manual (the pipeline): ring attention then runs its per-shard body
    # directly instead of opening a nested shard_map.
    manual_collectives: bool = False

    @property
    def use_ring(self) -> bool:
        return (self.mesh is not None and self.sp_axis is not None
                and self.mesh.shape.get(self.sp_axis, 1) > 1)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(key: jax.Array, cfg: TransformerConfig,
                dtype=jnp.float32) -> Params:
    if cfg.latent_tree:
        # latent attention, dropless experts, a dense prefix, residual
        # streams (models/latent.py)
        from . import latent
        return latent.init_params(key, cfg, dtype)
    h, hd = cfg.hidden_size, cfg.head_dim
    nh, nkv, m, L = cfg.num_heads, cfg.num_kv_heads, cfg.mlp_size, cfg.num_layers
    keys = iter(jax.random.split(key, 32))

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, dtype) * (fan_in ** -0.5)).astype(dtype)

    if cfg.layer_pattern:
        # layers of two kinds: blocks stacked per kind (models/hybrid.py)
        from . import hybrid
        hybrid_params: Params = {
            "embed": {"tokens": jax.random.normal(
                next(keys), (cfg.vocab_size, h), dtype) * 0.02},
            "blocks": hybrid.init_blocks(next(keys), cfg, dtype),
            "final_norm": {"scale": jnp.ones((h,), dtype)},
        }
        if not cfg.use_rmsnorm:     # a LayerNorm: a scale and a bias
            hybrid_params["final_norm"]["bias"] = jnp.zeros((h,), dtype)
        if not cfg.tied_embeddings:
            hybrid_params["lm_head"] = dense(next(keys),
                                             (h, cfg.vocab_size), h)
        if cfg.mtp_layers:
            # the multi-token-prediction block (``cfg.mtp_cfg``): the
            # embedding and the head are the model's
            ones = lambda: {"scale": jnp.ones((h,), dtype)}    # noqa: E731
            mtp_keys = jax.random.split(jax.random.fold_in(key, 3), 2)
            hybrid_params["mtp"] = {
                "proj": dense(mtp_keys[0], (2 * h, h), 2 * h),
                "embed_norm": ones(), "hidden_norm": ones(),
                "blocks": hybrid.init_blocks(mtp_keys[1], cfg.mtp_cfg, dtype),
                "final_norm": ones()}
        return hybrid_params

    def norm_p():
        p = {"scale": jnp.ones((L, h), dtype)}
        if not cfg.use_rmsnorm:
            p["bias"] = jnp.zeros((L, h), dtype)
        return p

    blocks: Params = {
        "attn_norm": norm_p(),
        "attn": {
            "wq": dense(next(keys), (L, h, nh * hd), h),
            "wk": dense(next(keys), (L, h, nkv * hd), h),
            "wv": dense(next(keys), (L, h, nkv * hd), h),
            "wo": dense(next(keys), (L, nh * hd, h), nh * hd),
        },
        "mlp_norm": norm_p(),
    }
    if not cfg.use_rmsnorm or cfg.use_qkv_bias:
        # GPT-2 style (all biases) or Qwen-2 style (Q/K/V biases only)
        blocks["attn"]["bq"] = jnp.zeros((L, nh * hd), dtype)
        blocks["attn"]["bk"] = jnp.zeros((L, nkv * hd), dtype)
        blocks["attn"]["bv"] = jnp.zeros((L, nkv * hd), dtype)
    if not cfg.use_rmsnorm:
        blocks["attn"]["bo"] = jnp.zeros((L, h), dtype)
    if cfg.num_experts > 1:
        e = cfg.num_experts
        blocks["moe"] = {
            "router": dense(next(keys), (L, h, e), h),
            "w_gate": dense(next(keys), (L, e, h, m), h),
            "w_in": dense(next(keys), (L, e, h, m), h),
            "w_out": dense(next(keys), (L, e, m, h), m),
        }
    else:
        mlp: Params = {
            "w_in": dense(next(keys), (L, h, m), h),
            "w_out": dense(next(keys), (L, m, h), m),
        }
        if cfg.use_swiglu:
            mlp["w_gate"] = dense(next(keys), (L, h, m), h)
        else:
            mlp["b_in"] = jnp.zeros((L, m), dtype)
            mlp["b_out"] = jnp.zeros((L, h), dtype)
        blocks["mlp"] = mlp

    params: Params = {
        "embed": {"tokens": (jax.random.normal(next(keys), (cfg.vocab_size, h),
                                               dtype) * 0.02)},
        "blocks": blocks,
        "final_norm": {"scale": jnp.ones((h,), dtype)},
    }
    if cfg.learned_positions:
        params["embed"]["pos"] = (
            jax.random.normal(next(keys), (cfg.max_seq_len, h), dtype) * 0.01)
    if not cfg.use_rmsnorm:
        params["final_norm"]["bias"] = jnp.zeros((h,), dtype)
    if not cfg.tied_embeddings:
        params["lm_head"] = dense(next(keys), (h, cfg.vocab_size), h)
    return params


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------
#
# Each part of a block is written once and carries a ``jax.named_scope``
# (attn, mlp, norm here; kv_write, kv_read, lm_head in decode.py; loss
# below; optimizer in the train step): compile-time metadata on its
# operations, which a profiler's op view groups by.

@jax.named_scope("norm")
def _norm(x, p, cfg: TransformerConfig):
    x32 = x.astype(jnp.float32)
    if cfg.use_rmsnorm:
        x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True)
                                  + cfg.norm_eps)
        return (x32 * p["scale"].astype(jnp.float32)).astype(x.dtype)
    mean = x32.mean(-1, keepdims=True)
    var = ((x32 - mean) ** 2).mean(-1, keepdims=True)
    x32 = (x32 - mean) * jax.lax.rsqrt(var + cfg.norm_eps)
    return (x32 * p["scale"] + p["bias"]).astype(x.dtype)


def plain_inv_freq(theta: float, dim: int) -> np.ndarray:
    """The plain rotary table's inverse frequencies [dim / 2], float64."""
    return 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)


def yarn_inv_freq(cfg: TransformerConfig, dim: int) -> np.ndarray:
    """Inverse frequencies of ``dim`` rotary dimensions [dim / 2], float32,
    under the configuration's YaRN (``rope_yarn_*``): a blend of the plain
    ones at ``rope_theta`` and the ones divided by ``factor``, by a linear
    ramp between the dimensions that turn ``beta_fast`` and ``beta_slow``
    times over the original context."""
    base = cfg.rope_theta
    plain = plain_inv_freq(base, dim)

    def turns_at(n_rot):        # the dimension that turns n_rot times
        return dim * math.log(cfg.rope_yarn_original_max
                              / (n_rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(turns_at(cfg.rope_yarn_beta_fast)), 0)
    high = min(math.ceil(turns_at(cfg.rope_yarn_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (plain / cfg.rope_yarn_factor * ramp
            + plain * (1 - ramp)).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_table(cfg: TransformerConfig, kind: str):
    """The rotary table of a pattern's layer of ``kind``: (inverse
    frequencies [head_dim / 2] float32, what cos and sin are multiplied by),
    YaRN's for the kinds of ``cfg.rope_yarn_kinds`` (the published
    ``attention_factor``: ``0.1 ln(factor) + 1``), plain for the others;
    None where the kind adds no positions."""
    if not cfg.use_rope or (cfg.rope_window_only and kind != "window"):
        return None
    dim = cfg.head_dim
    if kind in cfg.rope_yarn_kinds:
        return yarn_inv_freq(cfg, dim), (
            yarn_mscale(cfg.rope_yarn_factor, cfg.rope_yarn_mscale)
            / yarn_mscale(cfg.rope_yarn_factor, cfg.rope_yarn_mscale_all_dim))
    return plain_inv_freq(cfg.rope_theta, dim).astype(np.float32), 1.0


def _rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
          table=None) -> jnp.ndarray:
    """x: [B, S, H, D]; positions: [S]; ``table``: a kind's own
    (``rope_table``) in place of the plain one at ``theta``."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    mag = 1.0
    if table is not None:
        freqs, mag = jnp.asarray(table[0]), table[1]
    angles = positions[:, None].astype(jnp.float32) * freqs[None, :]  # [S, D/2]
    cos = (jnp.cos(angles) * mag)[None, :, None, :]
    sin = (jnp.sin(angles) * mag)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


@jax.named_scope("attn")
def _attention_block(x, p, cfg: TransformerConfig, positions,
                     pctx: ParallelContext, kind: Optional[str] = None):
    """``kind``: the layer's place in a pattern ("full", "window"), which
    says its span (``cfg.sliding_window``) and its rotary table
    (``rope_table``); None: a dense model's layer."""
    b, s, h = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    cast = x.dtype
    q = x @ p["wq"].astype(cast)
    k = x @ p["wk"].astype(cast)
    v = x @ p["wv"].astype(cast)
    if "bq" in p:
        q, k, v = q + p["bq"].astype(cast), k + p["bk"].astype(cast), v + p["bv"].astype(cast)
    q = q.reshape(b, s, nh, hd)
    k = k.reshape(b, s, nkv, hd)
    v = v.reshape(b, s, nkv, hd)
    window, scale, table = 0, None, None
    rotary = cfg.use_rope
    if kind is not None:
        if cfg.qk_head_norm:        # over a head, one scale for all heads
            q, k = _norm(q, p["q_norm"], cfg), _norm(k, p["k_norm"], cfg)
        table = rope_table(cfg, kind)
        rotary = table is not None
        window = cfg.sliding_window if kind == "window" else 0
        scale = cfg.attn_scale
        if window and (pctx.use_ring or cfg.attention_impl == "splash"):
            raise NotImplementedError(
                "a 'window' layer trains through the flash kernel's band "
                "or the plain mask: ring attention and the splash kernel "
                "have neither")
    if rotary:
        q = _rope(q, positions, cfg.rope_theta, table)
        k = _rope(k, positions, cfg.rope_theta, table)
    q = checkpoint_name(q, "attn_q")
    k = checkpoint_name(k, "attn_k")
    v = checkpoint_name(v, "attn_v")
    if pctx.use_ring and pctx.manual_collectives:
        from ..ops.ring_attention import _ring_attn_shard
        out = _ring_attn_shard(q, k, v, pctx.sp_axis, causal=cfg.causal,
                               logit_softcap=cfg.attn_logit_softcap)
    elif pctx.use_ring:
        from ..ops.ring_attention import ring_attention
        out = ring_attention(q, k, v, pctx.mesh, pctx.sp_axis,
                             causal=cfg.causal, batch_axes=pctx.batch_axes,
                             logit_softcap=cfg.attn_logit_softcap)
    else:
        # Kernels are shard_mapped over the batch on a multi-device mesh,
        # except inside a region that is already manual (pipeline, zero).
        kmesh = None if pctx.manual_collectives else pctx.mesh
        impl = cfg.attention_impl
        if impl == "splash":
            from ..ops.splash_attention import splash_mha
            out = splash_mha(q, k, v, causal=cfg.causal,
                             logit_softcap=cfg.attn_logit_softcap,
                             mesh=kmesh, batch_axes=pctx.batch_axes)
        elif impl == "plain":
            out = attend(q, k, v, causal=cfg.causal,
                         logit_softcap=cfg.attn_logit_softcap, window=window,
                         scale=scale)
        elif impl in ("flash", "auto"):
            # explicit "flash" raises where the kernel cannot run (softcap,
            # a shape that does not tile); only "auto" chooses
            out = mha(q, k, v, causal=cfg.causal,
                      logit_softcap=cfg.attn_logit_softcap,
                      use_flash=True if impl == "flash" else None,
                      mesh=kmesh, batch_axes=pctx.batch_axes, window=window,
                      scale=scale)
        else:
            raise ValueError(f"unknown attention_impl {impl!r}")
    out = checkpoint_name(out, "attn_out")
    out = out.reshape(b, s, nh * hd) @ p["wo"].astype(cast)
    if "bo" in p:
        out = out + p["bo"].astype(cast)
    return out


@jax.named_scope("mlp")
def _mlp_block(x, p, cfg: TransformerConfig):
    cast = x.dtype
    if cfg.use_swiglu:
        gate, up = x @ p["w_gate"].astype(cast), x @ p["w_in"].astype(cast)
        # a dense prefix's MLP is wide and alone: its two products kept for
        # the backward are 0.7 GB of a 16 GB chip at the share cell's
        # sizes, and their replay a hundredth of the step (PERF.md, PR 39)
        if not cfg.dense_prefix_layers:
            gate = checkpoint_name(gate, "mlp_gate")
            up = checkpoint_name(up, "mlp_up")
        return (jax.nn.silu(gate) * up) @ p["w_out"].astype(cast)
    hmid = x @ p["w_in"].astype(cast) + p["b_in"].astype(cast)
    hmid = checkpoint_name(hmid, "mlp_pre")
    hmid = jax.nn.gelu(hmid)
    return hmid @ p["w_out"].astype(cast) + p["b_out"].astype(cast)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _block(x: jnp.ndarray, layer_params: Params, cfg: TransformerConfig,
           positions: jnp.ndarray, pctx: ParallelContext):
    """One block on one residual stream: x [B, S, H] -> (x, aux), the
    sublayers chosen by what the configuration and the layer's parameters
    say: attention dense or latent (``cfg.kv_lora_rank``), the MLP dense
    (a layer with ``mlp``: every layer of a dense model, the dense prefix of
    an expert model), experts with a capacity (``moe_mlp``) or dropless
    (``cfg.moe_dropless``).  aux: ``moe_aux_loss`` (the capacity layer's
    balance term, else 0) and, of a dropless layer, ``moe_load`` [experts
    held] int32, the assignments each held expert computed."""
    y = _norm(x, layer_params["attn_norm"], cfg)
    if cfg.kv_lora_rank:
        from . import latent
        attn_out = latent.attention(y, layer_params["attn"], cfg,
                                    positions[None])
    else:
        attn_out = _attention_block(y, layer_params["attn"], cfg, positions,
                                    pctx)
    x = x + attn_out
    y = _norm(x, layer_params["mlp_norm"], cfg)
    aux = {"moe_aux_loss": jnp.zeros((), jnp.float32)}
    if "mlp" in layer_params:
        out = _mlp_block(y, layer_params["mlp"], cfg)
    elif cfg.moe_dropless:
        out, more = _dropless_block(y, layer_params["moe"], cfg, pctx)
        aux.update(more)
    else:
        out, aux["moe_aux_loss"] = moe_ops.moe_mlp(
            y, layer_params["moe"]["router"], layer_params["moe"]["w_gate"],
            layer_params["moe"]["w_in"], layer_params["moe"]["w_out"],
            cfg.experts_per_token, cfg.expert_capacity_factor)
    return x + out, aux


def _pattern_block(x, lp: Params, experts: Optional[Params],
                   cfg: TransformerConfig, positions, pctx: ParallelContext,
                   kind: str):
    """One layer of a pattern that trains (``cfg.pattern_untrained`` empty):
    ``_block``'s wiring with the attention of the layer's ``kind``; ``lp``
    the layer's leaves of ``models/hybrid.py``'s tree, ``experts`` its routed
    experts' three matrices [held, ...] where the model has them."""
    with jax.named_scope(f"layer_{kind}"):
        y = _norm(x, lp["attn_norm"], cfg)
        x = x + _attention_block(y, lp["attn"], cfg, positions, pctx, kind)
        y = _norm(x, lp["mlp_norm"], cfg)
        if "mlp" in lp:
            return x + _mlp_block(y, lp["mlp"], cfg), {}
        out, aux = _dropless_block(y, {**lp["moe"], **experts}, cfg, pctx)
        return x + out, aux


def _pattern_trunk(blocks: Params, x, cfg: TransformerConfig, positions,
                   pctx: ParallelContext, remat):
    """The layers of a pattern, a ``lax.scan`` over its periods: one period's
    layers are traced once, in the pattern's order, each with the span and
    the rotary table of its place (static: data of the place, not a second
    block).  ``blocks``: ``models/hybrid.py``'s tree, a kind's leaves
    [periods, layers of the kind a period, ...] and the routed experts
    [layers, held, ...] in layer order.  Returns (x, aux stacked
    [layers, ...])."""
    pattern, periods = cfg.layer_pattern, cfg.num_periods
    enabled, policy = remat_policy(remat)
    xs = {k: blocks[k] for k in dict.fromkeys(pattern)}
    if "experts" in blocks:
        xs["experts"] = jax.tree.map(
            lambda a: a.reshape((periods, len(pattern)) + a.shape[1:]),
            blocks["experts"])

    def period(x, pp):
        auxes, seen = [], {}
        for j, kind in enumerate(pattern):
            n = seen[kind] = seen.get(kind, -1) + 1
            layer = functools.partial(_pattern_block, cfg=cfg,
                                      positions=positions, pctx=pctx,
                                      kind=kind)
            if enabled:
                layer = jax.checkpoint(layer, policy=policy)
            x, aux = layer(x, jax.tree.map(lambda a: a[n], pp[kind]),
                           jax.tree.map(lambda a: a[j], pp["experts"])
                           if "experts" in pp else None)
            auxes.append(aux)
        return x, jax.tree.map(lambda *a: jnp.stack(a), *auxes)

    x, aux = jax.lax.scan(period, x, xs)
    return x, jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), aux)


def _dropless_block(y, mp: Params, cfg: TransformerConfig,
                    pctx: ParallelContext = ParallelContext()):
    """The dropless expert layer (``ops.moe.moe_dropless``) on y [B, S, H]
    with one layer's parameters ``mp``: the experts this holder has, cast to
    y's dtype and handed over as a stack of one layer; the router scores y
    in float32 with its float32 master weights.  On a mesh of more than one
    device the experts lie ``num_experts / ep`` a holder and the layer is
    ``moe_dropless_ep`` under a ``shard_map``: each holder routes its own
    sequences and the exchange over ``ep`` does the rest (the mesh decides;
    there is no switch).  Returns (out, aux): ``moe_load``
    [experts] int32, the assignments each expert computed (of the experts
    held here; all of them over ``ep``), ``moe_chip_load`` [holders], their
    sum a holder, ``moe_rows_live`` [holders], the share of a holder's
    sorted layout that lay in tiles holding an assignment
    (``ops.moe.rows_live_share``; over ``ep`` a mean over the ring's steps),
    and ``moe_balance``, the softmax router's balance term, a
    mean over the sequences (0 where the configuration weighs none)."""
    routed = ("w_gate", "w_in", "w_out")
    small = {k: v for k, v in mp.items() if k not in routed}
    kw = dict(experts_per_token=cfg.experts_per_token,
              scaling=cfg.routed_scaling_factor, router=cfg.moe_router)

    def balance(y, idx):
        """[B]: the balance term of each sequence's tokens."""
        if not cfg.moe_balance_weight:
            return jnp.zeros(y.shape[:1], jnp.float32)
        return jax.vmap(lambda t, i: moe_ops.balance_term(
            t, small["router"], i))(y, idx.reshape(y.shape[:2] + (-1,)))

    mesh = None if pctx.manual_collectives else pctx.mesh
    if mesh is not None and mesh.size > 1:
        # (whatever ``ep`` is: the kernels under the layer want a manual
        # region, and with one holder the walk is one step and no hop)
        if cfg.experts_held != cfg.num_experts or cfg.share_by_position:
            raise NotImplementedError(
                "a share of the experts (experts_held, share_by_position) "
                "is one chip's cut of a layer with no exchange; on a mesh "
                "with an ep axis the holders have every expert between them")
        P = jax.sharding.PartitionSpec
        over = tuple(a for a in pctx.batch_axes if a in mesh.axis_names)
        batch = P(over)
        groups = tuple(a for a in over if a != "ep")

        def holder(y, small, stacks):
            out, load, idx, rows_live = moe_ops.moe_dropless_ep(
                y.reshape(-1, y.shape[-1]), small,
                {k: jax.lax.optimization_barrier(v)[None].astype(y.dtype)
                 for k, v in stacks.items()}, 0, axis="ep", **kw)
            # (a holder's experts have a copy in every data-parallel group)
            load = jax.lax.psum(load, groups) if groups else load
            return out.reshape(y.shape), load, load.sum()[None], \
                rows_live[None], balance(y, idx), \
                idx.reshape(y.shape[:2] + (-1,))

        out, load, chips, rows_live, bal, idx = jax.shard_map(
            holder, mesh=mesh, in_specs=(batch, P(), P("ep")),
            out_specs=(batch, P("ep"), P("ep"), P("ep"), batch, batch),
            check_vma=False)(y, small, {k: mp[k] for k in routed})
        return out, {"moe_load": load, "moe_chip_load": chips,
                     "moe_rows_live": rows_live,
                     "moe_balance": bal.mean(), "moe_choices": idx}
    start = cfg.expert_start
    if cfg.share_by_position:
        # the held weights stand for another group of the router's outputs
        # at each position (config.py): the first expert of a token's group
        group = (start // cfg.experts_held + jnp.arange(y.shape[1])) \
            % (cfg.num_experts // cfg.experts_held)
        start = jnp.tile(group * cfg.experts_held, y.shape[0])
    out, _, idx, load = moe_ops.moe_dropless(
        y.reshape(-1, y.shape[-1]), small,
        # (the barrier keeps the cast in the layer: hoisted out of the scan
        # it is a second copy of every layer's experts)
        {k: jax.lax.optimization_barrier(mp[k])[None].astype(y.dtype)
         for k in routed}, 0, expert_start=start, **kw)
    return out.reshape(y.shape), {
        "moe_load": load, "moe_chip_load": load.sum()[None],
        "moe_rows_live": moe_ops.rows_live_share(
            load, y.shape[0] * y.shape[1] * cfg.experts_per_token)[None],
        "moe_balance": balance(y, idx).mean(),
        "moe_choices": idx.reshape(y.shape[:2] + (-1,))}


def block_forward(x: jnp.ndarray, layer_params: Params, cfg: TransformerConfig,
                  positions: jnp.ndarray,
                  pctx: ParallelContext = ParallelContext()):
    """One transformer block: x [B, S, H] -> (x, moe aux loss).  Shared by the
    layer scan below and the pipeline-parallel stage loop
    (parallel/pipeline.py)."""
    x, aux = _block(x, layer_params, cfg, positions, pctx)
    return x, aux["moe_aux_loss"]


def embed_tokens(params: Params, tokens: jnp.ndarray, cfg: TransformerConfig,
                 compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    """Token (+ learned positional) embedding: [B, S] -> [B, S, H]."""
    x = params["embed"]["tokens"][tokens].astype(compute_dtype)
    if cfg.learned_positions:
        s = tokens.shape[1]
        x = x + params["embed"]["pos"][:s][None].astype(compute_dtype)
    return x


def refuse_layer_pattern(cfg: TransformerConfig, what: str):
    """What the training path still cannot run.  A pattern of "full" and
    "window" attention layers trains (``_pattern_trunk``: the band has a
    backward, ``ops/flash_attention.py``); a pattern with a recurrent kind
    ("linear", "ssm": their chunk kernels are forward only), with "mlp"
    layers of their own, or with anything only the serving path's block
    wires (``cfg.pattern_untrained``) does not, and neither does what
    ``cfg.served_only`` names (several residual streams)."""
    if cfg.pattern_untrained:
        raise NotImplementedError(
            f"{what}: layer_pattern {cfg.layer_pattern} with "
            f"{', '.join(cfg.pattern_untrained)} is served (models/hybrid.py,"
            " models/decode.py: prefill, decode_step), not trained: the "
            "gated-delta-rule, state-space and selective-scan kernels have "
            "no backward, and the train step's block wires a pre-norm "
            "attention layer ('full' or 'window') with its MLP or experts "
            "beneath and no more (no 'gmu', no 'cross', no differential "
            "attention, no stack of segments)")
    if cfg.served_only:
        raise NotImplementedError(
            f"{what}: {', '.join(cfg.served_only)} is served "
            "(models/latent.py hc_*, models/decode.py layer_stack: prefill, "
            "decode_step), not trained: the train step's block wires one "
            "residual stream")


def apply_trunk(params: Params, tokens: jnp.ndarray, cfg: TransformerConfig,
                pctx: ParallelContext = ParallelContext(),
                compute_dtype=jnp.bfloat16,
                remat: Union[bool, str, None] = False
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """tokens: [B, S] int32 -> (final hidden states [B, S, H], aux dict).

    The trunk stops before the LM head so losses can run the head blockwise
    (see ``chunked_cross_entropy``) without ever materializing [B, S, V]."""
    refuse_layer_pattern(cfg, "apply_trunk")
    b, s = tokens.shape
    x = embed_tokens(params, tokens, cfg, compute_dtype)
    # Positions are global sequence positions; under jit with a sequence-sharded
    # batch XLA partitions this computation (only ring attention, which runs in
    # shard_map, handles per-shard offsets itself).
    positions = jnp.arange(s)
    if cfg.layer_pattern:
        x, aux = _pattern_trunk(params["blocks"], x, cfg, positions, pctx,
                                remat)
        aux.setdefault("moe_aux_loss", jnp.zeros((1,), jnp.float32))
        return _norm(x, params["final_norm"], cfg), _trunk_aux(aux)

    def scan_body(x, layer_params):
        return _block(x, layer_params, cfg, positions, pctx)

    enabled, policy = remat_policy(remat)
    if enabled:
        # Per-layer rematerialization: backward recomputes one block at a time,
        # so peak activation memory is O(saved names) in depth (HBM is the
        # bottleneck — trade FLOPs for memory). The policy picks which matmul
        # outputs survive; "save_acts" makes the replay nearly free while
        # keeping ~1/3 of the no-remat activation footprint.
        scan_body = jax.checkpoint(scan_body, policy=policy)

    # a dense prefix (models/latent.py's tree) is walked before the scan,
    # which is then over the expert layers
    for j in range(cfg.dense_prefix_layers):
        x, _ = scan_body(x, jax.tree.map(lambda a: a[j], params["prefix"]))
    x, aux = jax.lax.scan(scan_body, x, params["blocks"])
    x = _norm(x, params["final_norm"], cfg)
    return x, _trunk_aux(aux)


def _trunk_aux(aux):
    """The layers' stacked aux with its two terms of the loss as means over
    the layers."""
    return dict(aux, **{k: aux[k].mean()
                        for k in ("moe_aux_loss", "moe_balance") if k in aux})


def lm_head_weight(params: Params, cfg: TransformerConfig, dtype) -> jnp.ndarray:
    """[H, V] head weight (tied embedding transpose or separate lm_head)."""
    if cfg.tied_embeddings:
        return params["embed"]["tokens"].T.astype(dtype)
    return params["lm_head"].astype(dtype)


@jax.named_scope("lm_head")
def lm_head_logits(params: Params, x: jnp.ndarray,
                   cfg: TransformerConfig) -> jnp.ndarray:
    """Hidden states [..., H] -> f32 logits [..., V], the head applied in
    the hidden states' own dtype; divided by ``cfg.logits_scaling`` where
    the configuration has one."""
    logits = (x @ lm_head_weight(params, cfg, x.dtype)).astype(jnp.float32)
    return logits / cfg.logits_scaling if cfg.logits_scaling else logits


def apply(params: Params, tokens: jnp.ndarray, cfg: TransformerConfig,
          pctx: ParallelContext = ParallelContext(),
          compute_dtype=jnp.bfloat16,
          remat: Union[bool, str, None] = False
          ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """tokens: [B, S] int32 -> (logits [B, S, V] f32, aux dict)."""
    x, aux = apply_trunk(params, tokens, cfg, pctx, compute_dtype, remat=remat)
    return lm_head_logits(params, x, cfg), aux


@jax.named_scope("loss")
def chunked_cross_entropy(x: jnp.ndarray, w: jnp.ndarray,
                          targets: jnp.ndarray, chunk: int,
                          layout: Optional[HeadLayout] = None) -> jnp.ndarray:
    """Blockwise LM-head + softmax cross entropy: peak memory O(B*chunk*V)
    instead of O(B*S*V).

    The f32 [batch, seq, vocab] logits tensor is what OOMed the round-1 bench
    (llama-1b: 8*2048*32768*4B = 2 GiB forward + the same again in backward).
    Here the head matmul runs per sequence-chunk inside a rematerialized
    ``lax.scan``: forward keeps only the per-token NLL, backward recomputes one
    chunk's logits at a time.  MXU accumulation stays f32 via
    ``preferred_element_type`` so numerics match the unchunked f32 path.

    The backward is a hand-written VJP (not AD through a remat scan): forward
    saves only the per-token lse [B, S] f32; backward recomputes each chunk's
    logits once and forms d_logits = (softmax - onehot) * g analytically — the
    onehot is an iota-compare XLA fuses into the elementwise graph, so neither
    pass ever materializes more than one [B, chunk, V] tile, and the max/sum
    replay the generic remat path did is gone.

    ``layout`` (``head_whole_over_batch``): how the two chunk loops hold
    the head under a mesh.  ``w`` is an invariant of a loop, and the
    partitioner gathers a sharded invariant where it is used, in the body,
    once a chunk; constrained ahead of the loop it is gathered once a pass.
    What the backward keeps is ``w`` as it came, sharded.

    x: [B, S, H] (compute dtype), w: [H, V], targets: [B, S] int. -> nll [B, S] f32.
    """
    s = x.shape[1]
    if s % chunk != 0:
        # Static shapes only — shrink to the largest divisor of s instead of
        # silently materializing the full [B,S,V] logits (the round-1 OOM).
        chunk = next((c for c in range(min(chunk, s), 0, -1) if s % c == 0), s)
    return _chunked_ce(x, w, targets, chunk, layout)


class HeadLayout(NamedTuple):
    """Where the chunked loss's two loops keep the head under a mesh whose
    batch axes split the tokens (``head_whole_over_batch``)."""
    w: Any        # sharding of the [H, V] head a loop multiplies by
    dw: Any       # sharding of [shards, H, V]: each batch shard's own sum
    shards: int   # of the chunks' gradients of the head


def head_whole_over_batch(cfg: TransformerConfig,
                          pctx: ParallelContext) -> Optional[HeadLayout]:
    """The [H, V] head as the chunked loss's loops want it under ``pctx``:
    whole over the batch axes, which split the tokens it multiplies, and
    split as ``models/sharding.py`` has it over ``tp`` (the vocabulary of a
    separate head, the hidden size of a tied one, whose [V, H] table is read
    transposed).  Its gradient is summed over the chunks where the tokens
    are, a sum a batch shard, and meets the other shards' once, after the
    loop.  None where nothing is to gather: no mesh, no batch axis above
    one, or a caller inside its own ``shard_map``."""
    mesh = pctx.mesh
    if mesh is None or pctx.manual_collectives:
        return None
    batch = tuple(a for a in pctx.batch_axes if mesh.shape.get(a, 1) > 1)
    if not batch:
        return None
    tp = "tp" if "tp" in mesh.axis_names else None
    spec = (tp, None) if cfg.tied_embeddings else (None, tp)
    P, named = jax.sharding.PartitionSpec, jax.sharding.NamedSharding
    return HeadLayout(w=named(mesh, P(*spec)), dw=named(mesh, P(batch, *spec)),
                      shards=math.prod(mesh.shape[a] for a in batch))


def _ce_chunks(x, targets, chunk):
    b, s, h = x.shape
    n = s // chunk
    xs = x.reshape(b, n, chunk, h).swapaxes(0, 1)           # [n, B, C, H]
    ts = targets.reshape(b, n, chunk).swapaxes(0, 1)        # [n, B, C]
    return xs, ts


def _loop_head(w, layout):
    return w if layout is None else jax.lax.with_sharding_constraint(
        w, layout.w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _chunked_ce(x, w, targets, chunk, layout):
    return _chunked_ce_fwd(x, w, targets, chunk, layout)[0]


def _chunked_ce_fwd(x, w, targets, chunk, layout):
    b, s, _ = x.shape
    xs, ts = _ce_chunks(x, targets, chunk)
    res = (x, w, targets)
    w = _loop_head(w, layout)

    def body(carry, xt):
        xc, tc = xt
        logits = jnp.einsum("bch,hv->bcv", xc, w,
                            preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        return carry, (lse, lse - ll)

    _, (lses, nll) = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ts))
    nll = nll.swapaxes(0, 1).reshape(b, s)
    lse = lses.swapaxes(0, 1).reshape(b, s)
    return nll, res + (lse,)


def _chunked_ce_bwd(chunk, layout, res, g):
    x, w, targets, lse = res
    w_dtype, w = w.dtype, _loop_head(w, layout)
    b, s, h = x.shape
    v = w.shape[1]
    xs, ts = _ce_chunks(x, targets, chunk)
    gs = g.reshape(b, s // chunk, chunk).swapaxes(0, 1)     # [n, B, C] f32
    ls = lse.reshape(b, s // chunk, chunk).swapaxes(0, 1)

    # Under a layout the batch is read as [shards, B / shards]: a chunk's dw
    # summed within a shard needs no other chip, so the shards' float32 sums
    # meet once, after the loop, and not once a chunk.
    split = layout is not None and b % layout.shards == 0

    def by_shard(a):
        return a.reshape(layout.shards, -1, *a.shape[1:]) if split else a

    def body(dw, xt):
        xc, tc, gc, lc = xt
        logits = jnp.einsum("bch,hv->bcv", xc, w,
                            preferred_element_type=jnp.float32)
        p = jnp.exp(logits - lc[..., None])
        onehot = (jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
                  == tc[..., None])
        dlog = ((p - onehot) * gc[..., None]).astype(x.dtype)
        dxc = jnp.einsum("bcv,hv->bch", dlog, w)
        dw_c = jnp.einsum("...bch,...bcv->...hv", by_shard(xc), by_shard(dlog),
                          preferred_element_type=jnp.float32)
        return dw + dw_c, dxc

    dw0 = jnp.zeros((h, v), jnp.float32)
    if split:
        dw0 = jax.lax.with_sharding_constraint(
            jnp.zeros((layout.shards, h, v), jnp.float32), layout.dw)
    dw, dxs = jax.lax.scan(body, dw0, (xs, ts, gs, ls))
    if split:
        dw = dw.sum(0)
    dx = dxs.swapaxes(0, 1).reshape(b, s, h)
    dt = np.zeros(targets.shape, jax.dtypes.float0)
    return dx, dw.astype(w_dtype), dt


_chunked_ce.defvjp(_chunked_ce_fwd, _chunked_ce_bwd)


def causal_lm_loss(params: Params, batch: Dict[str, jnp.ndarray],
                   cfg: TransformerConfig,
                   pctx: ParallelContext = ParallelContext(),
                   compute_dtype=jnp.bfloat16,
                   moe_aux_weight: float = 0.01,
                   remat: Union[bool, str, None] = False,
                   loss_chunk: Optional[int] = 0):
    """batch: {"tokens": [B, S+1] or "tokens"+"targets"}. Returns (loss, metrics).

    loss_chunk: sequence-chunk size for the blockwise LM head.  0 (default)
    auto-enables chunking when the full logits tensor would be large
    (S*V > 2**25 elements); None disables; an int forces that chunk size.
    """
    if "targets" in batch:
        tokens, targets = batch["tokens"], batch["targets"]
    else:
        tokens, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    s = tokens.shape[1]
    if loss_chunk == 0:
        loss_chunk = 512 if s * cfg.vocab_size > 2 ** 25 else None
    if loss_chunk and pctx.use_ring:
        # sp shards the sequence dim; a seq-chunk scan would reshard it every
        # chunk.  The sp path already keeps per-shard logits small (S/sp).
        loss_chunk = None
    x, aux = apply_trunk(params, tokens, cfg, pctx, compute_dtype, remat=remat)
    if loss_chunk:
        w = lm_head_weight(params, cfg, x.dtype)
        nll = chunked_cross_entropy(x, w, targets, min(loss_chunk, s),
                                    head_whole_over_batch(cfg, pctx))
    else:
        logits = lm_head_logits(params, x, cfg)
        with jax.named_scope("loss"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, targets[..., None],
                                       axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    if mask is None:
        loss = nll.mean()
        denom = nll.size
    else:
        loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1)
        denom = mask.sum()
    total = loss + moe_aux_weight * aux["moe_aux_loss"]
    metrics = {"loss": loss, "moe_aux_loss": aux["moe_aux_loss"],
               "tokens": denom}
    if "moe_load" in aux:
        # what a trainer logs of dropless expert layers: the assignments
        # the experts held here computed, summed over the expert layers,
        # and the most and the fewest any one of them saw in a layer; the
        # same of a holder's experts together (over ``ep``: of a chip, the
        # slowest of which sets the step's pace); the share of the sorted
        # layouts' rows that lay in tiles holding an assignment, a mean
        # over layers, holders and the ring's steps (what the row movements
        # walk of what the layout is sized for: ``ops.moe.walks``); the
        # balance term, which joins the total and not ``loss``; and what a
        # holder sends a step for the exchange
        load, chips = aux["moe_load"], aux["moe_chip_load"]
        total = total + cfg.moe_balance_weight * aux["moe_balance"]
        metrics.update(moe_assignments_held=load.sum(),
                       moe_expert_load_max=load.max(),
                       moe_expert_load_min=load.min(),
                       moe_chip_load_max=chips.max(),
                       moe_chip_load_min=chips.min(),
                       moe_rows_live_share=aux["moe_rows_live"].mean(),
                       moe_balance=aux["moe_balance"],
                       moe_exchange_bytes=_exchange_bytes(
                           cfg, pctx, tokens.shape, compute_dtype,
                           remat_policy(remat)[0]))
    return total, metrics


def _exchange_bytes(cfg: TransformerConfig, pctx: ParallelContext, shape,
                    dtype, replayed: bool) -> float:
    """Bytes one holder sends a train step for the exchange of its expert
    layers over ``ep`` (``ops.moe.exchange_bytes``): the forward's two
    walks, their transposes in the backward, and the tokens' walk again
    where the layer is replayed.  0 without an ``ep`` axis."""
    mesh = None if pctx.manual_collectives else pctx.mesh
    ep = mesh.shape.get("ep", 1) if mesh is not None else 1
    if ep == 1:
        return 0.0
    shards = math.prod(mesh.shape[a] for a in pctx.batch_axes
                       if a in mesh.axis_names)
    tokens = shape[0] * shape[1] // shards
    args = (tokens, cfg.hidden_size, cfg.experts_per_token, ep,
            jnp.dtype(dtype).itemsize)
    once = moe_ops.exchange_bytes(*args)
    again = once - (ep - 1) * tokens * cfg.hidden_size * 4 if replayed else 0
    return float(cfg.expert_layers * (2 * once + again))   # past int32
