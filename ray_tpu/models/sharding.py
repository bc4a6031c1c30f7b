"""Sharding rules: PartitionSpec trees for transformer params and batches.

This is the heart of the TPU-native parallelism design (SURVEY §2.3): instead of the
reference's NCCL process groups (DDP/FSDP wrappers), parallelism is expressed as specs
over a named mesh and XLA inserts the collectives:

* ``dp``   — pure data parallel (batch axis)
* ``fsdp`` — ZeRO-style sharded data parallel: params/optimizer sharded, batch also
             split here (paper 2004.13336 in PAPERS.md)
* ``tp``   — tensor parallel: attention heads / MLP width
* ``sp``   — sequence/context parallel (ring attention)
* ``ep``   — expert parallel (MoE expert dim)
* ``pp``   — pipeline stages (see parallel/pipeline.py)
"""

from __future__ import annotations

from typing import Any, Dict

import jax
from jax.sharding import PartitionSpec as P

from .config import TransformerConfig

BATCH_AXES = ("dp", "fsdp")
#: what the dense leaves of a tree whose experts lie over ``ep`` are split
#: over, ZeRO-style: the holders of a layer's experts are data-parallel
#: ranks too, each with sequences of its own
FSDP_EP = ("fsdp", "ep")


def expert_parallel(cfg: TransformerConfig) -> bool:
    """Whether the configuration's experts are the dropless layer's, which
    on a mesh with ``ep`` > 1 lie over that axis with an explicit exchange
    (``ops.moe.moe_dropless_ep``), every one of them held between the
    holders.  Such a mesh's ``ep`` axis splits the batch too (a mesh of
    ``ep`` alone would else hand every chip the same sequences) and the
    dense leaves as ``fsdp`` does.  The capacity layer's ``ep`` einsum
    (``ops.moe.moe_mlp``) keeps the batch off the axis.  (Which trees have
    sharding rules at all is ``refuse_mesh``'s to say, not this one's.)"""
    return bool(cfg.moe_dropless)


def batch_axes(cfg: TransformerConfig):
    """The mesh axes the batch is split over under ``cfg``."""
    return BATCH_AXES + ("ep",) if expert_parallel(cfg) else BATCH_AXES


def batch_spec(cfg: TransformerConfig = None) -> P:
    """tokens [B, S]: batch over dp+fsdp (and ``ep`` where the experts are
    exchanged over it: ``batch_axes``), sequence over sp."""
    return P(batch_axes(cfg) if cfg is not None else BATCH_AXES, "sp")


def refuse_mesh(cfg: TransformerConfig, mesh, what: str):
    """``models/latent.py``'s tree (latent attention, a dense prefix, its
    dropless experts a holder's share) lives on one device: none of its
    leaves has a sharding rule, its latent flash call is not shard_mapped,
    and a holder of a share of the experts (``cfg.experts_held``) computes
    its part of a layer and no more.  As ``LLMEngine`` refuses ``tp > 1``
    for the same fields.  The dropless experts of a tree that has rules (a
    pattern's, ``expert_parallel``) do train on a mesh: over ``ep`` with
    their exchange, the batch over the batch axes and ``ep``; ``tp``, ``sp``
    and ``pp`` have no rule for them."""
    if cfg.latent_tree and mesh.size > 1:
        raise NotImplementedError(
            f"{what}: {', '.join(cfg.latent_tree)} on a mesh of "
            f"{dict(mesh.shape)}: models/latent.py's parameters have no "
            "sharding rule and its attention is not shard_mapped; one "
            "device holds the tree (a share of the experts: experts_held). "
            "Experts exchanged over ep are a layer_pattern's "
            "(ops/moe.py moe_dropless_ep)")
    if expert_parallel(cfg):
        over = {a: n for a, n in mesh.shape.items()
                if n > 1 and a in ("tp", "sp", "pp")}
        ep = mesh.shape.get("ep", 1)
        if over or cfg.num_experts % ep:
            raise NotImplementedError(
                f"{what}: dropless experts on a mesh "
                f"of {dict(mesh.shape)}: they are split over ep (a whole "
                f"number of the {cfg.num_experts} a holder) and the batch "
                "over dp, fsdp and ep; the exchange and the band's kernels "
                "have no tp, sp or pp form")


def _pattern_param_specs(cfg: TransformerConfig) -> Dict[str, Any]:
    """Specs of ``models/hybrid.py``'s tree for a pattern that trains:
    leaves [periods, layers of the kind a period, ...].  Matrices split on
    their hidden dimension over ``FSDP_EP``, as the dense block's over
    ``fsdp``; the routed experts [layers, experts, ...] over ``ep`` on their
    expert dimension and whole otherwise (the kernel reads an expert's
    matrix where it lies)."""
    from . import transformer
    shapes = jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), cfg))
    rows = {"wq": 2, "wk": 2, "wv": 2, "wo": 3, "w_gate": 2, "w_in": 2,
            "w_out": 3, "router": 2}

    def spec(path, leaf):
        names = [getattr(k, "key", None) for k in path]
        if names[0] in ("embed", "lm_head"):    # rows of [V, H], of [H, V]
            return P(FSDP_EP, None)
        if names[0] == "blocks" and names[1] == "experts":
            return P(None, "ep", *([None] * (leaf.ndim - 2)))
        at = rows.get(names[-1])
        if names[0] == "blocks" and at is not None and at < leaf.ndim:
            return P(*[FSDP_EP if i == at else None
                       for i in range(leaf.ndim)])
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(spec, shapes)


def logical_param_specs(cfg: TransformerConfig) -> Dict[str, Any]:
    """PartitionSpec tree matching init_params' structure."""
    if cfg.layer_pattern:
        return _pattern_param_specs(cfg)
    if cfg.latent_tree:
        # whole on its one device (``refuse_mesh``): the tree's own
        # structure, every leaf unsplit
        from . import transformer
        return jax.tree.map(
            lambda _: P(), jax.eval_shape(
                lambda: transformer.init_params(jax.random.PRNGKey(0), cfg)))
    def norm_spec(stacked: bool):
        p = {"scale": P(None, None) if stacked else P(None)}
        if not cfg.use_rmsnorm:
            p["bias"] = P(None, None) if stacked else P(None)
        return p

    attn = {
        "wq": P(None, "fsdp", "tp"),
        "wk": P(None, "fsdp", "tp"),
        "wv": P(None, "fsdp", "tp"),
        "wo": P(None, "tp", "fsdp"),
    }
    if not cfg.use_rmsnorm:
        attn.update({"bq": P(None, "tp"), "bk": P(None, "tp"),
                     "bv": P(None, "tp"), "bo": P(None, "fsdp")})

    blocks: Dict[str, Any] = {
        "attn_norm": norm_spec(True),
        "attn": attn,
        "mlp_norm": norm_spec(True),
    }
    if cfg.num_experts > 1:
        blocks["moe"] = {
            "router": P(None, "fsdp", None),
            "w_gate": P(None, "ep", "fsdp", "tp"),
            "w_in": P(None, "ep", "fsdp", "tp"),
            "w_out": P(None, "ep", "tp", "fsdp"),
        }
    else:
        mlp = {"w_in": P(None, "fsdp", "tp"), "w_out": P(None, "tp", "fsdp")}
        if cfg.use_swiglu:
            mlp["w_gate"] = P(None, "fsdp", "tp")
        else:
            mlp["b_in"] = P(None, "tp")
            mlp["b_out"] = P(None, "fsdp")
        blocks["mlp"] = mlp

    specs: Dict[str, Any] = {
        "embed": {"tokens": P("fsdp", "tp")},
        "blocks": blocks,
        "final_norm": norm_spec(False),
    }
    if cfg.learned_positions:
        specs["embed"]["pos"] = P(None, "fsdp")
    if not cfg.tied_embeddings:
        specs["lm_head"] = P("fsdp", "tp")
    return specs
