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


def batch_spec() -> P:
    """tokens [B, S]: batch over dp+fsdp, sequence over sp."""
    return P(BATCH_AXES, "sp")


def refuse_mesh(cfg: TransformerConfig, mesh, what: str):
    """``models/latent.py``'s tree (latent attention, dropless experts, a
    dense prefix) lives on one device: none of its kernels is shard_mapped,
    no rule here splits its leaves, and nothing exchanges tokens over
    ``ep``: a holder of a share of the experts (``cfg.experts_held``)
    computes its part of a layer and no more.  As ``LLMEngine`` refuses
    ``tp > 1`` for the same fields."""
    if cfg.latent_tree and mesh.size > 1:
        raise NotImplementedError(
            f"{what}: {', '.join(cfg.latent_tree)} on a mesh of "
            f"{dict(mesh.shape)}: these parameters have no sharding rule, "
            "the grouped-matmul and flash kernels under them are not "
            "shard_mapped, and no exchange of tokens over ep exists; one "
            "device holds the tree (a share of the experts: experts_held)")


def logical_param_specs(cfg: TransformerConfig) -> Dict[str, Any]:
    """PartitionSpec tree matching init_params' structure."""
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
