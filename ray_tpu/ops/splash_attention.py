"""Splash attention: the pallas TPU kernel with explicit backward blocks.

Third attention impl beside ``ops/attention.mha`` (plain) and
``ops/flash_attention`` (in-repo pallas flash).  What splash adds over the
in-repo flash kernel:

* **native GQA** — k/v stay at ``num_kv_heads``; no ``repeat`` materialising
  the full head count into HBM before the kernel,
* **separate backward block sizes** — ``block_q_dkv``/``block_kv_dkv`` and
  ``block_q_dq``/``block_kv_dq`` tune the dkv and dq backward passes
  independently of the forward (the forward-optimal tile is usually wrong
  for the backward at long sequence),
* **sparse mask skipping** — fully-masked causal tiles are never launched.

Layout matches the rest of ``ops/``: q ``[B, S, H, D]``, k/v
``[B, S, KV, D]``, output ``[B, S, H, D]``.  The kernel itself wants
per-batch ``[H, S, D]`` with a pre-scaled q, so the wrapper transposes and
vmaps over batch.

Dispatch contract (`splash_mha`): returns the attention output, or raises
``ValueError`` with the reason when the shape does not tile
(``splash_supported``).  It never degrades to another implementation: a
caller that asked for splash gets splash or an error.

On the CPU backend the kernel runs in pallas interpret mode (counted in
``flash_attention.INTERPRET_TRACES``), which is numerically faithful (tier-1
pins parity against ``ops/flash_attention`` on GQA+causal shapes) but slow —
interpret mode is for correctness gates, not benchmarks.  On a TPU it never
runs interpreted.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .flash_attention import kernel_batch_spec, resolve_interpret

__all__ = ["splash_mha", "splash_supported", "DEFAULT_BLOCK"]

#: Forward/backward tile edge used when the sequence allows it.  512 is the
#: sweet spot measured for the in-repo flash kernel on v5e (PROFILE_CORE.md);
#: splash shrinks it to the largest 128-multiple that divides the sequence.
DEFAULT_BLOCK = 512

def _pick_block(seq: int, cap: int) -> int:
    """Largest multiple of 128 that is <= cap and divides seq."""
    best = 128
    b = 128
    while b <= min(cap, seq):
        if seq % b == 0:
            best = b
        b += 128
    return best


def splash_supported(seq_q: int, seq_kv: int, num_heads: int,
                     num_kv_heads: int, head_dim: int) -> Optional[str]:
    """None when the shape tiles for the splash kernel, else the reason."""
    if head_dim % 128 != 0:
        return f"head_dim={head_dim} not a multiple of 128"
    if seq_q % 128 != 0 or seq_kv % 128 != 0:
        return f"seq ({seq_q}, {seq_kv}) not a multiple of 128"
    if num_kv_heads < 1 or num_heads % num_kv_heads != 0:
        return f"heads {num_heads} not a multiple of kv heads {num_kv_heads}"
    return None


@functools.lru_cache(maxsize=32)
def _get_kernel(num_q_heads: int, seq_q: int, seq_kv: int, causal: bool,
                softcap: float, block_q: int, block_kv: int,
                block_q_bwd: int, block_kv_bwd: int, interpret: bool):
    """Build (and cache) a SplashAttentionKernel for one static shape.

    The mask-info preprocessing inside make_splash_mha is numpy work
    proportional to (seq/block)^2 per head — caching keys on everything
    that changes the compiled kernel.
    """
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sak, splash_attention_mask as sam)
    shape = (seq_q, seq_kv)
    if causal:
        heads = [sam.CausalMask(shape=shape) for _ in range(num_q_heads)]
    else:
        heads = [sam.FullMask(shape) for _ in range(num_q_heads)]
    mask = sam.MultiHeadMask(heads)
    block_sizes = sak.BlockSizes(
        block_q=block_q, block_kv=block_kv, block_kv_compute=block_kv,
        block_q_dkv=block_q_bwd, block_kv_dkv=block_kv_bwd,
        block_kv_dkv_compute=block_kv_bwd,
        block_q_dq=block_q_bwd, block_kv_dq=block_kv_bwd)
    # The kernel object outlives the trace that first asks for it (the
    # cache above), so its mask-info arrays must be concrete: built inside a
    # shard_map or remat trace they would be tracers of that trace.
    with jax.ensure_compile_time_eval():
        return sak.make_splash_mha(
            mask, block_sizes=block_sizes, head_shards=1, q_seq_shards=1,
            attn_logits_soft_cap=(float(softcap) if softcap else None),
            interpret=interpret)


def _shard_map_call(kernel, qs, ks, vs, mesh, spec):
    """Multi-device path: batch-shard the kernel call via shard_map.

    Under plain jit the compiler refuses to partition the Mosaic call;
    shard_map keeps each device on its local batch shard (the SNIPPETS.md
    maxtext recipe).  head_shards and q_seq_shards stay 1 — batch is the
    only sharded dim here, so the kernel's manual_sharding_spec is the
    replicated spec.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    kernel_spec = kernel.manual_sharding_spec(
        NamedSharding(mesh, P(None, None)))
    fn = jax.shard_map(
        lambda kern, q, k, v: jax.vmap(kern)(q, k, v),
        mesh=mesh, in_specs=(kernel_spec, spec, spec, spec),
        out_specs=spec, check_vma=False)
    return fn(kernel, qs, ks, vs)


def splash_mha(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
               causal: bool = True, logit_softcap: float = 0.0,
               mesh=None, batch_axes: Tuple[str, ...] = ("dp", "fsdp"),
               interpret: Optional[bool] = None,
               block_q: int = DEFAULT_BLOCK, block_kv: int = DEFAULT_BLOCK,
               block_q_bwd: Optional[int] = None,
               block_kv_bwd: Optional[int] = None) -> jnp.ndarray:
    """Splash attention over [B, S, H, D] q and [B, S, KV, D] k/v.

    Raises ``ValueError`` when the shape does not tile for the kernel.

    With a ``mesh`` of more than one device the call is batch-sharded via
    shard_map (same rule as ``flash_attention``).  Pass no mesh from inside
    a manually-partitioned region (a shard_map body), where operands are
    per-device local already.
    """
    b, seq_q, num_heads, head_dim = q.shape
    seq_kv, num_kv = k.shape[1], k.shape[2]
    reason = splash_supported(seq_q, seq_kv, num_heads, num_kv, head_dim)
    if reason is not None:
        raise ValueError(f"splash attention cannot run this shape: {reason}")
    interpret = resolve_interpret(interpret, "splash")
    bq = _pick_block(seq_q, block_q)
    bkv = _pick_block(seq_kv, block_kv)
    bq_bwd = _pick_block(seq_q, block_q_bwd or block_q)
    bkv_bwd = _pick_block(seq_kv, block_kv_bwd or block_kv)
    kernel = _get_kernel(num_heads, seq_q, seq_kv, bool(causal),
                         float(logit_softcap), bq, bkv, bq_bwd, bkv_bwd,
                         interpret)
    # kernel applies no softmax scale itself; fold 1/sqrt(D) into q
    qs = (q * (head_dim ** -0.5)).swapaxes(1, 2)   # [B, H, Sq, D]
    ks = k.swapaxes(1, 2)                          # [B, KV, Skv, D]
    vs = v.swapaxes(1, 2)
    spec = kernel_batch_spec(mesh, batch_axes)
    if spec is not None:
        out = _shard_map_call(kernel, qs, ks, vs, mesh, spec)
    else:
        out = jax.vmap(kernel)(qs, ks, vs)
    return out.swapaxes(1, 2).astype(q.dtype)
