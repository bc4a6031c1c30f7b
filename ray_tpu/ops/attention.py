"""Attention ops: causal multi-head attention with GQA, plain XLA path +
Pallas flash kernel on TPU.

TPU-first notes: the plain path is two einsums XLA maps straight onto the MXU and is
the right choice for short sequences; the Pallas flash kernel (``flash_attention.py``)
wins once S is large enough that the S×S score matrix stops fitting VMEM-friendly
tiles.  ``attend_blockwise`` exposes the online-softmax accumulator used by ring
attention (``ring_attention.py``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def score_scale(scale: Optional[float], head_dim: int) -> float:
    """What the scores ``q k^T`` are multiplied by: ``scale`` where a caller
    gives one (a configuration's ``attn_scale``), else ``head_dim ** -0.5``;
    read by every attention here and in the kernels' files."""
    return head_dim ** -0.5 if scale is None else scale


def repeat_kv(k: jnp.ndarray, num_heads: int) -> jnp.ndarray:
    """[B, S, KV, D] -> [B, S, H, D] by repeating kv heads (GQA)."""
    num_kv = k.shape[2]
    if num_kv == num_heads:
        return k
    reps = num_heads // num_kv
    return jnp.repeat(k, reps, axis=2)


def attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
           causal: bool = True,
           q_offset: int | jnp.ndarray = 0,
           kv_offset: int | jnp.ndarray = 0,
           logit_softcap: float = 0.0, window: int = 0,
           scale: Optional[float] = None) -> jnp.ndarray:
    """Plain attention. q: [B, Sq, H, D], k/v: [B, Skv, KV, D] -> [B, Sq, H, D].
    ``scale`` multiplies the scores (None: ``D ** -0.5``).

    ``q_offset``/``kv_offset`` are the global positions of the first query/key —
    used by ring attention where each device holds a sequence shard.
    ``window`` > 0 (causal): a query reads its last ``window`` positions.
    """
    num_heads = q.shape[2]
    k = repeat_kv(k, num_heads)
    v = repeat_kv(v, num_heads)
    scale = score_scale(scale, q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k.astype(q.dtype)) * scale
    if logit_softcap > 0:
        logits = jnp.tanh(logits / logit_softcap) * logit_softcap
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = kv_offset + jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]
        if window:
            mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attend_blockwise(q, k, v, m, l, o, causal, q_offset, kv_offset,
                     logit_softcap: float = 0.0):
    """One online-softmax accumulation step over a KV block.

    State: m [B,H,Sq] running max (f32), l [B,H,Sq] running denom (f32),
    o [B,Sq,H,D] running numerator (f32).  Returns updated (m, l, o).
    This is the flash-attention recurrence; ring attention calls it once per
    rotated KV shard (PAPERS.md: blockwise/ring attention).
    """
    num_heads = q.shape[2]
    k = repeat_kv(k, num_heads)
    v = repeat_kv(v, num_heads)
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k.astype(q.dtype)) * scale
    s = s.astype(jnp.float32)
    if logit_softcap > 0:
        s = jnp.tanh(s / logit_softcap) * logit_softcap
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = kv_offset + jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * alpha + p.sum(axis=-1)
    o_new = o * alpha.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v).astype(jnp.float32)
    return m_new, l_new, o_new


def finalize_blockwise(m, l, o):
    """Normalize the online-softmax accumulator into the attention output."""
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return o / denom


def mha(q, k, v, causal: bool = True, logit_softcap: float = 0.0,
        use_flash: Optional[bool] = None, mesh=None,
        batch_axes: Tuple[str, ...] = ("dp", "fsdp"), window: int = 0,
        scale: Optional[float] = None):
    """Dispatch between the Pallas flash kernel (TPU, long seq) and plain XLA.
    q, k: [B, S, heads, Dqk]; v: [B, S, KV, Dv], as wide as q or not.
    ``window`` > 0: a query reads its last ``window`` positions (the
    kernel's banded forward, or the plain path's mask).  ``scale``
    multiplies the scores (None: ``Dqk ** -0.5``).

    ``use_flash=None`` chooses from what it can observe: the backend and the
    shape.  ``mesh``/``batch_axes`` go to the kernel, which must be
    shard_mapped by hand on a multi-device mesh (see ``flash_attention``)."""
    if use_flash is None:
        from .flash_attention import flash_supported
        # The flash kernel does not implement logit softcap; its heads are
        # whole multiples of 64 lanes, the value's and the query's each.
        use_flash = (jax.default_backend() == "tpu" and q.shape[1] >= 1024
                     and all(d in (64, 128, 192, 256)
                             for d in (q.shape[-1], v.shape[-1]))
                     and logit_softcap == 0.0
                     and flash_supported(q.shape[1], k.shape[1], q.shape[2],
                                         k.shape[2]) is None)
    if use_flash:
        if logit_softcap > 0.0:
            raise ValueError("flash_attention does not implement logit_softcap;"
                             " use use_flash=False (or leave it None to"
                             " let the dispatcher choose)")
        from .flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal, mesh=mesh,
                               batch_axes=batch_axes, window=window,
                               scale=scale)
    return attend(q, k, v, causal=causal, logit_softcap=logit_softcap,
                  window=window, scale=scale)
