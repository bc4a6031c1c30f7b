"""Ring attention: exact causal attention over a sequence-sharded mesh axis.

SURVEY §5.7: the reference has **no** sequence/context parallelism (verified negative)
— this is greenfield, first-class here.  Design: each device on the ``sp`` mesh axis
holds a contiguous sequence shard of Q/K/V; K/V shards rotate around the ICI ring with
``jax.lax.ppermute`` while each hop folds one KV block into the flash-attention
online-softmax accumulator (``ops.attention.attend_blockwise``).  Communication
overlaps compute hop-by-hop, HBM never materializes the S×S score matrix, and the
collective rides ICI neighbor links (the ppermute pattern XLA maps to an ICI ring).

Papers: Ring Attention (blockwise transformers), Ulysses all-to-all alternative
(``ulysses_attention`` below).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


from .attention import attend_blockwise, finalize_blockwise


def _ring_attn_shard(q, k, v, axis_name: str, causal: bool = True,
                     logit_softcap: float = 0.0):
    """Per-shard body (runs under shard_map): q/k/v [B, S_local, H|KV, D]."""
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    s_local = q.shape[1]
    b, _, h, d = q.shape

    m = jnp.full((b, h, s_local), -jnp.inf, dtype=jnp.float32)
    l = jnp.zeros((b, h, s_local), dtype=jnp.float32)
    o = jnp.zeros((b, s_local, h, d), dtype=jnp.float32)

    q_offset = my_idx * s_local

    def hop(carry, i):
        m, l, o, k_cur, v_cur = carry
        # The KV block currently held came from shard (my_idx - i) mod n.
        src = (my_idx - i) % axis_size
        kv_offset = src * s_local
        m, l, o = attend_blockwise(q, k_cur, v_cur, m, l, o,
                                   causal=causal, q_offset=q_offset,
                                   kv_offset=kv_offset,
                                   logit_softcap=logit_softcap)
        # Rotate KV to the next device (ring: i -> i+1).
        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (m, l, o, k_nxt, v_nxt), None

    (m, l, o, _, _), _ = jax.lax.scan(hop, (m, l, o, k, v),
                                      jnp.arange(axis_size))
    return finalize_blockwise(m, l, o).astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, axis_name: str = "sp",
                   causal: bool = True, batch_axes: tuple = ("dp",),
                   logit_softcap: float = 0.0):
    """Ring attention over `axis_name` of `mesh`.

    q: [B, S, H, D], k/v: [B, S, KV, D] with S sharded over `axis_name` and B
    over `batch_axes`. Returns [B, S, H, D] with the same sharding.
    """
    batch_spec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0],
                   axis_name, None, None)
    fn = jax.shard_map(
        functools.partial(_ring_attn_shard, axis_name=axis_name, causal=causal,
                          logit_softcap=logit_softcap),
        mesh=mesh,
        in_specs=(batch_spec, batch_spec, batch_spec),
        out_specs=batch_spec,
        check_vma=False)
    return fn(q, k, v)


def ulysses_attention(q, k, v, mesh: Mesh, axis_name: str = "sp",
                      causal: bool = True, batch_axes: tuple = ("dp",)):
    """DeepSpeed-Ulysses-style sequence parallelism: all-to-all so each device
    gets the full sequence for H/n heads, attends locally, all-to-all back.

    Cheaper than ring for moderate S (two all-to-alls vs n-1 ppermutes) but
    caps the sp degree at num_heads; ring has no such cap (SURVEY §2.3 SP row).
    """
    bspec = batch_axes if len(batch_axes) > 1 else batch_axes[0]

    def body(q, k, v):
        n = jax.lax.psum(1, axis_name)
        # [B, S/n, H, D] -> all-to-all -> [B, S, H/n, D]
        def a2a_fwd(x):
            return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                      tiled=True)

        def a2a_bwd(x):
            return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                      tiled=True)

        qf, kf, vf = a2a_fwd(q), a2a_fwd(k), a2a_fwd(v)
        from .attention import attend
        out = attend(qf, kf, vf, causal=causal)
        return a2a_bwd(out)

    spec = P(bspec, axis_name, None, None)
    kv_heads = k.shape[2]
    sp = mesh.shape[axis_name]
    if kv_heads % sp != 0:
        # GQA with fewer KV heads than the sp degree: fall back to ring.
        return ring_attention(q, k, v, mesh, axis_name, causal, batch_axes)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
