"""Pallas TPU flash attention: a forward kernel and one backward kernel, each
with a twin for a band (a sliding window).

The reference has no TPU kernels at all (its attention lives in external torch
models); this is greenfield TPU-first code (SURVEY §5.7, §7 stance).

Design:
* **Forward** is a Pallas kernel. Grid = (batch, q_heads, S/block_q); each
  program streams K/V blocks for its (batch, kv_head) out of VMEM with a
  `fori_loop`, folding them into the flash online-softmax accumulator
  (running max `m`, denominator `l`, numerator `acc`) so the S×S score matrix
  never exists — only a [block_q, block_kv] tile lives at a time.  Causal
  programs stop the loop at their diagonal block: the lower-triangle work that
  plain attention burns on masked logits is never issued to the MXU.
* **GQA without materialization**: the kv-head index map is
  ``h // (num_q_heads / num_kv_heads)`` so grouped-query K/V blocks are read
  in place; the `repeat_kv` copy the plain path makes is skipped.
* **Backward** recomputes attention blockwise from the saved (out, lse)
  residuals — standard flash-attention recurrence — in ONE Pallas kernel,
  `flash_dkv`: a live (q block, kv block) pair's score tile is computed once
  and dq, dk and dv are all taken from it, five products a tile.  dk / dv of
  a KV block are summed over its q blocks and the query heads that share it
  in O(block) VMEM; dq, which a q block gathers from every KV block, stays
  resident in f32 for the whole sequence of the query heads under a kv head
  and is written once.  That is the kernel's reach, a rule of the shapes
  (`flash_bwd_supported`): past it, and where blocks do not tile for the
  kernel (under 128 positions: the CPU tests' small shapes), the backward
  is `_bwd_blockwise`, the same recurrence as a `lax.scan` over KV blocks in
  plain JAX: correct and slow.
* **Two widths**: queries and keys are `d_qk` lanes wide, values, the output
  and its gradient `d_v`; each kernel reads both off its operands.  A latent
  head's 192 / 128 runs as it is; where the two are equal (every other
  caller) the kernels lower to what one width gave.
* **A query offset that is data** (`flash_attention_rows`): the forward
  kernel for a chunk of a row that continues what a slot holds, over rows
  where they lie: the stacked K/V cache with a position's heads side by side,
  or a latent head's rebuilt keys and values, a head's rows apart.

Numerics: logits and softmax statistics in f32 (MXU accumulates f32 via
``preferred_element_type``); probabilities cast back to the input dtype for
the PV matmul, matching ``attention.attend``.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from .attention import score_scale

NEG_INF = -1e30
#: resident K and V bytes up to which the forward kernel fits the compiler's
#: default scoped VMEM (16 MiB) with its blocks and products
FWD_VMEM_DEFAULT = 12 << 20
#: resident dq bytes (``_bwd_resident``) up to which the backward kernel fits
#: the compiler's default scoped VMEM with its blocks and products
BWD_VMEM_DEFAULT = 8 << 20
#: what the backward kernel's blocks and products take beside its resident dq
BWD_VMEM_BLOCKS = 12 << 20
#: resident dq bytes past which the backward is not the kernel's: with its
#: blocks and products, what a call may ask of a v5e's 128 MiB of VMEM
BWD_VMEM_REACH = 64 << 20
#: the forward kernel where its queries start after what a slot of the
#: stacked cache already holds (``flash_attention_rows``), as a trace shows it
KERNEL_FLASH_FWD = "flash_fwd"
KERNEL_FLASH_ROWS = "flash_fwd_rows"
#: the forward kernel with a band (``flash_attention(window=...)``): a
#: query reads its last ``window`` positions and a query block the KV
#: blocks its band touches
KERNEL_FLASH_WINDOW = "flash_window_prefill"
#: KV positions a step of the banded kernel takes: a band of 128 under a
#: query block of 512 touches 5 such blocks (640 positions), 2 of 512 (1,024)
WINDOW_BLOCK_KV = 128
#: the backward of the band (``_bwd_window_kernel``), a kernel of its own
KERNEL_FLASH_WINDOW_BWD = "flash_window_bwd"


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_kv: int,
                seq_kv: int, causal: bool, scale: float, q_start=None,
                window: int = 0):
    """One (batch, head, q-block) program: stream KV blocks, online softmax.
    ``q_start``: the position of the first query among the keys, a scalar
    that is data (``_fwd_rows_kernel``); None where query i sits at key i.
    ``window`` > 0 (causal): a query reads its last ``window`` positions,
    and the loop starts at the first KV block the q-block's band touches."""
    qi = pl.program_id(2)
    block_q = q_ref.shape[2]
    q = q_ref[0, 0]                                   # [block_q, d_qk]

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, v_ref.shape[3]), jnp.float32)

    def first():      # this q-block's first position among the keys
        return qi * block_q if q_start is None else q_start + qi * block_q

    if causal:
        # KV blocks strictly after this q-block's diagonal are fully masked:
        # don't even loop over them.
        num_kv = (first() + block_q + block_kv - 1) // block_kv
        if q_start is not None:     # a start that is data may say anything
            num_kv = jnp.minimum(num_kv, seq_kv // block_kv)
    else:
        num_kv = seq_kv // block_kv

    q_pos = first() + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(j * block_kv, block_kv), :]     # [bkv, d_qk]
        v = v_ref[0, 0, pl.ds(j * block_kv, block_kv), :]     # [bkv, d_v]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [bq, bkv]
        if causal:
            k_pos = j * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            seen = q_pos >= k_pos
            if window:
                seen = seen & (q_pos - k_pos < window)
            s = jnp.where(seen, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    # KV blocks wholly before the band of this q-block's first query
    lo = jnp.maximum(first() - (window - 1), 0) // block_kv if window else 0
    m, l, acc = jax.lax.fori_loop(lo, num_kv, body, (m0, l0, acc0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
    # TPU tiling wants the last two block dims (8, 128)-aligned; a [block_q]
    # row vector is not.  Replicate the row stats across 8 sublanes and let
    # the caller read lane 0.
    lse_ref[0, 0] = jnp.broadcast_to((m + jnp.log(l))[:, 0][None, :],
                                     (8, block_q))


def _fwd_vmem(kv_len: int, d_qk: int, d_v: int, dtype) -> dict:
    """What a forward call passes the compiler for a head's whole K and V,
    ``kv_len`` positions each at its own width, in VMEM double-buffered: past
    the compiler's default of 16 MiB (8,192 positions of 256 lanes are 4 MiB,
    of 128 lanes 2 MiB) the kernel asks for what it needs; below, nothing is
    passed and the call compiles as it always did."""
    lanes = sum(-(-d // 128) * 128 for d in (d_qk, d_v))
    resident = 2 * kv_len * lanes * jnp.dtype(dtype).itemsize
    return ({} if resident <= FWD_VMEM_DEFAULT else {
        "compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=resident + FWD_VMEM_DEFAULT)})


def _flash_fwd(q, k, v, causal: bool, block_q: int, block_kv: int,
               interpret: bool, window: int = 0,
               scale: Optional[float] = None):
    """q: [B, H, S, Dqk], k: [B, KV, S, Dqk], v: [B, KV, S, Dv] -> (out [B,
    H, S, Dv], lse [B, H, S]).  ``window``: the band (``_fwd_kernel``), a
    kernel of its own name; ``scale``: what multiplies the scores (None:
    ``Dqk ** -0.5``).  v may have fewer heads than k, each as wide as
    several of k's side by side ([B, KV / w, S, w Dqk]: differential
    attention's pairs): head ``h`` then reads value head ``h // (H / (KV /
    w))``, a block fetched once for the query heads under it."""
    b, h, s, d = q.shape
    d_v = v.shape[3]
    kv_heads = k.shape[1]
    reps, v_reps = h // kv_heads, h // v.shape[1]
    scale = score_scale(scale, d)
    block_q = min(block_q, s)
    block_kv = min(block_kv, s)

    grid = (b, h, s // block_q)
    kernel = functools.partial(_fwd_kernel, block_kv=block_kv, seq_kv=s,
                               causal=causal, scale=scale, window=window)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        **_fwd_vmem(s, d, d_v, k.dtype),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, s, d), lambda bi, hi, qi: (bi, hi // reps, 0, 0)),
            pl.BlockSpec((1, 1, s, d_v),
                         lambda bi, hi, qi: (bi, hi // v_reps, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d_v),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, 8, block_q), lambda bi, hi, qi: (bi, hi, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d_v), q.dtype),
            jax.ShapeDtypeStruct((b, h, 8, s), jnp.float32),
        ],
        interpret=interpret,
        name=KERNEL_FLASH_WINDOW if window else KERNEL_FLASH_FWD,
    )(q, k, v)
    return out, lse[:, :, 0, :]


def _fwd_rows_kernel(at_ref, *refs, **static):
    """``_fwd_kernel`` with its first query at position ``at_ref[2]`` of the
    keys (``at_ref``: layer, slot, start; the first two are the index maps')."""
    _fwd_kernel(*refs, q_start=at_ref[2], **static)


def _flash_fwd_rows(q, k_all, v_all, at, num_heads: int, kv_len: int,
                    block_q: int, block_kv: int, interpret: bool,
                    scale: Optional[float] = None):
    """The forward kernel on rows where they lie; at: int32 [3], (layer,
    slot, the first query's position).  Keys and queries are as wide as
    each other, values and the output as each other; both widths are read
    off the operands.  Two layouts, one kernel:

    * heads side by side: q [W, NH * Dqk], a position's heads in one row;
      k_all [layers, slots, max_len, NKV * Dqk], v_all [.., NKV * Dv].  A
      head is a block of lanes of a row (whole 128s), so nothing is sliced
      out of the stack and nothing is transposed on the way in or out.
      Returns [W, NH * Dv].
    * a head's rows apart: q [NH, W, Dqk]; k_all [layers, slots, NKV,
      max_len, Dqk], v_all [.., Dv], a block a head's whole width whatever it
      is (a latent head's 192 is no whole block of a row).  Returns [NH, W,
      Dv]."""
    apart = k_all.ndim == 5
    if apart:
        w, d, d_v = q.shape[1], q.shape[2], v_all.shape[4]
        reps, slots = num_heads // k_all.shape[2], k_all.shape[1]
        k_all, v_all = (a.reshape((-1,) + a.shape[2:]) for a in (k_all, v_all))
        q, shape = q[None], (1, num_heads, w, d_v)
        row_at = lambda at, g: (at[0] * slots + at[1], g, 0, 0)  # noqa: E731
        head_at = lambda hi, qi: (0, hi, qi, 0)                  # noqa: E731
    else:
        w, d = q.shape[0], q.shape[1] // num_heads
        reps = num_heads * d // k_all.shape[-1]
        d_v = v_all.shape[-1] * reps // num_heads
        q, shape = q[None, None], (1, 1, w, num_heads * d_v)
        row_at = lambda at, g: (at[0], at[1], 0, g)              # noqa: E731
        head_at = lambda hi, qi: (0, 0, qi, hi)                  # noqa: E731

    def rows(width):        # a KV head's rows of the slot, all kv_len
        return pl.BlockSpec((1, 1, kv_len, width),
                            lambda bi, hi, qi, at: row_at(at, hi // reps))

    def heads(width):       # a block of a head's queries, or of its output
        return pl.BlockSpec((1, 1, block_q, width),
                            lambda bi, hi, qi, at: head_at(hi, qi))

    out, _ = pl.pallas_call(
        functools.partial(_fwd_rows_kernel, block_kv=block_kv, seq_kv=kv_len,
                          causal=True, scale=score_scale(scale, d)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1, num_heads, w // block_q),
            in_specs=[heads(d), rows(d), rows(d_v)],
            out_specs=[heads(d_v),
                       pl.BlockSpec((1, 1, 8, block_q),
                                    lambda bi, hi, qi, at: (0, hi, 0, qi))],
        ),
        out_shape=[jax.ShapeDtypeStruct(shape, q.dtype),
                   jax.ShapeDtypeStruct((1, num_heads, 8, w), jnp.float32)],
        **_fwd_vmem(kv_len, d, d_v, k_all.dtype),
        interpret=interpret,
        name=KERNEL_FLASH_ROWS,
    )(at, q, k_all, v_all)
    return out[0] if apart else out[0, 0]


def _bwd_blockwise(q, k, v, out, lse, g, causal: bool, block_kv: int,
                   scale: Optional[float] = None, window: int = 0):
    """Flash backward, recompute-based, as a scan over KV blocks.

    q: [B, H, S, Dqk]; out/g: [B, H, S, Dv]; k: [B, KV, S, Dqk]; v: [B, KV,
    S, Dv]; lse: [B, H, S].  Returns (dq, dk, dv) with dk/dv in kv-head
    layout.
    """
    b, h, s, d = q.shape
    d_v = v.shape[3]
    kv_heads = k.shape[1]
    reps = h // kv_heads
    scale = score_scale(scale, d)
    block_kv = min(block_kv, s)
    n_blocks = s // block_kv

    qf = q.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    # D_i = rowsum(dO * O): the softmax-jacobian diagonal term.
    delta = (gf * out.astype(jnp.float32)).sum(-1)              # [B, H, S]
    q_pos = jnp.arange(s)

    kb = jnp.moveaxis(k.reshape(b, kv_heads, n_blocks, block_kv, d), 2, 0)
    vb = jnp.moveaxis(v.reshape(b, kv_heads, n_blocks, block_kv, d_v), 2, 0)

    def per_block(j, kj, vj):
        # kj: [B, KV, block_kv, Dqk], vj: [.., Dv] -> repeat to q heads.
        kjh = jnp.repeat(kj, reps, axis=1) if reps > 1 else kj
        vjh = jnp.repeat(vj, reps, axis=1) if reps > 1 else vj
        sj = jnp.einsum("bhqd,bhkd->bhqk", qf, kjh.astype(jnp.float32)) * scale
        if causal:
            k_pos = j * block_kv + jnp.arange(block_kv)
            seen = q_pos[:, None] >= k_pos[None, :]
            if window:
                seen = seen & (q_pos[:, None] - k_pos[None, :] < window)
            sj = jnp.where(seen[None, None], sj, NEG_INF)
        p = jnp.exp(sj - lse[..., None])                        # [B,H,S,bkv]
        dv_h = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vjh.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * scale
        dq_j = jnp.einsum("bhqk,bhkd->bhqd", ds, kjh.astype(jnp.float32))
        # fold q-head grads back to kv heads (GQA)
        dk_h = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        if reps > 1:
            dv_h = dv_h.reshape(b, kv_heads, reps, block_kv, d_v).sum(2)
            dk_h = dk_h.reshape(b, kv_heads, reps, block_kv, d).sum(2)
        return dq_j, dk_h, dv_h

    def scan_body(dq, xs):
        j, kj, vj = xs
        dq_j, dk_j, dv_j = per_block(j, kj, vj)
        return dq + dq_j, (dk_j, dv_j)

    scan_fn = jax.checkpoint(scan_body,
                             policy=jax.checkpoint_policies.nothing_saveable)
    dq, (dkb, dvb) = jax.lax.scan(
        scan_fn, jnp.zeros_like(qf), (jnp.arange(n_blocks), kb, vb))
    dk = jnp.moveaxis(dkb, 0, 2).reshape(b, kv_heads, s, d)
    dv = jnp.moveaxis(dvb, 0, 2).reshape(b, kv_heads, s, d_v)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# Pallas backward kernel
# ---------------------------------------------------------------------------
#
# The kernel computes the score tile TRANSPOSED — s_t = [block_kv(sublanes),
# block_q(lanes)] — so the per-q-row statistics (lse, delta) enter as natural
# [1, block_q] rows and broadcast over sublanes, which Mosaic supports
# directly; no lane-replicated stat arrays and no [1,N]->[N,1] relayout.
# Every matmul contracts either a head width (d_qk for the scores and dk,
# d_v for dp and dv) or a block dim (dq), all MXU-shaped.
#
# The grid iterates over BOTH block axes (q and kv), kv-major: an f32 VMEM
# scratch accumulator is initialised on the first visit of an output tile
# and flushed on the last.  dk / dv are revisited consecutively, O(block) of
# VMEM; dq is revisited once a kv block, so it stays resident for the whole
# sequence (``_bwd_resident``), which bounds the kernel's reach.


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, dlt_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                causal: bool, scale: float):
    """Grid (b, kv_heads, n_kv, reps, n_q): one (kv block, query head, q
    block) pair a step.  dk / dv of the kv block accumulate over the two
    innermost dims (GQA fold-back), which revisit their output tile
    consecutively; dq of the `reps` query heads sharing the kv head
    accumulates in ``dq_acc`` [reps, S, d_qk] over kv blocks ascending and
    leaves through ``dq_ref``, a block of the (batch, kv head)'s whole rows
    that the three inner dims revisit consecutively."""
    ki, r, qj = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    n_kv, n_rep, n_q = (pl.num_programs(i) for i in (2, 3, 4))
    block_kv, block_q = k_ref.shape[2], q_ref.shape[2]
    rows = pl.ds(pl.multiple_of(qj * block_q, block_q), block_q)

    @pl.when((r == 0) & (qj == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(ki == 0)           # a new (batch, kv head): what the scratch
    def _init_dq():             # holds is the last one's
        dq_acc[r, rows, :] = jnp.zeros((block_q, dq_acc.shape[2]),
                                       jnp.float32)

    # q blocks strictly before this kv-block's diagonal see none of it.
    live = ((qj + 1) * block_q > ki * block_kv) if causal else (qj >= 0)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]                               # [bq, d_qk]
        g = g_ref[0, 0]                               # [bq, d_v]
        k = k_ref[0, 0]                               # [bkv, d_qk]
        v = v_ref[0, 0]                               # [bkv, d_v]
        lse = lse_ref[0, 0]                           # [1, bq] f32
        dlt = dlt_ref[0, 0]
        s_t = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [bkv, bq]
        if causal:
            k_pos = ki * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_kv, block_q), 0)
            q_pos = qj * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_kv, block_q), 1)
            s_t = jnp.where(q_pos >= k_pos, s_t, NEG_INF)
        p_t = jnp.exp(s_t - lse)
        dv_acc[...] += jax.lax.dot_general(
            p_t.astype(g.dtype), g, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bkv, d_v]
        dp_t = jax.lax.dot_general(
            v, g, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds_t = (p_t * (dp_t - dlt) * scale).astype(q.dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds_t, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bkv, d_qk]
        dq_acc[r, rows, :] += jax.lax.dot_general(
            ds_t, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bq, d_qk]

    @pl.when((r == n_rep - 1) & (qj == n_q - 1))
    def _flush():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(ki == n_kv - 1)    # the q block has seen its last kv block
    def _flush_dq():
        dq_ref[0, r, rows, :] = dq_acc[r, rows, :].astype(dq_ref.dtype)


def _bwd_resident(seq: int, reps: int, d_qk: int, dtype) -> int:
    """Bytes of VMEM the backward kernel keeps for a (batch, kv head)'s dq:
    the f32 accumulator of the ``reps`` query heads' ``seq`` rows and their
    output block, double-buffered, each row whole tiles of 128 lanes."""
    lanes = -(-d_qk // 128) * 128
    return reps * seq * lanes * (4 + 2 * jnp.dtype(dtype).itemsize)


def _bwd_vmem(seq: int, reps: int, d_qk: int, dtype) -> dict:
    """What the backward call passes the compiler, as ``_fwd_vmem`` does for
    the forward's resident K and V: past what the compiler's default of 16
    MiB leaves beside the kernel's blocks and products (8,192 rows of a head
    of 192 lanes, or 4,096 of four heads of 128, are 16 MiB) the kernel asks
    for what it needs; below, nothing is passed."""
    resident = _bwd_resident(seq, reps, d_qk, dtype)
    return ({} if resident <= BWD_VMEM_DEFAULT else {
        "compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=resident + BWD_VMEM_BLOCKS)})


def _flash_bwd_pallas(q, k, v, out, lse, g, causal: bool, block_q: int,
                      block_kv: int, interpret: bool,
                      scale: Optional[float] = None):
    """Pallas flash backward: (dq, dk, dv), dk/dv in kv-head layout.  q, k
    (and dq, dk) are ``d`` lanes wide; v, out, g (and dv) ``d_v``."""
    b, h, s, d = q.shape
    d_v = v.shape[3]
    kv_heads = k.shape[1]
    reps = h // kv_heads
    scale = score_scale(scale, d)
    bq = min(block_q, s)
    bkv = min(block_kv, s)

    gf = g.astype(q.dtype)
    # D_i = rowsum(dO * O), the softmax-jacobian diagonal term (over d_v).
    delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    # Stats ride as [B, H, 1, S] so the (1, 1, 1, bq) block satisfies the
    # Mosaic tiling rule (second-to-last block dim == full array dim).
    lse4 = lse[:, :, None, :]
    dlt4 = delta[:, :, None, :]

    def q_rows(width):              # a q block of a query head: q, g
        return pl.BlockSpec((1, 1, bq, width),
                            lambda bi, gi, ki, r, qj: (bi, gi * reps + r, qj, 0))

    def kv_rows(width):             # a kv block of a kv head: k, v, dk, dv
        return pl.BlockSpec((1, 1, bkv, width),
                            lambda bi, gi, ki, r, qj: (bi, gi, ki, 0))

    stat = pl.BlockSpec((1, 1, 1, bq),
                        lambda bi, gi, ki, r, qj: (bi, gi * reps + r, 0, qj))
    # the whole rows of the query heads under a kv head (they are
    # contiguous: h = gi * reps + r)
    dq_rows = pl.BlockSpec((1, reps, s, d),
                           lambda bi, gi, ki, r, qj: (bi, gi, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, causal=causal, scale=scale),
        grid=(b, kv_heads, s // bkv, reps, s // bq),
        **_bwd_vmem(s, reps, d, q.dtype),
        in_specs=[q_rows(d), kv_rows(d), kv_rows(d_v), q_rows(d_v), stat,
                  stat],
        out_specs=[dq_rows, kv_rows(d), kv_rows(d_v)],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, kv_heads, s, d), k.dtype),
            jax.ShapeDtypeStruct((b, kv_heads, s, d_v), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((reps, s, d), jnp.float32),
                        pltpu.VMEM((bkv, d), jnp.float32),
                        pltpu.VMEM((bkv, d_v), jnp.float32)],
        interpret=interpret,
        name="flash_dkv",
    )(q, k, v, gf, lse4, dlt4)


def window_band_blocks(window: int, block: int) -> int:
    """Query blocks of ``block`` positions that the band of a KV block of as
    many touches: a key at ``p`` is read by the queries ``p .. p + window -
    1``.  3 for a band of 1,024 under blocks of 512."""
    return (block + window - 2) // block + 1


def _bwd_window_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, dlt_ref,
                       dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                       scale: float, window: int, n_q: int):
    """``_bwd_kernel`` for a band: grid (b, kv_heads, n_kv, reps, n_band),
    the q blocks a kv block's band touches and no others, the farthest
    first and the diagonal's last (blocks of one size, so q block ``ki`` is
    kv block ``ki``'s diagonal).  dk / dv accumulate over the two innermost
    dims as there.  dq of q block ``qj`` gathers from the kv blocks ``qj -
    n_band + 1 .. qj``, which the grid reaches in that order with other q
    blocks between: its running sum lies in slot ``qj % n_band`` of
    ``dq_acc`` [reps, n_band, block, d_qk] and goes out at every visit, the
    last of which (the diagonal's) leaves the whole sum."""
    ki, r, j = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    n_rep, n_band = pl.num_programs(3), pl.num_programs(4)
    block = k_ref.shape[2]
    ahead = n_band - 1 - j                  # q blocks past the diagonal
    qj = ki + ahead
    slot = qj % n_band

    @pl.when((r == 0) & (j == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # past the last q block the index maps repeat it: nothing to do there
    @pl.when(qj < n_q)
    def _compute():
        q = q_ref[0, 0]                               # [bq, d_qk]
        g = g_ref[0, 0]                               # [bq, d_v]
        k = k_ref[0, 0]                               # [bkv, d_qk]
        v = v_ref[0, 0]                               # [bkv, d_v]
        lse = lse_ref[0, 0]                           # [1, bq] f32
        dlt = dlt_ref[0, 0]
        s_t = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [bkv, bq]
        k_pos = ki * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 0)
        q_pos = qj * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 1)
        seen = (q_pos >= k_pos) & (q_pos - k_pos < window)
        p_t = jnp.where(seen, jnp.exp(jnp.where(seen, s_t, NEG_INF) - lse),
                        0.0)
        dv_acc[...] += jax.lax.dot_general(
            p_t.astype(g.dtype), g, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bkv, d_v]
        dp_t = jax.lax.dot_general(
            v, g, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds_t = (p_t * (dp_t - dlt) * scale).astype(q.dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds_t, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bkv, d_qk]
        dq = jax.lax.dot_general(
            ds_t, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bq, d_qk]
        # the q block's first kv block: the farthest, or the sequence's first
        first = (j == 0) | (ki == 0)

        @pl.when(first)
        def _():
            dq_acc[r, slot] = dq

        @pl.when(jnp.logical_not(first))
        def _():
            dq_acc[r, slot] += dq

        dq_ref[0, 0] = dq_acc[r, slot].astype(dq_ref.dtype)

    @pl.when((r == n_rep - 1) & (j == n_band - 1))
    def _flush():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_window_bwd_pallas(q, k, v, out, lse, g, block: int, window: int,
                             interpret: bool, scale: Optional[float] = None):
    """The band's backward: (dq, dk, dv) as ``_flash_bwd_pallas`` gives
    them, over the (kv block, q block) pairs the band touches."""
    b, h, s, d = q.shape
    d_v = v.shape[3]
    kv_heads = k.shape[1]
    reps = h // kv_heads
    n_q = s // block
    n_band = window_band_blocks(window, block)
    gf = g.astype(q.dtype)
    delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    lse4 = lse[:, :, None, :]
    dlt4 = delta[:, :, None, :]

    def q_at(ki, j):
        return jnp.minimum(ki + n_band - 1 - j, n_q - 1)

    def q_rows(width):              # a q block of a query head: q, g, dq
        return pl.BlockSpec(
            (1, 1, block, width),
            lambda bi, gi, ki, r, j: (bi, gi * reps + r, q_at(ki, j), 0))

    def kv_rows(width):             # a kv block of a kv head: k, v, dk, dv
        return pl.BlockSpec((1, 1, block, width),
                            lambda bi, gi, ki, r, j: (bi, gi, ki, 0))

    stat = pl.BlockSpec(
        (1, 1, 1, block),
        lambda bi, gi, ki, r, j: (bi, gi * reps + r, 0, q_at(ki, j)))
    return pl.pallas_call(
        functools.partial(_bwd_window_kernel, scale=score_scale(scale, d),
                          window=window, n_q=n_q),
        grid=(b, kv_heads, s // block, reps, n_band),
        in_specs=[q_rows(d), kv_rows(d), kv_rows(d_v), q_rows(d_v), stat,
                  stat],
        out_specs=[q_rows(d), kv_rows(d), kv_rows(d_v)],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, kv_heads, s, d), k.dtype),
            jax.ShapeDtypeStruct((b, kv_heads, s, d_v), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((reps, n_band, block, d), jnp.float32),
                        pltpu.VMEM((block, d), jnp.float32),
                        pltpu.VMEM((block, d_v), jnp.float32)],
        interpret=interpret,
        name=KERNEL_FLASH_WINDOW_BWD,
    )(q, k, v, gf, lse4, dlt4)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_window(q, k, v, block_q, block_kv, interpret, window, scale=None):
    """The banded forward with its backward: a query reads its last
    ``window`` positions."""
    out, _ = _flash_fwd(q, k, v, True, block_q, block_kv, interpret, window,
                        scale)
    return out


def _flash_window_vjp_fwd(q, k, v, block_q, block_kv, interpret, window,
                          scale):
    from jax.ad_checkpoint import checkpoint_name
    out, lse = _flash_fwd(q, k, v, True, block_q, block_kv, interpret,
                          window, scale)
    out = checkpoint_name(out, "attn_out")      # as ``_flash_vjp_fwd``
    lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, out, lse)


def _flash_window_vjp_bwd(block_q, block_kv, interpret, window, scale, res,
                          g):
    q, k, v, out, lse = res
    s = q.shape[2]
    # blocks of one size, whole 128s (``flash_bwd_supported``'s tiling rule;
    # nothing stays resident for the whole sequence here)
    if block_q % 128 == 0 and s % block_q == 0:
        return _flash_window_bwd_pallas(q, k, v, out, lse, g, block_q,
                                        window, interpret, scale)
    return _bwd_blockwise(q, k, v, out, lse, g, True, block_kv, scale,
                          window)


_flash_window.defvjp(_flash_window_vjp_fwd, _flash_window_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, block_q, block_kv, interpret, scale=None):
    out, _ = _flash_fwd(q, k, v, causal, block_q, block_kv, interpret,
                        scale=scale)
    return out


def _flash_vjp_fwd(q, k, v, causal, block_q, block_kv, interpret, scale):
    from jax.ad_checkpoint import checkpoint_name
    out, lse = _flash_fwd(q, k, v, causal, block_q, block_kv, interpret,
                          scale=scale)
    # Under `jax.checkpoint(policy=save_only_these_names(...))` these names let
    # the remat replay keep the flash residuals instead of re-running the
    # forward kernel (models/transformer.py REMAT_SAVE_NAMES).
    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, block_q, block_kv, interpret, scale, res, g):
    q, k, v, out, lse = res
    if flash_bwd_supported(q.shape[2], q.shape[1], k.shape[1], q.shape[3],
                           q.dtype, block_q, block_kv) is None:
        return _flash_bwd_pallas(q, k, v, out, lse, g, causal, block_q,
                                 block_kv, interpret, scale)
    return _bwd_blockwise(q, k, v, out, lse, g, causal, block_kv, scale)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


#: How many times each Pallas kernel wrapper was traced in interpret mode in
#: this process, keyed by kernel name ("flash", "splash").  Interpret mode is
#: the CPU stand-in for a kernel; a test reads this to see that a path took
#: it, and on a TPU it stays empty.
INTERPRET_TRACES: Dict[str, int] = {}


def resolve_interpret(interpret: Optional[bool], kernel: str) -> bool:
    """Whether a Pallas kernel runs interpreted: when the caller says so, or
    (``interpret=None``) when the backend is the CPU.  Every interpreted trace
    is counted in ``INTERPRET_TRACES``.  Never on a TPU: there the kernel
    compiles or the call fails."""
    backend = jax.default_backend()
    if interpret is None:
        interpret = backend == "cpu"
    if interpret:
        if backend == "tpu":
            raise ValueError(
                f"{kernel} attention: interpret=True on a TPU backend; the "
                f"interpreter is the CPU stand-in for the kernel")
        INTERPRET_TRACES[kernel] = INTERPRET_TRACES.get(kernel, 0) + 1
    return bool(interpret)


def flash_supported(seq_q: int, seq_kv: int, num_heads: int,
                    num_kv_heads: int, block_q: int = 512,
                    block_kv: int = 512) -> Optional[str]:
    """None when the shape tiles for the flash kernel, else the reason."""
    bq, bkv = min(block_q, seq_q), min(block_kv, seq_kv)
    if seq_q % bq or seq_kv % bkv:
        return (f"seq ({seq_q}, {seq_kv}) not a multiple of the blocks "
                f"({bq}, {bkv})")
    if num_kv_heads < 1 or num_heads % num_kv_heads:
        return f"heads {num_heads} not a multiple of kv heads {num_kv_heads}"
    return None


def flash_bwd_supported(seq: int, num_heads: int, num_kv_heads: int,
                        d_qk: int, dtype, block_q: int = 512,
                        block_kv: int = 512) -> Optional[str]:
    """None when a differentiated ``flash_attention`` call of this shape
    takes the Pallas backward kernel, else the reason it takes the scan in
    plain JAX (``_bwd_blockwise``: the same recurrence, correct and slow).
    A rule of the shapes alone: the blocks must tile for the kernel, and the
    dq it keeps resident (``_bwd_resident``: the f32 rows of the query heads
    under a kv head and their output block) must fit the chip's VMEM.  8,192
    positions of a head of 192 lanes and 4,096 of four heads of 128 (the two
    train cells) are 16 MiB; four heads of 128 at 32,768 positions are
    128 MiB and take the scan."""
    bq, bkv = min(block_q, seq), min(block_kv, seq)
    # bq rides the lane dim of the stat rows (must be 128-aligned); bkv the
    # sublane dim of the transposed score tile.
    if bq % 128 or bkv % 128 or seq % bq or seq % bkv:
        return (f"blocks ({bq}, {bkv}) are no whole 128s or do not tile "
                f"{seq} positions")
    resident = _bwd_resident(seq, num_heads // num_kv_heads, d_qk, dtype)
    if resident > BWD_VMEM_REACH:
        return (f"{resident} bytes of resident dq are past the kernel's "
                f"reach of {BWD_VMEM_REACH}")
    return None


def kernel_batch_spec(mesh, batch_axes) -> Optional[P]:
    """The [B, ...] PartitionSpec of a shard_map around a kernel call, or None
    when ``mesh`` is None or one device.  Mosaic kernels cannot be partitioned
    by the compiler, so on a mesh of more than one device the call is wrapped
    by hand: batch over ``batch_axes``, everything else replicated."""
    if mesh is None or mesh.size <= 1:
        return None
    axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    return P(axes or None, None, None, None)


def flash_attention(q, k, v, causal: bool = True, block_q: int = 512,
                    block_kv: int = 512,
                    interpret: Optional[bool] = None, mesh=None,
                    batch_axes: Tuple[str, ...] = ("dp", "fsdp"),
                    window: int = 0,
                    scale: Optional[float] = None) -> jnp.ndarray:
    """Flash attention. q: [B, Sq, H, Dqk], k: [B, Skv, KV, Dqk], v: [B, Skv,
    KV, Dv] -> [B, Sq, H, Dv].  ``scale`` multiplies the scores (None: ``Dqk
    ** -0.5``).

    Layout matches ``attention.attend``; internally transposed to [B, H, S, D]
    (the kernel wants the sequence on the sublane dim and a head's width on
    lanes: 64, 128, 192, 256; the value's may differ from the query's and
    key's, as a latent head's 128 beside 192).  Sequence lengths must be
    multiples of the block sizes: a shape that does not tile raises
    (``flash_supported`` says why; the ``mha`` dispatcher asks it before
    choosing this kernel).

    With a ``mesh`` of more than one device the call is wrapped in a
    shard_map over ``batch_axes``, each device on its local batch shard.
    Pass no mesh from inside a manual region (a shard_map body).

    ``window`` > 0 (causal): a query reads its last ``window`` positions,
    its own among them; KV blocks (of ``WINDOW_BLOCK_KV`` at most) that a
    query block's band does not touch are not computed.  Differentiable:
    the forward keeps ``lse`` and the backward is a kernel of its own
    (``flash_window_bwd``) over the (KV block, query block) pairs the band
    touches, as the forward skips the others; where the blocks do not tile
    for it (under 128 positions: the CPU tests' small shapes) the scan in
    plain JAX with the band's mask.
    """
    b, sq, h, d = q.shape
    if window:
        if not causal:
            raise ValueError("a window is causal")
        block_kv = min(block_kv, WINDOW_BLOCK_KV)
    reason = flash_supported(sq, k.shape[1], h, k.shape[2], block_q, block_kv)
    if reason is None and (k.shape[-1] != d or v.shape[:2] != k.shape[:2]
                           or k.shape[2] % v.shape[2]):
        reason = (f"keys {k.shape} are not as wide as queries {q.shape} or "
                  f"not as many as values {v.shape}, nor a whole number of "
                  "them a value head")
    if reason is not None:
        raise ValueError(f"flash attention cannot run this shape: {reason}")
    interpret = resolve_interpret(interpret, "flash")
    block_q = min(block_q, sq)
    block_kv = min(block_kv, k.shape[1])

    def local(q, k, v):
        heads_first = (q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2))
        if window:
            out = _flash_window(*heads_first, block_q, block_kv, interpret,
                                window, scale)
        else:
            out = _flash(*heads_first, causal, block_q, block_kv, interpret,
                         scale)
        return out.swapaxes(1, 2)

    spec = kernel_batch_spec(mesh, batch_axes)
    if spec is None:
        return local(q, k, v)
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def flash_rows_supported(width: int, kv_len: int, head_dim: int,
                         block_q: int = 512, block_kv: int = 512,
                         apart: bool = False) -> Optional[str]:
    """None when ``flash_attention_rows`` can take its kernel at this shape,
    else the reason.  ``head_dim``: a head's width, the widest of the two
    where they differ; ``apart``: a head's rows lie apart, not side by side
    with the position's other heads."""
    if head_dim % (64 if apart else 128):
        return (f"a head of {head_dim} lanes is no whole block of "
                f"{'64 lanes' if apart else 'a row'}")
    return flash_supported(width, kv_len, 1, 1, block_q, block_kv)


def flash_attention_rows(q, k_all, v_all, layer, slot, start, kv_len: int,
                         num_kv_heads: int, logit_softcap: float = 0.0,
                         block_q: int = 512, block_kv: int = 512,
                         use_kernel: Optional[bool] = None,
                         interpret: Optional[bool] = None,
                         scale: Optional[float] = None) -> jnp.ndarray:
    """Causal attention of W queries of one sequence that sit at positions
    ``start .. start + W`` of a slot of stacked rows, over the slot's rows
    ``0 .. kv_len`` where they lie, the queries' own rows among them
    (already written): the forward kernel with a query offset that is data.

    q: [1, W, NH, Dqk]; k_all, v_all: the stacked cache [layers, slots,
    max_len, NKV * D], a position's heads side by side, or [layers, slots,
    NKV, max_len, D], a head's rows apart (rows rebuilt for the call: a
    latent head's keys of 192 are no whole block of a row); keys are as wide
    as the queries, values may be narrower (192 / 128), and the result is as
    wide as they.  layer, slot, start: int32 scalars, traced or not;
    ``kv_len`` (static) bounds what the row may hold, ``start + W <=
    kv_len``; rows past a query's own position are masked, whatever they
    hold; ``scale`` multiplies the scores (None: ``Dqk ** -0.5``).  Returns
    [1, W, NH * Dv] in q's dtype.

    ``use_kernel=None`` takes the kernel (bf16 operands as the cache has
    them, float32 scores and accumulator) on a TPU outside a mesh where the
    shape tiles and there is no softcap; else the plain ``attend`` with
    ``q_offset`` over the slot's slab: the CPU path and the path under a
    mesh."""
    _, w, num_heads, d = q.shape
    apart = k_all.ndim == 5
    reason = flash_rows_supported(w, kv_len, d, block_q, block_kv, apart)
    if reason is None and logit_softcap:
        reason = "the flash kernel has no logit softcap"
    if use_kernel is None:
        use_kernel = reason is None and (bool(interpret) or (
            jax.default_backend() == "tpu"
            and jax.sharding.get_abstract_mesh().size <= 1))
    if use_kernel:
        if reason is not None:
            raise ValueError(f"flash attention cannot run this shape: {reason}")
        rows = q[0].astype(k_all.dtype)
        out = _flash_fwd_rows(
            rows.swapaxes(0, 1) if apart else rows.reshape(w, -1), k_all,
            v_all, jnp.stack([layer, slot, start]).astype(jnp.int32),
            num_heads, kv_len, min(block_q, w), min(block_kv, kv_len),
            resolve_interpret(interpret, "flash"), scale)
        if apart:
            out = out.swapaxes(0, 1).reshape(w, -1)
        return out[None].astype(q.dtype)
    from .attention import attend

    def slab(a):
        """The slot's rows [1, kv_len, NKV, D]."""
        if apart:
            return jax.lax.dynamic_slice(
                a, (layer, slot, 0, 0, 0),
                (1, 1, num_kv_heads, kv_len, a.shape[-1]))[0].swapaxes(1, 2)
        return jax.lax.dynamic_slice(
            a, (layer, slot, 0, 0), (1, 1, kv_len, a.shape[-1])).reshape(
                1, kv_len, num_kv_heads, -1)

    return attend(q, slab(k_all), slab(v_all), causal=True, q_offset=start,
                  logit_softcap=logit_softcap,
                  scale=scale).reshape(1, w, -1)
