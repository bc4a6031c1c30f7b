"""Kimi Delta Attention (KDA, arXiv 2510.26692): the gated delta rule with a
decay a key channel, as two Pallas TPU kernels and their plain twins.  The
sibling of ``gated_delta.py`` (a scalar decay a head), whose tile helpers,
unit-lower-triangular inverse and step tile this file imports.

Per head, with a state ``h`` of shape [key_dim, value_dim] held in float32,
a decay ``alpha_t = exp(g_t)`` in (0, 1]^key_dim and a write strength
``beta_t`` in [0, 2]::

    h_t = (I - beta_t k_t k_t^T) Diag(alpha_t) h_{t-1} + beta_t k_t v_t^T
    o_t = h_t^T q_t

* ``kda_chunk_fwd`` (prefill) runs a whole sequence in chunks of ``CHUNK``
  steps with the WY / UT transform of the scalar form.  A decay a channel
  does not factor out of ``q k^T``: entry (i, j) of a chunk's two [c, c]
  matrices is ``sum_d a_i[d] k_j[d] exp(G_i[d] - G_j[d])`` with ``G`` the
  cumulative log decay.  **No exponential of a positive number is taken**:
  a chunk is four sub-blocks of ``SUB`` steps; a row block against the
  blocks before it factors about the cumulative decay at its own first
  step (``exp(G_i - G_ref)`` and ``exp(G_ref - G_j)``, both exponents <=
  0), and a block against itself is computed by pairs, one column at a
  time.  A channel whose ``alpha`` is 0.05 at every step underflows to an
  exact 0 where the factored form ``exp(-G_j)`` would overflow after 30
  steps.
* ``kda_recurrent_step`` (decode) applies one step to every slot of one
  layer of a stacked state ``[layers, slots, heads, key_dim, value_dim]``,
  in place, taking the layer index itself (scalar prefetch).

Each kernel's math is one function on two-dimensional tiles
(``_chunk_tile``; the step's is ``gated_delta._step_tile`` with the decay a
column) that the kernel body calls on what it loaded and the twin ``vmap``s
over batch and heads.  The independent check of both is ``kda_recurrence``,
the equations above one token at a time.  A position with ``beta = 0, g =
0`` leaves the state untouched: right padding and idle slots.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import resolve_interpret
from .gated_delta import (CHUNK, F32, _HI, _col, _head_group, _lanes, _mm,
                          _step_tile, inv_unit_lower)

#: kernel names as a device trace shows them (``<name> [pallas]``); pinned by
#: tests/test_trace_names.py, read by the benchmark's kda_* readers
KERNEL_KDA_CHUNK_FWD = "kda_chunk_fwd"
KERNEL_KDA_RECURRENT_STEP = "kda_recurrent_step"

#: steps a sub-block of a chunk (``inv_unit_lower``'s block too)
SUB = 16
#: heads a grid step of the decode kernel, at most: 8 heads of 128 x 128
#: float32 are 512 KB in and 512 KB out a step
STEP_HEADS_A_STEP = 8


def _mm_nt32(a, b):
    """a [m, k] @ b [n, k]^T in float32, every bit of the operands used."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=_HI,
                               preferred_element_type=F32)


# ---------------------------------------------------------------------------
# Tile math, shared by the kernel and its twin
# ---------------------------------------------------------------------------

def _decayed_products(q, k, gc):
    """The chunk's two [c, c] matrices ``sum_d a_i[d] k_j[d] exp(gc_i[d] -
    gc_j[d])`` for ``a = k`` and ``a = q``, entries with ``j <= i`` (the
    rest is left 0).  q, k [c, dk] float32; gc [c, dk] the cumulative log
    decay inside the chunk.  Every exponent is <= 0 (module docstring)."""
    c = q.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, (SUB, c), 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (SUB, c), 0)
    kk, qk = [], []
    for b in range(c // SUB):
        at = b * SUB
        kb, qb, gb = (a[at:at + SUB] for a in (k, q, gc))
        kk_b = jnp.zeros((SUB, c), F32)
        qk_b = jnp.zeros((SUB, c), F32)
        if b:
            # against the blocks before: about this block's first step
            ref = gb[0:1]
            here = jnp.exp(gb - ref)
            before = k * jnp.exp(jnp.minimum(ref - gc, 0.0))
            earlier = cols < at
            kk_b = jnp.where(earlier, _mm_nt32(kb * here, before), 0.0)
            qk_b = jnp.where(earlier, _mm_nt32(qb * here, before), 0.0)
        # against itself: by pairs, column j of the block at a time
        for j in range(SUB):
            kj = kb[j:j + 1] * jnp.exp(jnp.minimum(gb - gb[j:j + 1], 0.0))
            seen = (cols == at + j) & (rows >= j)
            kk_b = kk_b + jnp.where(
                seen, jnp.sum(kb * kj, axis=1, keepdims=True), 0.0)
            qk_b = qk_b + jnp.where(
                seen, jnp.sum(qb * kj, axis=1, keepdims=True), 0.0)
        kk.append(kk_b)
        qk.append(qk_b)
    return jnp.concatenate(kk, axis=0), jnp.concatenate(qk, axis=0)


def _chunk_tile(q, k, v, gc, b_row, h):
    """One chunk of one head.  q, k [c, dk] (q already scaled), v [c, dv],
    gc [c, dk] the cumulative log decay inside the chunk, b_row [1, c]
    beta, h [dk, dv] float32 the state before the chunk.  Returns (o [c, dv]
    float32, state after)."""
    c = q.shape[0]
    kf, vf, qf = k.astype(F32), v.astype(F32), q.astype(F32)
    b_col = _col(b_row)
    rows = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    kk, qk = _decayed_products(qf, kf, gc)
    t = inv_unit_lower(jnp.where(rows > cols, kk * b_col, 0.0))
    total = jnp.exp(gc)                                # decay from the start
    w = _mm(t, kf * total * b_col)                     # [c, dk]
    u = _mm(t, vf * b_col)                             # [c, dv]
    v_new = u - _mm(w, h)
    o = _mm(qf * total, h) + _mm(qk, v_new)
    g_last = gc[c - 1:c]                               # [1, dk]
    k_dec = kf * jnp.exp(g_last - gc)
    return o, h * _col(jnp.exp(g_last)) + _mm(k_dec.T, v_new)


# ---------------------------------------------------------------------------
# The independent check: the equations, one token at a time
# ---------------------------------------------------------------------------

def kda_recurrence(q, k, v, g, beta, initial_state=None):
    """q, k [B, T, H, dk] (q scaled), v [B, T, H, dv], g [B, T, H, dk], beta
    [B, T, H] -> (o [B, T, H, dv] float32, state [B, H, dk, dv] float32)."""
    b, _, nh, dk = q.shape
    dv = v.shape[-1]
    h0 = (jnp.zeros((b, nh, dk, dv), F32) if initial_state is None
          else initial_state.astype(F32))

    def step(h, xs):
        q_t, k_t, v_t, g_t, b_t = xs                  # [B, H, ...]
        h = h * jnp.exp(g_t)[..., None]
        pred = jnp.einsum("bhkv,bhk->bhv", h, k_t, precision=_HI)
        delta = (v_t - pred) * b_t[..., None]
        h = h + k_t[..., :, None] * delta[..., None, :]
        return h, jnp.einsum("bhkv,bhk->bhv", h, q_t, precision=_HI)

    xs = tuple(a.astype(F32).swapaxes(0, 1) for a in (q, k, v, g, beta))
    h, o = jax.lax.scan(step, h0, xs)
    return o.swapaxes(0, 1), h


# ---------------------------------------------------------------------------
# Prefill: chunked forward
# ---------------------------------------------------------------------------

def _chunk_inputs(q, k, v, g, beta, lengths):
    """Mask positions at or beyond ``lengths`` (beta 0, g 0), pad the time
    axis to whole chunks, go to [B, H, T, d] and take the cumulative decay
    inside each chunk.  Returns (q, k, v, gc [B, H, N, c, dk], beta [B, H,
    N, c])."""
    b, t, nh, dk = q.shape
    if lengths is not None:
        live = jnp.arange(t)[None, :] < lengths[:, None]
        g = jnp.where(live[..., None, None], g, 0.0)
        beta = jnp.where(live[..., None], beta, 0.0)
    pad = -t % CHUNK
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                    * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    n = (t + pad) // CHUNK
    q, k, v, g = (a.swapaxes(1, 2) for a in (q, k, v, g))         # [B,H,T,d]
    gc = jnp.cumsum(g.astype(F32).reshape(b, nh, n, CHUNK, dk), axis=3)
    beta = beta.astype(F32).swapaxes(1, 2).reshape(b, nh, n, CHUNK)
    return q, k, v, gc, beta


def kda_chunk_fwd_jnp(q, k, v, g, beta, lengths=None):
    """The twin of ``kda_chunk_fwd``: the same tile math, ``vmap``ped over
    batch and heads and scanned over chunks.  Shapes as ``kda_chunk_fwd``."""
    b, t, nh, dk = q.shape
    dv = v.shape[-1]
    q, k, v, gc, beta = _chunk_inputs(q, k, v, g, beta, lengths)
    n = gc.shape[2]
    tile = jax.vmap(jax.vmap(_chunk_tile))

    def body(h, xs):
        qc, kc, vc, gcc, bc = xs
        o, h = tile(qc, kc, vc, gcc, bc[:, :, None, :], h)
        return h, o

    def chunks(a):                   # [B, H, n*c, d] -> [n, B, H, c, d]
        return jnp.moveaxis(a.reshape(b, nh, n, CHUNK, a.shape[-1]), 2, 0)

    h, o = jax.lax.scan(
        body, jnp.zeros((b, nh, dk, dv), F32),
        (chunks(q), chunks(k), chunks(v), jnp.moveaxis(gc, 2, 0),
         jnp.moveaxis(beta, 2, 0)))
    o = jnp.moveaxis(o, 0, 2).reshape(b, nh, n * CHUNK, dv)[:, :, :t]
    return o.swapaxes(1, 2).astype(v.dtype), h


def _chunk_kernel(q_ref, k_ref, v_ref, gc_ref, b_ref, o_ref, s_ref, h_ref):
    """Grid (batch, heads, chunks), chunks innermost and sequential: a
    head's state lives in ``h_ref`` (VMEM scratch) across a row's chunks."""
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    o, h = _chunk_tile(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0],
                       gc_ref[0, 0, 0], b_ref[0, 0, pl.ds(ci, 1), :],
                       h_ref[...])
    o_ref[0, 0] = o.astype(o_ref.dtype)
    h_ref[...] = h

    @pl.when(ci == pl.num_programs(2) - 1)
    def _flush():
        s_ref[0, 0] = h_ref[...]


def _chunk_fwd_pallas(q, k, v, g, beta, lengths, interpret: bool):
    b, t, nh, dk = q.shape
    dv = v.shape[-1]
    q, k, v, gc, beta = _chunk_inputs(q, k, v, g, beta, lengths)
    n = gc.shape[2]
    dkp, dvp = _lanes(dk), _lanes(dv)
    # padded key lanes: q and k zero and no decay, so they add nothing
    q, k = (jnp.pad(a, ((0, 0),) * 3 + ((0, dkp - dk),)) for a in (q, k))
    gc = jnp.pad(gc, ((0, 0),) * 4 + ((0, dkp - dk),))
    v = jnp.pad(v, ((0, 0),) * 3 + ((0, dvp - dv),))
    row = lambda bi, hi, ci: (bi, hi, ci, 0)    # noqa: E731
    whole = lambda bi, hi, ci: (bi, hi, 0, 0)   # noqa: E731
    o, s = pl.pallas_call(
        _chunk_kernel,
        grid=(b, nh, n),
        in_specs=[
            pl.BlockSpec((1, 1, CHUNK, dkp), row),
            pl.BlockSpec((1, 1, CHUNK, dkp), row),
            pl.BlockSpec((1, 1, CHUNK, dvp), row),
            pl.BlockSpec((1, 1, 1, CHUNK, dkp),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, n, CHUNK), whole),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, CHUNK, dvp), row),
            pl.BlockSpec((1, 1, dkp, dvp), whole),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nh, n * CHUNK, dvp), v.dtype),
            jax.ShapeDtypeStruct((b, nh, dkp, dvp), F32),
        ],
        scratch_shapes=[pltpu.VMEM((dkp, dvp), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_KDA_CHUNK_FWD,
    )(q, k, v, gc, beta)
    return o[:, :, :t, :dv].swapaxes(1, 2), s[:, :, :dk, :dv]


def kda_chunk_fwd(q, k, v, g, beta, lengths=None,
                  use_kernel: Optional[bool] = None,
                  interpret: Optional[bool] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The delta rule with a decay a channel over whole sequences from a
    zero state.

    q, k: [B, T, H, dk] (q already scaled, both already normalised);
    v: [B, T, H, dv]; g (log decay, <= 0): [B, T, H, dk]; beta: [B, T, H];
    ``lengths`` [B]: positions at or beyond a row's length do not touch its
    state.  Returns (o [B, T, H, dv] in v's dtype, state [B, H, dk, dv]
    float32 as of each row's length).  ``T`` may be any length; a chunk is
    ``CHUNK`` steps.

    ``use_kernel=None`` takes the Pallas kernel on a TPU and the twin
    elsewhere; ``interpret=True`` runs the kernel interpreted (tests)."""
    if use_kernel is None:
        use_kernel = bool(interpret) or jax.default_backend() == "tpu"
    if not use_kernel:
        return kda_chunk_fwd_jnp(q, k, v, g, beta, lengths)
    interpret = resolve_interpret(interpret, "kda_chunk")
    return _chunk_fwd_pallas(q, k, v, g, beta, lengths, interpret)


# ---------------------------------------------------------------------------
# Decode: one step, in place on the stacked state
# ---------------------------------------------------------------------------

def kda_recurrent_step_jnp(state, layer, q, k, v, g, beta):
    """The twin of ``kda_recurrent_step``; shapes as there."""
    h = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    tile = jax.vmap(jax.vmap(
        lambda h, q, k, v, a, b: _step_tile(h, q, k, v, _col(a), b)))
    o, h = tile(h, q[:, :, None], k[:, :, None], v[:, :, None],
                jnp.exp(g.astype(F32))[:, :, None], beta.astype(F32))
    state = jax.lax.dynamic_update_index_in_dim(state, h, layer, 0)
    return state, o[:, :, 0].astype(v.dtype)


def _step_kernel(layer_ref, b_ref, s_in, q_ref, k_ref, v_ref, a_ref,
                 s_out, o_ref, *, heads: int):
    del layer_ref                     # used by the index maps only
    si, gi = pl.program_id(0), pl.program_id(1)
    for i in range(heads):
        o, h = _step_tile(s_in[0, 0, i], q_ref[0, 0, i:i + 1],
                          k_ref[0, 0, i:i + 1], v_ref[0, 0, i:i + 1],
                          _col(a_ref[0, 0, i:i + 1]),
                          b_ref[si, gi * heads + i])
        s_out[0, 0, i] = h
        o_ref[0, 0, i:i + 1] = o.astype(o_ref.dtype)


def _recurrent_step_pallas(state, layer, q, k, v, g, beta, interpret: bool):
    _, slots, nh, dk, dv = state.shape
    hb = _head_group(nh, STEP_HEADS_A_STEP)
    ng = nh // hb
    alpha = jnp.exp(g.astype(F32))
    q, k, v, alpha = (a.reshape(slots, ng, hb, a.shape[-1])
                      for a in (q, k, v, alpha))
    small = lambda si, gi, lyr: (si, gi, 0, 0)          # noqa: E731
    big = lambda si, gi, lyr: (lyr[0], si, gi, 0, 0)    # noqa: E731
    state, o = pl.pallas_call(
        functools.partial(_step_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(slots, ng),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, 1, hb, dk, dv), big),
                pl.BlockSpec((1, 1, hb, dk), small),
                pl.BlockSpec((1, 1, hb, dk), small),
                pl.BlockSpec((1, 1, hb, dv), small),
                pl.BlockSpec((1, 1, hb, dk), small),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, hb, dk, dv), big),
                pl.BlockSpec((1, 1, hb, dv), small),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct((slots, ng, hb, dv), v.dtype),
        ],
        # operands count from the scalar-prefetch argument: 2 is the state
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=KERNEL_KDA_RECURRENT_STEP,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), beta.astype(F32),
      state, q, k, v, alpha)
    return state, o.reshape(slots, nh, dv)


def kda_recurrent_step(state, layer, q, k, v, g, beta,
                       use_kernel: Optional[bool] = None,
                       interpret: Optional[bool] = None):
    """One step of the delta rule with a decay a channel for every slot of
    layer ``layer``.

    state: [layers, slots, H, dk, dv] float32, updated in place (donate it);
    layer: int32 scalar (traced or not); q, k: [slots, H, dk]; v: [slots, H,
    dv]; g: [slots, H, dk]; beta: [slots, H] (``g = 0, beta = 0`` leaves a
    slot's state as it was).  Returns (state, o [slots, H, dv] in v's
    dtype).  Only the blocks of ``layer`` are read and written."""
    if use_kernel is None:
        use_kernel = bool(interpret) or jax.default_backend() == "tpu"
    if not use_kernel:
        return kda_recurrent_step_jnp(state, layer, q, k, v, g, beta)
    interpret = resolve_interpret(interpret, "kda_step")
    return _recurrent_step_pallas(state, layer, q, k, v, g, beta, interpret)
