"""Gated delta rule (Gated DeltaNet): the recurrent-state mixer of a
linear-attention layer, as two Pallas TPU kernels and their plain twins.

Per head, with a state ``h`` of shape [key_dim, value_dim] held in float32
(the transpose of the ``S`` of the papers), a decay ``alpha_t = exp(g_t)`` in
(0, 1] and a write strength ``beta_t`` in [0, 2]::

    h_t = alpha_t * (I - beta_t k_t k_t^T) h_{t-1} + beta_t k_t v_t^T
    o_t = h_t^T q_t

* ``gdn_chunk_fwd`` (prefill) runs a whole sequence in chunks of 64 steps
  (``CHUNK``):
  inside a chunk the rank-one updates are folded into one unit lower
  triangular solve (the WY / UT transform), so a chunk is a dozen small
  matmuls and only the chunk-to-chunk state is sequential.  A position with
  ``beta = 0, g = 0`` leaves the state untouched, which is how right padding
  is made harmless: the state returned is each row's as of its true length.
* ``gdn_recurrent_step`` (decode) applies one step to every slot of one
  layer of a stacked state, in place, taking the layer index itself (scalar
  prefetch), so that no layer slab is ever sliced out of the stack.  The
  stack holds the state packed (``pack_state``): ``[layers, slots, heads /
  p, key_dim, p * value_dim]``, ``p`` neighbouring heads side by side along
  the lanes, the fewest that fill whole 128-lane tiles (``packed_heads``: 2
  at a value_dim of 192, 1 at 128), so that the chip, which stores a
  float32 array's minor dimension in tiles of 128 lanes, stores and moves
  no lane that holds nothing.

Each kernel's math is one function on two-dimensional tiles
(``_chunk_tile``, ``_step_tile``) that the kernel body calls on what it
loaded and the twin ``vmap``s over batch and heads: the twin is the CPU
path and what the kernels are tested against on the chip.  The independent
check of both is ``gdn_recurrence``, the equations above one token at a
time.

Head sizes need not be multiples of the 128 lanes: the prefill wrapper pads
key and value dims to whole lane tiles on the way in and slices on the way
out (padded key lanes are zero, so they add nothing to any product), and
returns a plain state ``[B, heads, key_dim, value_dim]`` that its caller
packs.  The decode kernel moves whole slots of the packed stack, as many a
grid step as ``step_block`` plans from the state's shape, and steps a packed
tile as it lies: the step is element-wise on ``h`` but for two sums over
sublanes, so the ``p`` heads of a tile are stepped at once, each head's
column and scalars spread over its own lanes (``_step_lanes``), and every
element sees the operations of the plain step in their order: the packed
kernel's results are the plain step's to the bit.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import resolve_interpret

#: kernel names as a device trace shows them (``<name> [pallas]``); pinned by
#: tests/test_trace_names.py, read by the benchmark's gdn_* readers
KERNEL_CHUNK_FWD = "gdn_chunk_fwd"
KERNEL_RECURRENT_STEP = "gdn_recurrent_step"

#: steps a chunk of the prefill form (the family's default); one value, so
#: a constant: the benchmark's counts of the kernel assume it
CHUNK = 64

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def _mm(a, b):
    """a [m, k] @ b [k, n] in float32, every bit of the operands used."""
    return jax.lax.dot_general(a.astype(F32), b.astype(F32),
                               (((1,), (0,)), ((), ())), precision=_HI,
                               preferred_element_type=F32)


def _mm_nt(a, b):
    """a [m, k] @ b [n, k]^T with float32 accumulation, operands as given."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=F32)


def _eye(n: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _col(row):
    """[1, n] -> [n, 1] without a transpose (a masked lane reduction)."""
    n = row.shape[1]
    return jnp.sum(jnp.where(_eye(n), row, 0.0), axis=1, keepdims=True)


# ---------------------------------------------------------------------------
# Tile math, shared by the kernels and their twins
# ---------------------------------------------------------------------------

def inv_unit_lower(low):
    """(I + low)^-1 for a strictly lower triangular ``low`` [c, c], exact up
    to rounding, in ten matmuls and no slicing.  With ``low = d + r`` (``d``
    the part inside the diagonal ``block`` x ``block`` blocks, ``r`` the
    rest): ``x = (I + d)^-1 = (I - d)(I + d^2)(I + d^4)(I + d^8)`` since
    ``d^16 = 0``; then ``I + low = (I + d)(I + x r)`` and ``n = x r`` is
    strictly block lower with ``c / block = 4`` blocks a side, so
    ``(I + n)^-1 = (I - n)(I + n^2)``.  Powers stay inside 16 steps, which
    keeps their entries small where the plain product form over 64 steps
    lets them grow to the binomials of 62."""
    c, block = low.shape[0], 16
    assert c % block == 0 and c // block <= 4, c
    rows = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eye = (rows == cols).astype(F32)
    same = (rows // block) == (cols // block)
    d = jnp.where(same, low, 0.0)
    r = low - d
    x = eye - d
    p = d
    for _ in range(3):                       # d^2, d^4, d^8
        p = _mm(p, p)
        x = x + _mm(x, p)
    n = _mm(x, r)
    return _mm(_mm(eye - n, eye + _mm(n, n)), x)


def _chunk_tile(q, k, v, gc_row, b_row, h):
    """One chunk of one head.  q, k [c, dk] (q already scaled), v [c, dv],
    gc_row [1, c] the cumulative log decay inside the chunk, b_row [1, c]
    beta, h [dk, dv] float32 the state before the chunk.  Returns (o [c, dv]
    float32, state after)."""
    c = q.shape[0]
    gc_col, b_col = _col(gc_row), _col(b_row)
    rows = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # decay from step j to step i >= j; the clamp keeps the unused upper
    # triangle from overflowing
    decay = jnp.exp(jnp.minimum(gc_col - gc_row, 0.0))
    kk = _mm_nt(k, k) * decay
    t = inv_unit_lower(jnp.where(rows > cols, kk * b_col, 0.0))
    kf, vf, qf = k.astype(F32), v.astype(F32), q.astype(F32)
    w = _mm(t, kf * (b_col * jnp.exp(gc_col)))        # [c, dk]
    u = _mm(t, vf * b_col)                            # [c, dv]
    v_new = u - _mm(w, h)
    qk = jnp.where(rows >= cols, _mm_nt(q, k) * decay, 0.0)
    o = _mm(qf * jnp.exp(gc_col), h) + _mm(qk, v_new)
    g_last = gc_row[:, c - 1:c]                       # [1, 1]
    k_dec = kf * jnp.exp(g_last - gc_col)
    # [1, 1] -> [1, dv] -> [dk, dv]: one axis at a time, Mosaic has no
    # broadcast along lanes and sublanes at once
    keep = jnp.exp(jnp.broadcast_to(g_last, (1, h.shape[1])))
    return o, h * keep + _mm(k_dec.T, v_new)


def _step_lanes(h, q_cols, k_cols, v_row, alphas, betas):
    """One decode step on a tile of heads side by side along the lanes.  h
    [dk, p dv] float32; q_cols, k_cols, alphas, betas: a sequence the tile's
    ``p`` heads, a float32 column [dk, 1] and two scalars (or ``alpha`` a
    column: a decay a channel) a head; v_row [1, p dv] float32.  Each head's
    column and scalars are spread over its own lanes (a ``where`` on a lane
    iota, where the tile holds more than one head) and the step is the plain
    one, element for element: the sums run over sublanes, which no head
    shares.  Returns (o [1, p dv], h)."""
    width, heads = h.shape[1], len(q_cols)
    dv = width // heads

    def spread(per_head):
        out = per_head[-1]
        if heads > 1:
            rows = out.shape[0] if jnp.ndim(out) else 1     # column or scalar
            lane = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
            for j in reversed(range(heads - 1)):
                out = jnp.where(lane < (j + 1) * dv, per_head[j], out)
        return out

    k, q = spread(k_cols), spread(q_cols)
    h = h * spread(alphas)
    pred = jnp.sum(h * k, axis=0, keepdims=True)
    h = h + k * ((v_row - pred) * spread(betas))
    return jnp.sum(h * q, axis=0, keepdims=True), h


def _step_tile(h, q_row, k_row, v_row, alpha, beta):
    """One decode step of one head.  h [dk, dv] float32; q_row, k_row
    [1, dk]; v_row [1, dv]; alpha, beta scalars.  Returns (o [1, dv], h)."""
    return _step_lanes(h, [_col(q_row.astype(F32))], [_col(k_row.astype(F32))],
                       v_row.astype(F32), [alpha], [beta])


# ---------------------------------------------------------------------------
# The independent check: the equations, one token at a time
# ---------------------------------------------------------------------------

def gdn_recurrence(q, k, v, g, beta, initial_state=None):
    """q, k [B, T, H, dk] (q scaled), v [B, T, H, dv], g, beta [B, T, H] ->
    (o [B, T, H, dv] float32, state [B, H, dk, dv] float32)."""
    b, _, nh, dk = q.shape
    dv = v.shape[-1]
    h0 = (jnp.zeros((b, nh, dk, dv), F32) if initial_state is None
          else initial_state.astype(F32))

    def step(h, xs):
        q_t, k_t, v_t, g_t, b_t = xs                  # [B, H, ...]
        h = h * jnp.exp(g_t)[..., None, None]
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
    inside each chunk.  Returns (q, k, v, gc [B, H, N, c], beta the same)."""
    b, t, nh, _ = q.shape
    if lengths is not None:
        live = (jnp.arange(t)[None, :] < lengths[:, None])[..., None]
        g = jnp.where(live, g, 0.0)
        beta = jnp.where(live, beta, 0.0)
    pad = -t % CHUNK
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                    * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    n = (t + pad) // CHUNK
    q, k, v = (a.swapaxes(1, 2) for a in (q, k, v))               # [B,H,T,d]
    g = g.astype(F32).swapaxes(1, 2).reshape(b, nh, n, CHUNK)
    beta = beta.astype(F32).swapaxes(1, 2).reshape(b, nh, n, CHUNK)
    return q, k, v, jnp.cumsum(g, axis=-1), beta


def gdn_chunk_fwd_jnp(q, k, v, g, beta, lengths=None):
    """The twin of ``gdn_chunk_fwd``: the same tile math, ``vmap``ped over
    batch and heads and scanned over chunks.  Shapes as ``gdn_chunk_fwd``."""
    b, t, nh, dk = q.shape
    dv = v.shape[-1]
    q, k, v, gc, beta = _chunk_inputs(q, k, v, g, beta, lengths)
    n, chunk = gc.shape[2:]
    tile = jax.vmap(jax.vmap(_chunk_tile))

    def body(h, xs):
        qc, kc, vc, gcc, bc = xs
        o, h = tile(qc, kc, vc, gcc[:, :, None, :], bc[:, :, None, :], h)
        return h, o

    def chunks(a):                   # [B, H, n*c, d] -> [n, B, H, c, d]
        return jnp.moveaxis(a.reshape(b, nh, n, chunk, a.shape[-1]), 2, 0)

    h, o = jax.lax.scan(
        body, jnp.zeros((b, nh, dk, dv), F32),
        (chunks(q), chunks(k), chunks(v), jnp.moveaxis(gc, 2, 0),
         jnp.moveaxis(beta, 2, 0)))
    o = jnp.moveaxis(o, 0, 2).reshape(b, nh, n * chunk, dv)[:, :, :t]
    return o.swapaxes(1, 2).astype(v.dtype), h


def _chunk_kernel(q_ref, k_ref, v_ref, gc_ref, b_ref, o_ref, s_ref, h_ref):
    """Grid (batch, heads, chunks), chunks innermost and sequential: a
    head's state lives in ``h_ref`` (VMEM scratch) across a row's chunks."""
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    o, h = _chunk_tile(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0],
                       gc_ref[0, 0, pl.ds(ci, 1), :],
                       b_ref[0, 0, pl.ds(ci, 1), :], h_ref[...])
    o_ref[0, 0] = o.astype(o_ref.dtype)
    h_ref[...] = h

    @pl.when(ci == pl.num_programs(2) - 1)
    def _flush():
        s_ref[0, 0] = h_ref[...]


def _lanes(d: int) -> int:
    return -(-d // 128) * 128


def _head_group(nh: int, limit: int) -> int:
    """The largest divisor of ``nh`` up to ``limit``: heads a grid step."""
    return max(d for d in range(1, limit + 1) if nh % d == 0)


#: what the state's blocks of a grid step of the decode kernel may take of
#: VMEM, in and out and two buffers each (``step_block``): blocks of up to 4
#: MiB, ``ops/ssd.py``'s budget for a state streamed through such a kernel
#: (``ssd.STEP_STATE_VMEM``, PERF.md section 6, PR 55); one slot of the
#: hybrid's [15, 96, 384] float32, 2.1 MiB.  The kernel alone on the chip
#: (PERF.md section 6, PR 57; ``chiprun_out/pr57a/``, the script beside its
#: output), us a call over 25 slots and GB/s moved of the state's own 221 MB:
#: the parent's 6 plain heads of one slot a step (0.56 MB of which a quarter
#: padding) 225.2 us, 491 GB/s; **one packed slot 168.2, 658**; two 166.9;
#: three 166.2; five 164.8, 671; a plain copy of one slot's blocks 168.6, of
#: two 167.4: the body is hidden behind the copies at every plan, and what a
#: larger block buys (2% at five slots, 44 MB of VMEM) is the fewer grid steps
STEP_STATE_VMEM = 16 << 20
#: the decode kernel's VMEM, of the chip's 128 MiB: the state's blocks, q, k,
#: v and o of the block's slots twice, and the body's tiles
STEP_VMEM_LIMIT = 32 << 20
#: packed tiles the decode kernel's body unrolls, at most, as the float32
#: bytes they hold: a chunk of a slot's tiles, the rest a rolled loop (5 of
#: the hybrid's 15; 1, 3, 5 or 15 unrolled read 169.5 / 168.2 / 168.2 / 169.6
#: us a call there)
STEP_UNROLL_BYTES = 768 << 10


def _chunk_fwd_pallas(q, k, v, g, beta, lengths, interpret: bool):
    b, t, nh, dk = q.shape
    dv = v.shape[-1]
    q, k, v, gc, beta = _chunk_inputs(q, k, v, g, beta, lengths)
    n, chunk = gc.shape[2:]
    dkp, dvp = _lanes(dk), _lanes(dv)
    q, k = (jnp.pad(a, ((0, 0),) * 3 + ((0, dkp - dk),)) for a in (q, k))
    v = jnp.pad(v, ((0, 0),) * 3 + ((0, dvp - dv),))
    row = lambda bi, hi, ci: (bi, hi, ci, 0)    # noqa: E731
    whole = lambda bi, hi, ci: (bi, hi, 0, 0)   # noqa: E731
    o, s = pl.pallas_call(
        _chunk_kernel,
        grid=(b, nh, n),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, dkp), row),
            pl.BlockSpec((1, 1, chunk, dkp), row),
            pl.BlockSpec((1, 1, chunk, dvp), row),
            pl.BlockSpec((1, 1, n, chunk), whole),
            pl.BlockSpec((1, 1, n, chunk), whole),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, dvp), row),
            pl.BlockSpec((1, 1, dkp, dvp), whole),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nh, n * chunk, dvp), v.dtype),
            jax.ShapeDtypeStruct((b, nh, dkp, dvp), F32),
        ],
        scratch_shapes=[pltpu.VMEM((dkp, dvp), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_CHUNK_FWD,
    )(q, k, v, gc, beta)
    return o[:, :, :t, :dv].swapaxes(1, 2), s[:, :, :dk, :dv]


def gdn_chunk_fwd(q, k, v, g, beta, lengths=None,
                  use_kernel: Optional[bool] = None,
                  interpret: Optional[bool] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Gated delta rule over whole sequences from a zero state.

    q, k: [B, T, H, dk] (q already scaled, both already normalised);
    v: [B, T, H, dv]; g (log decay, <= 0), beta: [B, T, H]; ``lengths`` [B]:
    positions at or beyond a row's length do not touch its state.  Returns
    (o [B, T, H, dv] in v's dtype, state [B, H, dk, dv] float32 as of each
    row's length).  ``T`` may be any length; a chunk is ``CHUNK`` steps.

    ``use_kernel=None`` takes the Pallas kernel on a TPU and the twin
    elsewhere; ``interpret=True`` runs the kernel interpreted (tests)."""
    if use_kernel is None:
        use_kernel = bool(interpret) or jax.default_backend() == "tpu"
    if not use_kernel:
        return gdn_chunk_fwd_jnp(q, k, v, g, beta, lengths)
    interpret = resolve_interpret(interpret, "gdn_chunk")
    return _chunk_fwd_pallas(q, k, v, g, beta, lengths, interpret)


# ---------------------------------------------------------------------------
# Decode: one step, in place on the stacked state
# ---------------------------------------------------------------------------

def packed_heads(nh: int, dv: int) -> int:
    """Heads of ``dv`` value lanes that the stacked state holds side by side
    along the lanes: the fewest that fill whole tiles of 128 lanes, where
    that many divide the ``nh`` heads, else one (the plain layout, whose
    lanes the chip pads).  A function of the state's shape and of nothing
    else."""
    p = 128 // math.gcd(dv, 128)
    return p if nh % p == 0 else 1


def packed_shape(nh: int, dk: int, dv: int) -> Tuple[int, int, int]:
    """[heads, key_dim, value_dim] of a slot's state as the stack holds it."""
    p = packed_heads(nh, dv)
    return nh // p, dk, p * dv


def pack_state(h):
    """[..., H, dk, dv] -> [..., H / p, dk, p * dv]: head ``i p + j``'s
    columns in lanes ``j dv .. (j + 1) dv`` of tile ``i``."""
    *lead, nh, dk, dv = h.shape
    p = packed_heads(nh, dv)
    if p == 1:
        return h
    h = h.reshape(*lead, nh // p, p, dk, dv).swapaxes(-3, -2)
    return h.reshape(*lead, *packed_shape(nh, dk, dv))


def unpack_state(h, nh: int):
    """``pack_state``'s inverse for a state of ``nh`` heads: [..., H / p, dk,
    p * dv] -> [..., H, dk, dv]."""
    *lead, tiles, dk, width = h.shape
    p = nh // tiles
    if p == 1:
        return h
    h = h.reshape(*lead, tiles, dk, p, width // p).swapaxes(-3, -2)
    return h.reshape(*lead, nh, dk, width // p)


def gdn_recurrent_step_jnp(state, layer, q, k, v, g, beta):
    """The twin of ``gdn_recurrent_step``; shapes as there: the layer's
    state unpacked, stepped a head at a time and packed again."""
    nh = q.shape[1]
    h = unpack_state(
        jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False), nh)
    tile = jax.vmap(jax.vmap(_step_tile))
    o, h = tile(h, q[:, :, None], k[:, :, None], v[:, :, None],
                jnp.exp(g.astype(F32)), beta.astype(F32))
    state = jax.lax.dynamic_update_index_in_dim(state, pack_state(h), layer,
                                                0)
    return state, o[:, :, 0].astype(v.dtype)


def _tile_bytes(dk: int, width: int) -> int:
    """A packed tile's float32 [dk, width] as VMEM holds it, in whole (8,
    128) tiles."""
    return (-(-dk // 8) * 8) * _lanes(width) * 4


def step_block(slots: int, tiles: int, dk: int, width: int):
    """(slots, packed tiles) of the block of the state ``[layers, slots,
    tiles, dk, width]`` that a grid step of ``gdn_recurrent_step`` moves:
    whole slots (all tiles of a slot lie together in the stack: one copy),
    the most whose block in and out, two buffers each, fits
    ``STEP_STATE_VMEM``; where one slot does not fit, of one slot the most
    tiles that fit and divide it.  A function of what the kernel sees in its
    operands and of nothing else."""
    fit = STEP_STATE_VMEM // (4 * _tile_bytes(dk, width))   # four buffers
    if fit >= tiles:
        return min(slots, fit // tiles), tiles
    if fit < 1:
        raise ValueError(
            f"gdn_recurrent_step: four buffers of one [{dk}, {width}] "
            f"float32 tile do not fit {STEP_STATE_VMEM >> 20} MiB of VMEM")
    return 1, _head_group(tiles, fit)


def _step_kernel(layer_ref, a_ref, b_ref, s_in, q_ref, k_ref, v_ref,
                 s_out, o_ref, *, slots: int, p: int):
    """Grid (slot blocks, tile blocks).  s_in, s_out [1, sb, tb, dk, p dv];
    q_ref, k_ref [sb, tb / tu, tu p, dk], v_ref, o_ref [sb, tb / tu, tu, p
    dv]: the block's tiles in chunks of ``tu``, which the body unrolls.  The
    walk is a rolled loop over the slots the block holds (the last block may
    hold fewer than ``sb``) and their chunks."""
    del layer_ref                     # used by the index maps only
    sb, tb = s_in.shape[1:3]
    chunks, tu = v_ref.shape[1:3]
    first, tile0 = pl.program_id(0) * sb, pl.program_id(1) * tb

    def chunk(at, carry):
        # everything that is traced is once a chunk; a tile adds one index
        si, c = (at, 0) if chunks == 1 else (at // chunks, at % chunks)
        slot, base = first + si, (tile0 + c * tu) * p
        for i in range(tu):
            mine = range(i * p, (i + 1) * p)
            o, h = _step_lanes(
                s_in[0, si, c * tu + i],
                [_col(q_ref[si, c, r:r + 1].astype(F32)) for r in mine],
                [_col(k_ref[si, c, r:r + 1].astype(F32)) for r in mine],
                v_ref[si, c, i:i + 1].astype(F32),
                [a_ref[slot, base + r] for r in mine],
                [b_ref[slot, base + r] for r in mine])
            s_out[0, si, c * tu + i] = h
            o_ref[si, c, i:i + 1] = o.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, jnp.minimum(sb, slots - first) * chunks, chunk, 0)


def _recurrent_step_pallas(state, layer, q, k, v, g, beta, interpret: bool):
    _, slots, tiles, dk, width = state.shape
    nh = q.shape[1]
    p = nh // tiles
    sb, tb = step_block(slots, tiles, dk, width)
    tu = _head_group(tb, max(STEP_UNROLL_BYTES // _tile_bytes(dk, width), 1))
    q, k = (a.reshape(slots, tiles // tu, tu * p, dk) for a in (q, k))
    v = v.reshape(slots, tiles // tu, tu, width)
    small = lambda si, ti, lyr: (si, ti, 0, 0)          # noqa: E731
    big = lambda si, ti, lyr: (lyr[0], si, ti, 0, 0)    # noqa: E731
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    state, o = pl.pallas_call(
        functools.partial(_step_kernel, slots=slots, p=p),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(slots, sb), tiles // tb),
            in_specs=[
                smem, smem,
                pl.BlockSpec((1, sb, tb, dk, width), big),
                pl.BlockSpec((sb, tb // tu, tu * p, dk), small),
                pl.BlockSpec((sb, tb // tu, tu * p, dk), small),
                pl.BlockSpec((sb, tb // tu, tu, width), small),
            ],
            out_specs=[
                pl.BlockSpec((1, sb, tb, dk, width), big),
                pl.BlockSpec((sb, tb // tu, tu, width), small),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        # operands count from the scalar-prefetch argument: 3 is the state
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=STEP_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_RECURRENT_STEP,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), jnp.exp(g.astype(F32)),
      beta.astype(F32), state, q, k, v)
    return state, o.reshape(slots, nh, width // p)


def gdn_recurrent_step(state, layer, q, k, v, g, beta,
                       use_kernel: Optional[bool] = None,
                       interpret: Optional[bool] = None):
    """One gated-delta-rule step for every slot of layer ``layer``.

    state: [layers, slots, H / p, dk, p * dv] float32, ``p`` heads a tile
    (``pack_state``), updated in place (donate it); layer: int32 scalar
    (traced or not); q, k: [slots, H, dk]; v: [slots, H, dv]; g, beta:
    [slots, H] (``g = 0, beta = 0`` leaves a slot's state as it was).
    Returns (state, o [slots, H, dv] in v's dtype).  Only the blocks of
    ``layer`` are read and written: the index maps take the layer from
    scalar prefetch, no slab leaves the stack."""
    if use_kernel is None:
        use_kernel = bool(interpret) or jax.default_backend() == "tpu"
    if not use_kernel:
        return gdn_recurrent_step_jnp(state, layer, q, k, v, g, beta)
    interpret = resolve_interpret(interpret, "gdn_step")
    return _recurrent_step_pallas(state, layer, q, k, v, g, beta, interpret)
