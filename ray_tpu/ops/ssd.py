"""State-space dual (Mamba-2's SSD): the recurrent-state mixer of a
state-space layer, as two Pallas TPU kernels and their plain twins.

Per head ``h`` of width ``P``, with a state ``S`` of shape [P, N] held in
float32, a step ``dt_t > 0``, a decay ``a_t = exp(-exp(A_log_h) dt_t)`` in
(0, 1] (one a head) and keys ``B_t`` and queries ``C_t`` in ``R^N`` that the
``H / G`` heads of a group share (head ``h`` reads group ``h // (H / G)``)::

    S_t = a_t S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D_h x_t

No delta-rule correction and no normalised keys: the sibling of
``ops/gated_delta.py`` without its triangular solve.

* ``ssd_chunk_fwd`` (prefill) runs a whole sequence in chunks of ``CHUNK``
  steps.  With ``G_i`` the running sum of ``-exp(A_log) dt`` inside a chunk,
  ``y_i = sum_{j <= i} exp(G_i - G_j) (C_i . B_j) dt_j x_j + exp(G_i) S C_i``
  and the state moves a chunk at a time: five matmuls a head a chunk, ``C
  B^T`` once for the heads of a grid step (a group, or where a group is
  wider than ``CHUNK_HEADS_A_STEP`` a block of its heads: one group of 64
  heads is four steps that each read the group's B and C), and only the
  chunk-to-chunk state is sequential.  The running sums are taken inside
  the kernel (one product with a triangle of ones for a step's heads), not
  by ``jnp.cumsum``
  before it: XLA may sum a window in another order from one program to the
  next, and the last bit of ``G`` then goes with the program a row was
  compiled in (one of two causes found for two compilations of one prefill
  choosing other experts at near-ties: PERF.md section 6, PR 46).  **Only
  exponentials of non-positive numbers**: ``G`` falls along a chunk, so
  ``G_i - G_j <= 0`` for ``j <= i`` (never ``exp(G_i) exp(-G_j)``, which
  overflows where a head forgets fast).  A position with ``dt = 0`` leaves
  the state untouched, which is how right padding is made harmless: the
  state returned is each row's as of its true length.
* ``ssd_recurrent_step`` (decode) applies one step to every slot of one
  layer of a stacked state ``[layers, slots, heads, P, N]``, in place, taking
  the layer index itself (scalar prefetch), so that no layer slab is ever
  sliced out of the stack.  A grid step moves a block of the state planned
  from its shape (``step_block``): whole slots of the layer, as many as fit
  ``STEP_STATE_VMEM`` in and out and double-buffered, the last block
  partial where the slots are no whole blocks; where one slot alone does
  not fit, the widest block of its heads that is whole groups or divides a
  group.  x, B, C and y follow the state's block, and the body walks it in
  a rolled loop over slots and chunks of ``STEP_UNROLL`` heads at most.

Each kernel's math is one function on two-dimensional tiles (``_chunk_tile``,
``_step_tile``) that the kernel body calls on what it loaded and the twin
``vmap``s over batch, groups and heads: the twin is the CPU path and what the
kernels are tested against.  The independent check of both is
``ssd_recurrence``, the equations above one token at a time.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import resolve_interpret
from .gated_delta import F32, _col, _eye, _mm, _mm_nt

#: kernel names as a device trace shows them (``<name> [pallas]``); pinned by
#: tests/test_trace_names.py, read by the benchmark's ssd_* readers
KERNEL_SSD_CHUNK_FWD = "ssd_chunk_fwd"
KERNEL_SSD_RECURRENT_STEP = "ssd_recurrent_step"

#: steps a chunk of the prefill form (the published ``chunk_size``; any
#: chunk is the same arithmetic); one value, so a constant: the benchmark's
#: counts of the kernel assume it
CHUNK = 128

#: heads a grid step, at most, of the prefill kernel (one group, or a block
#: of its heads): ``_head_block``
CHUNK_HEADS_A_STEP = 16
#: heads the decode kernel's body unrolls, at most: a chunk of its block
#: (whole groups, or a block of one group's heads), ``_unrolled_heads``
STEP_UNROLL = 16
#: what the state's blocks of a grid step of the decode kernel may take of
#: VMEM, in and out and two buffers each (``step_block``): blocks of 4 MiB,
#: two slots' [64, 64, 128] float32 at both cells' shapes.  The kernel alone
#: on the chip (``PERF.md`` section 6, PR 55; ``chiprun_out/pr55a/``, the
#: script beside its output), us a call over 65 slots and GB/s moved of the
#: HBM's 819, Granite's one group of 64 heads / Nemotron's 8 groups of 8:
#: PR 53's 16 heads of one slot (0.5 MiB) 472 / 479 us, 577 / 569 GB/s; 32
#: heads 423 / 424; **one slot 419.7 / 420.2, two slots 420.0 / 420.3 (649
#: GB/s)**; three 420.8 / 421.8; four 426.7 / 427.7; five 433.6 / 434.4;
#: eight 439.4 / 440.0; thirteen 458.3 / 459.1.  A block of any size that is
#: only copied moves at 413-417 us (655-660 GB/s, read and write mixed), and
#: the arithmetic with nothing copied takes 352-358 us: small blocks leave a
#: step's copies and its arithmetic one after the other, large ones leave the
#: call's first fetch and last write with nothing beside them.
STEP_STATE_VMEM = 16 << 20
#: the decode kernel's VMEM, of the chip's 128 MiB: the state's blocks, x, B,
#: C and y of the block's slots twice, and the body's tiles
STEP_VMEM_LIMIT = 32 << 20


def _head_block(per: int, limit: int) -> int:
    """Heads a grid step of a group of ``per``: the group where it is no
    wider than ``limit``, else the most up to ``limit`` that divide it, so
    that a block of heads lies in one group and reads that group's B and
    C."""
    if per <= limit:
        return per
    return max(h for h in range(1, limit + 1) if per % h == 0)


def _mm_nt32(a, b):
    """a [m, k] @ b [n, k]^T in float32, every bit of the operands used."""
    return jax.lax.dot_general(a.astype(F32), b.astype(F32),
                               (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=F32)


def _row(col):
    """[n, 1] -> [1, n] without a transpose (a masked sublane reduction)."""
    n = col.shape[0]
    return jnp.sum(jnp.where(_eye(n), col, 0.0), axis=0, keepdims=True)


# ---------------------------------------------------------------------------
# Tile math, shared by the kernels and their twins
# ---------------------------------------------------------------------------

def _running_sums(gl_t):
    """gl_t [heads, n], a chunk's log decays a step -> their running sums
    [heads, n], each step's own included: one product with a triangle of
    ones, float32 with every bit used."""
    n = gl_t.shape[1]
    upto = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            <= jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return _mm(gl_t, upto.astype(F32))


def _chunk_tile(x, cb, c, b, g_row, dt_col, d_row, s):
    """One chunk of one head.  x [n, P]; cb [n, n] float32, ``C B^T`` of the
    head's group; c, b [n, N]; g_row [1, n] the running sum of the log decay
    inside the chunk; dt_col [n, 1]; d_row [1, P] the skip ``D``; s [P, N]
    float32 the state before the chunk.  Returns (y [n, P] float32, state
    after)."""
    n = x.shape[0]
    g_col = _col(g_row)
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    # decay from step j to step i >= j; the clamp keeps the unused upper
    # triangle from overflowing
    decay = jnp.exp(jnp.minimum(g_col - g_row, 0.0))
    xf = x.astype(F32)
    xdt = xf * dt_col
    y = (_mm(jnp.where(rows >= cols, cb * decay, 0.0), xdt)
         + jnp.exp(g_col) * _mm_nt32(c, s) + xf * d_row)
    g_last = g_col[n - 1:n]                                       # [1, 1]
    # [1, 1] -> [1, N] -> [P, N]: one axis at a time, Mosaic has no
    # broadcast along lanes and sublanes at once
    keep = jnp.exp(jnp.broadcast_to(g_last, (1, s.shape[1])))
    return y, s * keep + _mm((xdt * jnp.exp(g_last - g_col)).T, b)


def _step_tile(s, x_row, b_row, c_row, a, dt, d):
    """One decode step of one head.  s [P, N] float32; x_row [1, P]; b_row,
    c_row [1, N]; a, dt, d scalars.  Returns (y [1, P] float32, s)."""
    xf = x_row.astype(F32)
    s = s * a + _col(xf * dt) * b_row.astype(F32)
    y = jnp.sum(s * c_row.astype(F32), axis=1, keepdims=True)     # [P, 1]
    return _row(y) + xf * d, s


# ---------------------------------------------------------------------------
# The independent check: the equations, one token at a time
# ---------------------------------------------------------------------------

def ssd_recurrence(x, dt, a_log, b, c, d, initial_state=None):
    """x [B, T, H, P], dt [B, T, H], a_log, d [H], b, c [B, T, G, N] ->
    (y [B, T, H, P] float32, state [B, H, P, N] float32)."""
    bsz, _, nh, p = x.shape
    per = nh // b.shape[2]
    s0 = (jnp.zeros((bsz, nh, p, b.shape[-1]), F32) if initial_state is None
          else initial_state.astype(F32))
    rate = -jnp.exp(a_log.astype(F32))
    hi = jax.lax.Precision.HIGHEST

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs                       # [B, H, ...]
        b_t, c_t = (jnp.repeat(a, per, axis=1) for a in (b_t, c_t))
        s = (s * jnp.exp(rate * dt_t)[..., None, None]
             + (x_t * dt_t[..., None])[..., :, None] * b_t[..., None, :])
        return s, (jnp.einsum("bhpn,bhn->bhp", s, c_t, precision=hi)
                   + d.astype(F32)[:, None] * x_t)

    xs = tuple(a.astype(F32).swapaxes(0, 1) for a in (x, dt, b, c))
    s, y = jax.lax.scan(step, s0, xs)
    return y.swapaxes(0, 1), s


# ---------------------------------------------------------------------------
# Prefill: chunked forward
# ---------------------------------------------------------------------------

def _chunk_inputs(x, dt, a_log, b, c, d, lengths, hb=None):
    """Mask positions at or beyond ``lengths`` (dt 0), pad the time axis to
    whole chunks and lay the channels side by side (the heads of a group
    are neighbours).  ``hb``: heads a block, a divisor of a group's (None:
    the group's).  Returns (x [B, T, H P], b, c [B, T, G N], the log
    decay a step [B, H / hb, hb, T], dt [B, H / hb, T, hb], d [1, H P])."""
    bsz, t, nh, p = x.shape
    hb = hb or nh // b.shape[2]
    dt = dt.astype(F32)
    if lengths is not None:
        dt = jnp.where((jnp.arange(t)[None, :] < lengths[:, None])[..., None],
                       dt, 0.0)
    pad = -t % CHUNK
    if pad:
        x, dt, b, c = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                               * (a.ndim - 2)) for a in (x, dt, b, c))
    t += pad
    by_block = lambda a: a.reshape(                               # noqa: E731
        bsz, t, nh // hb, hb).swapaxes(1, 2)
    return (x.reshape(bsz, t, nh * p), b.reshape(bsz, t, -1),
            c.reshape(bsz, t, -1),
            by_block(-jnp.exp(a_log.astype(F32)) * dt).swapaxes(2, 3),
            by_block(dt), jnp.repeat(d.astype(F32), p)[None])


def ssd_chunk_fwd_jnp(x, dt, a_log, b, c, d, lengths=None):
    """The twin of ``ssd_chunk_fwd``: the same tile math, ``vmap``ped over
    batch, groups and a group's heads and scanned over chunks."""
    bsz, t, nh, p = x.shape
    groups, n = b.shape[2:]
    per = nh // groups
    x2, b2, c2, gl_t, dts, d_row = _chunk_inputs(x, dt, a_log, b, c, d,
                                                 lengths)
    chunks = x2.shape[1] // CHUNK

    def group(x, c, b, gl_t, dt_col, d_row, s):
        heads = jax.vmap(_chunk_tile,
                         in_axes=(0, None, None, None, 0, 0, 0, 0))
        return heads(x, _mm_nt(c, b), c, b, _running_sums(gl_t)[:, None],
                     dt_col, d_row, s)

    tile = jax.vmap(jax.vmap(group), in_axes=(0,) * 5 + (None, 0))

    def body(s, xs):
        y, s = tile(*xs, d_row.reshape(groups, per, 1, p), s)
        return s, y

    # time in chunks to the front: [chunks, B, G, (H / G,) CHUNK, ...]
    heads = lambda a, w: jnp.moveaxis(a.reshape(                  # noqa: E731
        bsz, chunks, CHUNK, groups, per, w), (1, 2), (0, 4))
    shared = lambda a: jnp.moveaxis(a.reshape(                    # noqa: E731
        bsz, chunks, CHUNK, groups, n), (1, 2), (0, 3))
    s, y = jax.lax.scan(
        body, jnp.zeros((bsz, groups, per, p, n), F32),
        (heads(x2, p), shared(c2), shared(b2),
         jnp.moveaxis(gl_t.reshape(bsz, groups, per, chunks, CHUNK), 3, 0),
         heads(dts.swapaxes(1, 2), 1)))
    # [chunks, B, G, H / G, CHUNK, P] -> [B, T, H, P]
    y = jnp.moveaxis(y, (0, 4), (1, 2)).reshape(bsz, -1, nh, p)[:, :t]
    return y.astype(x.dtype), s.reshape(bsz, nh, p, n)


def _chunk_kernel(x_ref, b_ref, c_ref, gl_ref, dt_ref, d_ref, y_ref, s_out,
                  s_ref, *, heads: int, p: int):
    """Grid (batch, head blocks, chunks), chunks innermost and sequential:
    the states of a block's ``heads`` heads (one group's, or a part of one's)
    live in ``s_ref`` (VMEM scratch) across a row's chunks; ``b_ref`` and
    ``c_ref`` are the block's group's."""
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    b, c = b_ref[0], c_ref[0]
    cb = _mm_nt(c, b)                       # once for the heads of the block
    g, dt = _running_sums(gl_ref[0, 0]), dt_ref[0, 0]
    for i in range(heads):
        cols = slice(i * p, (i + 1) * p)
        y, s = _chunk_tile(x_ref[0, :, cols], cb, c, b, g[i:i + 1, :],
                           dt[:, i:i + 1], d_ref[:, cols], s_ref[i])
        y_ref[0, :, cols] = y.astype(y_ref.dtype)
        s_ref[i] = s

    @pl.when(ci == pl.num_programs(2) - 1)
    def _flush():
        s_out[0] = s_ref[...]


def _chunk_fwd_pallas(x, dt, a_log, b, c, d, lengths, interpret: bool):
    bsz, t, nh, p = x.shape
    groups, n = b.shape[2:]
    per = nh // groups
    hb = _head_block(per, CHUNK_HEADS_A_STEP)
    blocks = per // hb              # of a group; 1: a block is its group
    x2, b2, c2, gl_t, dts, d_row = _chunk_inputs(x, dt, a_log, b, c, d,
                                                 lengths, hb)
    chunks = x2.shape[1] // CHUNK
    wide = lambda bi, hi, ci: (bi, ci, hi)          # noqa: E731
    shared = wide if blocks == 1 else (             # the block's group's
        lambda bi, hi, ci: (bi, ci, hi // blocks))
    y, s = pl.pallas_call(
        functools.partial(_chunk_kernel, heads=hb, p=p),
        grid=(bsz, nh // hb, chunks),
        in_specs=[
            pl.BlockSpec((1, CHUNK, hb * p), wide),
            pl.BlockSpec((1, CHUNK, n), shared),
            pl.BlockSpec((1, CHUNK, n), shared),
            pl.BlockSpec((1, 1, hb, CHUNK),
                         lambda bi, hi, ci: (bi, hi, 0, ci)),
            pl.BlockSpec((1, 1, CHUNK, hb),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, hb * p), lambda bi, hi, ci: (0, hi)),
        ],
        out_specs=[
            pl.BlockSpec((1, CHUNK, hb * p), wide),
            pl.BlockSpec((1, hb, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2.shape, x.dtype),
            jax.ShapeDtypeStruct((bsz, nh, p, n), F32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, p, n), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_SSD_CHUNK_FWD,
    )(x2, b2, c2, gl_t, dts, d_row)
    return y[:, :t].reshape(bsz, t, nh, p), s


def ssd_chunk_fwd(x, dt, a_log, b, c, d, lengths=None,
                  use_kernel: Optional[bool] = None,
                  interpret: Optional[bool] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The state-space recurrence over whole sequences from a zero state.

    x: [B, T, H, P]; dt: [B, T, H] float32, the step after its softplus;
    a_log, d: [H]; b, c: [B, T, G, N], ``H / G`` heads a group; ``lengths``
    [B]: positions at or beyond a row's length do not touch its state.
    Returns (y [B, T, H, P] in x's dtype, state [B, H, P, N] float32 as of
    each row's length).  ``T`` may be any length; a chunk is ``CHUNK`` steps.

    ``use_kernel=None`` takes the Pallas kernel on a TPU and the twin
    elsewhere; ``interpret=True`` runs the kernel interpreted (tests)."""
    if use_kernel is None:
        use_kernel = bool(interpret) or jax.default_backend() == "tpu"
    if not use_kernel:
        return ssd_chunk_fwd_jnp(x, dt, a_log, b, c, d, lengths)
    interpret = resolve_interpret(interpret, "ssd_chunk")
    return _chunk_fwd_pallas(x, dt, a_log, b, c, d, lengths, interpret)


# ---------------------------------------------------------------------------
# Decode: one step, in place on the stacked state
# ---------------------------------------------------------------------------

def _decay(dt, a_log):
    """dt [slots, H], a_log [H] -> (a = exp(-exp(A_log) dt), dt), float32."""
    dt = dt.astype(F32)
    return jnp.exp(-jnp.exp(a_log.astype(F32)) * dt), dt


def ssd_recurrent_step_jnp(state, layer, x, dt, a_log, b, c, d):
    """The twin of ``ssd_recurrent_step``; shapes as there."""
    s = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    per = x.shape[1] // b.shape[1]
    a, dt = _decay(dt, a_log)
    tile = jax.vmap(jax.vmap(_step_tile, in_axes=(0,) * 6 + (0,)),
                    in_axes=(0,) * 6 + (None,))
    y, s = tile(s, x[:, :, None], jnp.repeat(b, per, axis=1)[:, :, None],
                jnp.repeat(c, per, axis=1)[:, :, None], a, dt, d.astype(F32))
    state = jax.lax.dynamic_update_index_in_dim(state, s, layer, 0)
    return state, y[:, :, 0].astype(x.dtype)


def _step_kernel(layer_ref, a_ref, dt_ref, d_ref, s_in, x_ref, b_ref, c_ref,
                 s_out, y_ref, *, slots: int, hu: int, per: int):
    """Grid (slot blocks, head blocks).  s_in, s_out [1, sb, hb, P, N]; x_ref,
    y_ref [sb, hb / hu, hu, P]: the block's heads in chunks of ``hu``, which
    the body unrolls; b_ref, c_ref [sb, entries, rows, N]: an entry serves
    ``max(hu, per)`` heads, a chunk's groups or the group a chunk lies in.
    The walk is a rolled loop over the slots the block holds (the last block
    may hold fewer than ``sb``) and their chunks."""
    del layer_ref                     # used by the index maps only
    sb, hb = s_in.shape[1:3]
    chunks, shared = hb // hu, max(per // hu, 1)    # chunks an entry serves
    first, head0 = pl.program_id(0) * sb, pl.program_id(1) * hb

    def chunk(at, carry):
        # everything that is traced is once a chunk; a head adds one index
        si, g = (at, 0) if chunks == 1 else (at // chunks, at % chunks)
        slot, base = first + si, head0 + g * hu
        s_new = s_out.at[0, si, pl.ds(g * hu, hu)]
        s_old = s_in.at[0, si, pl.ds(g * hu, hu)]
        entry = g if shared == 1 else g // shared
        for i in range(hu):
            hd, row = base + i, (si, entry, slice(i // per, i // per + 1))
            y, s = _step_tile(s_old[i], x_ref[si, g, i:i + 1], b_ref[row],
                              c_ref[row], a_ref[slot, hd], dt_ref[slot, hd],
                              d_ref[hd])
            s_new[i] = s
            y_ref[si, g, i:i + 1] = y.astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, jnp.minimum(sb, slots - first) * chunks, chunk, 0)


def _head_bytes(p: int, n: int) -> int:
    """A head's float32 state [P, N] as VMEM holds it, in whole (8, 128)
    tiles."""
    return (-(-p // 8) * 8) * (-(-n // 128) * 128) * 4


def step_block(slots: int, nh: int, per: int, p: int, n: int):
    """(slots, heads) of the block of the state ``[layers, slots, H, P, N]``
    that a grid step of ``ssd_recurrent_step`` moves, ``per`` heads a group:
    whole slots (all heads of a slot lie together in the stack: one copy),
    the most whose block in and out, two buffers each, fits
    ``STEP_STATE_VMEM``; where one slot does not fit, of one slot the most
    whole groups that fit and divide the heads, or of a group wider than
    that the widest block that divides it (``_head_block``: a block of heads
    reads one group's B and C).  A function of what the kernel sees in its
    operands and of nothing else."""
    fit = STEP_STATE_VMEM // (4 * _head_bytes(p, n))    # heads, four buffers
    if fit >= nh:
        return min(slots, fit // nh), nh
    if fit < 1:
        raise ValueError(
            f"ssd_recurrent_step: four buffers of one head's [{p}, {n}] "
            f"float32 state do not fit {STEP_STATE_VMEM >> 20} MiB of VMEM")
    if per > fit:
        return 1, _head_block(per, fit)
    return 1, max(h for h in range(per, fit + 1, per) if nh % h == 0)


def _unrolled_heads(hb: int, per: int) -> int:
    """Heads of a chunk of a block of ``hb``, which the body unrolls: the
    most up to ``STEP_UNROLL`` that divide the block and are whole groups or
    divide a group."""
    return max(h for h in range(1, min(hb, STEP_UNROLL) + 1)
               if hb % h == 0 and (h % per == 0 or per % h == 0))


def _recurrent_step_pallas(state, layer, x, dt, a_log, b, c, d,
                           interpret: bool):
    _, slots, nh, p, n = state.shape
    groups = b.shape[1]
    per = nh // groups
    sb, hb = step_block(slots, nh, per, p, n)
    hu = _unrolled_heads(hb, per)
    wide = max(hu, per)             # heads an entry of B and C serves:
    gs = max(hu // per, 1)          # the ``gs`` groups of a chunk, or the
    entries = max(hb // wide, 1)    # group of ``wide / hu`` chunks
    x = x.reshape(slots, nh // hu, hu, p)
    b, c = (a.reshape(slots, groups // gs, gs, n) for a in (b, c))
    small = lambda si, hi, lyr: (si, hi, 0, 0)          # noqa: E731
    shared = small if hb >= wide else (                 # the block's group's
        lambda si, hi, lyr: (si, hi // (wide // hb), 0, 0))
    big = lambda si, hi, lyr: (lyr[0], si, hi, 0, 0)    # noqa: E731
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    state, y = pl.pallas_call(
        functools.partial(_step_kernel, slots=slots, hu=hu, per=per),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(slots, sb), nh // hb),
            in_specs=[
                smem, smem, smem,
                pl.BlockSpec((1, sb, hb, p, n), big),
                pl.BlockSpec((sb, hb // hu, hu, p), small),
                pl.BlockSpec((sb, entries, gs, n), shared),
                pl.BlockSpec((sb, entries, gs, n), shared),
            ],
            out_specs=[
                pl.BlockSpec((1, sb, hb, p, n), big),
                pl.BlockSpec((sb, hb // hu, hu, p), small),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct(x.shape, x.dtype),
        ],
        # operands count from the scalar-prefetch argument: 4 is the state
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=STEP_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_SSD_RECURRENT_STEP,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), *_decay(dt, a_log),
      d.astype(F32), state, x, b, c)
    return state, y.reshape(slots, nh, p)


def ssd_recurrent_step(state, layer, x, dt, a_log, b, c, d,
                       use_kernel: Optional[bool] = None,
                       interpret: Optional[bool] = None):
    """One state-space step for every slot of layer ``layer``.

    state: [layers, slots, H, P, N] float32, updated in place (donate it);
    layer: int32 scalar (traced or not); x: [slots, H, P]; dt: [slots, H]
    float32, the step after its softplus (``dt = 0`` leaves a slot's state
    as it was: an idle slot); a_log, d: [H]; b, c: [slots, G, N].  Returns
    (state, y [slots, H, P] in x's dtype).  Only the blocks of ``layer`` are
    read and written: the index maps take the layer from scalar prefetch, no
    slab leaves the stack."""
    if use_kernel is None:
        use_kernel = bool(interpret) or jax.default_backend() == "tpu"
    if not use_kernel:
        return ssd_recurrent_step_jnp(state, layer, x, dt, a_log, b, c, d)
    interpret = resolve_interpret(interpret, "ssd_step")
    return _recurrent_step_pallas(state, layer, x, dt, a_log, b, c, d,
                                  interpret)
