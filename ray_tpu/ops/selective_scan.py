"""Mamba-1's selective scan: the recurrent-state mixer of an "ssm1" layer, as
two Pallas TPU kernels and their plain twin.

Per channel ``c`` of ``C`` inner channels and state column ``n`` of ``N``,
with a step ``dt_t[c] > 0``, a rate ``A[n, c] < 0`` (a decay a channel AND a
state column: ``ops/ssd.py``'s is one a head, and so a product of matrices;
this one is not), keys ``B_t`` and queries ``C_t`` in ``R^N`` shared by all
channels, and the state ``S`` [N, C] in float32::

    S_t[n, c] = exp(dt_t[c] A[n, c]) S_{t-1}[n, c] + dt_t[c] u_t[c] B_t[n]
    y_t[c]    = sum_n S_t[n, c] C_t[n]

(the skip ``D u_t`` and the gate are the caller's).  There is no matrix form:
every step is elementwise over ``[N, C]`` and a sum over ``N``, so both
kernels are the vector unit's, and the layout is chosen for it.  **The state
lies ``[N, C / 128, 128]``**: channels on the lanes and the sublanes, a state
column a leading index, so that no lane holds nothing (16 columns on the
lanes would fill an eighth of a tile: PERF.md, PR 57's lesson), the sum over
``n`` is plain adds of whole vectors, and ``B_t[n]``, ``C_t[n]`` are one
number a vector, which the caller hands over laid along 128 lanes.

* ``selective_scan_chunk_fwd`` (prefill) walks a row in chunks of ``CHUNK``
  positions: grid (rows, chunks, channel blocks), a block ``ROWS`` x 128
  channels whose ``N`` state vectors stay in registers for the chunk's
  positions (a rolled loop inside the kernel, no trip of the program a
  position); the running state is the kernel's own output block, resident
  for a row.  A position with ``dt = 0`` leaves the state as it was, which is
  how right padding is made harmless; chunks wholly past a row's length are
  not computed (their ``y`` reads 0).
* ``selective_scan_step`` (decode) applies one step to every slot of one
  layer of the stacked state ``[layers, slots, N, C / 128, 128]``, in place,
  taking the layer index by scalar prefetch: no layer's slab leaves the
  stack.  A grid step moves ``STEP_SLOTS`` slots' states.

The twin is the recurrence one position at a time (``lax.scan``): the CPU
path and what the kernels are tested against.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import resolve_interpret

#: kernel names as a device trace shows them (``<name> [pallas]``); pinned by
#: tests/test_trace_names.py, read by the benchmark's selective_scan_* readers
KERNEL_CHUNK_FWD = "selective_scan_chunk_fwd"
KERNEL_STEP = "selective_scan_step"

F32 = jnp.float32
LANES = 128
#: positions a grid step of the prefill kernel
CHUNK = 128
#: rows of 128 channels a block, at most: a state column of a block is one
#: vector register
ROWS = 8
#: positions the prefill kernel's loop unrolls
UNROLL = 4
#: slots a grid step of the decode kernel moves, at most: 8 states of
#: [16, 40, 128] float32 are 2.6 MB, in and out and two buffers each 10.5 MB
STEP_SLOTS = 8
STEP_VMEM_LIMIT = 32 << 20


def state_shape(channels: int, columns: int) -> Tuple[int, int, int]:
    """A slot's state of one layer as the cache holds it."""
    if channels % LANES:
        raise ValueError(f"selective scan: {channels} channels are no whole "
                         f"tiles of {LANES} lanes")
    return columns, channels // LANES, LANES


def _rows_a_block(rows: int) -> int:
    """Rows of 128 channels a block: the most up to ``ROWS`` that divide
    the channels' rows."""
    return max(r for r in range(1, min(rows, ROWS) + 1) if rows % r == 0)


def _along_lanes(x):
    """[..., N] -> [..., N, 128] float32: each number laid along a vector's
    lanes, so that a kernel reads it as a row and never as a scalar."""
    return jnp.broadcast_to(x.astype(F32)[..., None], x.shape + (LANES,))


def _tiled(x):
    """[..., C] -> [..., C / 128, 128] float32."""
    return x.astype(F32).reshape(x.shape[:-1] + (-1, LANES))


# ---------------------------------------------------------------------------
# The twin: the recurrence, one position at a time
# ---------------------------------------------------------------------------

def _one_step(s, u, dt, a, b, c):
    """s [..., N, C]; u, dt [..., C]; a [N, C]; b, c [..., N] -> (y [...,
    C], s), float32."""
    dt = dt.astype(F32)
    s = (jnp.exp(dt[..., None, :] * a) * s
         + (dt * u.astype(F32))[..., None, :] * b.astype(F32)[..., None])
    return jnp.sum(s * c.astype(F32)[..., None], axis=-2), s


def selective_scan_jnp(u, dt, a, b, c, lengths=None, initial_state=None):
    """The twin of ``selective_scan_chunk_fwd``; shapes as there (the state
    given and returned as ``[B, N, C / 128, 128]``)."""
    bsz, t, ch = u.shape
    n = a.shape[0]
    if lengths is not None:
        dt = jnp.where((jnp.arange(t)[None] < lengths[:, None])[..., None],
                       dt, 0.0)
    s0 = (jnp.zeros((bsz, n, ch), F32) if initial_state is None
          else initial_state.reshape(bsz, n, ch).astype(F32))

    def step(s, xs):
        y, s = _one_step(s, *xs[:2], a.astype(F32), *xs[2:])
        return s, y

    s, y = jax.lax.scan(step, s0, tuple(x.swapaxes(0, 1)
                                        for x in (u, dt, b, c)))
    return y.swapaxes(0, 1), s.reshape((bsz,) + state_shape(ch, n))


def selective_scan_step_jnp(state, layer, u, dt, a, b, c):
    """The twin of ``selective_scan_step``; shapes as there."""
    s = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    slots, n = s.shape[:2]
    y, new = _one_step(s.reshape(slots, n, -1), u, dt, a.astype(F32), b, c)
    return jax.lax.dynamic_update_index_in_dim(
        state, new.reshape(s.shape), layer, 0), y


# ---------------------------------------------------------------------------
# Tile math, shared by the two kernels
# ---------------------------------------------------------------------------

def _advance(s, dt, dtu, a, b_row, c_row):
    """One step of one block of channels.  s, a: ``N`` arrays [rows, 128]
    (a state column each); dt, dtu [rows, 128]; b_row, c_row: ``N`` arrays
    [1, 128], the column's number along the lanes.  Returns (y [rows, 128],
    the ``N`` new columns)."""
    y, new = jnp.zeros_like(dt), []
    for s_n, a_n, b_n, c_n in zip(s, a, b_row, c_row):
        s_n = jnp.exp(dt * a_n) * s_n + dtu * b_n
        y = y + s_n * c_n
        new.append(s_n)
    return y, new


# ---------------------------------------------------------------------------
# Prefill: a row in chunks
# ---------------------------------------------------------------------------

def _chunk_kernel(len_ref, dt_ref, dtu_ref, b_ref, c_ref, a_ref, y_ref,
                  s_ref, *, rows: int):
    """Grid (batch, chunks, channel blocks).  dt, dtu, y [1, CHUNK, rows,
    128]; b, c [1, CHUNK, N, 128]; a [N, rows, 128]; s_ref [1, N, R, 128],
    the row's running state, resident while the row is walked."""
    bi, ci, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n = a_ref.shape[0]
    mine = pl.ds(pl.multiple_of(ki * rows, rows), rows)

    @pl.when(ci == 0)
    def _init():
        s_ref[0, :, mine, :] = jnp.zeros((n, rows, LANES), F32)

    @pl.when(ci * CHUNK >= len_ref[bi])
    def _past():                # nothing of the row is left: y reads 0
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(ci * CHUNK < len_ref[bi])
    def _walk():
        a = [a_ref[j] for j in range(n)]

        def some(i, s):         # ``UNROLL`` positions a trip, unrolled here
            for t in range(UNROLL):
                t = i * UNROLL + t
                row = lambda ref: [ref[0, t, pl.ds(j, 1), :]    # noqa: E731
                                   for j in range(n)]
                y, s = _advance(s, dt_ref[0, t], dtu_ref[0, t], a,
                                row(b_ref), row(c_ref))
                y_ref[0, t] = y
            return tuple(s)

        s = jax.lax.fori_loop(
            0, CHUNK // UNROLL, some,
            tuple(s_ref[0, j, mine, :] for j in range(n)))
        for j in range(n):
            s_ref[0, j, mine, :] = s[j]


def _chunk_fwd_pallas(u, dt, a, b, c, lengths, interpret: bool):
    bsz, t, ch = u.shape
    n = a.shape[0]
    r = ch // LANES
    rows = _rows_a_block(r)
    pad = -t % CHUNK
    if pad:
        u, dt, b, c = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                       for x in (u, dt, b, c))
    wide = lambda bi, ci, ki, ln: (bi, ci, ki, 0)       # noqa: E731
    shared = lambda bi, ci, ki, ln: (bi, ci, 0, 0)      # noqa: E731
    y, s = pl.pallas_call(
        functools.partial(_chunk_kernel, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, (t + pad) // CHUNK, r // rows),
            in_specs=[
                pl.BlockSpec((1, CHUNK, rows, LANES), wide),
                pl.BlockSpec((1, CHUNK, rows, LANES), wide),
                pl.BlockSpec((1, CHUNK, n, LANES), shared),
                pl.BlockSpec((1, CHUNK, n, LANES), shared),
                pl.BlockSpec((n, rows, LANES),
                             lambda bi, ci, ki, ln: (0, ki, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, CHUNK, rows, LANES), wide),
                pl.BlockSpec((1, n, r, LANES),
                             lambda bi, ci, ki, ln: (bi, 0, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bsz, t + pad, r, LANES), F32),
            jax.ShapeDtypeStruct((bsz, n, r, LANES), F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name=KERNEL_CHUNK_FWD,
    )(lengths.astype(jnp.int32), _tiled(dt), _tiled(dt * u.astype(F32)),
      _along_lanes(b), _along_lanes(c), _tiled(a))
    return y.reshape(bsz, t + pad, ch)[:, :t], s


def selective_scan_chunk_fwd(u, dt, a, b, c, lengths=None,
                             use_kernel: Optional[bool] = None,
                             interpret: Optional[bool] = None
                             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The selective scan over whole rows from a zero state.

    u: [B, T, C]; dt: [B, T, C] float32, the step after its softplus; a: [N,
    C] float32, the rates (negative); b, c: [B, T, N]; ``lengths`` [B]:
    positions at or beyond a row's length do not touch its state.  Returns
    (y [B, T, C] float32, without the skip; state [B, N, C / 128, 128]
    float32 as of each row's length).  ``T`` may be any length; a chunk is
    ``CHUNK`` positions.

    ``use_kernel=None`` takes the Pallas kernel on a TPU and the twin
    elsewhere; ``interpret=True`` runs the kernel interpreted (tests)."""
    if use_kernel is None:
        use_kernel = bool(interpret) or jax.default_backend() == "tpu"
    if not use_kernel:
        return selective_scan_jnp(u, dt, a, b, c, lengths)
    t = u.shape[1]
    if lengths is None:
        lengths = jnp.full((u.shape[0],), t, jnp.int32)
    dt = jnp.where((jnp.arange(t)[None] < lengths[:, None])[..., None],
                   dt.astype(F32), 0.0)
    return _chunk_fwd_pallas(u, dt, a, b, c, lengths,
                             resolve_interpret(interpret, "selective_scan"))


# ---------------------------------------------------------------------------
# Decode: one step, in place on the stacked state
# ---------------------------------------------------------------------------

def _step_kernel(layer_ref, dt_ref, dtu_ref, b_ref, c_ref, a_ref, s_in,
                 s_out, y_ref, *, slots: int, rows: int):
    """Grid (slot blocks,).  s_in, s_out [1, sb, N, R, 128]; dt, dtu, y [sb,
    R, 128]; b, c [sb, N, 128]; a [N, R, 128].  A rolled loop over the slots
    the block holds (the last block may hold fewer) and their blocks of
    channels."""
    del layer_ref                     # used by the index maps only
    sb, n, r = s_in.shape[1:4]
    blocks = r // rows
    first = pl.program_id(0) * sb

    def item(at, carry):
        si, ki = (at, 0) if blocks == 1 else (at // blocks, at % blocks)
        mine = pl.ds(pl.multiple_of(ki * rows, rows), rows)
        y, s = _advance(
            [s_in[0, si, j, mine, :] for j in range(n)],
            dt_ref[si, mine, :], dtu_ref[si, mine, :],
            [a_ref[j, mine, :] for j in range(n)],
            [b_ref[si, pl.ds(j, 1), :] for j in range(n)],
            [c_ref[si, pl.ds(j, 1), :] for j in range(n)])
        for j in range(n):
            s_out[0, si, j, mine, :] = s[j]
        y_ref[si, mine, :] = y
        return carry

    jax.lax.fori_loop(0, jnp.minimum(sb, slots - first) * blocks, item, 0)


def _step_pallas(state, layer, u, dt, a, b, c, interpret: bool):
    _, slots, n, r, _ = state.shape
    sb = min(slots, STEP_SLOTS)
    dt = dt.astype(F32)
    per_slot = lambda si, lyr: (si, 0, 0)               # noqa: E731
    big = lambda si, lyr: (lyr[0], si, 0, 0, 0)         # noqa: E731
    state, y = pl.pallas_call(
        functools.partial(_step_kernel, slots=slots, rows=_rows_a_block(r)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(slots, sb),),
            in_specs=[
                pl.BlockSpec((sb, r, LANES), per_slot),
                pl.BlockSpec((sb, r, LANES), per_slot),
                pl.BlockSpec((sb, n, LANES), per_slot),
                pl.BlockSpec((sb, n, LANES), per_slot),
                pl.BlockSpec((n, r, LANES), lambda si, lyr: (0, 0, 0)),
                pl.BlockSpec((1, sb, n, r, LANES), big),
            ],
            out_specs=[
                pl.BlockSpec((1, sb, n, r, LANES), big),
                pl.BlockSpec((sb, r, LANES), per_slot),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct((slots, r, LANES), F32),
        ],
        # operands count from the scalar-prefetch argument: 6 is the state
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=STEP_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_STEP,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), _tiled(dt),
      _tiled(dt * u.astype(F32)), _along_lanes(b), _along_lanes(c), _tiled(a),
      state)
    return state, y.reshape(slots, -1)


def selective_scan_step(state, layer, u, dt, a, b, c,
                        use_kernel: Optional[bool] = None,
                        interpret: Optional[bool] = None):
    """One step of the selective scan for every slot of layer ``layer``.

    state: [layers, slots, N, C / 128, 128] float32, updated in place
    (donate it); layer: int32 scalar (traced or not); u: [slots, C]; dt:
    [slots, C] float32, the step after its softplus (``dt = 0`` leaves a
    slot's state as it was: an idle slot); a: [N, C] float32; b, c: [slots,
    N].  Returns (state, y [slots, C] float32, without the skip).  Only the
    blocks of ``layer`` are read and written: the index maps take the layer
    from scalar prefetch, no slab leaves the stack."""
    if use_kernel is None:
        use_kernel = bool(interpret) or jax.default_backend() == "tpu"
    if not use_kernel:
        return selective_scan_step_jnp(state, layer, u, dt, a, b, c)
    return _step_pallas(state, layer, u, dt, a, b, c,
                        resolve_interpret(interpret, "selective_scan_step"))
