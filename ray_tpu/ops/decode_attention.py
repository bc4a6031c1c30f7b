"""Decode attention over the stacked KV cache where it lies: one new token a
slot against that slot's rows of one layer, as a Pallas TPU kernel and its
plain twin.

The cache is ``[layers, slots, max_len, NKV * D]``, a position's KV heads
side by side in one row (one layout for 8 heads and for 30: a head is a
slice of ``D`` lanes).  ``decode_attn`` takes the layer index and each
slot's live length by scalar prefetch, so that

* no layer's slab leaves the stack: the block index map picks ``[layer,
  slot, block]`` itself (what ``gdn_recurrent_step`` does for the recurrent
  state);
* blocks beyond a slot's live length, and every block of a slot of length
  0 (inactive), are neither fetched nor computed: the grid walks a list of
  the live blocks alone (``_plan``), slot after slot and as long as the
  list, so that each step's fetch runs under the step before it.

One grid axis, the work list, sequential; online softmax over a slot's
blocks with the running max, sum and accumulator in float32 VMEM scratch;
operands as stored (bf16) on the MXU with float32 accumulation, softmax in
float32.  Each head's scores are a matmul of the whole row with
its query placed in its own head's channels and zeros elsewhere (NKV times
the needed FLOPs, none of them felt beside the read of the rows, and no
reshape of the rows); the accumulator keeps whole rows and a head's own
channels are picked once, at the end.

The twin reshapes a layer's rows to heads and is plain einsums in the same
precisions.  It is the CPU path, and the path under a mesh of more than one
device: the compiler cannot partition a Mosaic kernel, and the twin's
einsums split by KV heads as the cache does.

**Several tokens a slot** (a speculative verify step's window): the W new
tokens' queries ride the same blocks as ``W * NH`` query rows, token-major,
each masked at its own position (query ``j`` of ``W`` reads ``live - (W - 1 -
j)`` positions); at ``W = 1`` the kernel is what it was.

**Values wider than keys** (``wide`` 2: differential attention's two score
maps over the values of a PAIR of K/V heads): query head ``h`` scores key
head ``h // reps`` as ever and weighs the ``wide * D`` lanes of the value
heads ``wide * (h // (wide * reps)) ..``.  The accumulator keeps whole rows
either way, so all that changes is which channels a head picks at the end
(``_own_channels``), and the output is ``wide * D`` lanes a head: a
position's K and V are fetched once whatever the maps.

``window_decode_attn`` is the sibling over a **ring**: ``[layers, slots,
ring, NKV * D]``, a position's row ``position mod ring``, for layers that
read their last ``window`` positions alone.  A slot is one block (the whole
ring) and a row is masked by the position it holds, which the slot's newest
position says: row ``r`` holds the newest position congruent to ``r``.

``mla_decode_attn`` is the latent sibling, on the same work list.  A token
caches ``C + R`` numbers a layer, shared by every head: the compressed keys
and values (``C``, after their norm) and the rotary key (``R``), 576 for
512 + 64.  They lie in two arrays, ``latent`` ``[layers, slots, max_len,
C]`` and ``rope_key`` ``[layers, slots, R, max_len]`` (a position's rotary
key is a column): HBM rows come in 128 lanes, so one row of 576 would take
640, and the layout the compiler picks to avoid that padding for a
parameter of that shape (positions minor-most) is one the kernel cannot
read without a copy of the whole cache a call (sandbox compile, PERF.md, PR
35).  Each head's query arrives carried into the latent space
(``models/latent.py``, the absorbed form), so a block is a ``[NH, C] x [C,
block]`` and a ``[NH, R] x [R, block]`` score matmul and one ``[NH, block]
x [block, C]`` value matmul off the one fetched block of rows: the values
are the latent rows themselves.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import score_scale
from .flash_attention import resolve_interpret

#: the kernel's name as a device trace shows it (``decode_attn [pallas]``);
#: pinned by tests/test_trace_names.py, read by the benchmark's
#: ``decode_attn_roofline``
KERNEL_DECODE_ATTN = "decode_attn"
#: the ring's (``window_decode_attn [pallas]``), read by the benchmark's
#: ``window_decode_attn_roofline``
KERNEL_WINDOW_DECODE_ATTN = "window_decode_attn"
#: the latent sibling's (``mla_decode_attn [pallas]``), read by the
#: benchmark's ``mla_decode_attn_roofline``
KERNEL_MLA_DECODE_ATTN = "mla_decode_attn"

NEG = -1e30
F32 = jnp.float32
#: bytes of one K (or V) block in VMEM, at most; K and V, double-buffered,
#: take four of them
BLOCK_BYTES = 2 << 20
#: positions a block, at most
BLOCK_LEN = 512
#: the kernel's VMEM: four blocks, the float32 accumulator and the
#: products of one block, with room; the chip has 128 MiB
VMEM_LIMIT = 48 << 20


def block_len(max_len: int, row_bytes: int) -> int:
    """Positions one grid step reads of a slot: the largest divisor of
    ``max_len`` that is a multiple of 16 (a bf16 tile's rows), at most
    ``BLOCK_LEN`` and at most ``BLOCK_BYTES`` of rows ``row_bytes`` wide;
    the whole row where ``max_len`` has no such divisor (tiny caches)."""
    cap = min(BLOCK_LEN, BLOCK_BYTES // row_bytes, max_len)
    fits = [t for t in range(16, cap + 1, 16) if max_len % t == 0]
    return max(fits) if fits else max_len


def _softcap(s, softcap: float):
    return softcap * jnp.tanh(s / softcap) if softcap else s


# ---------------------------------------------------------------------------
# The twin: plain attention over one layer's rows, heads apart
# ---------------------------------------------------------------------------

def _attend_rows(q, k_all, v_all, layer, seen, num_kv_heads: int,
                 softcap: float, tokens: int, scale: Optional[float] = None,
                 wide: int = 1):
    """Plain attention of ``tokens`` queries a slot over one layer's rows,
    heads apart.  q: [slots, tokens * NH, D], token-major; seen: [slots,
    tokens, rows], the rows each query reads; ``scale`` multiplies the
    scores (None: ``D ** -0.5``, here and in every function below);
    ``wide``: the value heads a key head's queries weigh, side by side."""
    slots, rows, hd = q.shape
    nh, span = rows // tokens, k_all.shape[2]
    k, v = (jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
            for a in (k_all, v_all))
    k = k.reshape(slots, span, num_kv_heads, hd)
    v = jnp.repeat(v.reshape(slots, span, num_kv_heads // wide, wide * hd),
                   wide, axis=2)
    qh = q.reshape(slots, tokens, num_kv_heads, nh // num_kv_heads, hd)
    s = jnp.einsum("swgrd,smgd->swgrm", qh.astype(k.dtype), k,
                   preferred_element_type=F32) * score_scale(scale, hd)
    seen = seen[:, :, None, None, :]
    s = jnp.where(seen, _softcap(s, softcap), NEG)
    p = jnp.where(seen, jnp.exp(s - s.max(axis=-1, keepdims=True)), 0.0)
    o = jnp.einsum("swgrm,smgd->swgrd", p.astype(v.dtype), v,
                   preferred_element_type=F32)
    o = o / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    return o.reshape(slots, rows, wide * hd).astype(q.dtype)


def decode_attn_jnp(q, k_all, v_all, layer, live, num_kv_heads: int,
                    softcap: float = 0.0, tokens: int = 1,
                    scale: Optional[float] = None, wide: int = 1):
    """The twin of ``decode_attn``; shapes as there."""
    # query j of a slot reads the positions before live - (tokens - 1 - j)
    edge = live[:, None] - (tokens - 1 - jnp.arange(tokens))[None]
    seen = jnp.arange(k_all.shape[2])[None, None] < edge[..., None]
    return _attend_rows(q, k_all, v_all, layer, seen, num_kv_heads, softcap,
                        tokens, scale, wide)


def ring_positions(newest, ring: int):
    """The position each row of a ring holds where the newest position
    written is ``newest`` [...]: row ``r`` holds the newest position
    congruent to ``r`` modulo ``ring`` (negative: nothing yet).  Returns
    [..., ring]."""
    newest = newest[..., None]
    return newest - jax.lax.rem(newest + ring - jnp.arange(ring), ring)


def window_decode_attn_jnp(q, k_all, v_all, layer, live, num_kv_heads: int,
                           window: int, tokens: int = 1,
                           scale: Optional[float] = None, wide: int = 1):
    """The twin of ``window_decode_attn``; shapes as there."""
    held = ring_positions(live - 1, k_all.shape[2])[:, None]  # [slots,1,ring]
    at = (live[:, None] - (tokens - jnp.arange(tokens))[None])[..., None]
    seen = (held >= 0) & (held <= at) & (at - held < window) \
        & (live > 0)[:, None, None]
    return _attend_rows(q, k_all, v_all, layer, seen, num_kv_heads, 0.0,
                        tokens, scale, wide)


# ---------------------------------------------------------------------------
# The kernel
# ---------------------------------------------------------------------------

def _block_update(q_row, k, v, seen, m, l, acc, *, scale: float,
                  softcap: float):
    """One block of one slot into the running softmax.  q_row [NH', C]
    (each head's query in its own channels); k, v [T, C], rows of the slot,
    of which those count that ``seen(shape) -> [NH', T]`` says; m, l [NH',
    1], acc [NH', C] float32.  Returns (m, l, acc)."""
    s = jax.lax.dot_general(q_row, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * scale   # [NH', T]
    s = jnp.where(seen(s.shape), _softcap(s, softcap), NEG)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
    acc = alpha * acc + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=F32)
    return m_new, l, acc


def _token_of(shape, heads: int, tokens: int):
    """Which of a slot's ``tokens`` new tokens a query row belongs to, for
    rows [tokens * heads (padded), ...] token-major: 0 where there is one."""
    if tokens == 1:
        return 0
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0) // heads


def _own_channels(rows, hd: int, heads_a_group: int, num_kv_heads: int,
                  heads: int, tokens: int):
    """rows [NH', C], each head's row over all KV heads' channels -> [NH',
    hd], a head's own channels."""
    row = jax.lax.broadcasted_iota(jnp.int32, (rows.shape[0], hd), 0)
    if tokens > 1:
        row = jax.lax.rem(row, heads)
    group = row // heads_a_group
    o = jnp.zeros((rows.shape[0], hd), F32)
    for g in range(num_kv_heads):
        o = o + jnp.where(group == g, rows[:, g * hd:(g + 1) * hd], 0.0)
    return o


def _kernel(layer_ref, live_ref, slot_ref, block_ref, total_ref, q_ref,
            k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, block: int,
            heads_a_group: int, num_kv_heads: int, scale: float,
            softcap: float, heads: int, tokens: int):
    del layer_ref                               # the index maps' only
    ti = pl.program_id(0)
    si, bi = slot_ref[ti], block_ref[ti]

    @pl.when(ti < total_ref[0])
    def _item():
        @pl.when(bi == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        q, k, v = q_ref[0], k_ref[0, 0], v_ref[0, 0]
        start, live = bi * block, live_ref[si]

        def seen(shape):
            # a row of the block counts for a query while it lies before
            # the query's own edge: the slot's live length, less the new
            # tokens after the query's
            pos = start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            edge = live
            if tokens > 1:
                edge = edge - (tokens - 1) + _token_of(shape, heads, tokens)
            return pos < edge

        m, l, acc = _block_update(
            q, k, v, seen, m_ref[...], l_ref[...], acc_ref[...], scale=scale,
            softcap=softcap)
        m_ref[...], l_ref[...], acc_ref[...] = m, l, acc

        @pl.when(bi == (live_ref[si] - 1) // block)   # the slot's last
        def _flush():
            o_ref[0] = _own_channels(
                acc / l, o_ref.shape[2], heads_a_group, num_kv_heads, heads,
                tokens).astype(o_ref.dtype)


def _ring_kernel(layer_ref, live_ref, slot_ref, total_ref, q_ref, k_ref,
                 v_ref, o_ref, *, ring: int, window: int,
                 heads_a_group: int, num_kv_heads: int, scale: float,
                 heads: int, tokens: int):
    """One slot a step: its whole ring is one block, each row masked by the
    position it holds."""
    del layer_ref
    ti = pl.program_id(0)
    si = slot_ref[ti]

    @pl.when(ti < total_ref[0])
    def _item():
        newest = live_ref[si] - 1       # the last position written

        def seen(shape):
            row = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            held = newest - jax.lax.rem(newest + ring - row, ring)
            at = newest - (tokens - 1) + _token_of(shape, heads, tokens)
            return (held >= 0) & (held <= at) & (at - held < window)

        rows = q_ref.shape[1]
        m, l, acc = _block_update(
            q_ref[0], k_ref[0, 0], v_ref[0, 0], seen,
            jnp.full((rows, 1), NEG, F32), jnp.zeros((rows, 1), F32),
            jnp.zeros((rows, k_ref.shape[3]), F32), scale=scale, softcap=0.0)
        del m
        o_ref[0] = _own_channels(
            acc / l, o_ref.shape[2], heads_a_group, num_kv_heads, heads,
            tokens).astype(o_ref.dtype)


def _plan(live, block: int, num_blocks: int):
    """The grid's work list, from the live lengths [slots]: the live blocks
    of the slots that have any, slot after slot (``slot_of``, ``block_of``
    [slots * num_blocks], of which the first ``total`` count), so that the
    grid is as long as the list and every step but the last fetches the
    next one while it computes."""
    work = -(-live // block)                 # live blocks a slot
    ends = jnp.cumsum(work)
    total = ends[-1]
    item = jnp.minimum(jnp.arange(live.shape[0] * num_blocks, dtype=jnp.int32),
                       jnp.maximum(total - 1, 0))
    slot_of = jnp.minimum(jnp.searchsorted(ends, item, side="right"),
                          live.shape[0] - 1).astype(jnp.int32)
    return slot_of, (item - (ends - work)[slot_of]).astype(jnp.int32), total


def _query_rows(q, nh: int, num_kv_heads: int, chan: int, dtype):
    """q [slots, rows, D] (``rows``: a token's NH heads, token after token)
    -> [slots, rows padded to 16, C]: each head's query in its own head's
    channels, zeros elsewhere; rows past the last belong to no head."""
    rows, hd = q.shape[1:]
    nhp, reps = -(-rows // 16) * 16, nh // num_kv_heads
    lane_group = jnp.arange(chan)[None, :] // hd
    head = jnp.arange(nhp)[:, None]
    if rows > nh:                       # several tokens: a row's head
        head = jnp.where(head < rows, head % nh, head)
    own = lane_group == head // reps                              # [NH', C]
    return jnp.where(own[None], jnp.tile(jnp.pad(
        q, ((0, 0), (0, nhp - rows), (0, 0))), (1, 1, num_kv_heads)),
        0).astype(dtype)


def _decode_attn_pallas(q, k_all, v_all, layer, live, num_kv_heads: int,
                        softcap: float, interpret: bool, tokens: int = 1,
                        scale: Optional[float] = None, wide: int = 1):
    slots, q_rows, hd = q.shape
    nh = q_rows // tokens
    max_len, chan = k_all.shape[2:]
    reps = nh // num_kv_heads
    nhp = -(-q_rows // 16) * 16
    block = block_len(max_len, chan * k_all.dtype.itemsize)
    num_blocks = max_len // block
    live = live.astype(jnp.int32)
    q_row = _query_rows(q, nh, num_kv_heads, chan, k_all.dtype)
    slot_of, block_of, total = _plan(live, block, num_blocks)

    def rows(ti, layer, live, slot_of, block_of, total):
        return (layer[0], slot_of[ti], block_of[ti], 0)

    def per_slot(ti, layer, live, slot_of, block_of, total):
        return (slot_of[ti], 0, 0)

    out = pl.pallas_call(
        # (a head's own channels at the end: ``wide`` K/V heads' worth)
        functools.partial(_kernel, block=block, heads_a_group=wide * reps,
                          num_kv_heads=num_kv_heads // wide,
                          scale=score_scale(scale, hd), softcap=softcap,
                          heads=nh, tokens=tokens),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            # as long as the work list; with nothing live, one step that
            # does nothing
            grid=(jnp.maximum(total, 1),),
            in_specs=[
                pl.BlockSpec((1, nhp, chan), per_slot),
                pl.BlockSpec((1, 1, block, chan), rows),
                pl.BlockSpec((1, 1, block, chan), rows),
            ],
            out_specs=pl.BlockSpec((1, nhp, wide * hd), per_slot),
            scratch_shapes=[pltpu.VMEM((nhp, 1), F32),
                            pltpu.VMEM((nhp, 1), F32),
                            pltpu.VMEM((nhp, chan), F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((slots, nhp, wide * hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_DECODE_ATTN,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), live, slot_of, block_of,
      jnp.reshape(total, (1,)).astype(jnp.int32), q_row, k_all, v_all)
    # a slot with nothing live is on no step of the grid: its rows of the
    # output were never written
    return jnp.where(live[:, None, None] > 0, out[:, :q_rows], 0)


def _window_decode_attn_pallas(q, k_all, v_all, layer, live,
                               num_kv_heads: int, window: int, tokens: int,
                               interpret: bool,
                               scale: Optional[float] = None, wide: int = 1):
    slots, rows, hd = q.shape
    nh = rows // tokens
    ring, chan = k_all.shape[2:]
    nhp = -(-rows // 16) * 16
    live = live.astype(jnp.int32)
    q_row = _query_rows(q, nh, num_kv_heads, chan, k_all.dtype)
    # the work list: the live slots, one block (the ring) each
    slot_of, _, total = _plan(jnp.minimum(live, 1), 1, 1)

    def held(ti, layer, live, slot_of, total):
        return (layer[0], slot_of[ti], 0, 0)

    def per_slot(ti, layer, live, slot_of, total):
        return (slot_of[ti], 0, 0)

    out = pl.pallas_call(
        functools.partial(_ring_kernel, ring=ring, window=window,
                          heads_a_group=wide * nh // num_kv_heads,
                          num_kv_heads=num_kv_heads // wide,
                          scale=score_scale(scale, hd), heads=nh,
                          tokens=tokens),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(jnp.maximum(total, 1),),
            in_specs=[pl.BlockSpec((1, nhp, chan), per_slot),
                      pl.BlockSpec((1, 1, ring, chan), held),
                      pl.BlockSpec((1, 1, ring, chan), held)],
            out_specs=pl.BlockSpec((1, nhp, wide * hd), per_slot),
        ),
        out_shape=jax.ShapeDtypeStruct((slots, nhp, wide * hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_WINDOW_DECODE_ATTN,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), live, slot_of,
      jnp.reshape(total, (1,)).astype(jnp.int32), q_row, k_all, v_all)
    return jnp.where(live[:, None, None] > 0, out[:, :rows], 0)


def _takes_kernel(use_kernel: Optional[bool], interpret: Optional[bool]):
    if use_kernel is None:
        use_kernel = bool(interpret) or (
            jax.default_backend() == "tpu"
            and jax.sharding.get_abstract_mesh().size <= 1)
    return use_kernel


def window_decode_attn(q, k_all, v_all, layer, live, num_kv_heads: int,
                       window: int, tokens: int = 1,
                       use_kernel: Optional[bool] = None,
                       interpret: Optional[bool] = None,
                       scale: Optional[float] = None, wide: int = 1):
    """Attention of ``tokens`` new tokens a slot over layer ``layer`` of a
    stack of rings, each query over the ``window`` positions up to its own.

    q: [slots, tokens * NH, D], token-major; k_all, v_all: [layers, slots,
    ring, NKV * D], position ``p``'s row ``p mod ring``, the new tokens' own
    rows already written, ``ring >= window + tokens - 1`` (else a later
    token's row has replaced one an earlier token reads); live: [slots]
    int32, each slot's length with the new tokens (0: inactive, zeros).
    Returns [slots, tokens * NH, wide * D] in q's dtype (``wide``: the value
    heads a head weighs, the module's docstring)."""
    if not _takes_kernel(use_kernel, interpret):
        return window_decode_attn_jnp(q, k_all, v_all, layer, live,
                                      num_kv_heads, window, tokens, scale,
                                      wide)
    return _window_decode_attn_pallas(
        q, k_all, v_all, layer, live, num_kv_heads, window, tokens,
        resolve_interpret(interpret, "window_decode_attn"), scale, wide)


def decode_attn(q, k_all, v_all, layer, live, num_kv_heads: int,
                softcap: float = 0.0, use_kernel: Optional[bool] = None,
                interpret: Optional[bool] = None, tokens: int = 1,
                scale: Optional[float] = None, wide: int = 1):
    """Attention of one new token a slot (or ``tokens``) over layer
    ``layer`` of the stacked cache.

    q: [slots, NH, D] (``tokens`` > 1: [slots, tokens * NH, D], token-major);
    k_all, v_all: [layers, slots, max_len, NKV * D], the
    tokens' own rows already written; layer: int32 scalar (traced or not);
    live: [slots] int32, the positions of each slot that count, the new
    tokens' among them (0: the slot is inactive, its output is zeros).
    ``scale`` multiplies the scores (None: ``D ** -0.5``); ``wide``: the
    value heads a head weighs (the module's docstring).
    Returns an array like q, ``wide * D`` lanes a head.  Only the live
    blocks of ``layer`` are read: no slab leaves the stack.

    ``use_kernel=None`` takes the Pallas kernel on a TPU and the twin
    elsewhere and under a mesh of more than one device (``LLMEngine`` with
    ``tp > 1`` enters its mesh: the compiler cannot partition the kernel);
    ``interpret=True`` runs the kernel interpreted (tests)."""
    if not _takes_kernel(use_kernel, interpret):
        return decode_attn_jnp(q, k_all, v_all, layer, live, num_kv_heads,
                               softcap, tokens, scale, wide)
    interpret = resolve_interpret(interpret, "decode_attn")
    return _decode_attn_pallas(q, k_all, v_all, layer, live, num_kv_heads,
                               softcap, interpret, tokens, scale, wide)


# ---------------------------------------------------------------------------
# The latent sibling: C + R numbers a token, shared by every head
# ---------------------------------------------------------------------------

def mla_decode_attn_jnp(q_lat, q_rope, latent_all, rope_all, layer, live,
                        scale: float):
    """The twin of ``mla_decode_attn``; shapes as there."""
    rows, keys = (jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
                  for a in (latent_all, rope_all))
    s = (jnp.einsum("shc,smc->shm", q_lat.astype(rows.dtype), rows,
                    preferred_element_type=F32)
         + jnp.einsum("shr,srm->shm", q_rope.astype(keys.dtype), keys,
                      preferred_element_type=F32)) * scale
    seen = (jnp.arange(rows.shape[1])[None, :] < live[:, None])[:, None, :]
    s = jnp.where(seen, s, NEG)
    p = jnp.where(seen, jnp.exp(s - s.max(axis=-1, keepdims=True)), 0.0)
    o = jnp.einsum("shm,smc->shc", p.astype(rows.dtype), rows,
                   preferred_element_type=F32)
    o = o / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    return o.astype(q_lat.dtype)


def _mla_kernel(layer_ref, live_ref, slot_ref, block_ref, total_ref, ql_ref,
                qr_ref, c_ref, k_ref, o_ref, m_ref, l_ref, acc_ref, *,
                block: int, scale: float):
    del layer_ref                               # the index maps' only
    ti = pl.program_id(0)
    si, bi = slot_ref[ti], block_ref[ti]

    @pl.when(ti < total_ref[0])
    def _item():
        @pl.when(bi == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        rows = c_ref[0, 0]                                        # [T, C]
        s = (jax.lax.dot_general(ql_ref[0], rows, (((1,), (1,)), ((), ())),
                                 preferred_element_type=F32)
             + jnp.dot(qr_ref[0], k_ref[0, 0],
                       preferred_element_type=F32)) * scale       # [NH', T]
        pos = bi * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < live_ref[si], s, NEG)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc = alpha * acc_ref[...] + jnp.dot(p.astype(rows.dtype), rows,
                                             preferred_element_type=F32)
        m_ref[...], l_ref[...], acc_ref[...] = m_new, l, acc

        @pl.when(bi == (live_ref[si] - 1) // block)   # the slot's last
        def _flush():
            o_ref[0] = (acc / l).astype(o_ref.dtype)


def _mla_decode_attn_pallas(q_lat, q_rope, latent_all, rope_all, layer, live,
                            scale: float, interpret: bool):
    slots, nh, c = q_lat.shape
    r, max_len = rope_all.shape[2:]
    nhp = -(-nh // 16) * 16
    block = block_len(max_len, (c + r) * latent_all.dtype.itemsize)
    live = live.astype(jnp.int32)
    slot_of, block_of, total = _plan(live, block, max_len // block)

    def rows(ti, layer, live, slot_of, block_of, total):
        return (layer[0], slot_of[ti], block_of[ti], 0)

    def keys(ti, layer, live, slot_of, block_of, total):
        return (layer[0], slot_of[ti], 0, block_of[ti])

    def per_slot(ti, layer, live, slot_of, block_of, total):
        return (slot_of[ti], 0, 0)

    def padded(q):
        return jnp.pad(q, ((0, 0), (0, nhp - nh), (0, 0))).astype(
            latent_all.dtype)

    out = pl.pallas_call(
        functools.partial(_mla_kernel, block=block, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(jnp.maximum(total, 1),),
            in_specs=[pl.BlockSpec((1, nhp, c), per_slot),
                      pl.BlockSpec((1, nhp, r), per_slot),
                      pl.BlockSpec((1, 1, block, c), rows),
                      pl.BlockSpec((1, 1, r, block), keys)],
            out_specs=pl.BlockSpec((1, nhp, c), per_slot),
            scratch_shapes=[pltpu.VMEM((nhp, 1), F32),
                            pltpu.VMEM((nhp, 1), F32),
                            pltpu.VMEM((nhp, c), F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((slots, nhp, c), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_MLA_DECODE_ATTN,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), live, slot_of, block_of,
      jnp.reshape(total, (1,)).astype(jnp.int32), padded(q_lat),
      padded(q_rope), latent_all, rope_all)
    return jnp.where(live[:, None, None] > 0, out[:, :nh], 0)


def mla_decode_attn(q_lat, q_rope, latent_all, rope_all, layer, live,
                    scale: float, use_kernel: Optional[bool] = None,
                    interpret: Optional[bool] = None):
    """Latent attention of one new token a slot over layer ``layer`` of the
    stacked latent cache.

    q_lat: [slots, NH, C], each head's query carried into the latent space;
    q_rope: [slots, NH, R], its rotary part; latent_all: [layers, slots,
    max_len, C] and rope_all: [layers, slots, R, max_len], the token's own
    entries already written; layer, live: as ``decode_attn``.  Scores are
    ``scale * (q_lat . latent + q_rope . rope_key)``.  Returns [slots, NH,
    C] in q_lat's dtype: softmax-weighted latent rows, which the caller
    carries out of the latent space."""
    if not _takes_kernel(use_kernel, interpret):
        return mla_decode_attn_jnp(q_lat, q_rope, latent_all, rope_all, layer,
                                   live, scale)
    interpret = resolve_interpret(interpret, "mla_decode_attn")
    return _mla_decode_attn_pallas(q_lat, q_rope, latent_all, rope_all, layer,
                                   live, scale, interpret)
