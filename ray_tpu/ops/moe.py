"""Mixture-of-experts layers, two of them.

``moe_mlp`` / ``top_k_routing``: top-k softmax routing with capacity-based
dense dispatch, for the training presets (Mixtral).  Dispatch and combine are
einsums against one-hot ``[T, E, C]`` routing tensors in float32, so
everything stays on the MXU with static shapes and, with the expert dimension
sharded over the ``ep`` mesh axis, XLA lowers the dispatch einsum into the
expert all-to-all over ICI.  It is **not for serving**: each expert accepts
at most ``C`` tokens and drops the rest, so a token's output depends on the
batch it came in, prefill-then-decode cannot match a full forward, and the
one-hot tensors are ``T x E x C`` floats (8 GB at 8,192 tokens, 64 experts).

``moe_dropless``: the layer that serves, and that trains without a capacity.
A router by its kind (``route``): sigmoid scores in float32, the top k of
score + selection bias, gates the chosen scores normalised and scaled
(``route_sigmoid``), or a softmax over all experts in float32, its top k, the
chosen probabilities over their sum (``route_softmax``, with its balance
term for the train step's total, ``balance_term``); the ``T x k`` assignments sorted by
expert, one grouped matmul over the experts that have a token (``moe_gmm``:
a Pallas kernel, group sizes by scalar prefetch, an expert with no token
neither fetched nor computed; its twin ``jax.lax.ragged_dot`` on the CPU),
combined by the gates, beside a shared expert on every token.  No capacity
and no dropped token: a token's output does not depend on its batch.  **The
backward**: the kernel is a ``custom_vjp``: the rows' gradient is the same
grouped product over the experts' matrices read transposed where they lie
(``moe_gmm_dx``), the weights' gradient a grouped ``x^T dy`` per expert,
summed over the expert's tiles in float32 (``moe_gmm_dw``), which writes
only the experts that had a token and leaves the rest zero; the twin's
derivative is ``ragged_dot``'s own.  The gates' gradient reaches the router
through its float32 scores; the top-k choice and the selection bias carry
none (no rule here moves the bias).  The sort's gather and the combine's
gather have gathers for transposes (``_rows_in``, ``_combine``), not the
scatter-adds autodiff would write.  **What the sorted layout is sized for,
and what is moved**: the layout has a row for every assignment of the
tokens it is given and a tile's padding an expert, because the layer has no
capacity and every assignment may land on the experts held here; where they
are a share of the router's experts most of it is empty, and the rows that
are moved are the rows that hold an assignment (PR 59): tokens are gathered
into the tiles that hold anything and no further (``_rows_in_live``: loops
whose trip count is data, ``tiles``, over the gathers XLA emits; the rest of
the buffer is never written, and every reader clamps to ``tiles`` or reads
by an assignment's row), the experts' rows are fetched back for the
assignments that are held and for no other (``_combine_live``,
``_by_token``: tokens in the order of how many they hold, so that a trip's
tokens need the same few fetches, one gather by token to put the sums
back), a row's gradient and a gate's are taken on the live rows from one
gather of the tokens' gradient.  The sums are the plain gathers' to the bit:
a term left out is an exact zero, and the order over a token's assignments
is kept.  **The rule that chooses** (``walks``, from the shapes alone): the
walk where a level load leaves it half the rows to move or fewer and the
layout is large enough for trips; a decode step's layout, a holder of half
the experts or of all, is the plain program.  The layer is
told which experts it holds (``expert_start`` and the leading dimension of
the weights it is given) and routes over all of them: assignments to experts
it does not hold are left out of its part of the result, as they would be
computed on the chips that hold those (on one chip the range is every
expert; a share's cell runs one holder's part and nothing stands in for the
absent chips).  ``moe_dropless_ep`` is the same layer with the experts on
the holders of a mesh axis and their **exchange**: the router scores a
token where it lives, a block of tokens walks the ring of holders by
``ppermute``, each holder computes its experts' part for the block it holds
and sends it straight back in float32; explicit collectives, no capacity,
and a sorted layout sized for every assignment of one block, of which the
rows that hold one are walked.  The
expert weights stay where they lie in the layer stack ``[layers, experts,
...]``: the kernel takes the layer index by scalar prefetch as
``decode_attn`` does.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import resolve_interpret

#: the grouped matmul's name as a device trace shows it (``moe_gmm
#: [pallas]``); pinned by tests/test_trace_names.py, read by the benchmark's
#: ``moe_gmm_roofline``
KERNEL_MOE_GMM = "moe_gmm"
#: the backward's two kernels: the rows' gradient (the grouped product over
#: the matrices transposed) and the weights' (``x^T dy`` an expert); read
#: by the benchmark's ``moe_gmm_train_roofline``
KERNEL_MOE_GMM_DX = "moe_gmm_dx"
KERNEL_MOE_GMM_DW = "moe_gmm_dw"
#: the call that hands a walk its buffer, not initialised and nothing
#: written (``_blank``): three a layer's part, under ``moe_sort`` and
#: ``moe_combine``
KERNEL_MOE_ROWS_BLANK = "moe_rows_blank"
F32 = jnp.float32


def top_k_routing(router_logits: jnp.ndarray, k: int,
                  capacity: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """router_logits: [T, E] -> (dispatch [T, E, C] bool, combine [T, E, C], aux_loss).

    Capacity-based: each expert accepts at most C tokens (overflow dropped),
    keeping shapes static for XLA.
    """
    t, e = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, k)          # [T, k]
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)  # renorm (Mixtral)

    # Position of each (token, choice) in its expert's capacity buffer:
    # earlier tokens with the same choice + tokens admitted by earlier choices.
    dispatch = jnp.zeros((t, e, capacity), dtype=jnp.float32)
    combine = jnp.zeros((t, e, capacity), dtype=jnp.float32)
    counts = jnp.zeros((e,), dtype=jnp.int32)
    for choice in range(k):
        idx = top_idx[:, choice]                                  # [T]
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)          # [T, E]
        prior = jnp.cumsum(onehot, axis=0) - onehot
        pos = (onehot * (prior + counts[None, :])).sum(-1)        # [T]
        ok = pos < capacity
        disp = (jax.nn.one_hot(idx, e)[:, :, None]
                * jax.nn.one_hot(pos, capacity)[:, None, :]
                * ok[:, None, None].astype(jnp.float32))
        dispatch = dispatch + disp
        combine = combine + disp * top_p[:, choice][:, None, None]
        counts = counts + (onehot * ok[:, None].astype(jnp.int32)).sum(0)

    # Load-balancing auxiliary loss (Switch Transformer style).
    me = probs.mean(axis=0)                            # [E] mean router prob
    ce = jax.nn.one_hot(top_idx[:, 0], e).mean(axis=0)  # [E] fraction routed
    aux_loss = e * jnp.sum(me * ce)
    return dispatch, combine, aux_loss


def moe_mlp(x: jnp.ndarray, router_w: jnp.ndarray, w_gate: jnp.ndarray,
            w_in: jnp.ndarray, w_out: jnp.ndarray, experts_per_token: int,
            capacity_factor: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sparse SwiGLU MLP with a capacity (training presets; not for serving:
    tokens over an expert's capacity are dropped, see the module docstring).
    x: [B, S, H]; router_w: [H, E]; w_gate/w_in: [E, H, M]; w_out: [E, M, H].
    Returns (out [B,S,H], aux_loss)."""
    b, s, h = x.shape
    e = router_w.shape[-1]
    tokens = x.reshape(b * s, h)
    capacity = max(1, int(capacity_factor * experts_per_token * b * s / e))
    logits = tokens @ router_w.astype(tokens.dtype)
    dispatch, combine, aux = top_k_routing(logits, experts_per_token, capacity)
    # Dispatch to expert buffers: [E, C, H]
    xs = jnp.einsum("tec,th->ech", dispatch.astype(tokens.dtype), tokens)
    gate = jnp.einsum("ech,ehm->ecm", xs, w_gate.astype(xs.dtype))
    up = jnp.einsum("ech,ehm->ecm", xs, w_in.astype(xs.dtype))
    act = jax.nn.silu(gate) * up
    out_e = jnp.einsum("ecm,emh->ech", act, w_out.astype(act.dtype))
    out = jnp.einsum("tec,ech->th", combine.astype(out_e.dtype), out_e)
    return out.reshape(b, s, h), aux


# ---------------------------------------------------------------------------
# The dropless layer
# ---------------------------------------------------------------------------

#: rows of the sorted assignments one grid step multiplies, at most: a
#: group is padded to whole tiles, so a decode step's two or three rows an
#: expert take the smallest bf16 tile and a prefill row's hundreds the MXU's
TILE_ROWS = (16, 32, 64, 128, 256)
#: output channels of a weight block of the backward's two kernels
BLOCK_N = 512
#: what a split weight block's output channels are whole multiples of
LANES = 128
#: the kernels' VMEM, of the chip's 128 MiB; the forward's weight block is
#: planned against it (``gmm_block``)
VMEM_LIMIT = 64 << 20


def route_sigmoid(x, router_w, bias, k: int, scaling: float):
    """The ``noaux_tc`` router without groups.  x: [T, H]; router_w: [H, E];
    bias: [E], the selection bias.  Scores ``sigmoid(x W_r)`` in float32;
    the experts are the top ``k`` of score + bias; the gates are the chosen
    scores (without the bias) over their sum, times ``scaling``.  Returns
    (experts [T, k] int32, gates [T, k] float32)."""
    scores = jax.nn.sigmoid(jnp.dot(x.astype(F32), router_w.astype(F32),
                                    precision=jax.lax.Precision.HIGHEST))
    # the choice is no function to differentiate: the gates' gradient goes
    # through the chosen scores, none through the bias
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(scores + bias.astype(F32)),
                           k)
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    gates = gates / (gates.sum(-1, keepdims=True) + 1e-20) * scaling
    return idx.astype(jnp.int32), gates


def route_softmax(x, router_w, k: int):
    """Qwen3-MoE's router (``norm_topk_prob`` true).  x: [T, H]; router_w:
    [H, E].  Probabilities ``softmax(x W_r)`` over all E in float32; the
    experts are the ``k`` most probable; the gates are the chosen
    probabilities over their sum.  No selection bias and no scaling.
    Returns (experts [T, k] int32, gates [T, k] float32)."""
    probs = jax.nn.softmax(jnp.dot(x.astype(F32), router_w.astype(F32),
                                   precision=jax.lax.Precision.HIGHEST), -1)
    # as ``route_sigmoid``: the choice is no function to differentiate
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(probs), k)
    gates = jnp.take_along_axis(probs, idx, axis=-1)
    return idx.astype(jnp.int32), gates / (gates.sum(-1, keepdims=True)
                                           + 1e-20)


def balance_term(x, router_w, idx):
    """The softmax router's load-balancing term over the tokens x [T, H]
    whose chosen experts are idx [T, k] (Switch Transformer's, as the
    family's ``load_balancing_loss_func`` has it): ``E sum_e f_e P_e``, f_e
    the assignments expert e got over T (they sum to k, and carry no
    gradient), P_e the mean probability the router gave it.  k where both
    are uniform."""
    probs = jax.nn.softmax(jnp.dot(x.astype(F32), router_w.astype(F32),
                                   precision=jax.lax.Precision.HIGHEST), -1)
    e = probs.shape[-1]
    f = jnp.zeros((e,), F32).at[idx.reshape(-1)].add(1.0) / idx.shape[0]
    return e * jnp.sum(f * probs.mean(0))


def route(x, small, k: int, scaling: float, router: str):
    """The layer's router by its kind (``TransformerConfig.moe_router``):
    (experts [T, k] int32, gates [T, k] float32)."""
    if router == "softmax":
        return route_softmax(x, small["router"], k)
    return route_sigmoid(x, small["router"], small["bias"], k, scaling)


def tile_rows(assignments: int, experts: int) -> int:
    """Rows a tile: the mean group, rounded up to one of ``TILE_ROWS``."""
    mean = -(-assignments // experts)
    return next((t for t in TILE_ROWS if t >= mean), TILE_ROWS[-1])


def layout_rows(assignments: int, experts: int, tile: int) -> int:
    """Rows of the sorted layout: every assignment and a tile's padding an
    expert, in whole tiles."""
    return -(-(assignments + experts * (tile - 1)) // tile) * tile


def sort_by_expert(idx, held, experts: int, tile: int):
    """Where each assignment goes in the expert-sorted, tile-padded layout.

    idx: [T, k] int32, each assignment's expert among the ``experts`` this
    layer holds (already shifted by its ``expert_start``); held: [T, k]
    bool, False for an assignment that is not this layer's (another chip's
    expert, a dead token).  Every expert's assignments are contiguous and
    start at a multiple of ``tile``.  Returns (dest [T, k] int32: the
    assignment's row, ``rows`` for one not held; source [rows] int32: the
    token of each row, T for padding; tile_expert [rows / tile] int32;
    tiles: how many tiles hold anything; sizes [experts]: assignments an
    expert)."""
    t, k = idx.shape
    rows = layout_rows(t * k, experts, tile)
    flat = jnp.where(held, idx, experts).reshape(-1)
    sizes = jnp.zeros((experts + 1,), jnp.int32).at[flat].add(1)[:experts]
    padded = -(-sizes // tile) * tile
    ends = jnp.cumsum(padded)
    order = jnp.argsort(flat, stable=True)
    # rank of an assignment among its expert's, in token order
    first = jnp.cumsum(sizes) - sizes
    rank = jnp.zeros_like(flat).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32)) - jnp.append(first, 0)[flat]
    dest = jnp.where(flat < experts,
                     jnp.append(ends - padded, 0)[flat] + rank, rows)
    source = jnp.full((rows,), t, jnp.int32).at[dest].set(
        jnp.arange(t * k, dtype=jnp.int32) // k, mode="drop")
    tile_expert = jnp.minimum(jnp.searchsorted(
        ends, jnp.arange(rows // tile, dtype=jnp.int32) * tile,
        side="right"), experts - 1).astype(jnp.int32)
    return (dest.reshape(t, k).astype(jnp.int32), source, tile_expert,
            (ends[-1] // tile).astype(jnp.int32), sizes)


def relu2(out):
    """Squared ReLU, the epilogue of an expert of two matrices."""
    return jnp.square(jnp.maximum(out, 0.0))


def _gmm_kernel(layer_ref, expert_ref, tiles_ref, x_ref, *refs, gated: bool,
                activation: Optional[str], transposed: bool):
    del layer_ref, expert_ref                   # the index maps' only
    o_ref = refs[-1]

    @pl.when(pl.program_id(1) < tiles_ref[0])
    def _tile():
        x = x_ref[...]
        if transposed:              # the matrix as stored [N, K]
            out = jax.lax.dot_general(x, refs[0][0, 0],
                                      (((1,), (1,)), ((), ())),
                                      preferred_element_type=F32)
        else:
            out = jnp.dot(x, refs[0][0, 0], preferred_element_type=F32)
        if gated:
            out = jax.nn.silu(out) * jnp.dot(x, refs[1][0, 0],
                                             preferred_element_type=F32)
        elif activation:
            out = relu2(out)
        o_ref[...] = out.astype(o_ref.dtype)


def _gmm_vmem(kdim: int, bn: int, matrices: int, tile: int,
              itemsize: int) -> int:
    """Bytes of VMEM a grid step of ``moe_gmm`` with weight blocks ``[K,
    bn]`` is counted at: two buffers of each matrix's block (the next
    expert's is fetched while this one's is multiplied), of the row tile and
    of its output; the float32 ``[tile, bn]`` temporaries, one where a single
    product goes out as it comes and six under the gated epilogue; and 2
    MiB.  The least limits that compiled for a described v5e at the five
    configurations' widths lie 1 to 6 MiB under this count (PR 52)."""
    return (2 * (matrices * kdim * bn + tile * kdim + tile * bn) * itemsize
            + (1 if matrices == 1 else 6) * tile * bn * 4 + (2 << 20))


def gmm_block(kdim: int, n: int, matrices: int, tile: int,
              itemsize: int) -> int:
    """Output channels of the weight block ``[K, bn]`` a grid step of
    ``moe_gmm`` fetches of an expert's ``[K, N]`` matrix (of each of the
    ``matrices`` a call multiplies): the whole matrix where that fits
    ``VMEM_LIMIT`` beside the row tile of ``tile`` rows (``_gmm_vmem``), else
    the fewest equal strips of whole ``LANES`` that do.  A function of what
    the kernel sees in its operands and of nothing else.  Why the largest
    (the kernel alone on the chip, ``PERF.md`` section 6, PR 52): a step's
    fetch is issued when the step before it has been waited for, so every
    step pays a latency the two buffers cannot hide: blocks of 7 to 15 MB
    move a decode step's experts at 735-746 GB/s where strips of 1 to 4 MB
    moved them at 639-685, and a prefill tile's at 485-567 for 362-476.
    Every output element is one ``dot`` over all of K whatever the strips."""
    for strips in range(1, max(n // LANES, 1) + 1):
        bn = n // strips
        if strips > 1 and (n % strips or bn % LANES):
            continue
        if _gmm_vmem(kdim, bn, matrices, tile, itemsize) <= VMEM_LIMIT:
            return bn
    raise ValueError(
        f"moe_gmm: no block of {matrices} [{kdim}, {n}] matrices of "
        f"{itemsize}-byte elements beside a tile of {tile} rows fits "
        f"{VMEM_LIMIT >> 20} MiB of VMEM")


def _gmm_pallas(x, weights, layer, tile_expert, tiles, tile: int,
                interpret: bool, activation: Optional[str] = None,
                transposed: bool = False):
    rows, kdim = x.shape
    n = weights[0].shape[-2 if transposed else -1]
    bn = gmm_block(kdim, n, len(weights), tile, x.dtype.itemsize)
    num_tiles = rows // tile

    def x_map(ni, ti, layer, tile_expert, tiles):
        return (_at(ti, tiles), 0)

    def w_map(ni, ti, layer, tile_expert, tiles):
        at = (ni, 0) if transposed else (0, ni)
        return (layer[0], tile_expert[_at(ti, tiles)], *at)

    def o_map(ni, ti, layer, tile_expert, tiles):
        return (_at(ti, tiles), ni)

    return pl.pallas_call(
        functools.partial(_gmm_kernel, gated=len(weights) == 2,
                          activation=activation, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # one step a tile where the block is the whole matrix; where it
            # is split, the strips outermost: a tile's neighbours are of its
            # expert, whose strip is then fetched once for them all
            grid=(n // bn, num_tiles),
            in_specs=[pl.BlockSpec((tile, kdim), x_map)]
            + [pl.BlockSpec((1, 1, bn, kdim) if transposed
                            else (1, 1, kdim, bn), w_map)] * len(weights),
            out_specs=pl.BlockSpec((tile, bn), o_map),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_MOE_GMM,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), tile_expert,
      jnp.reshape(tiles, (1,)).astype(jnp.int32), x, *weights)


def _gmm_jnp(x, weights, layer, tile_expert, tile: int,
             activation: Optional[str] = None, transposed: bool = False):
    """The twin of ``moe_gmm``: ``jax.lax.ragged_dot`` over the same padded
    layout (a tile is a group of its own), in the same precisions."""
    experts = weights[0].shape[1]
    sizes = jnp.zeros((experts,), jnp.int32).at[tile_expert].add(tile)
    ws = [jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)
          for w in weights]
    if transposed:
        ws = [w.swapaxes(-1, -2) for w in ws]
    # every tile is counted to its expert, those that hold nothing to the
    # last tile's: rows nobody reads
    out = jax.lax.ragged_dot(x, ws[0], sizes, preferred_element_type=F32)
    if len(ws) == 2:
        out = jax.nn.silu(out) * jax.lax.ragged_dot(
            x, ws[1], sizes, preferred_element_type=F32)
    elif activation:
        out = relu2(out)
    return out.astype(x.dtype)


def _no_grad(a):
    """The cotangent of an integer or boolean argument of a ``custom_vjp``."""
    return np.zeros(jnp.shape(a), jax.dtypes.float0)


def _at(ti, tiles):
    """Tiles past the last that holds anything repeat its blocks: nothing
    is fetched for them and nothing computed."""
    return jnp.minimum(ti, jnp.maximum(tiles[0] - 1, 0))


def _gmm_dx_kernel(layer_ref, expert_ref, tiles_ref, *refs):
    del layer_ref, expert_ref
    o_ref, half = refs[-1], (len(refs) - 1) // 2

    @pl.when(pl.program_id(1) < tiles_ref[0])
    def _tile():
        out = None
        for dy_ref, w_ref in zip(refs[:half], refs[half:-1]):
            part = jax.lax.dot_general(
                dy_ref[...], w_ref[0, 0], (((1,), (1,)), ((), ())),
                preferred_element_type=F32)
            out = part if out is None else out + part
        o_ref[...] = out.astype(o_ref.dtype)


def _gmm_dx_pallas(dys, weights, layer, tile_expert, tiles, tile: int,
                   interpret: bool):
    """The rows' gradient: ``sum_i dys[i] W_i^T`` a tile, the experts'
    matrices ``[layers, experts, K, N]`` read where they lie, contracted
    over N.  dys: one or two [rows, N]; returns [rows, K]."""
    rows, n = dys[0].shape
    kdim = weights[0].shape[-2]
    bk = BLOCK_N if kdim % BLOCK_N == 0 else kdim

    def dy_map(ki, ti, layer, tile_expert, tiles):
        return (_at(ti, tiles), 0)

    def w_map(ki, ti, layer, tile_expert, tiles):
        return (layer[0], tile_expert[_at(ti, tiles)], ki, 0)

    def o_map(ki, ti, layer, tile_expert, tiles):
        return (_at(ti, tiles), ki)

    return pl.pallas_call(
        _gmm_dx_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(kdim // bk, rows // tile),
            in_specs=[pl.BlockSpec((tile, n), dy_map)] * len(dys)
            + [pl.BlockSpec((1, 1, bk, n), w_map)] * len(weights),
            out_specs=pl.BlockSpec((tile, bk), o_map),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, kdim), dys[0].dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_MOE_GMM_DX,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), tile_expert,
      jnp.reshape(tiles, (1,)).astype(jnp.int32), *dys, *weights)


def _gmm_dw_kernel(expert_ref, tiles_ref, x_ref, dy_ref, zero_ref, o_ref):
    del zero_ref                    # aliased to the output: the zeros
    ti = pl.program_id(1)

    @pl.when(ti < tiles_ref[0])
    def _tile():
        part = jax.lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=F32)
        first = (ti == 0) | (expert_ref[ti]
                             != expert_ref[jnp.maximum(ti - 1, 0)])

        @pl.when(first)
        def _():
            o_ref[0] = part

        @pl.when(jnp.logical_not(first))
        def _():
            o_ref[0] += part

    # no tile at all (every assignment went to experts held elsewhere): the
    # grid still stands on the first tile's block and writes it back
    @pl.when((ti == 0) & (tiles_ref[0] == 0))
    def _none():
        o_ref[...] = jnp.zeros_like(o_ref)


def _gmm_dw_pallas(x, dy, experts: int, tile_expert, tiles, tile: int,
                   interpret: bool):
    """The weights' gradient: per expert ``x^T dy`` over its tiles, which
    are neighbours, so an expert's block stays in VMEM while they are
    summed, in float32.  x: [rows, K]; dy: [rows, N]; returns [experts, K,
    N] float32, zero for an expert with no tile: its block is never
    visited, and the output is a buffer of zeros given in."""
    rows, kdim = x.shape
    n = dy.shape[-1]
    bn = BLOCK_N if n % BLOCK_N == 0 else n

    def x_map(ni, ti, tile_expert, tiles):
        return (_at(ti, tiles), 0)

    def dy_map(ni, ti, tile_expert, tiles):
        return (_at(ti, tiles), ni)

    def o_map(ni, ti, tile_expert, tiles):
        return (tile_expert[_at(ti, tiles)], 0, ni)

    return pl.pallas_call(
        _gmm_dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // bn, rows // tile),
            in_specs=[pl.BlockSpec((tile, kdim), x_map),
                      pl.BlockSpec((tile, bn), dy_map),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, kdim, bn), o_map),
        ),
        out_shape=jax.ShapeDtypeStruct((experts, kdim, n), F32),
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_MOE_GMM_DW,
    )(tile_expert, jnp.reshape(tiles, (1,)).astype(jnp.int32), x, dy,
      jnp.zeros((experts, kdim, n), F32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _gmm_kernel_vjp(x, weights, layer, tile_expert, tiles, tile, interpret,
                    activation, transposed):
    return _gmm_pallas(x, weights, layer, tile_expert, tiles, tile,
                       interpret, activation, transposed)


def _gmm_vjp_fwd(x, weights, layer, tile_expert, tiles, tile, interpret,
                 activation, transposed):
    if activation or transposed:
        raise NotImplementedError(
            "moe_gmm with an activation in its epilogue or a matrix stored "
            "transposed has no backward: experts of two matrices are "
            "served, not trained (a layer_pattern has no backward pass)")
    plan = (layer, tile_expert, tiles)
    if len(weights) == 1:
        out = _gmm_pallas(x, weights, *plan, tile, interpret)
        return out, (x, weights, plan, None)
    # gate and up apart: the backward needs both, and a pass that is
    # differentiated is a layer's replay, which writes them once
    gate, up = (_gmm_pallas(x, (w,), *plan, tile, interpret)
                for w in weights)
    out = (jax.nn.silu(gate.astype(F32)) * up.astype(F32)).astype(x.dtype)
    return out, (x, weights, plan, (gate, up))


def _gmm_vjp_bwd(tile, interpret, activation, transposed, res, dy):
    x, weights, plan, gated = res
    layer, tile_expert, tiles = plan
    if gated is None:
        dys = (dy,)
    else:
        gate, up = (a.astype(F32) for a in gated)
        sig = jax.nn.sigmoid(gate)
        d32 = dy.astype(F32)
        dys = ((d32 * up * sig * (1.0 + gate * (1.0 - sig))).astype(x.dtype),
               (d32 * gate * sig).astype(x.dtype))
    dx = _gmm_dx_pallas(dys, weights, layer, tile_expert, tiles, tile,
                        interpret)
    # (rows of tiles past ``tiles`` are as undefined as the forward's:
    # no assignment sits there, and ``_rows_in``'s transpose reads none)
    experts = weights[0].shape[1]
    dws = tuple(
        jax.lax.dynamic_update_index_in_dim(
            jnp.zeros(w.shape, w.dtype),
            _gmm_dw_pallas(x, d, experts, tile_expert, tiles, tile,
                           interpret).astype(w.dtype), layer, 0)
        for w, d in zip(weights, dys))
    return dx, dws, _no_grad(layer), _no_grad(tile_expert), _no_grad(tiles)


_gmm_kernel_vjp.defvjp(_gmm_vjp_fwd, _gmm_vjp_bwd)


def _uses_kernel(use_kernel: Optional[bool], interpret: Optional[bool]):
    """``use_kernel=None``: the Pallas kernels on a TPU and where they are
    interpreted, the twin elsewhere."""
    if use_kernel is None:
        return bool(interpret) or jax.default_backend() == "tpu"
    return use_kernel


def moe_gmm(x, weights, layer, tile_expert, tiles, tile: int,
            use_kernel: Optional[bool] = None,
            interpret: Optional[bool] = None,
            activation: Optional[str] = None, transposed: bool = False):
    """Grouped matmul of expert-sorted rows with their experts' weights.

    x: [rows, K], rows in tiles of ``tile``, tile ``i`` of expert
    ``tile_expert[i]``, the first ``tiles`` of them holding anything
    (``sort_by_expert``); weights: one ``[layers, experts, K, N]`` stack, or
    two (gate and up) for ``silu(x W_g) * (x W_u)`` in one pass; layer: the
    int32 scalar index into the stacks, traced or not; ``activation``
    (static): None, or "relu2" for ``relu(x W)^2`` of the one-matrix form,
    applied to the float32 product before it is rounded and written (the up
    projection of an expert of two matrices: no round trip of the rows
    through HBM); ``transposed`` (static): the one stack is ``[layers,
    experts, N, K]``, each matrix as a linear map stores it, contracted over
    its last dimension.  Returns [rows, N] in x's dtype; rows of tiles past
    ``tiles`` are undefined.  Only the weight blocks of experts that have a
    tile are read, where they lie.  Differentiable in x and the weights (the
    module docstring's backward) in the plain forms; with an activation or a
    transposed stack the kernel refuses to be differentiated (the twin has
    ``ragged_dot``'s own derivative).

    ``use_kernel=None`` takes the Pallas kernel on a TPU and the twin
    elsewhere; ``interpret=True`` runs the kernel interpreted (tests)."""
    use_kernel = _uses_kernel(use_kernel, interpret)
    if activation not in (None, "relu2") or (
            (activation or transposed) and len(weights) != 1):
        raise ValueError(f"moe_gmm: activation {activation!r} (None or "
                         "'relu2') and transposed are of the one-matrix form")
    if not use_kernel:
        return _gmm_jnp(x, weights, layer, tile_expert, tile, activation,
                        transposed)
    return _gmm_kernel_vjp(x, tuple(weights), jnp.asarray(layer, jnp.int32),
                           tile_expert, jnp.asarray(tiles, jnp.int32), tile,
                           resolve_interpret(interpret, "moe_gmm"),
                           activation, transposed)


#: rows of the sorted layout one trip of a walk gathers, at most (``_walk``);
#: the chip, the movements alone at the two train cells' shapes (PR 59): 512
#: rows a trip move a block's 17,920 live rows in 673 us, 1,024 in 800, 4,096
#: in 877 (the trip's gather is written once more into the layout, and a
#: shorter one is still near when it is)
WALK_ROWS = 512
#: tokens one trip of a by-token walk sums (128 / 256 / 512 within 6% of one
#: another at both train cells' shapes; at an admit's 4,096 tokens 256 is
#: the best by a sixth)
WALK_TOKENS = 256


def walks(rows: int, tokens: int, k: int, held: int, experts: int,
          tile: int) -> bool:
    """Whether the layout's row movements walk what is live (``_rows_in_live``,
    ``_combine_live``) or move the layout as it stands (``_rows_in``,
    ``_combine``): from the shapes alone.  ``rows`` of the layout for
    ``tokens`` x ``k`` assignments over the ``held`` of the router's
    ``experts``.  Under a level load a walk moves the live tiles' rows in
    (the held share of the assignments and half a tile of padding an
    expert), the held assignments' rows out and one row a token to put the
    sums back in order; the plain program moves the whole layout in and
    ``k`` rows a token out.  The walk is taken where that is half the rows
    or fewer (a row costs a trip's short gather half as much again as a long
    one's: the chip, PR 59) and the layout is large enough for trips: a
    decode step's one or two thousand rows, a holder of half the experts or
    of all of them, is the plain program, which has no data-dependent loop
    to pay for."""
    assignments = tokens * k
    held_here = assignments * held // experts
    walk = held_here + held * (tile // 2) + held_here + tokens
    return (rows >= 4 * WALK_ROWS and tokens >= 2 * WALK_TOKENS
            and 2 * walk <= rows + assignments)


def rows_live_share(sizes, assignments: int):
    """The share of the sorted layout's rows that lie in tiles holding
    anything, for ``assignments`` (T x k) laid out over ``sizes.shape[-1]``
    experts of which expert e got ``sizes[..., e]``: what a walk moves over
    what the layout is sized for.  float32."""
    held = sizes.shape[-1]
    tile = tile_rows(assignments, held)
    return ((-(-sizes // tile)).sum(-1) * tile).astype(F32) / layout_rows(
        assignments, held, tile)


def _blank(shape, dtype, after, kernel: bool):
    """A buffer a walk writes its live rows into.  Under the compiled
    kernels, which clamp to ``tiles``, it is not initialised: no pass over a
    layout sized for the worst case.  It comes from a Pallas call that
    writes nothing (``KERNEL_MOE_ROWS_BLANK``) and takes ``after``, an array
    the walk reads anyway, so that the buffer begins to exist where the walk
    begins (``jax.lax.empty``'s ``AllocateBuffer`` has no operand, and the
    four-chip step's schedule put all 48 of them hundreds of instructions
    ahead of their loops: 2.4e9 bytes more at the peak; sandbox compile, PR
    59).  Zeros for the twin, whose ``ragged_dot`` multiplies every row, and
    where the kernels are interpreted."""
    if not kernel or jax.default_backend() != "tpu":
        return jnp.zeros(shape, dtype)
    return pl.pallas_call(
        lambda after_ref, o_ref: None,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        name=KERNEL_MOE_ROWS_BLANK)(after)


def _walk(rows: int, upto, body, init):
    """``body(at, stretch, carry)`` for every stretch of rows from ``at``
    that holds a row under ``upto`` (traced): ``WALK_ROWS`` rows, or the
    most of them that divide the layout's ``rows`` (whole tiles): no stretch
    laps another, so a body may write over what it read."""
    stretch = math.gcd(rows, WALK_ROWS)
    return jax.lax.fori_loop(
        0, -(-upto // stretch),
        lambda c, carry: body(c * stretch, stretch, carry), init)


def _take(x, at):
    """Rows ``at`` of x, zeros for an index past its end."""
    return jnp.take(x, at, axis=0, mode="fill", fill_value=0)


def _back(x, order):
    """Rows of x, which stand in the order ``order`` (a permutation), back
    in place: a gather that needs no zeros for an index out of range, which
    are a pass over its result."""
    _, back = jax.lax.sort((order, jnp.arange(
        order.shape[0], dtype=order.dtype)), num_keys=1)
    return x.at[back].get(mode="promise_in_bounds", unique_indices=True)


def _by_token(ys, dest, mine, gates):
    """``out[t] = sum_j gates[t, j] ys[dest[t, j]]`` in float32 over the
    assignments that are ``mine``, in the order j = 0 .. k-1, fetching no
    row for an assignment that is not (``gates`` None: unit gates).  The
    tokens are walked with those that hold most first, ``WALK_TOKENS`` a
    trip, a trip's tokens summed over as many of their held assignments as
    its first token has.  Returns (the sums in that order [T, H] float32,
    the order [T] int32): ``_back`` puts them in place, one gather by token.
    Leaving out a term that is an exact zero leaves a float32 sum as it
    was."""
    t, k = dest.shape
    rows, h = ys.shape
    count = mine.sum(-1).astype(jnp.int32)
    # a token's held assignments first, in their order, then the tokens by
    # how many they hold: two stable sorts that carry the rows and the gates
    # with their keys (an index taken and applied is a gather by element,
    # which costs the chip more than the rows it steers: PR 59's trace)
    held = (jnp.where(mine, dest, rows),) + (
        () if gates is None else (jnp.where(mine, gates, 0.0),))
    _, *held = jax.lax.sort((jnp.logical_not(mine).astype(jnp.int32), *held),
                            dimension=1, is_stable=True, num_keys=1)
    _, *held = jax.lax.sort((jnp.broadcast_to(-count[:, None], (t, k)), *held),
                            dimension=0, is_stable=True, num_keys=1)
    dest, gates = held if gates is not None else (held[0], None)
    count, order = jax.lax.sort((-count, jnp.arange(t, dtype=jnp.int32)),
                                is_stable=True, num_keys=1)
    count = -count

    def tokens(c, out):
        at = jnp.minimum(c * WALK_TOKENS, t - WALK_TOKENS)
        d = jax.lax.dynamic_slice_in_dim(dest, at, WALK_TOKENS)
        g = (None if gates is None
             else jax.lax.dynamic_slice_in_dim(gates, at, WALK_TOKENS))

        def column(i, acc):
            term = _take(ys, jax.lax.dynamic_index_in_dim(
                d, i, 1, keepdims=False)).astype(F32)
            if g is not None:
                term = jax.lax.dynamic_index_in_dim(g, i, 1) * term
            return acc + term

        acc = jax.lax.fori_loop(0, count[at], column,
                                jnp.zeros((WALK_TOKENS, h), F32))
        return jax.lax.dynamic_update_slice_in_dim(out, acc, at, 0)

    some = (count > 0).sum()
    out = jax.lax.fori_loop(0, -(-some // WALK_TOKENS), tokens,
                            jnp.zeros((t, h), F32))
    return out, order


@jax.custom_vjp
def _rows_in(x, source, dest):
    """The rows of x [T, H] in the sorted layout: row r is token
    ``source[r]`` (T: padding, zeros); every row of the layout is defined
    on return.  Its transpose is a gather too: a token's gradient is the
    sum of its assignments' rows, at ``dest``."""
    return _take(x, source)


def _rows_in_fwd(x, source, dest):
    return _rows_in(x, source, dest), (source, dest)


def _rows_in_bwd(res, g):
    source, dest = res
    # an assignment at a time: [T, k, H] at once is k copies of the tokens
    dx = sum(_take(g, dest[:, j]).astype(F32) for j in range(dest.shape[1]))
    return dx.astype(g.dtype), _no_grad(source), _no_grad(dest)


_rows_in.defvjp(_rows_in_fwd, _rows_in_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rows_in_live(x, source, dest, mine, live, kernel):
    """``_rows_in`` over the first ``live`` rows of the layout alone (whole
    tiles: ``tiles * tile``): the rows of the tiles that hold an assignment
    are defined on return, padding rows among them zeros (``moe_gmm_dw``
    sums over a whole tile); the rows past them are whatever ``_blank``
    left there, and no reader looks: the grouped kernels clamp to ``tiles``
    and the transposes read by ``dest``, which points at assignments.  Its
    transpose sums a token's held rows (``_by_token``)."""
    return _walk(source.shape[0], live, lambda at, n, xs: (
        jax.lax.dynamic_update_slice_in_dim(xs, _take(
            x, jax.lax.dynamic_slice_in_dim(source, at, n)), at, 0)),
        _blank((source.shape[0], x.shape[1]), x.dtype, source, kernel))


def _rows_in_live_fwd(x, source, dest, mine, live, kernel):
    return (_rows_in_live(x, source, dest, mine, live, kernel),
            (source, dest, mine, live))


def _rows_in_live_bwd(kernel, res, g):
    dx, order = _by_token(g, res[1], res[2], None)
    return (_back(dx.astype(g.dtype), order), *(_no_grad(a) for a in res))


_rows_in_live.defvjp(_rows_in_live_fwd, _rows_in_live_bwd)


def _picked(ys, dest, j):
    """Assignment j's row of ys for every token [T, H], float32; zeros
    where it is not held (``dest`` past the end)."""
    return _take(ys, dest[:, j]).astype(F32)


def _gate_row(rows, gates, dest):
    """The gate of the assignment that sits in each row (0: padding)."""
    return jnp.zeros((rows,), F32).at[dest.reshape(-1)].set(
        gates.reshape(-1), mode="drop")


@jax.custom_vjp
def _combine(ys, gates, mine, source, dest):
    """``out[t] = sum_j gates[t, j] ys[dest[t, j]]`` in float32 over the
    assignments that are ``mine``: the experts' rows back at their tokens,
    weighted.  ys: [rows, H]; gates: [T, k] float32; mine: [T, k] bool;
    source [rows], dest [T, k]: ``sort_by_expert``'s.  Its transposes are
    gathers too: a row's gradient is its token's, times the gate of the
    assignment that sits there (every row of the layout defined); a gate's
    is its row times its token's."""
    # an assignment at a time: [T, k, H] at once is k copies of the tokens
    gates = jnp.where(mine, gates, 0.0)
    return sum(gates[:, j, None] * _picked(ys, dest, j)
               for j in range(dest.shape[1]))


def _combine_fwd(ys, gates, mine, source, dest):
    return (_combine(ys, gates, mine, source, dest),
            (ys, jnp.where(mine, gates, 0.0), mine, source, dest))


def _combine_bwd(res, g):
    ys, gates, mine, source, dest = res
    # (the token's gradient rounded to the rows' type before it is spread
    # over them, as a dense layer's backward rounds it)
    d_ys = (_take(g.astype(ys.dtype), source)
            * _gate_row(ys.shape[0], gates, dest)[:, None]).astype(ys.dtype)
    d_gates = jnp.stack([(_picked(ys, dest, j) * g).sum(-1)
                         for j in range(dest.shape[1])], axis=1)
    return (d_ys, jnp.where(mine, d_gates, 0.0), _no_grad(mine),
            _no_grad(source), _no_grad(dest))


_combine.defvjp(_combine_fwd, _combine_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _combine_live(ys, gates, mine, source, dest, live, kernel):
    """``_combine`` that fetches the rows of held assignments alone
    (``_by_token``); ``live``: the layout's rows in tiles that hold
    anything.  Its transposes walk those rows: a row's gradient as
    ``_combine``'s, defined in the live tiles (padding rows zeros); a gate's
    gradient is taken where its row lies, the row times its token's
    gradient summed over H in float32, and fetched by ``dest`` as a scalar:
    one gather of the tokens' float32 gradient a stretch serves both, and
    under the kernels a stretch of the rows' gradient is written over the
    stretch of ``ys`` it was made from (``ys`` has no reader after this; its
    rows past the live tiles stay what they were, which no reader looks at:
    ``_rows_in_live``)."""
    return _back(*_by_token(ys, dest, mine, gates))


def _combine_live_fwd(ys, gates, mine, source, dest, live, kernel):
    return (_combine_live(ys, gates, mine, source, dest, live, kernel),
            (ys, jnp.where(mine, gates, 0.0), mine, source, dest, live))


def _combine_live_bwd(kernel, res, g):
    ys, gates, mine, source, dest, live = res
    rows = ys.shape[0]
    gate_row = _gate_row(rows, gates, dest)

    def stretch(at, n, carry):
        d_ys, dots = carry
        g_rows = _take(g, jax.lax.dynamic_slice_in_dim(source, at, n))
        # (rounded to the rows' type before it is spread, as ``_combine``'s)
        d = (g_rows.astype(ys.dtype) * jax.lax.dynamic_slice_in_dim(
            gate_row, at, n)[:, None]).astype(ys.dtype)
        dot = (jax.lax.dynamic_slice_in_dim(d_ys if kernel else ys, at,
                                            n).astype(F32) * g_rows).sum(-1)
        return (jax.lax.dynamic_update_slice_in_dim(d_ys, d, at, 0),
                jax.lax.dynamic_update_slice_in_dim(dots, dot, at, 0))

    # (the twin's ``ragged_dot`` multiplies every row: zeros past the live)
    d_ys, dots = _walk(rows, live, stretch, (
        ys if kernel else jnp.zeros_like(ys), jnp.zeros((rows,), F32)))
    d_gates = jnp.where(mine, _take(dots, dest.reshape(-1)).reshape(
        dest.shape), 0.0)
    return (d_ys, d_gates, *(_no_grad(a) for a in res[2:]))


_combine_live.defvjp(_combine_live_fwd, _combine_live_bwd)


def _held_part(x, idx, gates, live, stacks, layer, expert_start, k: int,
               use_kernel, interpret, experts: int):
    """What the experts held here add for the tokens x [T, H] (in the
    dtype they are multiplied in) under the router's choice idx [T, k] and
    gates [T, k]: the assignments to experts ``expert_start`` and on,
    sorted, through the grouped products, combined.  ``experts``: the
    router's width, which with the shapes chooses how the rows are moved
    (``walks``).  Returns (out [T, H]
    float32, sizes [held] int32: the assignments each held expert
    computed)."""
    t, _ = x.shape
    held = stacks["w_out"].shape[1]
    if jnp.ndim(expert_start):
        expert_start = expert_start[:, None]
    mine = (idx >= expert_start) & (idx < expert_start + held)
    if live is not None:
        mine = mine & live[:, None]
    with jax.named_scope("moe_sort"):
        tile = tile_rows(t * k, held)
        dest, source, tile_expert, tiles, sizes = sort_by_expert(
            idx - expert_start, mine, held, tile)
        walk = walks(source.shape[0], t, k, held, experts, tile)
        kernel = _uses_kernel(use_kernel, interpret)
        xs = (_rows_in_live(x, source, dest, mine, tiles * tile, kernel)
              if walk else _rows_in(x, source, dest))
    with jax.named_scope("moe_experts"):
        gmm = functools.partial(moe_gmm, layer=layer, tile_expert=tile_expert,
                                tiles=tiles, tile=tile, use_kernel=use_kernel,
                                interpret=interpret)
        if "w_gate" in stacks:
            act = gmm(xs, (stacks["w_gate"], stacks["w_in"]))
        else:
            act = gmm(xs, (stacks["w_up"],), activation="relu2",
                      transposed=True)
        ys = gmm(act, (stacks["w_out"],))
    with jax.named_scope("moe_combine"):
        # an assignment that is not this layer's reads a row past the end
        out = (_combine_live(ys, gates, mine, source, dest, tiles * tile,
                             kernel)
               if walk else _combine(ys, gates, mine, source, dest))
    return out, sizes


def _shared_expert(x, small):
    """The shared expert on every token x [T, H], float32."""
    if "shared_gate" in small:
        up = (jax.nn.silu(x @ small["shared_gate"].astype(x.dtype))
              * (x @ small["shared_in"].astype(x.dtype)))
    else:
        # squared in float32 before it is rounded, as the kernel's
        up = relu2(jnp.dot(x, small["shared_in"].astype(x.dtype),
                            preferred_element_type=F32)).astype(x.dtype)
    return (up @ small["shared_out"].astype(x.dtype)).astype(F32)


def moe_dropless(x, small, stacks, layer, *, experts_per_token: int,
                 scaling: float, compute_dtype=None, live=None,
                 expert_start=0, router: str = "sigmoid",
                 shared: bool = True, use_kernel: Optional[bool] = None,
                 interpret: Optional[bool] = None):
    """The dropless expert layer of one layer of a stack.  x: [T, H];
    ``small``: this layer's ``router`` [H, E], ``bias`` [E] and, where the
    model has one, the shared expert's ``shared_gate`` / ``shared_in`` [H,
    S] and ``shared_out`` [S, H]; ``stacks``: ``w_gate``, ``w_in`` [layers,
    held, H, M] and ``w_out`` [layers, held, M, H], or, an expert of two
    matrices ``W_out relu(W_up x)^2``, ``w_up`` and ``w_out``, both [layers,
    held, M, H]: the up projection as a linear map stores it, so that the
    stack's minor dimension is H whatever M is (an M that is no multiple of
    the 128 lanes would make the chip lay [.., H, M] out with H minor, and
    every program that hands it to the kernel copy the whole stack first:
    2.55 GB a dispatch at 64 experts of 2688 x 1856 in 4 layers, sandbox
    compile, PR 46); the shared expert is of the same form without
    ``shared_gate``; the experts
    ``expert_start`` to ``expert_start + held`` of all E (an int; or [T]
    int32, a first expert for each token, and the held weights stand for
    the experts from there on: ``TransformerConfig.share_by_position``);
    live: [T] bool, the tokens that count (a padded position, an idle slot:
    routed nowhere, their output is the shared expert's alone).
    ``router``: "sigmoid" or "softmax" (``route``).
    ``shared=False`` leaves the shared expert to another holder of this
    layer.  The router scores x as it comes (float32 where the caller has
    it); the experts multiply it in ``compute_dtype`` (x's own where none is
    given).

    Returns (out [T, H] in x's dtype, counts [2] int32: assignments this
    layer computed, experts it touched; experts [T, k] int32: the router's
    choice for every token, live or not, among all E; load [held] int32:
    the assignments each held expert computed)."""
    with jax.named_scope("moe_route"):
        idx, gates = route(x, small, experts_per_token, scaling, router)
    x = x.astype(compute_dtype or x.dtype)
    out, sizes = _held_part(x, idx, gates, live, stacks, layer, expert_start,
                            experts_per_token, use_kernel, interpret,
                            small["router"].shape[-1])
    if shared and "shared_in" in small:
        with jax.named_scope("moe_shared"):
            out = out + _shared_expert(x, small)
    counts = jnp.stack([sizes.sum(), (sizes > 0).sum()]).astype(jnp.int32)
    return out.astype(x.dtype), counts, idx, sizes


#: the scopes around the exchange's two collectives, for a profiler's view
#: (pinned by tests/test_trace_names.py): tokens out to the next holder,
#: results back to the tokens' owner
SCOPE_EXCHANGE_OUT = "moe_exchange_tokens_out"
SCOPE_EXCHANGE_BACK = "moe_exchange_results_back"


def exchange_bytes(tokens: int, hidden: int, k: int, holders: int,
                   itemsize: int) -> int:
    """Bytes one holder sends in ``moe_dropless_ep``'s forward for its
    ``tokens`` tokens: a block of tokens with its choices and gates on each
    of ``holders - 1`` hops, and a float32 block of results back from each
    of as many steps.  (The backward sends the same again, transposed.)"""
    block = tokens * (hidden * itemsize + k * 8)
    return (holders - 1) * (block + tokens * hidden * 4)


def moe_dropless_ep(x, small, stacks, layer, *, axis: str,
                    experts_per_token: int, scaling: float,
                    router: str = "sigmoid", compute_dtype=None,
                    use_kernel: Optional[bool] = None,
                    interpret: Optional[bool] = None):
    """``moe_dropless`` where the experts lie on the ``n`` holders of the
    mesh axis ``axis``, ``held = E / n`` each, holder ``i`` the experts ``i
    held`` and on: the body of a ``shard_map`` in which ``axis`` is manual.
    x: [T, H], this holder's own tokens; ``small``: the layer's router
    (whole on every holder); ``stacks``: this holder's experts [layers,
    held, ...] in the dtype they multiply in.

    **The exchange**, explicit collectives and no capacity: the router
    scores a token on the holder that owns it; a block (the tokens, their
    chosen experts, their gates) then walks the ring of holders, one hop a
    step (``ppermute``; ``SCOPE_EXCHANGE_OUT``), and at step ``s`` a holder
    computes what ITS experts add for the block of holder ``i - s``:
    the block's assignments that land here, sorted, through the grouped
    products, combined under the block's gates (``_held_part``; the sorted
    layout is sized for every assignment of ONE block landing here, ``T k``
    rows, not of all ``n``: nothing is dropped however the router leans;
    under a level load a holder gets ``1 / n`` of them, and the rows moved
    in and out of the layout are those that hold an assignment, chosen from
    the shapes: ``walks``), and sends that float32 partial result straight
    back to the block's owner (``ppermute`` by ``-s``;
    ``SCOPE_EXCHANGE_BACK``), which sums the ``n`` parts in float32.  A hop
    has no consumer before the next step, so it can run under this step's
    grouped products.  Each step's part is a ``jax.checkpoint``: the
    backward keeps a block, not its sorted rows, and replays the step.
    The transposes are ``ppermute``'s own: a gate's gradient comes home to
    the router that made it.

    Returns (out [T, H] in x's dtype; load [held] int32, the assignments
    each expert held here computed over the ``n`` blocks; experts [T, k]
    int32, the router's choice for this holder's tokens; the share of the
    sorted layout's rows that lay in tiles holding an assignment, float32,
    a mean over the ``n`` steps: ``rows_live_share``)."""
    n = jax.lax.axis_size(axis)
    held = stacks["w_out"].shape[1]
    start = jax.lax.axis_index(axis) * held
    with jax.named_scope("moe_route"):
        idx, gates = route(x, small, experts_per_token, scaling, router)
    x = x.astype(compute_dtype or x.dtype)

    @jax.checkpoint
    def part(block, stacks):
        return _held_part(*block, None, stacks, layer, start,
                          experts_per_token, use_kernel, interpret, n * held)

    block, out, load = (x, idx, gates), None, jnp.zeros((held,), jnp.int32)
    rows_live = 0.0
    for s in range(n):
        if s < n - 1:
            with jax.named_scope(SCOPE_EXCHANGE_OUT):
                onward = jax.lax.ppermute(
                    block, axis, [(j, (j + 1) % n) for j in range(n)])
        mine, sizes = part(block, stacks)
        load = load + sizes
        rows_live += rows_live_share(sizes, idx.size) / n
        if s:
            with jax.named_scope(SCOPE_EXCHANGE_BACK):
                mine = jax.lax.ppermute(
                    mine, axis, [(j, (j - s) % n) for j in range(n)])
        out = mine if out is None else out + mine
        if s < n - 1:
            block = onward
    if "shared_in" in small:
        with jax.named_scope("moe_shared"):
            out = out + _shared_expert(x, small)
    return out.astype(x.dtype), load, idx, rows_live
