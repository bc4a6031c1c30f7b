"""Layers of several kinds in one model: layers with a recurrent state
beside full-attention layers (``TransformerConfig.layer_pattern``), on the
serving path.  This file is what is particular to the three recurrent
kinds, "linear" (the gated delta rule), "ssm" (Mamba-2's state-space mixer)
and "ssm1" (Mamba-1's selective scan; a model has one of the three): their
weights, their per-slot state and their mixers, for whole rows
(``linear_prefill``, ``ssm_prefill``, ``ssm1_prefill``) and for one token a
slot (``linear_step``, ``ssm_step``, ``ssm1_step``), the gated memory unit
that reads what an "ssm1" layer hands down the stack (``gmu``), and the
pattern's parameter tree (``init_blocks``).  The walk over the layers, the
block's wiring, the full-attention layers and the MLP or dropless experts
(under every mixer,
or as "mlp" layers of their own where ``cfg.sublayers_alone``) are
``decode.py``'s, which hands a kind's two (``recurrent``) to
``decode.layer_stack`` where the cache tree has a ``state``;
``serve/llm.py`` runs the same calls on it as on any cache:

* ``k``, ``v``: [full_layers, slots, max_len, NKV * D], the dense cache of
  ``decode.py`` with rows for the full-attention layers only, written and
  read by ``decode.prefill_attention`` / ``decode_attention``;
* ``state``: [linear_layers, slots, heads / p, key_dim, p * value_dim]
  float32, the delta rule's state (``ops/gated_delta.py``, ``ops/kda.py``)
  with ``p`` neighbouring heads side by side along the lanes, the fewest
  that fill whole 128-lane tiles (``gated_delta.pack_state``: 2 at a
  value_dim of 192, 1, the plain [.., heads, key_dim, value_dim], at 128),
  or [ssm_layers, slots, heads, head width P, state width N] float32, the
  state-space one (``ops/ssd.py``), or [ssm1_layers, slots, state columns
  N, inner / 128, 128] float32, the selective scan's
  (``ops/selective_scan.py``: channels on the lanes and the sublanes, so
  that 16 columns leave no lane empty): constant in the context;
* ``conv``: [recurrent layers, slots, conv_width - 1, channels], the last
  inputs of the mixer's causal convolution;
* ``length``: [slots].

A linear layer's mixer, for input ``x`` (``linear_*`` sizes of the config)::

    [q; k; v] = silu(causal_conv(W_qkv x));  q = l2norm(q) / sqrt(dk)
    k = l2norm(k);  beta = (2 if neg_eigval else 1) * sigmoid(w_b x)
    g = -exp(A_log) * softplus(w_a x + dt_bias)        (alpha = exp(g))
    o = gated_delta_rule(q, k, v, g, beta)
    y = W_o [rmsnorm_head(o) * silu(W_g x)]

Its variant (Kimi Delta Attention, ``linear_decay_per_channel``) makes ``g``
one decay a key channel, ``-exp(A_log_head) * softplus(W_f_up W_f_down x +
dt_bias)`` in ``R^{heads x dk}``, the rule ``ops/kda.py``'s and the gate a
sigmoid, the decay's and the gate's projections through a bottleneck of
``linear_gate_rank``.  A full layer may gate its
attention's output (``attn_output_gate``, ``decode._proj_out``) and have
heads of a published width (``attn_head_dim``).

An ssm layer's mixer (``linear_num_heads`` H heads of ``linear_value_dim``
P, ``ssm_groups`` G groups of ``linear_key_dim`` N, head ``h`` reads group
``h // (H / G)``)::

    [z | xBC | dt~] = W_in x;  xBC = silu(causal_conv(xBC) + b_conv)
    dt = softplus(dt~ + dt_bias);  a = exp(-exp(A_log) dt)     float32
    S_t = a_t S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
    out = W_out [ rmsnorm_group(y * silu(z)) ]    groups of H P / G channels

An ssm1 layer's mixer (``ssm1_inner`` C channels, ``ssm1_state`` N columns,
``ssm1_dt_rank`` R; no heads, no groups, no norm inside)::

    [u | z] = W_in x;  u = silu(causal_conv(u) + b_conv)
    [dt~ | B | C] = W_x u;  dt = softplus(W_dt dt~ + dt_bias)  in R^C, float32
    S_t = exp(dt_t A) * S_{t-1} + (dt_t u_t) B_t^T     A = -exp(A_log) [N, C]
    y_t = S_t C_t + D u_t;  out = W_out (y * silu(z))

and ``y``, before the gate, is the memory the layer hands down the stack: a
"gmu" layer below is ``W_out(memory * silu(W_in x))`` at the same position,
with no state and no convolution of its own (a cross-decoder's,
``decode.py``: its "cross" layers read the one "full" layer's K/V rows).
Where the configuration has ``diff_attn`` an attention layer carries four
vectors for ``lam`` and the scale of the norm over a pair's two value heads
(``decode.diff_combine``).

Blocks are wired ``h = x + norm(mixer(x)); out = h + norm(mlp(h))``
(``norm_on_output``) or pre-norm, or each layer is one of the two
(``sublayers_alone``), and nothing adds positions
(``no_positions``): the recurrences and convolutions carry them.

Parameters are stacked per kind with leading dims [periods, layers of the
kind in a period], so one ``lax.scan`` over periods traces one period's
layers whatever the depth.  Right padding is harmless by construction, not
by causality: a position at or beyond a row's length has ``beta = 0, g =
0`` (the state passes through) and the convolution tail is gathered at the
row's length, so the state a prefill leaves is each row's as of its true
length.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops import gated_delta, selective_scan, ssd
from .config import TransformerConfig
from .transformer import Params, _norm

L2_EPS = 1e-6


#: the published draw of the ssm kind's step and decay (Mamba-2's
#: ``time_step_min`` / ``_max`` and ``A_init_range``): dt log-uniform, A
#: uniform, so that ``a = exp(-A dt)`` spreads over about (0.2, 0.999)
SSM_DT_RANGE = (0.001, 0.1)
SSM_A_RANGE = (1.0, 16.0)


def _channels(cfg: TransformerConfig) -> Tuple[int, int]:
    """(all key channels, all value channels) of the mixer."""
    return (cfg.linear_num_heads * cfg.linear_key_dim,
            cfg.linear_num_heads * cfg.linear_value_dim)


# ---------------------------------------------------------------------------
# Parameters and per-slot state
# ---------------------------------------------------------------------------

def init_blocks(key: jax.Array, cfg: TransformerConfig, dtype) -> Params:
    """``params["blocks"]`` of a model with a layer pattern: an entry a kind
    of its pattern (``"linear"``, ``"ssm"``, ``"full"``, ``"window"``,
    ``"mlp"``), leaves
    [periods, layers of the kind a period, ...], and with dropless experts
    ``"experts"``, the routed experts of every expert layer in layer order
    [expert layers, experts held, ...] (a layer then has the router and the
    shared expert under ``moe`` where it had ``mlp``).  An MLP lies under
    every mixer, or, with ``cfg.sublayers_alone``, in the "mlp" layers
    alone; with ``cfg.mlp_act`` it is two matrices (no ``w_gate``; a routed
    expert's up projection is ``w_up`` [M, H], ``ops.moe.moe_dropless``).

    The decay's parameters are drawn so that ``alpha`` spreads over about
    (0.9, 1) across heads, or across channels where the decay is one a
    channel (``-log alpha`` log-uniform in 0.002..0.08): a state that decays
    to nothing within a few tokens would make every check of it vacuous.
    The gate projections ``w_a`` (``w_f_up``) / ``w_b`` are small for the
    same reason: they see the residual stream, whose scale grows with
    depth.  The ssm kind's are the published draw (``SSM_DT_RANGE``,
    ``SSM_A_RANGE``), the step's columns of ``w_in`` small for that
    reason."""
    h, m, hd = cfg.hidden_size, cfg.mlp_size, cfg.head_dim
    nh, nkv, lh = cfg.num_heads, cfg.num_kv_heads, cfg.linear_num_heads
    kd, vd = _channels(cfg)
    pattern, periods = cfg.layer_pattern, cfg.num_periods
    keys = iter(jax.random.split(key, 24))
    # what the variants add draws from keys of its own: the scalar-decay
    # mixer's and the plain full layer's weights are what they were
    more = iter(jax.random.split(jax.random.fold_in(key, 1), 24))

    def dense(lead, shape, fan_in, gain=1.0, keys=keys):
        return (jax.random.normal(next(keys), lead + shape, dtype)
                * (gain * fan_in ** -0.5)).astype(dtype)

    def ones(lead, n):
        if not cfg.use_rmsnorm:     # a LayerNorm: a scale and a bias
            return {"scale": jnp.ones(lead + (n,), dtype),
                    "bias": jnp.zeros(lead + (n,), dtype)}
        return {"scale": jnp.ones(lead + (n,), dtype)}

    gated = not cfg.mlp_act

    def mlp(lead, under_mixer=True, sparse=cfg.moe_dropless):
        """The feed-forward of the layers ``lead`` and its norm; nothing
        under a mixer whose layer is that sublayer alone, and the norm alone
        where a dense prefix makes the layers of a kind differ: their MLPs
        then lie by layer (``"dense"``, ``"moe"`` below)."""
        if cfg.sublayers_alone and under_mixer:
            return {}
        norm = {"mlp_norm": ones(lead, h)}
        if cfg.dense_prefix_layers and under_mixer:
            return norm
        if not sparse:
            if gated:
                ws = {"w_gate": dense(lead, (h, m), h),
                      "w_in": dense(lead, (h, m), h),
                      "w_out": dense(lead, (m, h), m)}
            else:
                ws = {"w_in": dense(lead, (h, m), h, keys=more),
                      "w_out": dense(lead, (m, h), m, keys=more)}
            return {"mlp": ws, **norm}
        e, sm = cfg.num_experts, cfg.shared_experts * cfg.expert_mlp_size
        moe = {"router": dense(lead, (h, e), h, keys=more),
               "bias": jnp.zeros(lead + (e,), dtype)}
        if sm and gated:
            moe["shared_gate"] = dense(lead, (h, sm), h, keys=more)
        if sm:
            moe.update(shared_in=dense(lead, (h, sm), h, keys=more),
                       shared_out=dense(lead, (sm, h), sm, keys=more))
        return {"moe": moe, **norm}

    blocks: Params = {}
    if "linear" in pattern:
        lead = (periods, pattern.count("linear"))
        rank, decays = cfg.linear_gate_rank, (
            kd if cfg.linear_decay_per_channel else lh)
        rate = jnp.exp(jax.random.uniform(
            next(keys), lead + (decays,), jnp.float32,
            jnp.log(0.002), jnp.log(0.08)))
        mixer = {"w_qkv": dense(lead, (h, 2 * kd + vd), h),
                 "conv_w": dense(lead, (cfg.linear_conv_width, 2 * kd + vd),
                                 cfg.linear_conv_width)}
        if cfg.linear_decay_per_channel:
            mixer.update(w_f_down=dense(lead, (h, rank), h, keys=more),
                         w_f_up=dense(lead, (rank, kd), rank, 0.1, more),
                         w_g_down=dense(lead, (h, rank), h, keys=more),
                         w_g_up=dense(lead, (rank, vd), rank, keys=more))
        else:
            mixer["w_a"] = dense(lead, (h, lh), h, 0.1)
        mixer.update(
            w_b=dense(lead, (h, lh), h, 0.5),
            A_log=jnp.zeros(lead + (lh,), dtype),
            # softplus^-1(rate), so that alpha = exp(-rate) at w_a x = 0
            dt_bias=jnp.log(jnp.expm1(rate)).astype(dtype))
        if not cfg.linear_decay_per_channel:
            mixer["w_g"] = dense(lead, (h, vd), h)
        mixer.update(o_norm=ones(lead, cfg.linear_value_dim),
                     w_o=dense(lead, (vd, h), vd))
        blocks["linear"] = {"mixer": mixer, "mixer_norm": ones(lead, h),
                            **mlp(lead)}
    if "ssm" in pattern:
        lead = (periods, pattern.count("ssm"))
        inner, mixed = cfg.ssm_channels
        width = cfg.linear_conv_width
        dt = jnp.exp(jax.random.uniform(
            next(more), lead + (lh,), jnp.float32,
            *(jnp.log(v) for v in SSM_DT_RANGE)))
        a = jax.random.uniform(next(more), lead + (lh,), jnp.float32,
                               *SSM_A_RANGE)
        w_in = dense(lead, (h, inner + mixed + lh), h, keys=more)
        blocks["ssm"] = {
            "mixer": {
                # [z | x B C | dt]: the step's columns small
                "w_in": w_in.at[..., inner + mixed:].multiply(0.1),
                "conv_w": dense(lead, (width, mixed), width, keys=more),
                "conv_b": jnp.zeros(lead + (mixed,), dtype),
                "A_log": jnp.log(a).astype(dtype),
                "D": jnp.ones(lead + (lh,), dtype),
                # softplus^-1(dt), so that the step is dt at w_in x = 0
                "dt_bias": jnp.log(jnp.expm1(dt)).astype(dtype),
                "o_norm": ones(lead, inner),
                "w_out": dense(lead, (inner, h), inner, keys=more)},
            "mixer_norm": ones(lead, h), **mlp(lead)}
    # the kinds of a stack of segments draw from keys of their own
    late = iter(jax.random.split(jax.random.fold_in(key, 3), 24))
    if "ssm1" in pattern:
        lead = (periods, pattern.count("ssm1"))
        inner, n, rank = cfg.ssm1_inner, cfg.ssm1_state, cfg.ssm1_dt_rank
        width = cfg.linear_conv_width
        dt = jnp.exp(jax.random.uniform(
            next(late), lead + (inner,), jnp.float32,
            *(jnp.log(v) for v in SSM_DT_RANGE)))
        blocks["ssm1"] = {
            "mixer": {
                "w_in": dense(lead, (h, 2 * inner), h, keys=late),  # [u | z]
                "conv_w": dense(lead, (width, inner), width, keys=late),
                "conv_b": jnp.zeros(lead + (inner,), dtype),
                # [dt~ | B | C]
                "w_x": dense(lead, (inner, rank + 2 * n), inner, keys=late),
                "w_dt": dense(lead, (rank, inner), rank, keys=late),
                # softplus^-1(dt), so that the step is dt at w_dt dt~ = 0
                "dt_bias": jnp.log(jnp.expm1(dt)).astype(dtype),
                # the published draw: A = 1 .. N down a channel's columns;
                # stored [N, inner], as the state lies
                "A_log": jnp.broadcast_to(
                    jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None],
                    lead + (n, inner)).astype(dtype),
                "D": jnp.ones(lead + (inner,), dtype),
                "w_out": dense(lead, (inner, h), inner, keys=late)},
            "mixer_norm": ones(lead, h), **mlp(lead)}
    if "gmu" in pattern:
        lead = (periods, pattern.count("gmu"))
        inner = cfg.ssm1_inner
        blocks["gmu"] = {
            "mixer": {"w_in": dense(lead, (h, inner), h, keys=late),
                      "w_out": dense(lead, (inner, h), inner, keys=late)},
            "mixer_norm": ones(lead, h), **mlp(lead)}

    def differential(lead):
        """What differential attention adds to an attention layer: four
        vectors of a head's width for ``lam`` (N(0, 0.1), the published
        draw) and the scale of the norm over a pair's two value heads."""
        if not cfg.diff_attn:
            return {}
        return {**{n: (jax.random.normal(next(late), lead + (hd,), dtype)
                       * 0.1).astype(dtype)
                   for n in ("lam_q1", "lam_k1", "lam_q2", "lam_k2")},
                "sub_norm": {"scale": jnp.ones(lead + (2 * hd,), dtype)}}

    if "cross" in pattern:      # a query and an output projection alone
        lead = (periods, pattern.count("cross"))
        blocks["cross"] = {
            "attn": {"wq": dense(lead, (h, nh * hd), h, keys=late),
                     "wo": dense(lead, (nh * hd, h), nh * hd, keys=late),
                     **differential(lead)},
            "attn_norm": ones(lead, h), **mlp(lead)}
    for kind in ("full", "window"):     # one attention, two spans
        if kind not in pattern:
            continue
        lead = (periods, pattern.count(kind))
        # (the window kind's draws are its own: a model of full layers
        # alone keeps the weights it had)
        draw = keys if kind == "full" else iter(
            jax.random.split(jax.random.fold_in(key, 2), 4))
        attn = {
            "wq": dense(lead, (h, nh * hd), h, keys=draw),
            "wk": dense(lead, (h, nkv * hd), h, keys=draw),
            "wv": dense(lead, (h, nkv * hd), h, keys=draw),
            "wo": dense(lead, (nh * hd, h), nh * hd, keys=draw),
            **differential(lead),
        }
        if cfg.attn_output_gate:
            attn["w_gate"] = dense(lead, (h, nh * hd), h, keys=more)
        if cfg.qk_norm:
            attn["q_norm"] = ones(lead, nh * hd)
            attn["k_norm"] = ones(lead, nkv * hd)
        if cfg.qk_head_norm:        # one scale vector, shared by the heads
            attn["q_norm"] = ones(lead, hd)
            attn["k_norm"] = ones(lead, hd)
        blocks[kind] = {"attn": attn, "attn_norm": ones(lead, h),
                        **mlp(lead)}
    if "mlp" in pattern:
        blocks["mlp"] = mlp((periods, pattern.count("mlp")), False)
    if cfg.dense_prefix_layers:
        # the MLPs by layer, where a kind's layers do not all have the same:
        # the dense ones of the first layers, then each expert layer's
        # router and shared expert in layer order, as its routed experts lie
        blocks["dense"] = mlp((cfg.dense_prefix_layers,), False, False)["mlp"]
        blocks["moe"] = mlp((cfg.expert_layers,), False)["moe"]
    if cfg.moe_dropless:
        from .latent import expert_stack
        em = cfg.expert_mlp_size
        blocks["experts"] = {
            name: expert_stack(next(more), cfg, cfg.expert_layers, shape,
                               fan, dtype)
            for name, shape, fan in ((("w_gate", (h, em), h),
                                      ("w_in", (h, em), h),
                                      ("w_out", (em, h), em)) if gated else
                                     # as a linear map stores it: ops/moe.py
                                     (("w_up", (em, h), h),
                                      ("w_out", (em, h), em)))}
    return blocks


def init_state(cfg: TransformerConfig, num_slots: int,
               dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    """What a slot keeps for the recurrent layers, beside the rows of K/V
    that ``decode.init_kv_cache`` allocates for the full-attention layers."""
    lh, width = cfg.linear_num_heads, cfg.linear_conv_width
    if cfg.ssm1_layers:
        # channels on the lanes and the sublanes, a state column a leading
        # index: whole tiles whatever the 16 columns (ops/selective_scan.py)
        layers, channels = cfg.ssm1_layers, cfg.ssm1_inner
        state = selective_scan.state_shape(cfg.ssm1_inner, cfg.ssm1_state)
    elif cfg.ssm_layers:
        layers, channels = cfg.ssm_layers, cfg.ssm_channels[1]
        state = (lh, cfg.linear_value_dim, cfg.linear_key_dim)
    else:
        kd, vd = _channels(cfg)
        layers, channels = cfg.linear_layers, 2 * kd + vd
        state = gated_delta.packed_shape(lh, cfg.linear_key_dim,
                                         cfg.linear_value_dim)
    return {
        "state": jnp.zeros((layers, num_slots) + state, jnp.float32),
        "conv": jnp.zeros((layers, num_slots, width - 1, channels), dtype),
    }


# ---------------------------------------------------------------------------
# The linear mixer's pieces
# ---------------------------------------------------------------------------

def _scope(cfg: TransformerConfig, part: str = "") -> str:
    """The named scope of the mixer's pieces: ``gdn`` / ``gdn_conv`` with a
    decay a head, ``kda`` / ``kda_conv`` / ``kda_gate`` with one a channel."""
    return ("kda" if cfg.linear_decay_per_channel else "gdn") + part


def _gates(x, mp, cfg: TransformerConfig, live=None):
    """x [..., H] -> (g [..., heads] or, with a decay a channel, [..., heads,
    dk]; beta [..., heads]), float32; where ``live`` is given and false the
    step is the identity on the state (g 0, beta 0)."""
    cast = x.dtype
    with jax.named_scope(_scope(cfg, "_gate" if cfg.linear_decay_per_channel
                                else "")):
        if cfg.linear_decay_per_channel:
            a = (x @ mp["w_f_down"].astype(cast)) @ mp["w_f_up"].astype(cast)
        else:
            a = x @ mp["w_a"].astype(cast)
        g = jax.nn.softplus(a.astype(jnp.float32)
                            + mp["dt_bias"].astype(jnp.float32))
        rate = -jnp.exp(mp["A_log"].astype(jnp.float32))
        if cfg.linear_decay_per_channel:        # A_log a head, g a channel
            g = g.reshape(g.shape[:-1] + (cfg.linear_num_heads,
                                          cfg.linear_key_dim))
            rate = rate[..., None]
        g = rate * g
        beta = jax.nn.sigmoid(
            (x @ mp["w_b"].astype(cast)).astype(jnp.float32))
        if cfg.linear_neg_eigval:
            beta = 2.0 * beta
        if live is None:
            return g, beta
        return (jnp.where(live[..., None] if g.ndim > beta.ndim else live,
                          g, 0.0), jnp.where(live, beta, 0.0))


def _split_heads(y, cfg: TransformerConfig):
    """Convolved channels [..., 2 kd + vd] -> q, k [..., heads, dk], v [...,
    heads, dv]: q and k l2-normalised per head, q scaled by dk^-0.5."""
    kd, _ = _channels(cfg)
    nh, dk = cfg.linear_num_heads, cfg.linear_key_dim
    lead = y.shape[:-1]
    with jax.named_scope(_scope(cfg)):
        q = y[..., :kd].reshape(lead + (nh, dk)).astype(jnp.float32)
        k = y[..., kd:2 * kd].reshape(lead + (nh, dk)).astype(jnp.float32)
        v = y[..., 2 * kd:].reshape(lead + (nh, cfg.linear_value_dim))
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
        return (q * dk ** -0.5).astype(y.dtype), k.astype(y.dtype), v


def _mixer_out(o, x, mp, cfg: TransformerConfig):
    """o [..., heads, dv], x [..., H] -> W_o [rmsnorm_head(o) * gate(x)]:
    the gate SiLU of one projection or, in the variant with a decay a
    channel, a sigmoid of two through a bottleneck."""
    cast = x.dtype
    with jax.named_scope(_scope(cfg)):
        if cfg.linear_decay_per_channel:
            gate = jax.nn.sigmoid((x @ mp["w_g_down"].astype(cast))
                                  @ mp["w_g_up"].astype(cast))
        else:
            gate = jax.nn.silu(x @ mp["w_g"].astype(cast))
        y = _norm(o, mp["o_norm"], cfg).astype(cast).reshape(gate.shape) * gate
        return y @ mp["w_o"].astype(cast)


def _rule(cfg: TransformerConfig):
    """The delta rule's two kernels for this configuration's decay: whole
    rows to a plain state [B, heads, dk, dv], and one step on the stack of
    packed states (``gated_delta.pack_state``)."""
    if cfg.linear_decay_per_channel:
        from ..ops import kda

        def kda_step(state, li, q, k, *rest):
            # ``ops/kda.py`` steps a plain stack, which the packed one is
            # at its published heads of 128 lanes (both are the identity)
            state, o = kda.kda_recurrent_step(
                gated_delta.unpack_state(state, q.shape[1]), li, q, k, *rest)
            return gated_delta.pack_state(state), o

        return kda.kda_chunk_fwd, kda_step
    return gated_delta.gdn_chunk_fwd, gated_delta.gdn_recurrent_step


# ---------------------------------------------------------------------------
# The mixers, over whole rows and one token a slot
# ---------------------------------------------------------------------------

def _conv_rows(proj, mp, width: int, lengths):
    """The causal depthwise convolution over time and its SiLU on whole
    right-padded rows.  proj: [B, S, C] -> (mixed [B, S, C], the tail [B,
    width - 1, C] each row leaves at its length: its last width-1 inputs)."""
    s, cast = proj.shape[1], proj.dtype
    tail_pos = lengths[:, None] - (width - 1) + jnp.arange(width - 1)[None]
    padded = jnp.pad(proj, ((0, 0), (width - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + s] * mp["conv_w"][j].astype(cast)
               for j in range(width))
    if "conv_b" in mp:
        conv = conv + mp["conv_b"].astype(cast)
    tail = jnp.take_along_axis(
        proj, jnp.maximum(tail_pos, 0)[..., None], axis=1)
    return jax.nn.silu(conv), jnp.where((tail_pos >= 0)[..., None], tail, 0)


def _conv_step(proj, mp, width: int, tail):
    """The same for one new input a slot.  proj: [slots, C]; tail: [slots,
    width - 1, C] -> (mixed [slots, C], the window's last width-1 inputs)."""
    cast = proj.dtype
    window = jnp.concatenate([tail.astype(cast), proj[:, None]], 1)
    conv = sum(window[:, j] * mp["conv_w"][j].astype(cast)
               for j in range(width))
    if "conv_b" in mp:
        conv = conv + mp["conv_b"].astype(cast)
    return jax.nn.silu(conv), window[:, 1:]


def _state_io(li, conv, live, mix):
    """Layer ``li``'s tail read out of the stack ``conv``, ``mix(tail) ->
    (mixed, new tail)`` run on it, and the new tail written back in place
    for the ``live`` slots [slots, 1] alone.  Returns (mixed, conv)."""
    with jax.named_scope("state_read"):
        tail = jax.lax.dynamic_index_in_dim(conv, li, 0, keepdims=False)
    mixed, new = mix(tail)
    with jax.named_scope("state_write"):
        conv = jax.lax.dynamic_update_index_in_dim(
            conv, jnp.where(live[..., None], new.astype(conv.dtype), tail),
            li, 0)
    return mixed, conv


def linear_prefill(x, mp, cfg: TransformerConfig, lengths):
    """One linear layer's mixer over whole right-padded rows.  x: [B, S, H]
    (any S) -> (mixer output [B, S, H], the state [B, heads / p, dk, p dv]
    as the cache holds it and the convolution tail [B, width - 1, C] each
    row leaves at its length)."""
    with jax.named_scope(_scope(cfg)):
        proj = x @ mp["w_qkv"].astype(x.dtype)                  # [B, S, C]
    with jax.named_scope(_scope(cfg, "_conv")):
        conv, tail = _conv_rows(proj, mp, cfg.linear_conv_width, lengths)
    q, k, v = _split_heads(conv, cfg)
    g, beta = _gates(x, mp, cfg)
    with jax.named_scope(_scope(cfg)):
        # positions at or beyond a row's length leave its state alone
        o, state = _rule(cfg)[0](q, k, v, g, beta, lengths)
    with jax.named_scope("state_write"):
        state = gated_delta.pack_state(state)
    return _mixer_out(o, x, mp, cfg), state, tail


def linear_step(x, mp, cfg: TransformerConfig, li, state, conv, active):
    """One linear layer's mixer for one new token a slot.  x: [slots, 1, H];
    state, conv: the stacks of every linear layer, of which this is layer
    ``li``, updated in place; an inactive slot's recurrent state and
    convolution tail stay as they were.  Returns (mixer output [slots, 1,
    H], state, conv)."""
    y, live = x[:, 0], active[:, None]                 # [slots, H], [slots, 1]
    with jax.named_scope(_scope(cfg)):
        proj = y @ mp["w_qkv"].astype(x.dtype)                  # [slots, C]

    def mix(tail):
        with jax.named_scope(_scope(cfg, "_conv")):
            return _conv_step(proj, mp, cfg.linear_conv_width, tail)

    mixed, conv = _state_io(li, conv, live, mix)
    q, k, v = _split_heads(mixed, cfg)
    g, beta = _gates(y, mp, cfg, live)
    with jax.named_scope(_scope(cfg)):
        state, o = _rule(cfg)[1](state, li, q, k, v, g, beta)
    return _mixer_out(o, y, mp, cfg)[:, None], state, conv


def _ssm_split(mixed, cfg: TransformerConfig):
    """Convolved channels [..., inner + 2 G N] -> x [..., H, P], B and C
    [..., G, N]."""
    inner, _ = cfg.ssm_channels
    lead, gn = mixed.shape[:-1], cfg.ssm_groups * cfg.linear_key_dim
    groups = lead + (cfg.ssm_groups, cfg.linear_key_dim)
    return (mixed[..., :inner].reshape(
                lead + (cfg.linear_num_heads, cfg.linear_value_dim)),
            mixed[..., inner:inner + gn].reshape(groups),
            mixed[..., inner + gn:].reshape(groups))


def _ssm_step_size(dt, mp, live=None):
    """``softplus(dt~ + dt_bias)`` in float32; 0 where ``live`` is given
    and false: the identity on the state."""
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + mp["dt_bias"].astype(jnp.float32))
    return dt if live is None else jnp.where(live, dt, 0.0)


def _ssm_out(y, z, mp, cfg: TransformerConfig):
    """y [..., H, P], z [..., H P] -> W_out [rmsnorm_group(y * silu(z))],
    the norm over the ``H P / G`` channels of a group."""
    cast = z.dtype
    with jax.named_scope("ssm"):
        gated = (y.reshape(z.shape).astype(jnp.float32)
                 * jax.nn.silu(z.astype(jnp.float32)))
        by_group = gated.reshape(z.shape[:-1] + (cfg.ssm_groups, -1))
        by_group = by_group * jax.lax.rsqrt(
            jnp.mean(by_group * by_group, -1, keepdims=True) + cfg.norm_eps)
        normed = (by_group.reshape(z.shape)
                  * mp["o_norm"]["scale"].astype(jnp.float32)).astype(cast)
        return normed @ mp["w_out"].astype(cast)


def _ssm_in(x, mp, cfg: TransformerConfig):
    """x [..., H] -> (z, xBC, dt~) of ``W_in x``."""
    inner, mixed = cfg.ssm_channels
    with jax.named_scope("ssm"):
        proj = x @ mp["w_in"].astype(x.dtype)
    return (proj[..., :inner], proj[..., inner:inner + mixed],
            proj[..., inner + mixed:])


def ssm_prefill(x, mp, cfg: TransformerConfig, lengths):
    """One ssm layer's mixer over whole right-padded rows.  x: [B, S, H]
    (any S) -> (mixer output [B, S, H], the state [B, heads, P, N] and the
    convolution tail [B, width - 1, C] each row leaves at its length)."""
    z, xbc, dt = _ssm_in(x, mp, cfg)
    with jax.named_scope("ssm_conv"):
        mixed, tail = _conv_rows(xbc, mp, cfg.linear_conv_width, lengths)
    xs, b, c = _ssm_split(mixed, cfg)
    with jax.named_scope("ssm"):
        # positions at or beyond a row's length leave its state alone
        y, state = ssd.ssd_chunk_fwd(xs, _ssm_step_size(dt, mp), mp["A_log"],
                                     b, c, mp["D"], lengths)
    return _ssm_out(y, z, mp, cfg), state, tail


def ssm_step(x, mp, cfg: TransformerConfig, li, state, conv, active):
    """One ssm layer's mixer for one new token a slot; arguments and
    results as ``linear_step``'s."""
    y, live = x[:, 0], active[:, None]                 # [slots, H], [slots, 1]
    z, xbc, dt = _ssm_in(y, mp, cfg)

    def mix(tail):
        with jax.named_scope("ssm_conv"):
            return _conv_step(xbc, mp, cfg.linear_conv_width, tail)

    mixed, conv = _state_io(li, conv, live, mix)
    xs, b, c = _ssm_split(mixed, cfg)
    with jax.named_scope("ssm"):
        state, o = ssd.ssd_recurrent_step(
            state, li, xs, _ssm_step_size(dt, mp, live), mp["A_log"], b, c,
            mp["D"])
    return _ssm_out(o, z, mp, cfg)[:, None], state, conv


# ---------------------------------------------------------------------------
# Mamba-1's mixer ("ssm1") and the gated memory unit that reads its memory
# ---------------------------------------------------------------------------

def _ssm1_in(x, mp):
    """x [..., H] -> (u, z) [..., inner] of ``W_in x``."""
    with jax.named_scope("selective_scan"):
        proj = x @ mp["w_in"].astype(x.dtype)
    return jnp.split(proj, 2, axis=-1)


def _ssm1_controls(u, mp, cfg: TransformerConfig, live=None):
    """The convolved u [..., inner] -> (dt [..., inner] float32 after its
    softplus, 0 where ``live`` is given and false: the identity on the
    state; the rates A [N, inner] float32; B, C [..., N])."""
    rank, n = cfg.ssm1_dt_rank, cfg.ssm1_state
    with jax.named_scope("selective_scan"):
        dbc = u @ mp["w_x"].astype(u.dtype)
        dt = jax.nn.softplus(
            (dbc[..., :rank] @ mp["w_dt"].astype(u.dtype)).astype(jnp.float32)
            + mp["dt_bias"].astype(jnp.float32))
        if live is not None:
            dt = jnp.where(live, dt, 0.0)
        return (dt, -jnp.exp(mp["A_log"].astype(jnp.float32)),
                dbc[..., rank:rank + n], dbc[..., rank + n:])


def _ssm1_out(y, u, z, mp):
    """The scan's y [..., inner] float32 -> (the mixer's output [..., H],
    the memory: ``y + D u`` before the gate, what a "gmu" layer reads)."""
    cast = z.dtype
    with jax.named_scope("selective_scan"):
        memory = (y + mp["D"].astype(jnp.float32)
                  * u.astype(jnp.float32)).astype(cast)
        return (memory * jax.nn.silu(z)) @ mp["w_out"].astype(cast), memory


def ssm1_prefill(x, mp, cfg: TransformerConfig, lengths):
    """One ssm1 layer's mixer over whole right-padded rows.  x: [B, S, H]
    (any S) -> (mixer output [B, S, H], the state [B, N, inner / 128, 128]
    and the convolution tail [B, width - 1, inner] each row leaves at its
    length, the memory [B, S, inner])."""
    u, z = _ssm1_in(x, mp)
    with jax.named_scope("selective_scan_conv"):
        u, tail = _conv_rows(u, mp, cfg.linear_conv_width, lengths)
    dt, a, b, c = _ssm1_controls(u, mp, cfg)
    with jax.named_scope("selective_scan"):
        # positions at or beyond a row's length leave its state alone
        y, state = selective_scan.selective_scan_chunk_fwd(u, dt, a, b, c,
                                                           lengths)
    out, memory = _ssm1_out(y, u, z, mp)
    return out, state, tail, memory


def ssm1_step(x, mp, cfg: TransformerConfig, li, state, conv, memory,
              active):
    """One ssm1 layer's mixer for one new token a slot; arguments and
    results as ``linear_step``'s, with the memory [slots, 1, inner] this
    layer hands on in place of the one it was handed."""
    del memory
    y, live = x[:, 0], active[:, None]                 # [slots, H], [slots, 1]
    u, z = _ssm1_in(y, mp)

    def mix(tail):
        with jax.named_scope("selective_scan_conv"):
            return _conv_step(u, mp, cfg.linear_conv_width, tail)

    u, conv = _state_io(li, conv, live, mix)
    dt, a, b, c = _ssm1_controls(u, mp, cfg, live)
    with jax.named_scope("selective_scan"):
        state, o = selective_scan.selective_scan_step(state, li, u, dt, a, b,
                                                      c)
    out, memory = _ssm1_out(o, u, z, mp)
    return out[:, None], state, conv, memory[:, None]


@jax.named_scope("gmu")
def gmu(x, mp, memory):
    """A gated memory unit: ``W_out(memory * silu(W_in x))``, x [..., H] and
    the memory [..., inner] of the same positions."""
    cast = x.dtype
    gate = jax.nn.silu(x @ mp["w_in"].astype(cast))
    return (memory.astype(cast) * gate) @ mp["w_out"].astype(cast)


def recurrent(cfg: TransformerConfig):
    """(kind, its mixer over whole rows, its mixer for one token a slot) of
    the pattern's recurrent kind."""
    if cfg.ssm1_layers:
        return "ssm1", ssm1_prefill, ssm1_step
    if cfg.ssm_layers:
        return "ssm", ssm_prefill, ssm_step
    return "linear", linear_prefill, linear_step
