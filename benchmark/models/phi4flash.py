"""Block kind ``phi4flash``: SambaY, a decoder-hybrid-decoder (HF ``model_type``
"phi4flash", Phi-4-mini-flash-reasoning; arXiv 2507.06607; the attention's
form is the differential transformer's, arXiv 2410.05258).  A self-decoder of
Mamba-1 layers alternating with window attention, ONE full-attention layer,
and a cross-decoder of gated memory units alternating with cross attention
over that one layer's keys and values.  The four groups of
``benchmark/README.md``, "A block kind":

1. published keys -> the program's configuration, with its refusals;
2. the program's entry points for this block kind;
3. the plain float32 reference, written from the layer equations below and
   sharing nothing with ``ray_tpu.models`` or ``ray_tpu.ops``;
4. operations and bytes, the numerators of every roofline share, among them
   the kernels' under their names (``selective_scan_chunk_fwd``,
   ``selective_scan_step``, ``decode_attn``, ``window_decode_attn``,
   ``flash_window_prefill``, ``flash_attention``).

With ``L`` ``num_hidden_layers`` (32; any multiple of 4), ``half = L / 2``,
``LN`` a LayerNorm with scale and bias (eps ``layer_norm_eps``), ``E`` the
embedding, which is the head too, and no positions anywhere::

    x_0    = E[ids]
    h      = x + mixer_l( LN1(x) )
    x'     = h + W_2( silu(W_g LN2(h)) * (W_u LN2(h)) )     intermediate_size
    logits = LN_f(x_L) E^T

The mixer by layer index ``l``:

* ``l`` even, ``l <= half`` (``mb_per_layer`` 2: every second layer):
  **Mamba-1**, ``d_inner = 2 hidden`` channels, ``d_state`` 16 columns,
  ``d_conv`` 4 taps with a bias, ``dt_rank = hidden / 16``::

      [u | z] = W_in x;   u = silu(conv(u) + b)         causal, depthwise
      [dt~ | B | C] = W_x u                              dt_rank + 16 + 16
      dt = softplus(W_dt dt~ + b_dt)                     in R^d_inner
      S_t = exp(dt_t A) * S_{t-1} + (dt_t u_t) B_t^T     A = -exp(A_log)
      y_t = S_t C_t + D u_t;   out = W_out (y * silu(z))

  with ``A`` in ``R^{d_inner x 16}``, a decay a channel AND a state column.
  Layer ``half`` also hands ``y`` (before the gate) on as the memory ``m``.
* ``l`` odd, ``l < half``: **differential attention** over the last
  ``sliding_window`` positions; ``l = half + 1``: the same over the whole
  context.  Heads in pairs: with ``q`` [NH, D], ``k``, ``v`` [NKV, D] laid
  ``q[g, i, r]`` (K/V pair ``g`` of ``NKV / 2``, member ``i`` of the pair,
  query ``r`` of ``NH / NKV`` a K/V head), ``k[g, i]`` and ``V_g = [v[g, 0] |
  v[g, 1]]`` (2 D wide)::

      A_i   = softmax(q[g, i, r] k[g, i]^T / sqrt(D))          under the mask
      o     = (1 - lam0) * rmsnorm_2D(A_0 V_g - lam A_1 V_g) * scale
      lam   = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0
      lam0  = 0.8 - 0.6 exp(-0.3 l)

  then ``W_o`` over the ``NH / 2`` pairs' outputs, ``(g, r)`` in order.
* ``l`` even, ``l >= half + 2``: a **gated memory unit**, ``W_out(m *
  silu(W_in x))``, ``m`` the memory of layer ``half`` at the same position.
* ``l`` odd, ``l >= half + 3``: **cross attention**: ``W_q``, ``W_o``, its own
  ``lam`` vectors and scale only; the same differential form over the keys
  and values of layer ``half + 1``.

Nothing here imports JAX while the file is loaded.  It refuses to load on a
tree whose program has no stack of segments, so that a cell of this kind
fails at once there instead of inside a replica that never turns healthy.
"""

from __future__ import annotations

import importlib.util
import os


def _require_program():
    spec = importlib.util.find_spec("ray_tpu")
    root = os.path.dirname(spec.origin) if spec and spec.origin else None
    path = os.path.join(root, "models", "config.py") if root else ""
    if os.path.isfile(path):
        with open(path) as f:
            if "layer_segments" in f.read():
                return
    why = ("block kind phi4flash: this tree's ray_tpu/models/config.py has no "
           "layer_segments (a stack of more than one pattern, the 'ssm1', "
           "'gmu' and 'cross' kinds of layer, differential attention); the "
           "kind cannot run here")
    try:
        from benchmark.lib.manifest import ManifestError
    except ImportError:
        raise ImportError(why) from None
    raise ManifestError(why)


_require_program()

# ------------------------- 1. published keys -> the program's configuration

_KEYS = {
    "vocab_size": "vocab_size",
    "num_hidden_layers": "num_layers",
    "hidden_size": "hidden_size",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "mlp_size",
    "max_position_embeddings": "max_seq_len",
    "layer_norm_eps": "norm_eps",
    "tie_word_embeddings": "tied_embeddings",
    "sliding_window": "sliding_window",
}
#: the sizes the published file leaves to the modelling code (the
#: configuration's ``assumed``): Mamba-1's, by the family's convention
D_STATE, D_CONV, EXPAND, DT_RANK_DIVISOR = 16, 4, 2, 16


def kinds(doc: dict) -> tuple:
    """Every layer's kind in the program's names, in order."""
    n = doc["num_hidden_layers"]
    if n % 4 or n < 8:
        raise ValueError(f"num_hidden_layers {n}: a self-decoder and a "
                         "cross-decoder of whole pairs of layers each, the "
                         "full layer's pair between them (a multiple of 4, 8 "
                         "at least)")
    half = n // 2
    return tuple(
        ("ssm1" if l <= half else "gmu") if l % 2 == 0 else
        "window" if l < half else "full" if l == half + 1 else "cross"
        for l in range(n))


def segments(doc: dict) -> tuple:
    """The stack as the program walks it: ``(pattern, periods)`` a
    segment."""
    half = doc["num_hidden_layers"] // 2
    return ((("ssm1", "window"), half // 2), (("ssm1", "full"), 1),
            (("gmu", "cross"), (half - 2) // 2))


def program_kwargs(doc: dict) -> dict:
    missing = [k for k in (*_KEYS, "hidden_act", "mb_per_layer", "mlp_bias",
                           "lm_head_bias") if k not in doc]
    if missing:
        raise ValueError(f"configuration lacks published keys {missing}")
    refusals = (
        (doc["hidden_act"] != "silu", "hidden_act: the block's MLP, Mamba "
         "and memory units gate with SiLU"),
        (doc["mb_per_layer"] != 2, "mb_per_layer: the block's Mamba layers "
         "are every second layer of the self-decoder"),
        (doc["mlp_bias"] or doc["lm_head_bias"], "mlp_bias / lm_head_bias: "
         "the block's linear maps have none"),
        (not doc["tie_word_embeddings"], "tie_word_embeddings false: the "
         "block's head is its embedding"),
        (not doc["sliding_window"] > 0, "sliding_window: the window layers "
         "read their last sliding_window positions, 1 or more"),
        (doc["hidden_size"] % doc["num_attention_heads"] != 0,
         "hidden_size is not whole heads of num_attention_heads"),
        (doc["num_key_value_heads"] % 2 != 0 or doc["num_attention_heads"]
         % (2 * doc["num_key_value_heads"]) != 0,
         "num_attention_heads / num_key_value_heads: differential attention "
         "takes both in whole pairs"),
        (EXPAND * doc["hidden_size"] % 128 != 0 or doc["hidden_size"]
         % DT_RANK_DIVISOR != 0, "hidden_size: Mamba's 2 x hidden channels "
         "are whole tiles of 128 lanes and its dt_rank hidden / 16"),
        (any(doc.get(k) for k in ("embd_pdrop", "resid_pdrop")),
         "embd_pdrop / resid_pdrop: nothing here drops out"),
    )
    for refused, why in refusals:
        if refused:
            raise ValueError(why)
    kinds(doc)
    kw = {field: doc[key] for key, field in _KEYS.items()}
    kw.update(layer_segments=segments(doc), use_rope=False, no_positions=True,
              use_rmsnorm=False, use_swiglu=True, use_qkv_bias=False,
              attention_impl="auto", diff_attn=True,
              ssm1_inner=EXPAND * doc["hidden_size"], ssm1_state=D_STATE,
              ssm1_dt_rank=doc["hidden_size"] // DT_RANK_DIVISOR,
              linear_conv_width=D_CONV)
    return kw


def program_config(doc: dict):
    """What ``LLMEngine`` and the entry points below take as ``cfg``."""
    from ray_tpu.models.config import TransformerConfig
    return TransformerConfig(**program_kwargs(doc))


# ---------------------- 2. the program's entry points for this block kind

def init_params(key, cfg, dtype):
    """The program's random parameters, the embedding (which is the head)
    redrawn N(0, 1 / hidden): 0.0198 at the published 2,560, the program's
    own 0.02 to a hundredth, and logits of std 1 at any width (at the tests'
    width of 64 the program's 0.02 gives 0.16, and a tolerance in absolute
    terms would compare little)."""
    import jax
    from ray_tpu.models import transformer
    params = transformer.init_params(key, cfg, dtype=dtype)
    table = params["embed"]["tokens"]
    table = (jax.random.normal(jax.random.fold_in(key, 0xE3B), table.shape,
                               dtype) * cfg.hidden_size ** -0.5).astype(dtype)
    return dict(params, embed=dict(params["embed"], tokens=table))


def init_cache(cfg, num_slots: int, length: int, dtype):
    """Keys and values for the ONE full layer, rings for the window layers,
    the float32 state and the convolution tail for the Mamba layers."""
    from ray_tpu.models import decode
    return decode.init_kv_cache(cfg, num_slots, length, dtype)


#: the engine's rows are whole buckets, every one whole blocks of this many
#: positions (512 .. 4096); so is the row ``prefill`` walks
ROW_BLOCK = 512


def prefill(params, cache, tokens, lengths, slots, cfg):
    """The program's prefill on rows right-padded to whole ``ROW_BLOCK``s
    (or to the slot's length, where that is shorter), as the engine's admits
    are padded to its buckets: the comparison's prompt, of a length that is
    no multiple of a chunk or of the window, then runs what a request of
    that length runs, the flash kernels from 1,024 positions up and the
    chunked scan with the row's end inside a chunk."""
    import jax.numpy as jnp
    from ray_tpu.models import decode
    tokens = jnp.asarray(tokens)
    s = tokens.shape[1]
    to = min(-(-s // ROW_BLOCK) * ROW_BLOCK, cache["k"].shape[2])
    return decode.prefill(params, cache,
                          jnp.pad(tokens, ((0, 0), (0, max(to - s, 0)))),
                          lengths, slots, cfg)


def decode_step(params, cache, tokens, active, cfg):
    from ray_tpu.models import decode
    return decode.decode_step(params, cache, tokens, active, cfg)


# ------------------------------------------------- 3. the plain reference
# The equations of the module's docstring, in float32 and under
# ``jax.default_matmul_precision("highest")``: every layer over every
# position, the selective scan one token at a time (``lax.scan`` over
# positions, no chunks), the attention as two dense softmax maps a block of
# queries at a time over the whole row, no cache, no kernel.  Weights are
# the program's parameter tree (``blocks.<kind>``, leaves [1, layers of the
# kind, ...]; ``A_log`` lies [state columns, channels]), upcast a layer at a
# time; each segment is a ``lax.scan`` over its pairs of layers, so that the
# program XLA compiles holds six layers and not thirty-two.  Nothing of
# ``ray_tpu`` runs here.  With no router nothing in the equations is
# discontinuous, and the reference needs nothing of the compared run.

QUERY_BLOCK = 512
#: rows of the vocabulary a block of the head: the table is cast up a block
#: at a time (200,064 x 2,560 float32 whole is 2 GB beside a resident engine)
HEAD_BLOCKS = 3


def _layer_norm(x, p, eps):
    import jax
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _mamba(x, mp, doc):
    """x [S, H] (normed) -> (the mixer's output [S, H], y [S, d_inner]
    before the gate: the memory, where this is the layer that hands it on)."""
    import jax
    import jax.numpy as jnp
    s, width = x.shape[0], mp["conv_w"].shape[0]
    rank = mp["w_dt"].shape[0]
    u, z = jnp.split(x @ mp["w_in"], 2, axis=-1)
    padded = jnp.concatenate([jnp.zeros((width - 1, u.shape[1]),
                                        jnp.float32), u])
    u = jax.nn.silu(sum(padded[j:j + s] * mp["conv_w"][j]
                        for j in range(width)) + mp["conv_b"])
    dbc = u @ mp["w_x"]
    n = (dbc.shape[1] - rank) // 2
    dt = jax.nn.softplus(dbc[:, :rank] @ mp["w_dt"] + mp["dt_bias"])
    a = -jnp.exp(mp["A_log"])                                 # [N, d_inner]

    def step(state, xs):                                      # [N, d_inner]
        u_t, dt_t, b_t, c_t = xs
        state = jnp.exp(dt_t * a) * state + (dt_t * u_t) * b_t[:, None]
        return state, c_t @ state

    y = jax.lax.scan(step, jnp.zeros_like(a),
                     (u, dt, dbc[:, rank:rank + n], dbc[:, rank + n:]))[1]
    y = y + mp["D"] * u
    return (y * jax.nn.silu(z)) @ mp["w_out"], y


def _differential(q, k, v, ap, doc, depth, seen):
    """q [Sq, NH * D] at the rows ``seen`` [Sq, Sk] allows, k, v [Sk, NKV *
    D] -> the differential attention's output [Sq, H] after ``W_o``: two
    dense softmax maps a pair, a block of queries at a time."""
    import jax
    import jax.numpy as jnp
    nh, nkv = doc["num_attention_heads"], doc["num_key_value_heads"]
    d = doc["hidden_size"] // nh
    sq, sk = q.shape[0], k.shape[0]
    q = q.reshape(sq, nkv // 2, 2, nh // nkv, d)              # [q, g, i, r, d]
    k = k.reshape(sk, nkv // 2, 2, d)                         # [k, g, i, d]
    v = v.reshape(sk, nkv // 2, 2 * d)                        # [k, g, 2 d]
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * depth)
    lam = (jnp.exp(jnp.sum(ap["lam_q1"] * ap["lam_k1"]))
           - jnp.exp(jnp.sum(ap["lam_q2"] * ap["lam_k2"])) + lam0)
    outs = []
    for q0 in range(0, sq, QUERY_BLOCK):
        q1 = min(q0 + QUERY_BLOCK, sq)
        scores = jnp.einsum("qgird,kgid->girqk", q[q0:q1], k) * d ** -0.5
        probs = jax.nn.softmax(
            jnp.where(seen[q0:q1][None, None, None], scores, -jnp.inf), -1)
        maps = jnp.einsum("girqk,kgc->qgirc", probs, v)
        diff = maps[:, :, 0] - lam * maps[:, :, 1]            # [q, g, r, 2 d]
        diff = diff * jax.lax.rsqrt(
            jnp.mean(diff * diff, axis=-1, keepdims=True)
            + doc["layer_norm_eps"])
        outs.append(((1.0 - lam0) * diff * ap["sub_norm"]["scale"])
                    .reshape(q1 - q0, nh * d))
    return jnp.concatenate(outs) @ ap["wo"]


def _mlp(x, mp):
    import jax
    return (jax.nn.silu(x @ mp["w_gate"]) * (x @ mp["w_in"])) @ mp["w_out"]


def hidden_states(params, tokens, doc: dict):
    """tokens [S] int32 -> final normed hidden states [S, H] float32: every
    layer over every position."""
    import jax
    import jax.numpy as jnp
    F32 = jnp.float32
    eps, blocks = doc["layer_norm_eps"], params["blocks"]
    s = tokens.shape[0]
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    band = causal & (pos[:, None] - pos[None, :] < doc["sliding_window"])
    half = len(kinds(doc)) // 2

    def weights(kind, j):
        return jax.tree.map(lambda a: a[0, j].astype(F32), blocks[kind])

    def beneath(x, lp):
        return x + _mlp(_layer_norm(x, lp["mlp_norm"], eps), lp["mlp"])

    def mamba_layer(x, j):
        lp = weights("ssm1", j)
        out, y = _mamba(_layer_norm(x, lp["mixer_norm"], eps), lp["mixer"],
                        doc)
        return beneath(x + out, lp), y

    def attention_layer(x, kind, j, depth, seen):
        lp = weights(kind, j)
        ap, seen_x = lp["attn"], _layer_norm(x, lp["attn_norm"], eps)
        k, v = seen_x @ ap["wk"], seen_x @ ap["wv"]
        out = _differential(seen_x @ ap["wq"], k, v, ap, doc, depth, seen)
        return beneath(x + out, lp), (k, v)

    def self_pair(x, p):                 # layers 2 p (Mamba), 2 p + 1
        x, _ = mamba_layer(x, p)
        return attention_layer(x, "window", p, 2 * p + 1, band)[0], None

    def cross_pair(x, p, memory, kv):    # layers half + 2 + 2 p and the next
        lp = weights("gmu", p)
        seen_x = _layer_norm(x, lp["mixer_norm"], eps)
        x = beneath(x + (memory * jax.nn.silu(
            seen_x @ lp["mixer"]["w_in"])) @ lp["mixer"]["w_out"], lp)
        lp = weights("cross", p)
        q = _layer_norm(x, lp["attn_norm"], eps) @ lp["attn"]["wq"]
        return beneath(x + _differential(q, *kv, lp["attn"], doc,
                                         half + 3 + 2 * p, causal), lp), None

    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(F32)
        x, _ = jax.lax.scan(self_pair, x, jnp.arange(half // 2))
        x, memory = mamba_layer(x, half // 2)
        x, kv = attention_layer(x, "full", 0, half + 1, causal)
        x, _ = jax.lax.scan(
            lambda x, p: cross_pair(x, p, memory, kv), x,
            jnp.arange((half - 2) // 2))
        return _layer_norm(x, jax.tree.map(lambda a: a.astype(F32),
                                           params["final_norm"]), eps)


def logits(params, tokens, doc: dict, positions=None):
    """tokens [S] -> float32 logits [S, V], or [len(positions), V]: the tied
    head, the table cast up a block of rows at a time."""
    import jax
    import jax.numpy as jnp
    x = hidden_states(params, tokens, doc)
    if positions is not None:
        x = x[positions]
    table = params["embed"]["tokens"]
    blocks = HEAD_BLOCKS if table.shape[0] % HEAD_BLOCKS == 0 else 1
    with jax.default_matmul_precision("highest"):
        out = jax.lax.map(lambda rows: x @ rows.astype(jnp.float32).T,
                          table.reshape(blocks, -1, table.shape[1]))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], -1)


def loss(params, tokens, doc: dict):
    """Mean next-token cross entropy of one sequence ``tokens`` [S + 1]."""
    import jax
    import jax.numpy as jnp
    lg = logits(params, tokens[:-1], doc)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).mean()


# ------------------------------------------------ 4. operations and bytes
# From the published keys alone (and the assumed Mamba sizes above).

CHUNK = 128              # positions a grid step of the chunked scan
BAND_BLOCK_Q, BAND_BLOCK_KV = 512, 128      # the banded flash forward's


def _dims(doc: dict) -> dict:
    all_ = kinds(doc)
    h = doc["hidden_size"]
    return dict(
        h=h, v=doc["vocab_size"], nh=doc["num_attention_heads"],
        nkv=doc["num_key_value_heads"],
        hd=h // doc["num_attention_heads"], inner=EXPAND * h, n=D_STATE,
        rank=h // DT_RANK_DIVISOR, width=D_CONV, m=doc["intermediate_size"],
        span=doc["sliding_window"], layers=len(all_),
        **{kind: all_.count(kind)
           for kind in ("ssm1", "window", "full", "gmu", "cross")})


def layer_matrix_params(doc: dict) -> dict:
    """Matrix parameters of the parts of a layer: the four mixers and the
    ``mlp`` beneath each."""
    d = _dims(doc)
    wide, narrow = d["nh"] * d["hd"], d["nkv"] * d["hd"]
    return {"mamba": d["h"] * 2 * d["inner"]
            + d["inner"] * (d["rank"] + 2 * d["n"]) + d["rank"] * d["inner"]
            + d["inner"] * d["h"],
            "attention": 2 * d["h"] * wide + 2 * d["h"] * narrow,
            "gmu": 2 * d["h"] * d["inner"],
            "cross": 2 * d["h"] * wide,
            "mlp": 3 * d["h"] * d["m"]}


def _layer_matrices(doc: dict) -> int:
    """Matrix parameters of all the layers: what a decode step reads of
    them, once."""
    d, per = _dims(doc), layer_matrix_params(doc)
    return (d["ssm1"] * per["mamba"]
            + (d["window"] + d["full"]) * per["attention"]
            + d["gmu"] * per["gmu"] + d["cross"] * per["cross"]
            + d["layers"] * per["mlp"])


def num_params(doc: dict) -> int:
    """Every parameter of the program's tree: the matrices, the embedding
    (the head is the same table) and the small ones (convolution taps and
    bias, ``A_log``, ``D``, ``dt_bias``, the four ``lam`` vectors and the
    pair norm's scale an attention layer, LayerNorm scales and biases)."""
    d = _dims(doc)
    mamba_small = (d["width"] + 3 + d["n"]) * d["inner"]
    attn_small = 6 * d["hd"]
    return (_layer_matrices(doc) + d["ssm1"] * mamba_small
            + (d["window"] + d["full"] + d["cross"]) * attn_small
            + 4 * d["layers"] * d["h"] + d["v"] * d["h"] + 2 * d["h"])


def state_bytes_per_slot(doc: dict) -> int:
    """Bytes of selective-scan state one sequence holds over all Mamba
    layers (float32)."""
    d = _dims(doc)
    return d["ssm1"] * d["n"] * d["inner"] * 4


def conv_bytes_per_slot(doc: dict, dtype_bytes: int = 2) -> int:
    d = _dims(doc)
    return d["ssm1"] * (d["width"] - 1) * d["inner"] * dtype_bytes


def kv_bytes_held_per_token(doc: dict, dtype_bytes: int = 2) -> int:
    """Bytes of K and V one token KEEPS: the one full layer's rows."""
    d = _dims(doc)
    return 2 * d["nkv"] * d["hd"] * dtype_bytes * d["full"]


def kv_bytes_per_token(doc: dict, dtype_bytes: int = 2) -> int:
    """Bytes of K and V a decode step READS a live token: the full layer's
    rows once a layer that reads them, its own and every cross layer."""
    d = _dims(doc)
    return kv_bytes_held_per_token(doc, dtype_bytes) * (1 + d["cross"])


def ring_bytes_per_slot(doc: dict, positions: float,
                        dtype_bytes: int = 2) -> float:
    """Bytes of K and V of ``positions`` positions of a slot's rings, over
    the window layers."""
    d = _dims(doc)
    return d["window"] * positions * 2 * d["nkv"] * d["hd"] * dtype_bytes


def _attn_flops_per_position(doc: dict) -> float:
    """FLOPs a query head's two products take a position read: scores over
    ``D`` and values ``2 D`` wide, over all heads of one layer."""
    d = _dims(doc)
    return 2.0 * d["nh"] * d["hd"] * 3


def _state_flops_per_token(doc: dict) -> float:
    """The recurrence's FLOPs a token: ``dt A``, the decay's product, the
    rank-one update and ``S C`` (6 a state entry; the exponential is none),
    over all Mamba layers."""
    d = _dims(doc)
    return d["ssm1"] * 6.0 * d["n"] * d["inner"]


def train_flops_per_token(doc: dict, seq_len: int) -> float:
    """Forward plus backward FLOPs per token training would need.  No cell
    trains this kind: a pattern of these kinds has no backward pass."""
    d = _dims(doc)
    return (6.0 * (_layer_matrices(doc) + d["v"] * d["h"])
            + 1.5 * _attn_flops_per_position(doc)
            * ((d["full"] + d["cross"]) * seq_len
               + d["window"] * 2 * min(d["span"], seq_len))
            + 3.0 * _state_flops_per_token(doc))


def decode_state_bytes(doc: dict, active_slots: float) -> float:
    """``decode_step_bytes``'s state term: the float32 state, in and out."""
    return 2.0 * active_slots * state_bytes_per_slot(doc)


def decode_shared_kv_bytes(doc: dict, live_kv_tokens: float,
                           dtype_bytes: int = 2) -> float:
    """``decode_step_bytes``'s largest term: the full layer's rows of the
    live tokens, once a layer that reads them."""
    return float(live_kv_tokens * kv_bytes_per_token(doc, dtype_bytes))


def decode_step_bytes(doc: dict, active_slots: float, live_kv_tokens: float,
                      dtype_bytes: int = 2) -> float:
    """Bytes one decode step has to move, four terms: every layer's
    matrices and the head (the embedding's table) once; the float32 state
    read and written once per active slot per Mamba layer; the window
    layers' rings (``sliding_window`` positions a slot: the cell's contexts
    are past it); K and V of the live tokens, once a layer that reads the
    one full layer's rows."""
    d = _dims(doc)
    return ((_layer_matrices(doc) + d["v"] * d["h"]) * dtype_bytes
            + decode_state_bytes(doc, active_slots)
            + active_slots * ring_bytes_per_slot(doc, d["span"], dtype_bytes)
            + decode_shared_kv_bytes(doc, live_kv_tokens, dtype_bytes))


def decode_step_flops(doc: dict, active_slots: float,
                      live_kv_tokens: float) -> float:
    d = _dims(doc)
    return (2.0 * (_layer_matrices(doc) + d["v"] * d["h"]) * active_slots
            + _state_flops_per_token(doc) * active_slots
            + decode_attn_flops(doc, live_kv_tokens)
            + window_decode_attn_flops(doc, active_slots))


def selective_scan_chunk_fwd_flops(doc: dict, tokens: float) -> float:
    """FLOPs the scan needs for ``tokens`` positions in every Mamba layer:
    the recurrence has no matrix form, so the chunked kernel does what a
    step does a position."""
    return _state_flops_per_token(doc) * tokens


def selective_scan_chunk_fwd_bytes(doc: dict, tokens: float,
                                   dtype_bytes: int = 2) -> float:
    """Bytes the same call has to move a position: u read and y written, dt
    at 4 bytes, B and C (the final state, once a row, is left out; the
    kernel is handed B and C laid along 128 lanes and dt u beside dt, which
    is its own)."""
    d = _dims(doc)
    per_token = (2 * dtype_bytes + 4) * d["inner"] + 2 * d["n"] * dtype_bytes
    return float(d["ssm1"] * per_token * tokens)


def selective_scan_step_flops(doc: dict, slot_steps: float) -> float:
    return _state_flops_per_token(doc) * slot_steps


def selective_scan_step_bytes(doc: dict, slot_steps: float,
                              dtype_bytes: int = 2) -> float:
    """The state read and written once per (slot, step) per Mamba layer,
    plus the step's u and y, dt at 4 bytes, B and C."""
    d = _dims(doc)
    small = d["ssm1"] * ((2 * dtype_bytes + 4) * d["inner"]
                         + 2 * d["n"] * dtype_bytes)
    return slot_steps * (2.0 * state_bytes_per_slot(doc) + small)


def decode_attn_flops(doc: dict, live_tokens: float) -> float:
    """FLOPs of decode attention over ``live_tokens`` cached positions
    (summed over slots), once a layer that reads the full layer's rows."""
    d = _dims(doc)
    return _attn_flops_per_position(doc) * (d["full"] + d["cross"]) \
        * live_tokens


def decode_attn_bytes(doc: dict, live_tokens: float,
                      dtype_bytes: int = 2) -> float:
    return decode_shared_kv_bytes(doc, live_tokens, dtype_bytes)


def window_decode_attn_flops(doc: dict, slot_steps: float) -> float:
    """FLOPs of the ring kernel for ``slot_steps`` (live slot, step) pairs
    in every window layer: a query over its ``sliding_window`` positions."""
    d = _dims(doc)
    return _attn_flops_per_position(doc) * d["window"] * d["span"] \
        * slot_steps


def window_decode_attn_bytes(doc: dict, slot_steps: float,
                             dtype_bytes: int = 2) -> float:
    """Bytes the same calls have to move: K and V of the ``sliding_window``
    positions a slot's query reads, once a step and window layer."""
    d = _dims(doc)
    return float(slot_steps * ring_bytes_per_slot(doc, d["span"],
                                                  dtype_bytes))


def _band_blocks(doc: dict, seq_len: int) -> int:
    """(query block, KV block) pairs the banded kernel computes for one row
    of ``seq_len`` positions (whole query blocks) and one head."""
    span, bq, bkv = doc["sliding_window"], BAND_BLOCK_Q, BAND_BLOCK_KV
    bq = min(bq, seq_len)
    pairs = 0
    for first in range(0, seq_len, bq):
        pairs += -(-(first + bq) // bkv) - max(first - (span - 1), 0) // bkv
    return pairs


def flash_window_prefill_flops(doc: dict, batch: int, seq_len: int) -> float:
    """FLOPs the banded flash forward needs for ``batch`` rows of
    ``seq_len`` in every window layer, counted by the blocks the band
    needs: QK^T over ``D`` and PV over ``2 D`` of each computed pair."""
    d = _dims(doc)
    pair = 2.0 * 3 * min(BAND_BLOCK_Q, seq_len) * BAND_BLOCK_KV * d["hd"]
    return d["window"] * batch * d["nh"] * _band_blocks(doc, seq_len) * pair


def flash_window_prefill_bytes(doc: dict, batch: int, seq_len: int,
                               dtype_bytes: int = 2) -> float:
    """q read for every query head and o written twice as wide, k and v for
    every KV head, once a row."""
    d = _dims(doc)
    row = (3 * d["nh"] + 2 * d["nkv"]) * d["hd"] * dtype_bytes
    return float(d["window"] * batch * seq_len * row)


def flash_attention_flops(doc: dict, batch: int, seq_len: int,
                          backward: bool = False) -> float:
    """FLOPs causal flash attention needs for ``batch`` rows in the full
    layer: QK^T over ``D`` and PV over ``2 D``, halved by causality (no
    backward: nothing here trains)."""
    d = _dims(doc)
    one = 2.0 * seq_len * seq_len * d["hd"] * d["nh"] / 2
    return d["full"] * batch * one * (3 + (7.5 if backward else 0))


def flash_attention_bytes(doc: dict, batch: int, seq_len: int,
                          backward: bool = False,
                          dtype_bytes: int = 2) -> float:
    d = _dims(doc)
    row = (3 * d["nh"] + 2 * d["nkv"]) * d["hd"] * dtype_bytes
    return float(d["full"] * batch * seq_len * row
                 * (1 + (2 if backward else 0)))


def prefill_row_flops(doc: dict, seq_len: int) -> float:
    """FLOPs an admitted row of ``seq_len`` positions needs: the
    self-decoder's matrices over the row, its attention and its scans, and
    the cross-decoder's matrices and the head over ONE token."""
    d, per = _dims(doc), layer_matrix_params(doc)
    self_layers = d["ssm1"] + d["window"] + d["full"]
    self_mats = (d["ssm1"] * per["mamba"]
                 + (d["window"] + d["full"]) * per["attention"]
                 + self_layers * per["mlp"])
    cross_mats = _layer_matrices(doc) - self_mats
    return (2.0 * self_mats * seq_len + 2.0 * (cross_mats + d["v"] * d["h"])
            + flash_attention_flops(doc, 1, seq_len)
            + flash_window_prefill_flops(doc, 1, seq_len)
            + selective_scan_chunk_fwd_flops(doc, seq_len))
