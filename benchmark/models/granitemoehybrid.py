"""Block kind ``granitemoehybrid``: a decoder whose layers are a mixer with a
dense gated MLP beneath, the mixer by a published list (``layer_types``)
either a Mamba-2 state-space mixer (``mamba``) or causal softmax attention
without a position embedding (``attention``), with four published scalars on
the embedding, the residual branches, the attention's scores and the logits
(HF ``model_type`` "granitemoehybrid"; Granite 4.0-H, with
``num_local_experts`` 0: no routed experts, the "shared" MLP alone; Mamba-2's
state-space dual, arXiv 2405.21060).  The four groups of
``benchmark/README.md``, "A block kind":

1. published keys -> the program's configuration, with its refusals;
2. the program's entry points for this block kind;
3. the plain float32 reference, written from the layer equations below and
   sharing nothing with ``ray_tpu.models`` or ``ray_tpu.ops``;
4. operations and bytes, the numerators of every roofline share, among them
   the kernels' under their names (``ssd_chunk_fwd``, ``ssd_recurrent_step``,
   ``decode_attn``, ``flash_attention``).

With ``e`` ``embedding_multiplier``, ``r`` ``residual_multiplier``, ``s``
``attention_multiplier`` and ``l`` ``logits_scaling``; ``N`` an RMSNorm with
a learned scale (eps ``rms_norm_eps``); ``E`` the embedding, which is the
head too (``tie_word_embeddings``)::

    x_0 = e E[ids]
    h   = x + r mixer_l( N1(x) )
    x'  = h + r W_out( silu(W_g N2(h)) * (W_u N2(h)) )
                                         W_g, W_u: shared_intermediate_size
    logits = ( N_f(x_L) E^T ) / l

A ``mamba`` mixer (``mamba_n_heads`` H heads of ``mamba_d_head`` P,
``mamba_n_groups`` G groups of ``mamba_d_state`` N, head ``h`` reads group
``h // (H / G)``; ``mamba_d_conv`` taps with a bias), for input ``u``::

    [z | xBC | dt~] = W_in u                 H P + (H P + 2 G N) + H columns
    xBC = silu(conv(xBC) + b_conv)           causal depthwise, over time
    dt_t = softplus(dt~_t + dt_bias)         in R^H, float32
    a_t = exp(-exp(A_log) * dt_t)            one decay a head, in (0, 1]
    S_t = a_t S_{t-1} + dt_t x_t B_t^T       S in R^{P x N} a head, float32
    y_t = S_t C_t + D_h x_t
    out = W_out [ N_grouped( y_t * silu(z_t) ) ]    RMSNorm over the H P / G
                                             channels of a group, scale [H P]

An ``attention`` mixer is ``softmax(s q k^T) v``, causal,
``num_attention_heads`` query heads over ``num_key_value_heads`` key / value
heads of ``hidden_size / num_attention_heads``, nothing rotary
(``position_embedding_type`` nope), no bias: ``out = W_o attn(W_q x, W_k x,
W_v x)``.

Nothing here imports JAX while the file is loaded.  It refuses to load on a
tree whose program has no field for the four scalars, so that a cell of this
kind fails at once there instead of inside a replica that never turns
healthy.
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
            if "embedding_multiplier" in f.read():
                return
    why = ("block kind granitemoehybrid: this tree's ray_tpu/models/config.py "
           "has no embedding_multiplier (the four published scalars on the "
           "embedding, the residual branches, the attention's scores and "
           "the logits); the kind cannot run here")
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
    "shared_intermediate_size": "mlp_size",
    "max_position_embeddings": "max_seq_len",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tied_embeddings",
    "mamba_n_heads": "linear_num_heads",
    "mamba_d_head": "linear_value_dim",
    "mamba_d_state": "linear_key_dim",
    "mamba_d_conv": "linear_conv_width",
    "mamba_n_groups": "ssm_groups",
    "embedding_multiplier": "embedding_multiplier",
    "residual_multiplier": "residual_multiplier",
    "attention_multiplier": "attention_multiplier",
    "logits_scaling": "logits_scaling",
}
#: an entry of ``layer_types`` -> the program's kind of layer
_KINDS = {"mamba": "ssm", "attention": "full"}


def kinds(doc: dict) -> tuple:
    """Every layer's kind in the program's names, in order."""
    types = doc["layer_types"]
    if set(types) - set(_KINDS) or len(types) != doc["num_hidden_layers"]:
        raise ValueError(f"layer_types {types!r}: one of {sorted(_KINDS)} a "
                         f"layer, num_hidden_layers "
                         f"{doc['num_hidden_layers']} of them")
    return tuple(_KINDS[t] for t in types)


def period(doc: dict) -> tuple:
    """The shortest period the layers' kinds are whole repeats of (the
    published 40 are four of ``M M M M M * M M M M``)."""
    all_, n = kinds(doc), doc["num_hidden_layers"]
    return next(all_[:p] for p in range(1, n + 1)
                if n % p == 0 and all_ == all_[:p] * (n // p))


def program_kwargs(doc: dict) -> dict:
    missing = [k for k in (*_KEYS, "layer_types", "hidden_act",
                           "mamba_conv_bias", "mamba_expand",
                           "num_local_experts", "position_embedding_type")
               if k not in doc]
    if missing:
        raise ValueError(f"configuration lacks published keys {missing}")
    refusals = (
        (doc["num_local_experts"] or doc.get("num_experts_per_tok"),
         "num_local_experts / num_experts_per_tok: the block has the dense "
         "MLP of shared_intermediate_size alone, no routed experts"),
        (doc["hidden_act"] != "silu", "hidden_act: the block's MLP and "
         "state-space mixer gate and convolve with SiLU"),
        (not doc["mamba_conv_bias"], "mamba_conv_bias false: the block's "
         "convolution has a bias"),
        (any(doc.get(k) for k in ("mamba_proj_bias", "attention_bias")),
         "mamba_proj_bias / attention_bias: the block's linear maps have "
         "none"),
        (not doc["tie_word_embeddings"], "tie_word_embeddings false: the "
         "block's head is its embedding"),
        (doc["position_embedding_type"] != "nope", "position_embedding_type:"
         " the block's attention adds no positions (nope)"),
        (doc.get("rope_scaling") is not None, "rope_scaling: nothing here is "
         "rotary"),
        (doc.get("normalization_function", "rmsnorm") != "rmsnorm",
         "normalization_function: the block's norms are RMSNorms"),
        (doc["mamba_n_heads"] % doc["mamba_n_groups"] != 0,
         "mamba_n_heads is not whole groups of mamba_n_groups"),
        (doc["mamba_n_heads"] * doc["mamba_d_head"]
         != doc["mamba_expand"] * doc["hidden_size"],
         "mamba_n_heads x mamba_d_head is not mamba_expand x hidden_size"),
        (doc["hidden_size"] % doc["num_attention_heads"] != 0,
         "hidden_size is not whole heads of num_attention_heads"),
        (any(not doc[k] > 0 for k in ("embedding_multiplier",
                                      "residual_multiplier",
                                      "attention_multiplier",
                                      "logits_scaling")),
         "embedding_multiplier / residual_multiplier / attention_multiplier "
         "/ logits_scaling: the block's four scalars are positive"),
    )
    for refused, why in refusals:
        if refused:
            raise ValueError(why)
    kw = {field: doc[key] for key, field in _KEYS.items()}
    kw.update(layer_pattern=period(doc), use_rope=False, no_positions=True,
              use_rmsnorm=True, use_swiglu=True, use_qkv_bias=False,
              attention_impl="auto")
    return kw


def program_config(doc: dict):
    """What ``LLMEngine`` and the entry points below take as ``cfg``."""
    from ray_tpu.models.config import TransformerConfig
    return TransformerConfig(**program_kwargs(doc))


# ---------------------- 2. the program's entry points for this block kind

#: the embedding's draw, N(0, EMBED_STD): with ``embedding_multiplier`` 12 the
#: stream starts at a rms of 1.2 and with the tied head over ``logits_scaling``
#: 8 the logits have a std of 0.57; at the program's own 0.02 they would have
#: 0.11, and a tolerance in absolute terms would compare nothing
EMBED_STD = 0.1


def init_params(key, cfg, dtype):
    """The program's random parameters, the embedding (which is the head)
    redrawn at ``EMBED_STD``."""
    import jax
    from ray_tpu.models import transformer
    params = transformer.init_params(key, cfg, dtype=dtype)
    table = params["embed"]["tokens"]
    table = (jax.random.normal(jax.random.fold_in(key, 0xE3B), table.shape,
                               dtype) * EMBED_STD).astype(dtype)
    return dict(params, embed=dict(params["embed"], tokens=table))


def init_cache(cfg, num_slots: int, length: int, dtype):
    """Keys and values for the attention layers, the state-space state and
    the convolution tail for the ``mamba`` layers."""
    from ray_tpu.models import decode
    return decode.init_kv_cache(cfg, num_slots, length, dtype)


#: the engine's rows are whole buckets, every one whole blocks of this many
#: positions (512 .. 4096); so is the row ``prefill`` walks
ROW_BLOCK = 512


def prefill(params, cache, tokens, lengths, slots, cfg):
    """The program's prefill on rows right-padded to whole ``ROW_BLOCK``s
    (or to the slot's length, where that is shorter), as the engine's admits
    are padded to its buckets: the comparison's prompt, of a length that is
    no multiple of a chunk, then runs what a request of that length runs,
    the flash kernel from 1,024 positions up and the chunked scan with the
    row's end inside a chunk."""
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
# ``jax.default_matmul_precision("highest")``: the state-space recurrence one
# token at a time (``lax.scan`` over positions, no chunks), attention a block
# of queries at a time over the whole row, no cache, no kernel.  Weights are
# the program's parameter tree (``blocks.ssm`` / ``blocks.full``, leaves
# [periods, layers of the kind in a period, ...]), upcast a layer at a time;
# the periods are a ``lax.scan`` whose body is one period's layers, so that
# the program XLA compiles holds ten layers and not forty.  Nothing of
# ``ray_tpu`` runs here: the section reads the parameter tree and calls
# ``jax`` alone.  With no router nothing in the equations is discontinuous,
# and the reference needs nothing of the compared run.

QUERY_BLOCK = 512


def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _mamba(u, mp, doc):
    """u [S, H] (normed) -> the state-space mixer's output [S, H]."""
    import jax
    import jax.numpy as jnp
    s = u.shape[0]
    nh, p, n, g = (doc["mamba_n_heads"], doc["mamba_d_head"],
                   doc["mamba_d_state"], doc["mamba_n_groups"])
    width, inner = doc["mamba_d_conv"], nh * p
    proj = u @ mp["w_in"]
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * g * n],
                  proj[:, 2 * inner + 2 * g * n:])
    padded = jnp.concatenate([jnp.zeros((width - 1, xbc.shape[1]),
                                        jnp.float32), xbc])
    xbc = jax.nn.silu(sum(padded[j:j + s] * mp["conv_w"][j]
                          for j in range(width)) + mp["conv_b"])
    # heads by group: head h reads group h // (H / G)
    x = xbc[:, :inner].reshape(s, g, nh // g, p)
    b, c = (xbc[:, lo:lo + g * n].reshape(s, g, n)
            for lo in (inner, inner + g * n))
    dt = jax.nn.softplus(dt + mp["dt_bias"]).reshape(s, g, nh // g)
    a = jnp.exp(-jnp.exp(mp["A_log"]).reshape(g, nh // g) * dt)

    def step(state, xs):                         # state [G, H / G, P, N]
        x_t, b_t, c_t, dt_t, a_t = xs
        state = (a_t[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None]
                 * b_t[:, None, None, :])
        return state, jnp.einsum("gqpn,gn->gqp", state, c_t)

    y = jax.lax.scan(step, jnp.zeros((g, nh // g, p, n), jnp.float32),
                     (x, b, c, dt, a))[1]
    y = y + mp["D"].reshape(1, g, nh // g, 1) * x
    grouped = y.reshape(s, g, inner // g) * jax.nn.silu(z).reshape(
        s, g, inner // g)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True)
        + doc["rms_norm_eps"])
    return (grouped.reshape(s, inner) * mp["o_norm"]["scale"]) @ mp["w_out"]


def _attention(x, ap, doc):
    """x [S, H] (normed) -> causal softmax attention [S, H], no positions,
    the scores times ``attention_multiplier``."""
    import jax
    import jax.numpy as jnp
    s = x.shape[0]
    nh, nkv = doc["num_attention_heads"], doc["num_key_value_heads"]
    d = doc["hidden_size"] // nh
    q = (x @ ap["wq"]).reshape(s, nkv, nh // nkv, d)
    k = (x @ ap["wk"]).reshape(s, nkv, d)
    v = (x @ ap["wv"]).reshape(s, nkv, d)
    outs = []
    for q0 in range(0, s, QUERY_BLOCK):
        q1 = min(q0 + QUERY_BLOCK, s)
        scores = jnp.einsum("qgrd,kgd->grqk", q[q0:q1], k[:q1]) \
            * doc["attention_multiplier"]
        seen = jnp.arange(q1)[None, :] <= jnp.arange(q0, q1)[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf),
                               -1)
        outs.append(jnp.einsum("grqk,kgd->qgrd", probs, v[:q1]))
    return jnp.concatenate(outs).reshape(s, nh * d) @ ap["wo"]


def _mlp(x, mp):
    import jax
    return (jax.nn.silu(x @ mp["w_gate"]) * (x @ mp["w_in"])) @ mp["w_out"]


def hidden_states(params, tokens, doc: dict):
    """tokens [S] int32 -> final normed hidden states [S, H] float32."""
    import jax
    import jax.numpy as jnp
    F32 = jnp.float32
    eps, r = doc["rms_norm_eps"], doc["residual_multiplier"]
    pattern, blocks = period(doc), params["blocks"]
    mixers = {"ssm": ("mixer_norm", "mixer", _mamba),
              "full": ("attn_norm", "attn", _attention)}

    def a_period(x, p):
        at = dict.fromkeys(mixers, 0)
        for kind in pattern:
            j, (norm, name, mixer) = at[kind], mixers[kind]
            at[kind] = j + 1
            lp = jax.tree.map(lambda a: a[p, j].astype(F32),
                              blocks[kind])              # this layer only
            x = x + r * mixer(_rms_norm(x, lp[norm]["scale"], eps),
                              lp[name], doc)
            x = x + r * _mlp(_rms_norm(x, lp["mlp_norm"]["scale"], eps),
                             lp["mlp"])
        return x, None

    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(F32) \
            * doc["embedding_multiplier"]
        x, _ = jax.lax.scan(
            a_period, x,
            jnp.arange(doc["num_hidden_layers"] // len(pattern)))
        return _rms_norm(x, params["final_norm"]["scale"].astype(F32), eps)


def logits(params, tokens, doc: dict, positions=None):
    """tokens [S] -> float32 logits [S, V], or [len(positions), V]: the tied
    head over ``logits_scaling``."""
    import jax
    import jax.numpy as jnp
    x = hidden_states(params, tokens, doc)
    if positions is not None:
        x = x[positions]
    with jax.default_matmul_precision("highest"):
        return (x @ params["embed"]["tokens"].astype(jnp.float32).T) \
            / doc["logits_scaling"]


def loss(params, tokens, doc: dict):
    """Mean next-token cross entropy of one sequence ``tokens`` [S + 1]."""
    import jax
    import jax.numpy as jnp
    lg = logits(params, tokens[:-1], doc)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).mean()


# ------------------------------------------------ 4. operations and bytes
# From the published keys alone.

CHUNK = 128              # of the chunked state-space dual in the program


def _dims(doc: dict) -> dict:
    all_ = kinds(doc)
    nh, p = doc["mamba_n_heads"], doc["mamba_d_head"]
    g, n = doc["mamba_n_groups"], doc["mamba_d_state"]
    return dict(
        h=doc["hidden_size"], v=doc["vocab_size"],
        nh=doc["num_attention_heads"], nkv=doc["num_key_value_heads"],
        hd=doc["hidden_size"] // doc["num_attention_heads"], lh=nh, p=p, g=g,
        n=n, inner=nh * p, mixed=nh * p + 2 * g * n,
        width=doc["mamba_d_conv"], m=doc["shared_intermediate_size"],
        layers=len(all_), ssm=all_.count("ssm"), full=all_.count("full"))


def layer_matrix_params(doc: dict) -> dict:
    """Matrix parameters of the parts of a layer: the ``mamba`` mixer, the
    ``attention`` and the ``mlp`` beneath either."""
    d = _dims(doc)
    return {"mamba": d["h"] * (d["inner"] + d["mixed"] + d["lh"])
            + d["inner"] * d["h"],
            "attention": 2 * d["h"] * d["nh"] * d["hd"]
            + 2 * d["h"] * d["nkv"] * d["hd"],
            "mlp": 3 * d["h"] * d["m"]}


def _layer_matrices(doc: dict) -> int:
    """Matrix parameters of all the layers: what a decode step reads of
    them, once."""
    d, per = _dims(doc), layer_matrix_params(doc)
    return (d["ssm"] * per["mamba"] + d["full"] * per["attention"]
            + d["layers"] * per["mlp"])


def num_params(doc: dict) -> int:
    """Every parameter of the program's tree: the matrices, the embedding
    (the head is the same table) and the small ones (convolution taps and
    bias, ``A_log``, ``D``, ``dt_bias``, norm scales)."""
    d = _dims(doc)
    ssm_small = (d["width"] + 1) * d["mixed"] + 3 * d["lh"] + d["inner"]
    return (_layer_matrices(doc) + d["ssm"] * ssm_small
            + 2 * d["layers"] * d["h"] + d["v"] * d["h"] + d["h"])


def state_bytes_per_slot(doc: dict) -> int:
    """Bytes of state-space state one sequence holds over all ``mamba``
    layers (float32)."""
    d = _dims(doc)
    return d["ssm"] * d["lh"] * d["p"] * d["n"] * 4


def kv_bytes_per_token(doc: dict, dtype_bytes: int = 2) -> int:
    """Bytes of K and V one token holds: the attention layers only."""
    d = _dims(doc)
    return 2 * d["nkv"] * d["hd"] * dtype_bytes * d["full"]


def _state_flops_per_token(doc: dict) -> float:
    """The recurrence's FLOPs a token: the decay, the rank-one update and
    ``S C``, over all ``mamba`` layers."""
    d = _dims(doc)
    return d["ssm"] * d["lh"] * 5.0 * d["p"] * d["n"]


def train_flops_per_token(doc: dict, seq_len: int) -> float:
    """Forward plus backward FLOPs per token training would need.  No cell
    trains this kind: a pattern has no backward pass."""
    d = _dims(doc)
    return (6.0 * (_layer_matrices(doc) + d["v"] * d["h"])
            + 6.0 * d["full"] * d["nh"] * d["hd"] * seq_len
            + 3.0 * _state_flops_per_token(doc))


def decode_step_bytes(doc: dict, active_slots: float, live_kv_tokens: float,
                      dtype_bytes: int = 2) -> float:
    """Bytes one decode step has to move, three terms: every layer's
    matrices and the head (the embedding's table) once; the state-space
    state read and written once per active slot per ``mamba`` layer, at 4
    bytes; K and V of the live tokens, attention layers only."""
    d = _dims(doc)
    return ((_layer_matrices(doc) + d["v"] * d["h"]) * dtype_bytes
            + decode_state_bytes(doc, active_slots)
            + live_kv_tokens * kv_bytes_per_token(doc, dtype_bytes))


def decode_state_bytes(doc: dict, active_slots: float) -> float:
    """``decode_step_bytes``'s second term: the recurrent state, in and
    out."""
    return 2.0 * active_slots * state_bytes_per_slot(doc)


def decode_step_flops(doc: dict, active_slots: float,
                      live_kv_tokens: float) -> float:
    d = _dims(doc)
    return (2.0 * (_layer_matrices(doc) + d["v"] * d["h"]) * active_slots
            + _state_flops_per_token(doc) * active_slots
            + decode_attn_flops(doc, live_kv_tokens))


def ssd_chunk_fwd_flops(doc: dict, tokens: float) -> float:
    """FLOPs the chunked form needs for ``tokens`` positions in every
    ``mamba`` layer, chunk 128: per chunk ``C B^T`` once a group (2 c^2 N:
    what the mathematics needs, whatever blocks of heads a kernel walks a
    group in), and a head the decayed product with ``dt x`` (2 c^2 P), ``C
    S^T`` and the state's update (2 c P N each).  The decays are
    exponentials, not products."""
    d, c = _dims(doc), CHUNK
    per_token = (d["g"] * 2.0 * c * d["n"]
                 + d["lh"] * (2.0 * c * d["p"] + 4.0 * d["p"] * d["n"]))
    return d["ssm"] * per_token * tokens


def ssd_chunk_fwd_bytes(doc: dict, tokens: float,
                        dtype_bytes: int = 2) -> float:
    """Bytes the same call has to move: x read and y written per position,
    B and C of every group, and a head's step and running log decay (that
    one in both layouts) at 4 bytes (the final state, once a row, is left
    out)."""
    d = _dims(doc)
    per_token = ((2 * d["inner"] + 2 * d["g"] * d["n"]) * dtype_bytes
                 + 3 * d["lh"] * 4)
    return float(d["ssm"] * per_token * tokens)


def ssd_recurrent_step_flops(doc: dict, active_slots: float) -> float:
    return _state_flops_per_token(doc) * active_slots


def ssd_recurrent_step_bytes(doc: dict, active_slots: float,
                             dtype_bytes: int = 2) -> float:
    """The state read and written once per active slot per ``mamba`` layer,
    plus the step's x and y, B and C of every group, and a head's decay and
    step at 4 bytes."""
    d = _dims(doc)
    small = d["ssm"] * ((2 * d["inner"] + 2 * d["g"] * d["n"]) * dtype_bytes
                        + 2 * d["lh"] * 4)
    return active_slots * (2.0 * state_bytes_per_slot(doc) + small)


def decode_attn_flops(doc: dict, live_tokens: float) -> float:
    """FLOPs of decode attention over ``live_tokens`` cached positions
    (summed over slots), attention layers: scores and values, 2 per
    multiply-add."""
    d = _dims(doc)
    return 4.0 * d["full"] * d["nh"] * d["hd"] * live_tokens


def decode_attn_bytes(doc: dict, live_tokens: float,
                      dtype_bytes: int = 2) -> float:
    return float(live_tokens * kv_bytes_per_token(doc, dtype_bytes))


def flash_attention_flops(doc: dict, batch: int, seq_len: int,
                          backward: bool = False) -> float:
    """FLOPs causal flash attention needs for ``batch`` rows in every
    attention layer: QK^T and PV, 2 S^2 D a head each, halved by causality
    (5 more matmuls backward, which nothing here runs)."""
    d = _dims(doc)
    one = 2.0 * seq_len * seq_len * d["hd"] * d["nh"] / 2
    return d["full"] * batch * one * (2 + (5 if backward else 0))


def flash_attention_bytes(doc: dict, batch: int, seq_len: int,
                          backward: bool = False,
                          dtype_bytes: int = 2) -> float:
    """q read and o written for every query head, k and v for every KV
    head, once a row (forward)."""
    d = _dims(doc)
    row = (2 * d["nh"] + 2 * d["nkv"]) * d["hd"] * dtype_bytes
    return float(d["full"] * batch * seq_len * row
                 * (1 + (2 if backward else 0)))
