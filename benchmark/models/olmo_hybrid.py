"""Block kind ``olmo_hybrid``: a decoder whose layers are of two kinds in a
fixed period (HF ``model_type`` "olmo_hybrid", ``layer_types``):
``linear_attention`` layers, each a Gated DeltaNet mixer with a recurrent
state per sequence, and ``full_attention`` layers with an RMSNorm on q and k
and no position embedding; every block is wired ``h = x + N(mixer(x)); out
= h + N(mlp(h))`` with a SiLU-gated MLP.  The four groups of
``benchmark/README.md``, "A block kind":

1. published keys -> the program's configuration, with its refusals;
2. the program's entry points for this block kind;
3. the plain float32 reference, written from the layer equations below and
   sharing nothing with ``ray_tpu.models`` or ``ray_tpu.ops``;
4. operations and bytes, the numerators of every roofline share, among them
   the two gated-delta-rule kernels' under their names.

The layer equations (``x`` the layer's input, ``N`` an RMSNorm with a learned
scale).  A linear layer, per head ``h`` of ``linear_num_key_heads``::

    [q~; k~; v~] = silu(conv(W_qkv x))      causal depthwise, width
                                            linear_conv_kernel_dim, over time
    q = l2norm(q~_h) / sqrt(dk);  k = l2norm(k~_h);  v = v~_h
    beta_t = 2 * sigmoid(w_b . x_t)         (the 2: linear_allow_neg_eigval)
    alpha_t = exp(-exp(A_log_h) * softplus(w_a . x_t + dt_bias_h))
    S_t = alpha_t * S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t                           S in R^{dv x dk}
    y_t = W_o [ N_head(o_t) * silu(W_g x_t) ]

A full layer is causal softmax attention over heads of ``hidden_size /
num_attention_heads`` with ``q = N(W_q x)``, ``k = N(W_k x)`` (the norm over
the whole projection) and nothing rotary.  QK-norm, the norm on the branch's
output and the reading of the null ``rope_theta`` are the OLMo 2 / 3
family's convention, not keys of the published file: a configuration lists
them under ``assumed``.

Nothing here imports JAX while the file is loaded.  It refuses to load on a
tree whose program has no layers of two kinds, so that a cell of this kind
fails at once there instead of inside a replica that never turns healthy.
"""

from __future__ import annotations

import importlib.util
import os


def _require_program():
    spec = importlib.util.find_spec("ray_tpu")
    root = os.path.dirname(spec.origin) if spec and spec.origin else None
    if not (root and os.path.isfile(os.path.join(root, "models",
                                                 "hybrid.py"))):
        raise ImportError(
            "block kind olmo_hybrid: this tree's ray_tpu has no "
            "models/hybrid.py (layers of two kinds, a recurrent state "
            "beside the KV cache); the kind cannot run here")


_require_program()

KINDS = {"linear_attention": "linear", "full_attention": "full"}
L2_EPS = 1e-6            # of the l2 norm on q and k (fla's)

# ------------------------- 1. published keys -> the program's configuration

_KEYS = {
    "vocab_size": "vocab_size",
    "num_hidden_layers": "num_layers",
    "hidden_size": "hidden_size",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "mlp_size",
    "max_position_embeddings": "max_seq_len",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tied_embeddings",
    "linear_num_key_heads": "linear_num_heads",
    "linear_key_head_dim": "linear_key_dim",
    "linear_value_head_dim": "linear_value_dim",
    "linear_conv_kernel_dim": "linear_conv_width",
    "linear_allow_neg_eigval": "linear_neg_eigval",
}


def period(doc: dict) -> tuple:
    """One period of ``layer_types`` in the program's names; refuses a list
    that is not whole repeats of its shortest period."""
    kinds = doc["layer_types"]
    if len(kinds) != doc["num_hidden_layers"]:
        raise ValueError(f"layer_types has {len(kinds)} entries for "
                         f"{doc['num_hidden_layers']} layers")
    unknown = sorted(set(kinds) - set(KINDS))
    if unknown:
        raise ValueError(f"layer_types names {unknown}; the block has "
                         f"{sorted(KINDS)}")
    for n in range(1, min(len(kinds), 8) + 1):
        if len(kinds) % n == 0 and kinds == kinds[:n] * (len(kinds) // n):
            return tuple(KINDS[k] for k in kinds[:n])
    raise ValueError("layer_types is not whole periods of a pattern of at "
                     "most 8 layers")


def program_kwargs(doc: dict) -> dict:
    missing = [k for k in (*_KEYS, "layer_types") if k not in doc]
    if missing:
        raise ValueError(f"configuration lacks published keys {missing}")
    if doc.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {doc['hidden_act']!r}: the block's "
                         "gated MLP is SiLU")
    if doc["tie_word_embeddings"]:
        raise ValueError("tie_word_embeddings: the block has its own head")
    if (doc.get("rope_parameters") or {}).get("rope_theta") is not None \
            or doc.get("rope_theta") is not None:
        raise ValueError("rope_theta is set: the block's full-attention "
                         "layers add no position embedding")
    if doc.get("attention_bias"):
        raise ValueError("attention_bias: the block's projections have none")
    if doc.get("linear_num_value_heads",
               doc["linear_num_key_heads"]) != doc["linear_num_key_heads"]:
        raise ValueError("linear_num_value_heads differs from "
                         "linear_num_key_heads: one value head a key head")
    head_dim = doc["hidden_size"] // doc["num_attention_heads"]
    if doc.get("head_dim", head_dim) != head_dim:
        raise ValueError(f"head_dim {doc['head_dim']} is not hidden_size / "
                         f"num_attention_heads = {head_dim}")
    kw = {field: doc[key] for key, field in _KEYS.items()}
    kw.update(layer_pattern=period(doc), use_rope=False, no_positions=True,
              qk_norm=True, norm_on_output=True, use_rmsnorm=True,
              use_swiglu=True, use_qkv_bias=False, num_experts=1,
              attention_impl="auto")
    return kw


def program_config(doc: dict):
    """What ``LLMEngine`` and the entry points below take as ``cfg``."""
    from ray_tpu.models.config import TransformerConfig
    return TransformerConfig(**program_kwargs(doc))


# ---------------------- 2. the program's entry points for this block kind

def init_params(key, cfg, dtype):
    from ray_tpu.models import transformer
    return transformer.init_params(key, cfg, dtype=dtype)


def init_cache(cfg, num_slots: int, length: int, dtype):
    """Keys and values for the full layers, the delta rule's state and the
    convolution tail for the linear ones."""
    from ray_tpu.models import decode
    return decode.init_kv_cache(cfg, num_slots, length, dtype)


def prefill(params, cache, tokens, lengths, slots, cfg):
    from ray_tpu.models import decode
    return decode.prefill(params, cache, tokens, lengths, slots, cfg)


def decode_step(params, cache, tokens, active, cfg):
    from ray_tpu.models import decode
    return decode.decode_step(params, cache, tokens, active, cfg)


# ------------------------------------------------- 3. the plain reference
# The equations of the module's docstring, in float32 and under
# ``jax.default_matmul_precision("highest")``: the delta rule one token at a
# time (``lax.scan`` over positions, no chunks), attention as one softmax
# over the whole row, no cache.  Weights are the program's parameter tree
# (``blocks.linear`` / ``blocks.full``, leaves [periods, layers of the kind
# in a period, ...]), upcast one layer at a time.

def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _mlp(x, mlp):
    import jax
    return (jax.nn.silu(x @ mlp["w_gate"]) * (x @ mlp["w_in"])) @ mlp["w_out"]


def _delta_rule(q, k, v, alpha, beta):
    """q, k [S, H, dk]; v [S, H, dv]; alpha, beta [S, H] -> o [S, H, dv]."""
    import jax
    import jax.numpy as jnp

    def step(state, xs):                              # state [H, dv, dk]
        q_t, k_t, v_t, a_t, b_t = xs
        sk = jnp.einsum("hvk,hk->hv", state, k_t)
        state = a_t[:, None, None] * (
            state - b_t[:, None, None] * sk[:, :, None] * k_t[:, None, :]) \
            + b_t[:, None, None] * v_t[:, :, None] * k_t[:, None, :]
        return state, jnp.einsum("hvk,hk->hv", state, q_t)

    s0 = jnp.zeros((q.shape[1], v.shape[2], q.shape[2]), jnp.float32)
    return jax.lax.scan(step, s0, (q, k, v, alpha, beta))[1]


def _linear_layer(x, lp, doc):
    import jax
    import jax.numpy as jnp
    s = x.shape[0]
    nh, dk = doc["linear_num_key_heads"], doc["linear_key_head_dim"]
    dv, width = doc["linear_value_head_dim"], doc["linear_conv_kernel_dim"]
    eps, mp = doc["rms_norm_eps"], lp["mixer"]
    proj = jnp.concatenate(
        [jnp.zeros((width - 1, mp["w_qkv"].shape[1]), jnp.float32),
         x @ mp["w_qkv"]])
    conv = jax.nn.silu(sum(proj[j:j + s] * mp["conv_w"][j]
                           for j in range(width)))
    q = conv[:, :nh * dk].reshape(s, nh, dk)
    k = conv[:, nh * dk:2 * nh * dk].reshape(s, nh, dk)
    v = conv[:, 2 * nh * dk:].reshape(s, nh, dv)
    q = q * jax.lax.rsqrt((q * q).sum(-1, keepdims=True) + L2_EPS) \
        * dk ** -0.5
    k = k * jax.lax.rsqrt((k * k).sum(-1, keepdims=True) + L2_EPS)
    beta = jax.nn.sigmoid(x @ mp["w_b"]) \
        * (2.0 if doc["linear_allow_neg_eigval"] else 1.0)
    alpha = jnp.exp(-jnp.exp(mp["A_log"])
                    * jax.nn.softplus(x @ mp["w_a"] + mp["dt_bias"]))
    o = _delta_rule(q, k, v, alpha, beta)
    o = _rms_norm(o, mp["o_norm"]["scale"], eps).reshape(s, nh * dv)
    y = (o * jax.nn.silu(x @ mp["w_g"])) @ mp["w_o"]
    h = x + _rms_norm(y, lp["mixer_norm"]["scale"], eps)
    return h + _rms_norm(_mlp(h, lp["mlp"]), lp["mlp_norm"]["scale"], eps)


def _full_layer(x, lp, doc):
    import jax
    import jax.numpy as jnp
    s = x.shape[0]
    nh, nkv = doc["num_attention_heads"], doc["num_key_value_heads"]
    d, eps, ap = doc["hidden_size"] // nh, doc["rms_norm_eps"], lp["attn"]
    q = _rms_norm(x @ ap["wq"], ap["q_norm"]["scale"], eps).reshape(s, nh, d)
    k = _rms_norm(x @ ap["wk"], ap["k_norm"]["scale"], eps).reshape(s, nkv, d)
    v = (x @ ap["wv"]).reshape(s, nkv, d)
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) * d ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores,
                       -jnp.inf)
    attn = jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, axis=-1), v)
    h = x + _rms_norm(attn.reshape(s, nh * d) @ ap["wo"],
                      lp["attn_norm"]["scale"], eps)
    return h + _rms_norm(_mlp(h, lp["mlp"]), lp["mlp_norm"]["scale"], eps)


def hidden_states(params, tokens, doc: dict):
    """tokens [S] int32 -> final normed hidden states [S, H] float32."""
    import jax
    import jax.numpy as jnp
    F32 = jnp.float32
    kinds = period(doc)

    def one_period(x, pp):
        at = {"linear": 0, "full": 0}
        for kind in kinds:
            lp = jax.tree.map(lambda a: a[at[kind]].astype(F32),
                              pp[kind])                  # this layer only
            x = (_linear_layer if kind == "linear" else _full_layer)(
                x, lp, doc)
            at[kind] += 1
        return x, None

    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(F32)
        x, _ = jax.lax.scan(one_period, x, params["blocks"])
        return _rms_norm(x, params["final_norm"]["scale"].astype(F32),
                         doc["rms_norm_eps"])


def logits(params, tokens, doc: dict, positions=None):
    """tokens [S] -> float32 logits [S, V], or [len(positions), V]."""
    import jax
    import jax.numpy as jnp
    x = hidden_states(params, tokens, doc)
    if positions is not None:
        x = x[positions]
    with jax.default_matmul_precision("highest"):
        return x @ params["lm_head"].astype(jnp.float32)


def loss(params, tokens, doc: dict):
    """Mean next-token cross entropy of one sequence ``tokens`` [S + 1]."""
    import jax
    import jax.numpy as jnp
    lg = logits(params, tokens[:-1], doc)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).mean()


# ------------------------------------------------ 4. operations and bytes
# From the published keys alone, at the published (unpadded) head sizes: a
# kernel that pads 96 and 192 to whole lane tiles moves and multiplies more
# than this, which shows as a lower share of its roofline.

CHUNK = 64               # of the chunked delta rule, the family's default


def _dims(doc: dict) -> dict:
    kinds = doc["layer_types"]
    nh = doc["num_attention_heads"]
    return dict(
        h=doc["hidden_size"], m=doc["intermediate_size"],
        v=doc["vocab_size"], nh=nh, nkv=doc["num_key_value_heads"],
        hd=doc["hidden_size"] // nh, lh=doc["linear_num_key_heads"],
        dk=doc["linear_key_head_dim"], dv=doc["linear_value_head_dim"],
        width=doc["linear_conv_kernel_dim"],
        linear=sum(k == "linear_attention" for k in kinds),
        full=sum(k == "full_attention" for k in kinds))


def layer_matrix_params(doc: dict) -> dict:
    """Matrix parameters of one layer of each kind (what a decode step has
    to read and multiply by): the mixer or the attention, plus the MLP."""
    d = _dims(doc)
    kd, vd = d["lh"] * d["dk"], d["lh"] * d["dv"]
    mlp = 3 * d["h"] * d["m"]
    linear = d["h"] * (2 * kd + 2 * vd) + vd * d["h"] + 2 * d["h"] * d["lh"]
    full = 2 * d["h"] * d["nh"] * d["hd"] + 2 * d["h"] * d["nkv"] * d["hd"]
    return {"linear": linear + mlp, "full": full + mlp}


def _matrix_params(doc: dict) -> int:
    d, per = _dims(doc), layer_matrix_params(doc)
    return d["linear"] * per["linear"] + d["full"] * per["full"]


def num_params(doc: dict) -> int:
    """Every parameter of the program's tree: the matrices, the embedding
    and the head, and the small ones (convolution taps, ``A_log``,
    ``dt_bias``, norm scales)."""
    d = _dims(doc)
    kd, vd = d["lh"] * d["dk"], d["lh"] * d["dv"]
    linear_small = (d["width"] * (2 * kd + vd) + 2 * d["lh"] + d["dv"]
                    + 2 * d["h"])
    full_small = d["nh"] * d["hd"] + d["nkv"] * d["hd"] + 2 * d["h"]
    return (_matrix_params(doc) + d["linear"] * linear_small
            + d["full"] * full_small + 2 * d["v"] * d["h"] + d["h"])


def state_bytes_per_slot(doc: dict) -> int:
    """Bytes of delta-rule state one sequence holds over all linear layers
    (float32)."""
    d = _dims(doc)
    return d["linear"] * d["lh"] * d["dk"] * d["dv"] * 4


def kv_bytes_per_token(doc: dict, dtype_bytes: int = 2) -> int:
    """Bytes of K and V one token holds: the full layers only."""
    d = _dims(doc)
    return 2 * d["nkv"] * d["hd"] * dtype_bytes * d["full"]


def _state_flops_per_token(doc: dict) -> float:
    """The recurrence's FLOPs a token: S k, the rank-one update and S q, 2
    per multiply-add, over all linear layers."""
    d = _dims(doc)
    return d["linear"] * d["lh"] * 6.0 * d["dk"] * d["dv"]


def train_flops_per_token(doc: dict, seq_len: int) -> float:
    """Forward plus backward FLOPs per token training would need: 6 per
    matrix parameter and head weight, the score and value matmuls of the
    full layers (counted as ``models/mistral.py`` counts them), and three
    times the recurrence's forward.  No cell trains this kind yet."""
    d = _dims(doc)
    return (6.0 * (_matrix_params(doc) + d["v"] * d["h"])
            + 6.0 * d["full"] * 2 * seq_len * d["h"]
            + 3.0 * _state_flops_per_token(doc))


def decode_step_bytes(doc: dict, active_slots: float, live_kv_tokens: float,
                      dtype_bytes: int = 2) -> float:
    """Bytes one decode step has to move, three terms: every matrix and the
    head once; the delta rule's state read and written once per active slot
    per linear layer, at 4 bytes; K and V of the live tokens, full layers
    only."""
    d = _dims(doc)
    weights = (_matrix_params(doc) + d["v"] * d["h"]) * dtype_bytes
    return (weights + 2.0 * active_slots * state_bytes_per_slot(doc)
            + live_kv_tokens * kv_bytes_per_token(doc, dtype_bytes))


def decode_step_flops(doc: dict, active_slots: float,
                      live_kv_tokens: float) -> float:
    d = _dims(doc)
    return (2.0 * (_matrix_params(doc) + d["v"] * d["h"]) * active_slots
            + _state_flops_per_token(doc) * active_slots
            + 4.0 * d["full"] * d["nh"] * d["hd"] * live_kv_tokens)


def gdn_chunk_fwd_flops(doc: dict, tokens: float) -> float:
    """FLOPs the chunked (WY) form needs for ``tokens`` positions in every
    linear layer, chunk 64: per chunk and head k k^T, q k^T and T [k beta]
    (2 c^2 dk each), T [v beta] and the intra-chunk output (2 c^2 dv each),
    the triangular solve (2 c^3 / 3), and the three products with the state
    (2 c dk dv each)."""
    d, c = _dims(doc), CHUNK
    per_token = (6.0 * c * d["dk"] + 4.0 * c * d["dv"]
                 + 6.0 * d["dk"] * d["dv"] + 2.0 * c * c / 3)
    return d["linear"] * d["lh"] * per_token * tokens


def gdn_chunk_fwd_bytes(doc: dict, tokens: float,
                        dtype_bytes: int = 2) -> float:
    """Bytes the same call has to move: q, k, v read and o written per
    position, the two gates at 4 bytes (the final state, once a row, is
    left out)."""
    d = _dims(doc)
    per_token = (2 * d["dk"] + 2 * d["dv"]) * dtype_bytes + 8
    return float(d["linear"] * d["lh"] * per_token * tokens)


def gdn_recurrent_step_flops(doc: dict, active_slots: float) -> float:
    return _state_flops_per_token(doc) * active_slots


def gdn_recurrent_step_bytes(doc: dict, active_slots: float,
                             dtype_bytes: int = 2) -> float:
    """The state read and written once per active slot per linear layer,
    plus the step's q, k, v and o."""
    d = _dims(doc)
    small = d["linear"] * d["lh"] * (2 * d["dk"] + 2 * d["dv"]) * dtype_bytes
    return active_slots * (2.0 * state_bytes_per_slot(doc) + small)
