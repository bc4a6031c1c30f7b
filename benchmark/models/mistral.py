"""Block kind ``mistral``: the dense decoder block with grouped-query
attention, rotary position embedding and a SwiGLU MLP (HF ``model_type``
"mistral").  ``lib/manifest.Cell`` finds this file by the configuration's
``model_type`` and the harness calls nothing else that knows the model.  It
holds four groups (``benchmark/README.md`` has the signatures):

1. published keys -> the program's configuration, with its refusals;
2. the program's entry points for this block kind, as plain callables;
3. the plain float32 reference, which shares nothing with ``ray_tpu.models``;
4. operations and bytes, the numerators of every roofline share.

The file supplies functions, never a verdict: positions, tolerances, norms
and ``correct`` are the harness's.  Nothing here imports JAX while the file
is loaded: the parent of a serve cell loads it for its counts and has to
stay off the chip.
"""

from __future__ import annotations

# ------------------------- 1. published keys -> the program's configuration
# The configuration file carries the model's ``config.json`` keys verbatim at
# its top level; this is the only place that maps them onto the repo's names,
# and it refuses what the repo's dense block cannot express instead of
# running something else under the model's name.

#: published key -> TransformerConfig field
_KEYS = {
    "vocab_size": "vocab_size",
    "num_hidden_layers": "num_layers",
    "hidden_size": "hidden_size",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "mlp_size",
    "max_position_embeddings": "max_seq_len",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tied_embeddings",
}


def program_kwargs(doc: dict) -> dict:
    """Keyword arguments of ``TransformerConfig`` for a configuration file."""
    missing = [k for k in _KEYS if k not in doc]
    if missing:
        raise ValueError(f"configuration lacks published keys {missing}")
    if doc.get("sliding_window") is not None:
        raise ValueError("sliding_window is set: the dense block attends "
                         "over the whole context")
    if doc.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {doc['hidden_act']!r}: the block's "
                         "gated MLP is SiLU (SwiGLU)")
    head_dim = doc["hidden_size"] // doc["num_attention_heads"]
    if doc.get("head_dim", head_dim) != head_dim:
        raise ValueError(f"head_dim {doc['head_dim']} is not hidden_size / "
                         f"num_attention_heads = {head_dim}, which is what "
                         "TransformerConfig derives")
    kw = {field: doc[key] for key, field in _KEYS.items()}
    kw.update(use_rope=True, use_rmsnorm=True, use_swiglu=True,
              use_qkv_bias=False, num_experts=1, attention_impl="auto")
    return kw


def program_config(doc: dict):
    """What ``LLMEngine``, ``make_train_step`` and the entry points below
    take as ``cfg``."""
    from ray_tpu.models.config import TransformerConfig
    return TransformerConfig(**program_kwargs(doc))


# ---------------------- 2. the program's entry points for this block kind
# ``LLMEngine``, ``make_train_step`` and ``serve.run`` are the system's path
# for every model and stay named in the harness; these are the calls that
# belong to the block.

def init_params(key, cfg, dtype):
    """The parameter tree from a PRNG key (traceable: the harness jits it,
    with the key as an argument)."""
    from ray_tpu.models import transformer
    return transformer.init_params(key, cfg, dtype=dtype)


def init_cache(cfg, num_slots: int, length: int, dtype):
    """The per-slot state for ``num_slots`` sequences of up to ``length``
    positions: here the dense KV cache."""
    from ray_tpu.models import decode
    return decode.init_kv_cache(cfg, num_slots, length, dtype)


def prefill(params, cache, tokens, lengths, slots, cfg):
    """tokens [B, S], lengths [B], slots [B] -> (cache, logits [B, V] at each
    row's last prompt position)."""
    from ray_tpu.models import decode
    return decode.prefill(params, cache, tokens, lengths, slots, cfg)


def decode_step(params, cache, tokens, active, cfg):
    """tokens [slots], active [slots] bool -> (cache, logits [slots, V])."""
    from ray_tpu.models import decode
    return decode.decode_step(params, cache, tokens, active, cfg)


# ------------------------------------------------- 3. the plain reference
# Mistral's decoder block as published (HF ``modeling_mistral``: RMSNorm,
# grouped-query attention with rotary position embedding in the rotate-half
# pairing, SwiGLU), in straightforward ``jax.numpy`` and float32.  No kernel,
# no cache, no batching; every matmul under
# ``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul
# otherwise runs in bf16 passes).  Weights are the program's own parameter
# tree (stacked over layers), upcast one layer at a time inside the scan.
# It shares nothing with ``ray_tpu.models``: ``correct`` compares the
# program against these functions.

def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rotate_half(x):
    import jax.numpy as jnp
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _rope(x, cos, sin):
    """x [S, heads, D]; cos, sin [S, D]."""
    return x * cos[:, None, :] + _rotate_half(x) * sin[:, None, :]


def _attention(q, k, v):
    """Causal grouped-query attention.  q [S, NH, D]; k, v [S, NKV, D].
    One key/value head at a time, so the [reps, S, S] scores stay small."""
    import jax
    import jax.numpy as jnp
    s, nh, d = q.shape
    nkv = k.shape[1]
    reps = nh // nkv
    qg = q.reshape(s, nkv, reps, d).transpose(1, 2, 0, 3)   # [NKV, reps, S, D]
    kg = k.transpose(1, 0, 2)                               # [NKV, S, D]
    vg = v.transpose(1, 0, 2)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one_group(args):
        qh, kh, vh = args                                   # [reps,S,D] [S,D]
        scores = jnp.einsum("rsd,td->rst", qh, kh) * (d ** -0.5)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("rst,td->rsd", probs, vh)

    out = jax.lax.map(one_group, (qg, kg, vg))              # [NKV, reps, S, D]
    return out.transpose(2, 0, 1, 3).reshape(s, nh * d)


def hidden_states(params, tokens, doc: dict):
    """tokens [S] int32 -> final normed hidden states [S, H] float32."""
    import jax
    import jax.numpy as jnp
    F32 = jnp.float32
    s = tokens.shape[0]
    nh, nkv = doc["num_attention_heads"], doc["num_key_value_heads"]
    d = doc["hidden_size"] // nh
    eps = doc["rms_norm_eps"]
    inv_freq = 1.0 / (doc["rope_theta"]
                      ** (jnp.arange(0, d, 2, dtype=F32) / d))
    freqs = jnp.arange(s, dtype=F32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = jnp.cos(emb), jnp.sin(emb)

    def block(x, lp):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)      # this layer only
        y = _rms_norm(x, lp["attn_norm"]["scale"], eps)
        q = (y @ lp["attn"]["wq"]).reshape(s, nh, d)
        k = (y @ lp["attn"]["wk"]).reshape(s, nkv, d)
        v = (y @ lp["attn"]["wv"]).reshape(s, nkv, d)
        attn = _attention(_rope(q, cos, sin), _rope(k, cos, sin), v)
        x = x + attn @ lp["attn"]["wo"]
        y = _rms_norm(x, lp["mlp_norm"]["scale"], eps)
        mlp = lp["mlp"]
        x = x + (jax.nn.silu(y @ mlp["w_gate"]) * (y @ mlp["w_in"])) \
            @ mlp["w_out"]
        return x, None

    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(F32)
        x, _ = jax.lax.scan(block, x, params["blocks"])
        return _rms_norm(x, params["final_norm"]["scale"].astype(F32), eps)


def _head(params, doc):
    import jax.numpy as jnp
    if doc.get("tie_word_embeddings"):
        return params["embed"]["tokens"].astype(jnp.float32).T
    return params["lm_head"].astype(jnp.float32)


def logits(params, tokens, doc: dict, positions=None):
    """tokens [S] -> float32 logits [S, V], or [len(positions), V]."""
    import jax
    x = hidden_states(params, tokens, doc)
    if positions is not None:
        x = x[positions]
    with jax.default_matmul_precision("highest"):
        return x @ _head(params, doc)


def loss(params, tokens, doc: dict):
    """Mean next-token cross entropy of one sequence ``tokens`` [S + 1]."""
    import jax
    import jax.numpy as jnp
    lg = logits(params, tokens[:-1], doc)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).mean()


# ------------------------------------------------ 4. operations and bytes
# What the algorithms need, computed from shapes: the numerators of every
# roofline and utilisation share the benchmark reports, kept here so that no
# PR that claims a gain can change them.  All take the configuration file's
# published keys.  The decode functions share one signature, ``(doc,
# active_slots, live_kv_tokens)``; kernel costs are named by kernel.

def _dims(doc: dict):
    h = doc["hidden_size"]
    nh, nkv = doc["num_attention_heads"], doc["num_key_value_heads"]
    return h, nh, nkv, h // nh, doc["intermediate_size"], \
        doc["num_hidden_layers"], doc["vocab_size"]


def layer_params(doc: dict) -> int:
    """Matrix parameters of one block: q and o, k and v, gate, up, down."""
    h, nh, nkv, hd, m, _, _ = _dims(doc)
    return 2 * h * nh * hd + 2 * h * nkv * hd + 3 * h * m


def num_params(doc: dict) -> int:
    h, _, _, _, _, L, v = _dims(doc)
    emb = v * h * (1 if doc.get("tie_word_embeddings") else 2)
    return L * layer_params(doc) + emb


def train_flops_per_token(doc: dict, seq_len: int) -> float:
    """Forward plus backward FLOPs per token that training requires: 6 per
    active matrix parameter (the input embedding is a gather, the head a
    matmul) plus the attention score and value matmuls.  Recomputation does
    not count.  Copied from ``TransformerConfig.flops_per_token`` (which
    counts the quadratic term unhalved by causality; kept so that MFU here
    equals the number ``bench.py`` printed)."""
    h, _, _, _, _, L, v = _dims(doc)
    n_active = L * layer_params(doc) + v * h
    return 6.0 * n_active + 6.0 * L * 2 * seq_len * h


def kv_bytes_per_token(doc: dict, dtype_bytes: int = 2) -> int:
    """Bytes of K and V one token holds over all layers."""
    _, _, nkv, hd, _, L, _ = _dims(doc)
    return 2 * nkv * hd * dtype_bytes * L


def decode_step_bytes(doc: dict, active_slots: float, live_kv_tokens: float,
                      dtype_bytes: int = 2) -> float:
    """Bytes one decode step has to read from HBM: every block matrix and
    the head once (the embedding is a gather of a few rows), plus the keys
    and values of the tokens that are live in the batch.  A dense block has
    no state per slot beyond those, so ``active_slots`` adds nothing here."""
    h, _, _, _, _, L, v = _dims(doc)
    weights = (L * layer_params(doc) + v * h) * dtype_bytes
    return weights + live_kv_tokens * kv_bytes_per_token(doc, dtype_bytes)


def decode_step_flops(doc: dict, active_slots: float,
                      live_kv_tokens: float) -> float:
    """FLOPs of one decode step: 2 per matrix parameter per active slot and
    the score and value products over the live context."""
    h, nh, _, hd, _, L, v = _dims(doc)
    return (2.0 * (L * layer_params(doc) + v * h) * active_slots
            + 4.0 * L * nh * hd * live_kv_tokens)


def flash_attention_flops(doc: dict, batch: int, seq_len: int,
                          backward: bool) -> float:
    """FLOPs causal flash attention needs for ``batch`` sequences in every
    layer: 2 matmuls forward (QK^T, PV) and 5 backward (S recomputed once,
    dP, dV, dQ, dK: the published algorithm), each 2*S*S*D per head, halved
    by causality."""
    _, nh, _, hd, _, L, _ = _dims(doc)
    one = 2.0 * seq_len * seq_len * hd * nh / 2
    return L * batch * one * (2 + (5 if backward else 0))


def flash_attention_bytes(doc: dict, batch: int, seq_len: int,
                          backward: bool, dtype_bytes: int = 2) -> float:
    """Bytes the same calls have to move: forward reads Q, K, V and writes
    O; backward reads Q, K, V, O, dO and writes dQ, dK, dV."""
    _, nh, nkv, hd, _, L, _ = _dims(doc)
    q = seq_len * nh * hd * dtype_bytes
    kv = seq_len * nkv * hd * dtype_bytes
    fwd = 2 * q + 2 * kv
    bwd = 4 * q + 4 * kv
    return L * batch * (fwd + (bwd if backward else 0))
