"""The plain reference: Mistral's decoder block as published (HF
``modeling_mistral``: RMSNorm, grouped-query attention with rotary position
embedding in the rotate-half pairing, SwiGLU), in straightforward
``jax.numpy`` and float32.  No kernel, no cache, no batching; every matmul
under ``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul
otherwise runs in bf16 passes).  Weights are the program's own parameter
tree (stacked over layers), upcast one layer at a time inside the scan.

It shares nothing with ``ray_tpu.models``: ``correct`` compares the program
against this file.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _rope(x, cos, sin):
    """x [S, heads, D]; cos, sin [S, D]."""
    return x * cos[:, None, :] + _rotate_half(x) * sin[:, None, :]


def _attention(q, k, v):
    """Causal grouped-query attention.  q [S, NH, D]; k, v [S, NKV, D].
    One key/value head at a time, so the [reps, S, S] scores stay small."""
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
    if doc.get("tie_word_embeddings"):
        return params["embed"]["tokens"].astype(F32).T
    return params["lm_head"].astype(F32)


def logits(params, tokens, doc: dict, positions=None):
    """tokens [S] -> float32 logits [S, V], or [len(positions), V]."""
    x = hidden_states(params, tokens, doc)
    if positions is not None:
        x = x[positions]
    with jax.default_matmul_precision("highest"):
        return x @ _head(params, doc)


def loss(params, tokens, doc: dict):
    """Mean next-token cross entropy of one sequence ``tokens`` [S + 1]."""
    lg = logits(params, tokens[:-1], doc)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).mean()
