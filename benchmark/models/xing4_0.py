"""Block kind ``xing4_0``: a decoder whose first ``first_k_dense_replace``
layers have a dense SwiGLU MLP and whose other layers a mixture of experts,
with latent attention (DeepSeek-V2's MLA, whose keys the published file
uses) in every layer and ``hc_mult`` residual streams mixed by
manifold-constrained hyper-connections (arXiv 2512.24880) around every
sublayer (HF ``model_type`` "xing4_0").  The four groups of
``benchmark/README.md``, "A block kind":

1. published keys -> the program's configuration, with its refusals;
2. the program's entry points for this block kind;
3. the plain float32 reference, written from the layer equations below and
   sharing nothing with ``ray_tpu.models`` or ``ray_tpu.ops``;
4. operations and bytes, the numerators of every roofline share, among them
   the two kernels' under their names (``moe_gmm``, ``mla_decode_attn``).

The layer equations (``x`` a sublayer's input, ``N`` an RMSNorm with a
learned scale, eps ``rms_norm_eps``).

Latent attention, heads ``h`` of ``num_attention_heads``::

    c_q = N(x W_dq)                       q_lora_rank
    q_h = [q_nope | q_rope] = (c_q W_uq)_h    qk_nope_head_dim | qk_rope_head_dim
    [c_kv | k_r] = x W_dkv                kv_lora_rank | qk_rope_head_dim
    c_kv = N(c_kv);  k_r = rope(k_r)      one k_r for all heads
    [k_nope | v]_h = (c_kv W_ukv)_h       qk_nope_head_dim | v_head_dim
    score = (q_nope . k_nope + rope(q_rope) . k_r) * scale, causal softmax
    o = concat_h(softmax v_h) W_o

with YaRN on the rotary dimensions (``rope_scaling``; ``mscale`` equal to
``mscale_all_dim`` leaves cos and sin unscaled) and ``scale = (qk_nope +
qk_rope)^-0.5 * (0.1 mscale_all_dim ln factor + 1)^2``.

An expert layer (``topk_method`` "noaux_tc", ``scoring_func`` "sigmoid",
``n_group`` = ``topk_group`` = 1)::

    s = sigmoid(x W_r)                    float32, n_routed_experts
    idx = top num_experts_per_tok of (s + b)        b the selection bias
    g = s[idx] / sum(s[idx]) * routed_scaling_factor
    y = sum_i g_i E_idx_i(x) + S(x)       E, S: SwiGLU of moe_intermediate_size

No capacity, no dropped token; one chip holds every routed expert.

The residual is ``X`` in ``R^{n x H}`` a token, ``n = hc_mult``; the
embedding fills every stream.  Around each sublayer ``F`` (attention or MLP,
with its pre-norm ``N_F``), from that sublayer's own small parameters::

    z = N(vec(X))                         n H
    [p_pre | p_post | p_res] = z phi      n | n | n^2
    h_pre = sigmoid(a_pre p_pre + b_pre)
    h_post = 2 sigmoid(a_post p_post + b_post)
    H_res = SK(clamp(a_res mat(p_res) + b_res, mhc_h_res_clamp_min, _max))
    X <- H_res X + h_post^T F(N_F(h_pre X))

``SK`` is ``exp``, then ``hc_sinkhorn_iters`` rounds of column and row
normalisation with ``hc_eps`` added to each divisor, in float32.  The
streams are summed before the final norm and the head.  The multi-token
prediction head (``num_nextn_predict_layers``) is a training head the main
model does not read: not served, no weights.

Nothing here imports JAX while the file is loaded.  It refuses to load on a
tree whose program has no latent attention, so that a cell of this kind
fails at once there instead of inside a replica that never turns healthy.
"""

from __future__ import annotations

import importlib.util
import math
import os


def _require_program():
    spec = importlib.util.find_spec("ray_tpu")
    root = os.path.dirname(spec.origin) if spec and spec.origin else None
    if root and os.path.isfile(os.path.join(root, "models", "latent.py")):
        return
    why = ("block kind xing4_0: this tree's ray_tpu has no models/latent.py "
           "(latent attention, dropless experts, residual streams); the "
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
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tied_embeddings",
    "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "n_routed_experts": "num_experts",
    "num_experts_per_tok": "experts_per_token",
    "moe_intermediate_size": "expert_mlp_size",
    "n_shared_experts": "shared_experts",
    "routed_scaling_factor": "routed_scaling_factor",
    "first_k_dense_replace": "dense_prefix_layers",
    "hc_mult": "hc_mult",
    "hc_sinkhorn_iters": "hc_sinkhorn_iters",
    "hc_eps": "hc_eps",
}
_YARN = {
    "factor": "rope_yarn_factor",
    "original_max_position_embeddings": "rope_yarn_original_max",
    "beta_fast": "rope_yarn_beta_fast",
    "beta_slow": "rope_yarn_beta_slow",
    "mscale": "rope_yarn_mscale",
    "mscale_all_dim": "rope_yarn_mscale_all_dim",
}


def program_kwargs(doc: dict) -> dict:
    missing = [k for k in (*_KEYS, "rope_scaling", "mhc_h_res_clamp_min",
                           "mhc_h_res_clamp_max") if k not in doc]
    if missing:
        raise ValueError(f"configuration lacks published keys {missing}")
    refusals = (
        (doc.get("hidden_act", "silu") != "silu", "hidden_act: the block's "
         "gated MLPs are SiLU"),
        (doc["tie_word_embeddings"], "tie_word_embeddings: the block has "
         "its own head"),
        (doc.get("attention_bias"), "attention_bias: the block's "
         "projections have none"),
        (doc.get("scoring_func") != "sigmoid"
         or doc.get("topk_method") != "noaux_tc", "scoring_func / "
         "topk_method: the block's router is sigmoid scores with a "
         "selection bias (noaux_tc)"),
        (doc.get("n_group", 1) != 1 or doc.get("topk_group", 1) != 1,
         "n_group / topk_group: the block's router has no group limit"),
        (not doc.get("norm_topk_prob"), "norm_topk_prob false: the block "
         "divides the gates by their sum"),
        (doc.get("moe_layer_freq", 1) != 1, "moe_layer_freq: every layer "
         "after the dense ones is an expert layer"),
        (doc["rope_scaling"].get("type") != "yarn", "rope_scaling.type: the "
         "block's rotary embedding is YaRN"),
        (doc["mhc_h_res_clamp_max"] != -doc["mhc_h_res_clamp_min"],
         "mhc_h_res_clamp_*: the block clamps symmetrically"),
        (doc.get("ep_size", 1) != 1, "ep_size: one chip holds the experts "
         "this file names; no exchange"),
    )
    for refused, why in refusals:
        if refused:
            raise ValueError(why)
    kw = {field: doc[key] for key, field in _KEYS.items()}
    kw.update({field: doc["rope_scaling"][key]
               for key, field in _YARN.items()})
    kw.update(hc_res_clamp=float(doc["mhc_h_res_clamp_max"]),
              moe_dropless=True, use_rope=True, use_rmsnorm=True,
              use_swiglu=True, use_qkv_bias=False, attention_impl="auto")
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
    """The latent rows and rotary keys, one of each a token a layer, and the
    record of each token's routing that the comparison follows
    (``program_choices``)."""
    from ray_tpu.models import decode
    return decode.init_kv_cache(cfg, num_slots, length, dtype,
                                expert_choices=True)


def prefill(params, cache, tokens, lengths, slots, cfg):
    from ray_tpu.models import decode
    return decode.prefill(params, cache, tokens, lengths, slots, cfg)


def decode_step(params, cache, tokens, active, cfg):
    from ray_tpu.models import decode
    return decode.decode_step(params, cache, tokens, active, cfg)


def program_choices(params, tokens, doc: dict, prompt_len: int):
    """The experts the program's routers chose for ``tokens`` [S], run as
    the comparison runs it (``serve_app._check_reference``): a prefill of
    the first ``prompt_len`` into a one-slot cache of its length, then a
    decode step for each of the rest.  [expert layers, S, k] int32."""
    import jax
    import jax.numpy as jnp
    cfg, s = program_config(doc), tokens.shape[0]
    cache = init_cache(cfg, 1, -(-(s + 1) // 128) * 128, jnp.bfloat16)
    cache, _ = prefill(params, cache, tokens[None, :prompt_len],
                       jnp.full((1,), prompt_len, jnp.int32),
                       jnp.zeros((1,), jnp.int32), cfg)

    def step(cache, token):
        return decode_step(params, cache, token[None], jnp.ones((1,), bool),
                           cfg)[0], None

    cache, _ = jax.lax.scan(step, cache, tokens[prompt_len:])
    return cache["expert_choices"][:, 0, :s]


# ------------------------------------------------- 3. the plain reference
# The equations of the module's docstring, in float32 and under
# ``jax.default_matmul_precision("highest")``: expanded attention over the
# whole sequence, no cache, no kernel.  So that it fits beside a serving
# engine on the chip: attention a block of queries at a time (32 heads x
# 4,157^2 float32 scores are 2.2 GB at once), the experts one at a time,
# every expert on every token times its gate (zero where it was not chosen;
# three matrices upcast at a time), the head a slice of the vocabulary at a
# time.  Weights are the program's parameter tree (``prefix`` and
# ``blocks``, leaves stacked [layers of the group, ...]).
#
# One thing it takes from the program, and only through ``logits``: which
# way a router's near-tie fell.  The top k of 64 scores is the one step of
# the equations that is not continuous: where the k-th and the next score lie
# closer than the program's rounding moves them, either set is the
# equations' answer "up to rounding", the two answers differ by a whole
# expert's output, and the token's later routers then see another input and
# turn over too (on the chip 6% of the (token, layer) pairs at the first
# expert layer and 65% at the sixth, and a difference of logits of 0.34-0.46
# of their deviation that no lower precision could be told from: PERF.md,
# PR 35).  So the reference is told the program's choices (``follow``) and
# takes a token's on two conditions, else it keeps its own set and the
# program's shows as the whole expert's output it is:
#   - every expert in it scores, by the reference's own float32 scores,
#     within ``FOLLOW_MARGIN`` of the reference's own k-th: what the program
#     computed before this router moved the scores no further than bf16
#     compute moves them;
#   - the program's router (``ops.moe.route_sigmoid``, the function its
#     expert layer calls), asked about the reference's own input, gives the
#     reference's own set, ties of ``ROUTER_EXACT`` apart, at this token and
#     at ``ROUTER_TRUSTED`` of the layer's tokens: its arithmetic is the
#     float32 the configuration states.  A router that scores in bf16 moves
#     a score by less than the margin has to allow, so nothing but the same
#     input could tell it; it misses the float32 set at one token in twenty
#     (the float32 one at none), and a layer whose router does is followed
#     nowhere, which reads as the plain reference does.
# The gates are always the reference's own scores.

QUERY_BLOCK = 512
VOCAB_BLOCK = 16384
#: how far below the reference's k-th score + bias an expert the program
#: chose may score and still be followed (scores are sigmoids, in (0, 1))
FOLLOW_MARGIN = 0.02
#: how far apart two float32 sums of the same 3,584 products may put a score
ROUTER_EXACT = 1e-5
#: the share of a layer's tokens at which the program's router has to give
#: the reference's own set for any of the layer's choices to be followed
ROUTER_TRUSTED = 0.99


def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _swiglu(x, gate, up, down):
    import jax
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def yarn_inv_freq(doc: dict):
    """[qk_rope_head_dim / 2] inverse frequencies, numpy float64."""
    import numpy as np
    rs, dim, base = doc["rope_scaling"], doc["qk_rope_head_dim"], \
        float(doc["rope_theta"])
    extra = base ** -(np.arange(0, dim, 2) / dim)

    def correction_dim(turns):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    mask = 1.0 - ramp                     # 1: keep the plain frequency
    return extra / rs["factor"] * (1 - mask) + extra * mask


def _mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def attention_scale(doc: dict) -> float:
    rs = doc["rope_scaling"]
    scale = (doc["qk_nope_head_dim"] + doc["qk_rope_head_dim"]) ** -0.5
    if rs.get("mscale_all_dim"):
        scale *= _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def _rope(x, doc):
    """x [S, heads, R] at positions 0..S-1, rotated in halves."""
    import jax.numpy as jnp
    rs = doc["rope_scaling"]
    mag = _mscale(rs["factor"], rs["mscale"]) \
        / _mscale(rs["factor"], rs["mscale_all_dim"])
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(yarn_inv_freq(doc), jnp.float32)
    cos, sin = jnp.cos(angles)[:, None] * mag, jnp.sin(angles)[:, None] * mag
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(x, ap, doc):
    """x [S, H] -> [S, H]: latent attention, the expanded form."""
    import jax
    import jax.numpy as jnp
    s = x.shape[0]
    nh, dn, dr = doc["num_attention_heads"], doc["qk_nope_head_dim"], \
        doc["qk_rope_head_dim"]
    dv, cr, eps = doc["v_head_dim"], doc["kv_lora_rank"], doc["rms_norm_eps"]
    c_q = _rms_norm(x @ ap["w_dq"], ap["q_norm"]["scale"], eps)
    q = (c_q @ ap["w_uq"]).reshape(s, nh, dn + dr)
    down = x @ ap["w_dkv"]
    c_kv = _rms_norm(down[:, :cr], ap["kv_norm"]["scale"], eps)
    k_r = _rope(down[:, None, cr:], doc)                       # [S, 1, R]
    kv = (c_kv @ ap["w_ukv"]).reshape(s, nh, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], doc)], axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (s, nh, dr))],
                        axis=-1)
    v, scale, outs = kv[..., dn:], attention_scale(doc), []
    for q0 in range(0, s, QUERY_BLOCK):
        q1 = min(q0 + QUERY_BLOCK, s)
        scores = jnp.einsum("qhd,khd->hqk", q[q0:q1], k[:q1]) * scale
        seen = jnp.arange(q1)[None, :] <= jnp.arange(q0, q1)[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v[:q1]))
    return jnp.concatenate(outs).reshape(s, nh * dv) @ ap["wo"]


def route(x, router, bias, doc, follow=None):
    """x [S, H] float32 -> (experts [S, k], gates [S, k], short [S]).
    ``follow`` [S, k]: the program's choice, taken for a token on the two
    conditions of the section's head; ``short`` is how far below this
    router's k-th score + bias the lowest expert of that choice scores (0
    where the sets are one, or with nothing to follow; infinite where there
    was no choice, or the program's router is not this one on this input)."""
    import jax
    import jax.numpy as jnp
    k = doc["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ router)
    biased = scores + bias
    kth, idx = jax.lax.top_k(biased, k)
    short = jnp.zeros(x.shape[:1], jnp.float32)
    if follow is not None:
        from ray_tpu.ops import moe

        def below(chosen):
            return kth[:, -1] - jnp.take_along_axis(
                biased, jnp.maximum(chosen, 0), axis=-1).min(-1)

        asked, _ = moe.route_sigmoid(x, router, bias, k,
                                     doc["routed_scaling_factor"])
        exact = below(asked) <= ROUTER_EXACT
        short = jnp.where((follow >= 0).all(-1) & exact
                          & (exact.mean() >= ROUTER_TRUSTED),
                          below(follow), jnp.inf)
        idx = jnp.where((short <= FOLLOW_MARGIN)[:, None], follow, idx)
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, gates / gates.sum(-1, keepdims=True) \
        * doc["routed_scaling_factor"], short


def _expert_layer(x, small, stacks, layer, doc, follow=None):
    """x [S, H] float32; ``small`` this layer's router, bias and shared
    expert (float32); ``stacks`` the experts' three matrices as stored,
    [layers, experts, ...], of which this is ``layer``.  Returns (out, the
    router's ``short``)."""
    import jax
    import jax.numpy as jnp
    F32 = jnp.float32
    idx, gates, short = route(x, small["router"], small["bias"], doc, follow)

    def one(e, acc):
        gate, up, down = (jax.lax.dynamic_index_in_dim(
            jax.lax.dynamic_index_in_dim(stacks[n], layer, 0, False),
            e, 0, False).astype(F32) for n in ("w_gate", "w_in", "w_out"))
        weight = jnp.where(idx == e, gates, 0.0).sum(-1)
        return acc + weight[:, None] * _swiglu(x, gate, up, down)

    out = jax.lax.fori_loop(0, doc["n_routed_experts"], one,
                            jnp.zeros_like(x))
    if "shared_gate" in small:
        out = out + _swiglu(x, small["shared_gate"], small["shared_in"],
                            small["shared_out"])
    return out, short


def sinkhorn(logits, iters: int, eps: float):
    import jax.numpy as jnp
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
    return m


def hc_coefficients(X, hp, doc):
    """X [S, n, H] -> (h_pre [S, n], h_post [S, n], H_res [S, n, n])."""
    import jax
    import jax.numpy as jnp
    n, s = doc["hc_mult"], X.shape[0]
    z = _rms_norm(X.reshape(s, -1), hp["norm"]["scale"], doc["rms_norm_eps"])
    p = z @ hp["phi"]
    a, b = hp["a"], hp["b"]
    pre = jax.nn.sigmoid(a[0] * p[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * p[:, n:2 * n] + b[n:2 * n])
    res = jnp.clip((a[2] * p[:, 2 * n:] + b[2 * n:]).reshape(s, n, n),
                   doc["mhc_h_res_clamp_min"], doc["mhc_h_res_clamp_max"])
    return pre, post, sinkhorn(res, doc["hc_sinkhorn_iters"], doc["hc_eps"])


def _sublayer(X, hp, norm_scale, f, doc):
    """``X <- H_res X + h_post^T f(N(h_pre X))``; ``f`` returns its output
    and whatever else it has to say, which comes back beside X."""
    import jax.numpy as jnp
    pre, post, res = hc_coefficients(X, hp, doc)
    seen = _rms_norm(jnp.einsum("sn,snh->sh", pre, X), norm_scale,
                     doc["rms_norm_eps"])
    out, said = f(seen)
    return (jnp.einsum("smn,snh->smh", res, X)
            + post[:, :, None] * out[:, None, :]), said


def _layer(X, lp, doc, mlp):
    """One layer on the streams; returns (X, what ``mlp`` said)."""
    X, _ = _sublayer(X, lp["hc_attn"], lp["attn_norm"]["scale"],
                     lambda y: (_attention(y, lp["attn"], doc), None), doc)
    return _sublayer(X, lp["hc_mlp"], lp["mlp_norm"]["scale"], mlp, doc)


def hidden_states(params, tokens, doc: dict, follow=None):
    """tokens [S] int32 -> (final normed hidden states [S, H] float32, the
    routers' ``short`` [expert layers, S]); ``follow`` [expert layers, S,
    k]: choices for ``route`` to follow."""
    import jax
    import jax.numpy as jnp
    F32 = jnp.float32
    routed = ("w_gate", "w_in", "w_out")

    def upcast(tree, i):
        return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, i, 0, False).astype(F32), tree)

    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(F32)
        X = jnp.broadcast_to(x[:, None], (x.shape[0], doc["hc_mult"],
                                          x.shape[1]))
        for j in range(doc["first_k_dense_replace"]):
            lp = upcast(params["prefix"], j)
            X, _ = _layer(X, lp, doc, lambda y, lp=lp: (_swiglu(
                y, lp["mlp"]["w_gate"], lp["mlp"]["w_in"],
                lp["mlp"]["w_out"]), None))
        blocks = params["blocks"]
        small = {k: v for k, v in blocks.items() if k != "moe"}
        small["moe"] = {k: v for k, v in blocks["moe"].items()
                        if k not in routed}
        stacks = {k: blocks["moe"][k] for k in routed}

        def expert_layer(X, step):
            i, chosen = step
            lp = upcast(small, i)
            return _layer(X, lp, doc, lambda y: _expert_layer(
                y, lp["moe"], stacks, i, doc, chosen))

        X, short = jax.lax.scan(
            expert_layer, X,
            (jnp.arange(doc["num_hidden_layers"]
                        - doc["first_k_dense_replace"]), follow))
        return _rms_norm(X.sum(axis=1),
                         params["final_norm"]["scale"].astype(F32),
                         doc["rms_norm_eps"]), short


def logits(params, tokens, doc: dict, positions=None, follow="program"):
    """tokens [S] -> float32 logits [S, V], or [len(positions), V].

    ``follow``: the routers' choices to follow where they are tie-breaks
    (the section's head), [expert layers, S, k]; None for the reference on
    its own; by default the program's own, run as the harness's comparison
    runs it: a prefill up to the first of ``positions`` (concrete there),
    a decode step a token after it, or one prefill where none are given."""
    import jax
    import jax.numpy as jnp
    if isinstance(follow, str):
        import numpy as np
        follow = program_choices(
            params, tokens, doc, tokens.shape[0] if positions is None
            else int(np.asarray(positions)[0]) + 1)
    x, _ = hidden_states(params, tokens, doc, follow)
    if positions is not None:
        x = x[positions]
    head, v = params["lm_head"], doc["vocab_size"]
    block = VOCAB_BLOCK if v % VOCAB_BLOCK == 0 else v
    with jax.default_matmul_precision("highest"):
        parts = jax.lax.map(
            lambda i: x @ jax.lax.dynamic_slice_in_dim(
                head, i * block, block, axis=1).astype(jnp.float32),
            jnp.arange(v // block))                   # [blocks, S, block]
    return parts.transpose(1, 0, 2).reshape(x.shape[0], v)


def loss(params, tokens, doc: dict):
    """Mean next-token cross entropy of one sequence ``tokens`` [S + 1]."""
    import jax
    import jax.numpy as jnp
    lg = logits(params, tokens[:-1], doc, follow=None)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).mean()


# ------------------------------------------------ 4. operations and bytes
# From the published keys alone, at the published widths: a program that
# pads the 192-wide heads to 256 for its prefill kernel multiplies more than
# this, which shows as a lower share of a roofline.

def _dims(doc: dict) -> dict:
    nh = doc["num_attention_heads"]
    dn, dr, dv = doc["qk_nope_head_dim"], doc["qk_rope_head_dim"], \
        doc["v_head_dim"]
    dense = doc["first_k_dense_replace"]
    return dict(
        h=doc["hidden_size"], v=doc["vocab_size"], nh=nh, dn=dn, dr=dr,
        dv=dv, qr=doc["q_lora_rank"], cr=doc["kv_lora_rank"],
        m=doc["intermediate_size"], em=doc["moe_intermediate_size"],
        e=doc["n_routed_experts"],
        k=doc["num_experts_per_tok"], sh=doc["n_shared_experts"],
        n=doc["hc_mult"], layers=doc["num_hidden_layers"], dense=dense,
        sparse=doc["num_hidden_layers"] - dense)


def layer_matrix_params(doc: dict) -> dict:
    """Matrix parameters of the parts of a layer: ``attention``, one
    ``expert``, the ``shared`` expert(s), the ``router``, the two
    hyper-connection projections ``hc``, and a dense layer's ``mlp``."""
    d = _dims(doc)
    attention = (d["h"] * d["qr"] + d["qr"] * d["nh"] * (d["dn"] + d["dr"])
                 + d["h"] * (d["cr"] + d["dr"])
                 + d["cr"] * d["nh"] * (d["dn"] + d["dv"])
                 + d["nh"] * d["dv"] * d["h"])
    return {"attention": attention, "expert": 3 * d["h"] * d["em"],
            "shared": d["sh"] * 3 * d["h"] * d["em"],
            "router": d["h"] * d["e"],
            "hc": 2 * d["n"] * d["h"] * (2 * d["n"] + d["n"] ** 2),
            "mlp": 3 * d["h"] * d["m"]}


def _outside_experts(doc: dict) -> int:
    """Matrix parameters every decode step reads whatever it routes: all
    layers' attention and hyper-connections, the dense layers' MLPs, the
    expert layers' shared expert and router."""
    d, per = _dims(doc), layer_matrix_params(doc)
    return (d["layers"] * (per["attention"] + per["hc"])
            + d["dense"] * per["mlp"]
            + d["sparse"] * (per["shared"] + per["router"]))


def num_params(doc: dict) -> int:
    """Every parameter of the program's tree: the matrices, the embedding
    and the head, and the small ones (norm scales, the selection bias, the
    hyper-connections' gains and biases)."""
    d, per = _dims(doc), layer_matrix_params(doc)
    coeffs = 2 * d["n"] + d["n"] ** 2
    small = (2 * d["h"] + d["qr"] + d["cr"]
             + 2 * (d["n"] * d["h"] + 3 + coeffs))
    return (_outside_experts(doc) + d["sparse"] * d["e"] * per["expert"]
            + d["layers"] * small + d["sparse"] * d["e"]
            + 2 * d["v"] * d["h"] + d["h"])


def kv_bytes_per_token(doc: dict, dtype_bytes: int = 2) -> int:
    """Bytes one token caches: a latent row and a rotary key a layer."""
    d = _dims(doc)
    return d["layers"] * (d["cr"] + d["dr"]) * dtype_bytes


def experts_touched(doc: dict, tokens: float) -> float:
    """Experts of one layer that ``tokens`` tokens reach under uniform
    routing."""
    d = _dims(doc)
    return d["e"] * (1.0 - (1.0 - d["k"] / d["e"]) ** tokens)


def train_flops_per_token(doc: dict, seq_len: int) -> float:
    """Forward plus backward FLOPs per token training would need: 6 per
    matrix parameter a token multiplies (its experts, not all) and per head
    weight, and the score and value matmuls.  No cell trains this kind."""
    d, per = _dims(doc), layer_matrix_params(doc)
    active = _outside_experts(doc) + d["sparse"] * d["k"] * per["expert"]
    return (6.0 * (active + d["v"] * d["h"])
            + 6.0 * d["layers"] * d["nh"] * (d["dn"] + d["dr"] + d["dv"])
            * seq_len)


def decode_step_bytes(doc: dict, active_slots: float, live_kv_tokens: float,
                      dtype_bytes: int = 2) -> float:
    """Bytes one decode step has to move: everything outside the experts
    and the head once; of each expert layer the experts ``active_slots``
    tokens reach (never all where fewer can be touched); the live tokens'
    latent rows and rotary keys."""
    d, per = _dims(doc), layer_matrix_params(doc)
    weights = (_outside_experts(doc) + d["v"] * d["h"]
               + d["sparse"] * experts_touched(doc, active_slots)
               * per["expert"])
    return (weights * dtype_bytes
            + live_kv_tokens * kv_bytes_per_token(doc, dtype_bytes))


def decode_step_flops(doc: dict, active_slots: float,
                      live_kv_tokens: float) -> float:
    d, per = _dims(doc), layer_matrix_params(doc)
    a_token = (_outside_experts(doc) + d["v"] * d["h"]
               + d["sparse"] * d["k"] * per["expert"])
    return (2.0 * a_token * active_slots
            + mla_decode_attn_flops(doc, live_kv_tokens))


def moe_gmm_flops(doc: dict, assignments: float) -> float:
    """FLOPs of the grouped matmuls for ``assignments`` (token, expert)
    pairs: gate, up and down, 2 per multiply-add."""
    return 2.0 * layer_matrix_params(doc)["expert"] * assignments


def moe_gmm_bytes(doc: dict, assignments: float, experts_read: float,
                  dtype_bytes: int = 2) -> float:
    """Bytes the same calls have to move: the three matrices of each expert
    read (``experts_read``: experts with a token, summed over layers and
    calls), and an assignment's rows in and out of the two matmuls."""
    d, per = _dims(doc), layer_matrix_params(doc)
    rows = 2 * (d["h"] + d["em"])
    return (experts_read * per["expert"] + assignments * rows) * dtype_bytes


def mla_decode_attn_flops(doc: dict, live_tokens: float) -> float:
    """FLOPs of absorbed decode attention over ``live_tokens`` cached
    positions (summed over slots), every layer: scores over the latent row
    and the rotary key, values over the latent row, 2 per multiply-add."""
    d = _dims(doc)
    return (2.0 * d["layers"] * d["nh"] * (2 * d["cr"] + d["dr"])
            * live_tokens)


def mla_decode_attn_bytes(doc: dict, live_tokens: float,
                          dtype_bytes: int = 2) -> float:
    return float(live_tokens * kv_bytes_per_token(doc, dtype_bytes))
