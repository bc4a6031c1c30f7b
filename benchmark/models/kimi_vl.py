"""Block kind ``kimi_vl``: the language model of Kimi-VL (HF ``model_type``
"kimi_vl"; its decoder is DeepSeek-V3's block): latent attention with the
queries projected directly (``q_lora_rank`` null), a dense SwiGLU MLP in the
first ``first_k_dense_replace`` layers and a mixture of experts in the
others, one residual stream.  The vision tower and its projector are not in
the published language-model settings this kind reads, and are not built:
the decoder takes token ids.  The four groups of ``benchmark/README.md``, "A
block kind":

1. published keys -> the program's configuration, with its refusals;
2. the program's entry points for this block kind (a train cell's: the
   serving ones raise and say why);
3. the plain float32 reference, written from the layer equations below and
   sharing nothing with ``ray_tpu.models`` or ``ray_tpu.ops``;
4. operations and bytes, the numerators of every roofline share, among them
   the train step's grouped products (``moe_gmm_train_*``) and its attention
   kernels at the published head sizes (``mla_flash_train_*``).

The layer equations (``x`` a sublayer's input after its pre-norm, ``N`` an
RMSNorm with a learned scale, eps ``rms_norm_eps``; the block is ``x + F(N
x)`` for attention and then for the MLP).

Latent attention, heads ``h`` of ``num_attention_heads``::

    q_h = [q_nope | q_rope] = (x W_q)_h       qk_nope_head_dim | qk_rope_head_dim
    [c_kv | k_r] = x W_dkv                kv_lora_rank | qk_rope_head_dim
    c_kv = N(c_kv);  k_r = rope(k_r)      one k_r for all heads
    [k_nope | v]_h = (c_kv W_ukv)_h       qk_nope_head_dim | v_head_dim
    score = (q_nope . k_nope + rope(q_rope) . k_r) * (qk_nope + qk_rope)^-0.5
    o = concat_h(causal softmax(score) v_h) W_o

with plain rotary embedding at ``rope_theta`` (``rope_scaling`` null).

An expert layer (``topk_method`` "noaux_tc", ``scoring_func`` "sigmoid",
``n_group`` = ``topk_group`` = 1)::

    s = sigmoid(x W_r)                    float32, the router's 64 outputs
    idx = top num_experts_per_tok of (s + b)        b the selection bias
    g = s[idx] / sum(s[idx]) * routed_scaling_factor
    y = sum_i g_i E_idx_i(x) + S(x)       E: SwiGLU of moe_intermediate_size
                                          S: one of n_shared_experts times it

**The share.**  A configuration of this kind is one chip of ``share.chips``
that divide each layer between them: the router keeps its published width
(``reduced.n_routed_experts.published``) and its experts a token, this chip
has the weights of ``n_routed_experts`` of them from ``share.expert_start``
on, and of the vocabulary the first ``vocab_size`` ids.  The sum above runs
over the chosen experts that are held here; what the absent ones would have
added is left out, in the program and in this reference alike, and that
partial result goes on to the next layer.  Ids, logits and the loss are over
the slice.  Nothing stands in for the other chips or their traffic.

``share.by_position`` true: which group of ``n_routed_experts`` router
outputs the held weights stand for changes with the position in the
sequence: at position ``p`` it is group ``share.expert_start /
n_routed_experts + p`` modulo ``share.chips``, and held expert ``j`` is that
group's ``j``-th.  A router's output is then held at an eighth of the
positions and left out at the others, whichever output it is, so the share
of a step's assignments computed here is ``n_routed_experts`` of the
router's width whatever the router learns.  With one fixed group the
router of a model under training learns to send its tokens past it, because
the absent experts add nothing and the held ones, on random targets, add
noise (PERF.md section 6, PR 39).

Nothing here imports JAX while the file is loaded.  It refuses to load on a
tree whose program cannot train these layers, so that a cell of this kind
fails at once there.
"""

from __future__ import annotations

import importlib.util
import os


def _require_program():
    spec = importlib.util.find_spec("ray_tpu")
    root = os.path.dirname(spec.origin) if spec and spec.origin else None
    try:
        with open(os.path.join(root, "ops", "moe.py")) as f:
            if "KERNEL_MOE_GMM_DW" in f.read():
                return
    except (OSError, TypeError):
        pass
    why = ("block kind kimi_vl: this tree's ray_tpu/ops/moe.py has no "
           "backward for the grouped expert matmul (moe_gmm_dw) and its "
           "train step refuses latent attention and dropless experts; the "
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
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "n_routed_experts": "experts_held",
    "num_experts_per_tok": "experts_per_token",
    "moe_intermediate_size": "expert_mlp_size",
    "n_shared_experts": "shared_experts",
    "routed_scaling_factor": "routed_scaling_factor",
    "first_k_dense_replace": "dense_prefix_layers",
}


def router_experts(doc: dict) -> int:
    """The router's width: the published count of routed experts, of which
    ``n_routed_experts`` are held here."""
    cut = doc.get("reduced", {}).get("n_routed_experts")
    return int(cut["published"]) if cut else int(doc["n_routed_experts"])


def expert_start(doc: dict) -> int:
    return int(doc.get("share", {}).get("expert_start", 0))


def by_position(doc: dict) -> bool:
    """Whether the held experts stand for another group of the router's
    outputs at each position (``share.by_position``; the module's
    docstring, "The share")."""
    return bool(doc.get("share", {}).get("by_position", False))


def program_kwargs(doc: dict) -> dict:
    missing = [k for k in (*_KEYS, "q_lora_rank", "rope_scaling")
               if k not in doc]
    if missing:
        raise ValueError(f"configuration lacks published keys {missing}")
    refusals = (
        (doc["q_lora_rank"] is not None, "q_lora_rank: this block projects "
         "its queries directly (the published null)"),
        (doc["rope_scaling"] is not None, "rope_scaling: the block's rotary "
         "embedding is plain"),
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
        (doc.get("ep_size", 1) != 1, "ep_size: the program exchanges no "
         "tokens; a chip's share of the experts is `share` and `reduced`"),
        (expert_start(doc) + doc["n_routed_experts"] > router_experts(doc),
         "share.expert_start + n_routed_experts is past the router's width"),
    )
    for refused, why in refusals:
        if refused:
            raise ValueError(why)
    kw = {field: doc[key] for key, field in _KEYS.items()}
    kw.update(num_experts=router_experts(doc),
              expert_start=expert_start(doc),
              share_by_position=by_position(doc), q_lora_rank=0,
              moe_dropless=True, use_rope=True, use_rmsnorm=True,
              use_swiglu=True, use_qkv_bias=False, attention_impl="auto")
    return kw


def program_config(doc: dict):
    """What ``make_train_step``, ``state_shardings`` and ``init_params``
    take as ``cfg``."""
    from ray_tpu.models.config import TransformerConfig
    return TransformerConfig(**program_kwargs(doc))


# ---------------------- 2. the program's entry points for this block kind

def init_params(key, cfg, dtype):
    from ray_tpu.models import transformer
    return transformer.init_params(key, cfg, dtype=dtype)


def _train_only(what: str):
    raise NotImplementedError(
        f"block kind kimi_vl: {what} is a serve cell's; this kind's "
        "configurations are trained (kind: train).  Served, the model is "
        "the latent cache and dropless experts that the kind xing4_0's "
        "cell already measures, less its hyper-connections")


def init_cache(cfg, num_slots: int, length: int, dtype):
    _train_only("init_cache")


def prefill(params, cache, tokens, lengths, slots, cfg):
    _train_only("prefill")


def decode_step(params, cache, tokens, active, cfg):
    _train_only("decode_step")


# ------------------------------------------------- 3. the plain reference
# The equations of the module's docstring, in float32 and under
# ``jax.default_matmul_precision("highest")``: expanded attention over the
# whole sequence, no kernel, no sort of the assignments: every held expert
# on every token, times its gate (zero where the router did not choose it).
# So that one 8,192-token sequence's loss fits beside a train state:
# attention a block of queries at a time, the experts one at a time, the
# head and the loss a block of positions at a time.  Weights are the
# program's parameter tree (``prefix`` and ``blocks``, leaves stacked
# [layers of the group, ...]; the experts' [layers, held, ...]).

QUERY_BLOCK = 512
LOSS_BLOCK = 1024


def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _swiglu(x, gate, up, down):
    import jax
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _rope(x, doc):
    """x [S, heads, R] at positions 0..S-1, rotated in halves."""
    import jax.numpy as jnp
    dim = x.shape[-1]
    inv = float(doc["rope_theta"]) ** -(
        jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
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
    q = (x @ ap["wq"]).reshape(s, nh, dn + dr)
    down = x @ ap["w_dkv"]
    c_kv = _rms_norm(down[:, :cr], ap["kv_norm"]["scale"], eps)
    k_r = _rope(down[:, None, cr:], doc)                       # [S, 1, R]
    kv = (c_kv @ ap["w_ukv"]).reshape(s, nh, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], doc)], axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (s, nh, dr))],
                        axis=-1)
    v, scale, outs = kv[..., dn:], (dn + dr) ** -0.5, []
    for q0 in range(0, s, QUERY_BLOCK):
        q1 = min(q0 + QUERY_BLOCK, s)
        scores = jnp.einsum("qhd,khd->hqk", q[q0:q1], k[:q1]) * scale
        seen = jnp.arange(q1)[None, :] <= jnp.arange(q0, q1)[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v[:q1]))
    return jnp.concatenate(outs).reshape(s, nh * dv) @ ap["wo"]


def route(x, router, bias, doc):
    """x [S, H] float32 -> (experts [S, k] among the router's outputs,
    gates [S, k])."""
    import jax
    import jax.numpy as jnp
    scores = jax.nn.sigmoid(x @ router)
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(scores + bias),
                           doc["num_experts_per_tok"])
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, gates / gates.sum(-1, keepdims=True) \
        * doc["routed_scaling_factor"]


def _expert_layer(x, mp, doc, start=None, shared=True):
    """x [S, H] float32; ``mp`` one layer's router, bias, shared experts and
    the held experts' three matrices [held, ...], which are the router's
    experts ``start`` (the configuration's ``share.expert_start``) and on.
    The share's part of the layer: the held experts' outputs under the
    router's gates, and the shared experts' (``shared=False``: without)."""
    import jax
    import jax.numpy as jnp
    start = expert_start(doc) if start is None else start
    held = mp["w_gate"].shape[0]
    if by_position(doc):
        groups = router_experts(doc) // held
        start = ((start // held + jnp.arange(x.shape[0])) % groups
                 * held)[:, None]
    idx, gates = route(x, mp["router"], mp["bias"], doc)

    def one(e, acc):
        gate, up, down = (jax.lax.dynamic_index_in_dim(mp[n], e, 0, False)
                          for n in ("w_gate", "w_in", "w_out"))
        weight = jnp.where(idx == e + start, gates, 0.0).sum(-1)
        return acc + weight[:, None] * _swiglu(x, gate, up, down)

    out = jax.lax.fori_loop(0, held, one, jnp.zeros_like(x))
    if shared and "shared_gate" in mp:
        out = out + _swiglu(x, mp["shared_gate"], mp["shared_in"],
                            mp["shared_out"])
    return out


def _layer(x, lp, doc):
    eps = doc["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, lp["attn_norm"]["scale"], eps),
                       lp["attn"], doc)
    y = _rms_norm(x, lp["mlp_norm"]["scale"], eps)
    if "mlp" in lp:
        return x + _swiglu(y, lp["mlp"]["w_gate"], lp["mlp"]["w_in"],
                           lp["mlp"]["w_out"])
    return x + _expert_layer(y, lp["moe"], doc)


def hidden_states(params, tokens, doc: dict):
    """tokens [S] int32 -> final normed hidden states [S, H] float32."""
    import jax
    import jax.numpy as jnp
    F32 = jnp.float32

    def upcast(tree, i=None):
        return jax.tree.map(
            lambda a: (a if i is None else a[i]).astype(F32), tree)

    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(F32)
        for j in range(doc["first_k_dense_replace"]):
            x = _layer(x, upcast(params["prefix"], j), doc)
        x, _ = jax.lax.scan(
            lambda x, lp: (_layer(x, upcast(lp), doc), None), x,
            params["blocks"])
        return _rms_norm(x, params["final_norm"]["scale"].astype(F32),
                         doc["rms_norm_eps"])


def logits(params, tokens, doc: dict, positions=None):
    """tokens [S] -> float32 logits over the vocabulary's slice [S, V], or
    [len(positions), V]."""
    import jax
    import jax.numpy as jnp
    x = hidden_states(params, tokens, doc)
    if positions is not None:
        x = x[positions]
    with jax.default_matmul_precision("highest"):
        return x @ params["lm_head"].astype(jnp.float32)


def loss(params, tokens, doc: dict):
    """Mean next-token cross entropy of one sequence ``tokens`` [S + 1],
    over the vocabulary's slice; the head a block of positions at a time."""
    import jax
    import jax.numpy as jnp
    x = hidden_states(params, tokens[:-1], doc)
    s = x.shape[0]
    block = LOSS_BLOCK if s % LOSS_BLOCK == 0 else s
    head = params["lm_head"].astype(jnp.float32)

    def nll(part):
        xb, tb = part
        with jax.default_matmul_precision("highest"):
            logp = jax.nn.log_softmax(xb @ head, axis=-1)
        return -jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0].sum()

    return jax.lax.map(nll, (x.reshape(s // block, block, -1),
                             tokens[1:].reshape(s // block, block))
                       ).sum() / s


# ------------------------------------------------ 4. operations and bytes
# From the published keys and the share, at the published widths: a program
# that pads the 192- and 128-wide heads to 256 for its kernels multiplies
# more than this, which shows as a lower share of a roofline.

def _dims(doc: dict) -> dict:
    dense = doc["first_k_dense_replace"]
    return dict(
        h=doc["hidden_size"], v=doc["vocab_size"],
        nh=doc["num_attention_heads"], dn=doc["qk_nope_head_dim"],
        dr=doc["qk_rope_head_dim"], dv=doc["v_head_dim"],
        cr=doc["kv_lora_rank"], m=doc["intermediate_size"],
        em=doc["moe_intermediate_size"], held=doc["n_routed_experts"],
        e=router_experts(doc), k=doc["num_experts_per_tok"],
        sh=doc["n_shared_experts"], layers=doc["num_hidden_layers"],
        dense=dense, sparse=doc["num_hidden_layers"] - dense)


def layer_matrix_params(doc: dict) -> dict:
    """Matrix parameters of the parts of a layer: ``attention``, one
    ``expert``, the ``shared`` experts, the ``router``, a dense layer's
    ``mlp``."""
    d = _dims(doc)
    attention = (d["h"] * d["nh"] * (d["dn"] + d["dr"])
                 + d["h"] * (d["cr"] + d["dr"])
                 + d["cr"] * d["nh"] * (d["dn"] + d["dv"])
                 + d["nh"] * d["dv"] * d["h"])
    return {"attention": attention, "expert": 3 * d["h"] * d["em"],
            "shared": d["sh"] * 3 * d["h"] * d["em"],
            "router": d["h"] * d["e"], "mlp": 3 * d["h"] * d["m"]}


def assignments_held(doc: dict, tokens: float) -> float:
    """(token, expert) pairs that land on the experts held here, one layer,
    for ``tokens`` tokens under uniform routing: the expected count."""
    d = _dims(doc)
    return tokens * d["k"] * d["held"] / d["e"]


def num_params(doc: dict) -> int:
    """Every parameter this chip holds: the matrices, the embedding's and
    the head's slices, and the small ones (norm scales, selection bias)."""
    d, per = _dims(doc), layer_matrix_params(doc)
    small = 2 * d["h"] + d["cr"]
    return (d["layers"] * (per["attention"] + small)
            + d["dense"] * per["mlp"]
            + d["sparse"] * (d["held"] * per["expert"] + per["shared"]
                             + per["router"] + d["e"])
            + 2 * d["v"] * d["h"] + d["h"])


def train_flops_per_token(doc: dict, seq_len: int) -> float:
    """What this chip does for a token, forward and backward: 6 per matrix
    parameter the token meets here (its routed experts at the expected
    share that lands on the experts held) and per head weight of the slice,
    plus the score and value matmuls at the published head sizes, halved by
    causality.  No recomputation counted."""
    d, per = _dims(doc), layer_matrix_params(doc)
    met = (d["layers"] * per["attention"] + d["dense"] * per["mlp"]
           + d["sparse"] * (per["shared"] + per["router"]
                            + assignments_held(doc, 1.0) * per["expert"])
           + d["v"] * d["h"])
    return (6.0 * met + 3.0 * d["layers"] * d["nh"]
            * (d["dn"] + d["dr"] + d["dv"]) * seq_len)


def _replays(doc: dict) -> bool:
    """Whether the configuration's ``remat`` runs a layer's forward again in
    the backward: every policy but none does, and none of them keeps the
    expert layer's sorted rows."""
    return bool(doc.get("train", {}).get("remat"))


def moe_gmm_train_calls(doc: dict) -> dict:
    """Kernel calls of one expert layer in one train step, by kernel name
    (a test counts them in the lowered step): the forward's three grouped
    products (gate and up apart where the pass is differentiated, and
    down), the same again where ``remat`` replays the layer, the rows'
    gradient (through down; through gate and up in one call) and the
    weights' (one a matrix)."""
    return {"moe_gmm": 3 * (2 if _replays(doc) else 1), "moe_gmm_dx": 2,
            "moe_gmm_dw": 3}


def moe_gmm_train_passes(doc: dict) -> int:
    """Passes over an assignment's three matrices a step: forward, the rows'
    gradient, the weights' gradient, and the forward again where the
    configuration's ``remat`` replays it."""
    return 3 + (1 if _replays(doc) else 0)


def moe_gmm_train_flops(doc: dict, tokens: float) -> float:
    """FLOPs of one step's grouped products for ``tokens`` tokens at the
    expected assignments: 2 per multiply-add, three matrices an assignment,
    every expert layer, every pass the program runs."""
    d, per = _dims(doc), layer_matrix_params(doc)
    return (2.0 * per["expert"] * assignments_held(doc, tokens)
            * d["sparse"] * moe_gmm_train_passes(doc))


def moe_gmm_train_bytes(doc: dict, tokens: float,
                        dtype_bytes: int = 2) -> float:
    """Bytes the same calls have to move: a pass reads each held expert's
    three matrices once (the weights' gradient writes them, in float32) and
    an assignment's rows in and out of its three products."""
    d, per = _dims(doc), layer_matrix_params(doc)
    passes = moe_gmm_train_passes(doc)
    weights = d["held"] * per["expert"] * ((passes - 1) * dtype_bytes + 4)
    rows = (assignments_held(doc, tokens) * (2 * d["h"] + 3 * d["em"])
            * dtype_bytes * passes)
    return d["sparse"] * (weights + rows)


def mla_flash_train_calls(doc: dict) -> dict:
    """Attention kernel calls of one layer in one train step, by name: the
    forward (kept by ``save_acts``, run again under ``full``), and the
    backward's two."""
    remat = doc.get("train", {}).get("remat")
    return {"flash_fwd": 2 if remat in (True, "full") else 1,
            "flash_dq": 1, "flash_dkv": 1}


def mla_flash_train_flops(doc: dict, batch: float, seq_len: int) -> float:
    """FLOPs causal attention needs for ``batch`` sequences in every layer
    of one train step at the published head sizes: forward QK^T over
    ``qk_nope + qk_rope`` and PV over ``v_head_dim``; backward the published
    algorithm's five (S again, dP, dV, dQ, dK), each 2 S^2 d a head, halved
    by causality; the forward once more where ``remat`` runs it again."""
    d = _dims(doc)
    qk, dv = d["dn"] + d["dr"], d["dv"]
    forward = qk + dv
    per_head = forward * mla_flash_train_calls(doc)["flash_fwd"] \
        + 3 * qk + 2 * dv
    return (d["layers"] * batch * d["nh"] * per_head
            * 2.0 * seq_len * seq_len / 2)


def mla_flash_train_bytes(doc: dict, batch: float, seq_len: int,
                          dtype_bytes: int = 2) -> float:
    """Bytes the same calls have to move at the published head sizes:
    forward reads Q, K, V and writes O; backward reads Q, K, V, O, dO and
    writes dQ, dK, dV."""
    d = _dims(doc)
    qk, dv = d["dn"] + d["dr"], d["dv"]
    forward = (2 * qk + 2 * dv) * mla_flash_train_calls(doc)["flash_fwd"]
    backward = 4 * qk + 4 * dv
    return (d["layers"] * batch * seq_len * d["nh"] * (forward + backward)
            * dtype_bytes)


def decode_step_bytes(doc: dict, active_slots: float, live_kv_tokens: float,
                      dtype_bytes: int = 2) -> float:
    _train_only("decode_step_bytes")


def decode_step_flops(doc: dict, active_slots: float,
                      live_kv_tokens: float) -> float:
    _train_only("decode_step_flops")
