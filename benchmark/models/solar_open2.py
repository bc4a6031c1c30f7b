"""Block kind ``solar_open2``: a decoder whose layers are of two kinds in a
fixed period, Kimi Delta Attention (KDA, arXiv 2510.26692: the gated delta
rule with a decay a key channel) and gated softmax attention without a
position embedding, with a mixture of experts under every layer (HF
``model_type`` "solar_open2": ``gqa_layers``, ``linear_attn_config``,
``use_gqa_gate``, ``n_routed_experts``).  The four groups of
``benchmark/README.md``, "A block kind":

1. published keys -> the program's configuration, with its refusals;
2. the program's entry points for this block kind;
3. the plain float32 reference, written from the layer equations below and
   sharing nothing with ``ray_tpu.models`` or ``ray_tpu.ops``;
4. operations and bytes, the numerators of every roofline share, among them
   the kernels' under their names (``kda_chunk_fwd``, ``kda_recurrent_step``,
   ``moe_gmm``, ``decode_attn``, ``flash_attention``).

The layer equations (``x`` a sublayer's normed input, ``N`` an RMSNorm with a
learned scale, eps ``rms_norm_eps``).  A KDA layer (``linear_attn_config``:
``num_heads`` heads of ``head_dim`` for keys and values alike, ``num_kv_heads``
null = as many; ``short_conv_kernel_size``), per head ``h``::

    q~ = silu(conv_q(W_q x));  k~ = silu(conv_k(W_k x));  v = silu(conv_v(W_v x))
                                            causal depthwise, over time
    q = l2norm(q~_h) / sqrt(d);  k = l2norm(k~_h)
    g_t = -exp(A_log_h) * softplus(W_f^up W_f^down x_t + dt_bias_h)   in R^d
    alpha_t = exp(g_t);  beta_t = 2 * sigmoid(w_b . x_t)   (kda_allow_neg_eigval)
    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                         S in R^{d x d}, float32
    y_t = W_o [ N_head(o_t) * sigmoid(W_g^up W_g^down x_t) ]

``kda_use_full_proj`` false: the decay's and the gate's projections go
through a bottleneck (``W^down`` hidden x rank, ``W^up`` rank x heads * d).
The program keeps ``W_q | W_k | W_v`` and the three convolutions' taps side by
side in one matrix each (``w_qkv``, ``conv_w``): the same arithmetic.

A GQA layer (``gqa_layers``) is causal softmax attention, ``num_attention_heads``
query heads over ``num_key_value_heads`` key / value heads of ``head_dim``,
nothing rotary (``use_rope`` false), with ``use_gqa_gate``::

    y = W_o [ attn(q, k, v) * sigmoid(W_gate x) ]

The expert MLP, under every layer (``first_k_dense_replace`` 0)::

    s = sigmoid(x W_r)                    float32, over all the router's experts
    idx = top num_experts_per_tok of (s + b)        b the selection bias
    g = s[idx] / sum(s[idx]) * routed_scaling_factor        (norm_topk_prob)
    y = sum_i g_i E_idx_i(x) + S(x)       E, S: SwiGLU of moe_intermediate_size

A block is ``h = x + mixer(N(x)); out = h + moe(N(h))``; a final norm and an
untied head follow.

**The share.**  A configuration may hold a chip's share of each layer
(``share``, ``reduced``): ``n_routed_experts`` experts from
``share.expert_start`` on, of the ``reduced.n_routed_experts.published`` the
router scores, and a slice of the vocabulary.  The router keeps its width and
its experts a token; what the absent experts would add is left out, here and
in the program alike, and the gates are normalised over all the chosen.

Nothing here imports JAX while the file is loaded.  It refuses to load on a
tree whose program has no KDA kernels, so that a cell of this kind fails at
once there instead of inside a replica that never turns healthy.
"""

from __future__ import annotations

import importlib.util
import os


def _require_program():
    spec = importlib.util.find_spec("ray_tpu")
    root = os.path.dirname(spec.origin) if spec and spec.origin else None
    if root and os.path.isfile(os.path.join(root, "ops", "kda.py")):
        return
    why = ("block kind solar_open2: this tree's ray_tpu has no ops/kda.py "
           "(the delta rule with a decay a channel, experts under a layer "
           "pattern); the kind cannot run here")
    try:
        from benchmark.lib.manifest import ManifestError
    except ImportError:
        raise ImportError(why) from None
    raise ManifestError(why)


_require_program()

L2_EPS = 1e-6            # of the l2 norm on q and k (fla's)

# ------------------------- 1. published keys -> the program's configuration

_KEYS = {
    "vocab_size": "vocab_size",
    "num_hidden_layers": "num_layers",
    "hidden_size": "hidden_size",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "attn_head_dim",
    "intermediate_size": "mlp_size",
    "max_position_embeddings": "max_seq_len",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tied_embeddings",
    "n_routed_experts": "experts_held",
    "num_experts_per_tok": "experts_per_token",
    "moe_intermediate_size": "expert_mlp_size",
    "n_shared_experts": "shared_experts",
    "routed_scaling_factor": "routed_scaling_factor",
    "use_gqa_gate": "attn_output_gate",
    "kda_allow_neg_eigval": "linear_neg_eigval",
}


def period(doc: dict) -> tuple:
    """One period of the layers' kinds in the program's names ("full" for a
    layer of ``gqa_layers``, "linear" for a KDA layer); refuses a depth that
    is not whole repeats of its shortest period."""
    n, gqa = doc["num_hidden_layers"], set(doc["gqa_layers"])
    if not gqa <= set(range(n)):
        raise ValueError(f"gqa_layers {sorted(gqa)} of {n} layers")
    kinds = ["full" if i in gqa else "linear" for i in range(n)]
    for p in range(1, min(n, 8) + 1):
        if n % p == 0 and kinds == kinds[:p] * (n // p):
            return tuple(kinds[:p])
    raise ValueError("gqa_layers is not whole periods of a pattern of at "
                     "most 8 layers")


def router_experts(doc: dict) -> int:
    """The router's width: the published count of routed experts, of which
    ``n_routed_experts`` are held here."""
    cut = doc.get("reduced", {}).get("n_routed_experts")
    return int(cut["published"]) if cut else int(doc["n_routed_experts"])


def expert_start(doc: dict) -> int:
    return int(doc.get("share", {}).get("expert_start", 0))


def gate_rank(doc: dict) -> int:
    """The bottleneck of the decay's and the output gate's projections: the
    KDA head's width (``fla.layers.kda``; listed under ``assumed``)."""
    return int(doc["linear_attn_config"]["head_dim"])


def program_kwargs(doc: dict) -> dict:
    missing = [k for k in (*_KEYS, "linear_attn_config", "gqa_layers",
                           "use_rope", "kda_use_full_proj",
                           "first_k_dense_replace", "norm_topk_prob")
               if k not in doc]
    if missing:
        raise ValueError(f"configuration lacks published keys {missing}")
    la = doc["linear_attn_config"]
    refusals = (
        (doc["use_rope"], "use_rope: the block's softmax layers add no "
         "position embedding"),
        (doc["kda_use_full_proj"], "kda_use_full_proj: the block's decay "
         "and gate projections go through a bottleneck"),
        (doc.get("hidden_act", "silu") != "silu", "hidden_act: the block's "
         "gated MLPs are SiLU"),
        (doc["tie_word_embeddings"], "tie_word_embeddings: the block has "
         "its own head"),
        (doc.get("attention_bias"), "attention_bias: the block's "
         "projections have none"),
        (doc["first_k_dense_replace"] != 0, "first_k_dense_replace: every "
         "layer of the period has the expert MLP"),
        (not doc["norm_topk_prob"], "norm_topk_prob false: the block "
         "divides the gates by their sum"),
        (doc.get("scoring_func", "sigmoid") != "sigmoid"
         or doc.get("topk_method", "noaux_tc") != "noaux_tc",
         "scoring_func / topk_method: the block's router is sigmoid scores "
         "with a selection bias (noaux_tc)"),
        (doc.get("n_group", 1) != 1 or doc.get("topk_group", 1) != 1,
         "n_group / topk_group: the block's router has no group limit"),
        (la.get("num_kv_heads") not in (None, la["num_heads"]),
         "linear_attn_config.num_kv_heads: one key / value head a KDA head"),
        (doc.get("ep_size", 1) != 1, "ep_size: the program exchanges no "
         "tokens; a chip's share of the experts is `share` and `reduced`"),
        (expert_start(doc) + doc["n_routed_experts"] > router_experts(doc),
         "share.expert_start + n_routed_experts is past the router's width"),
    )
    for refused, why in refusals:
        if refused:
            raise ValueError(why)
    kw = {field: doc[key] for key, field in _KEYS.items()}
    kw.update(layer_pattern=period(doc), num_experts=router_experts(doc),
              expert_start=expert_start(doc), moe_dropless=True,
              linear_num_heads=la["num_heads"],
              linear_key_dim=la["head_dim"], linear_value_dim=la["head_dim"],
              linear_conv_width=la["short_conv_kernel_size"],
              linear_decay_per_channel=True, linear_gate_rank=gate_rank(doc),
              use_rope=False, no_positions=True,
              qk_norm=False, norm_on_output=False, use_rmsnorm=True,
              use_swiglu=True, use_qkv_bias=False, attention_impl="auto")
    return kw


def program_config(doc: dict):
    """What ``LLMEngine`` and the entry points below take as ``cfg``."""
    from ray_tpu.models.config import TransformerConfig
    return TransformerConfig(**program_kwargs(doc))


# ---------------------- 2. the program's entry points for this block kind

#: tokens of the seeded sample the selection biases are balanced on, and the
#: balancing rule's rounds and step (scores are sigmoids, in (0, 1))
BALANCE_TOKENS = 4096
LEVEL_ROUNDS, LEVEL_RATE = 400, 0.004


def init_params(key, cfg, dtype):
    """The program's random parameters, with each layer's selection bias set
    so that the router's load is level (``balanced``)."""
    from ray_tpu.models import transformer
    return balanced(transformer.init_params(key, cfg, dtype=dtype), key, cfg)


def _level(scores, k: int):
    """scores [T, E] -> the bias [E] under which the top ``k`` of score +
    bias load every expert alike on these T tokens: from equal mean biased
    scores, ``LEVEL_ROUNDS`` rounds of ``noaux_tc``'s rule (an expert over
    the mean load has its bias lowered, one under it raised), the step in
    proportion to the error."""
    import jax
    import jax.numpy as jnp
    t, e = scores.shape

    def a_round(_, bias):
        _, idx = jax.lax.top_k(scores + bias, k)
        load = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0)
        return bias - LEVEL_RATE * (load * e / (t * k) - 1.0)

    return jax.lax.fori_loop(0, LEVEL_ROUNDS, a_round,
                             scores.mean() - scores.mean(axis=0))


def balanced(params, key, cfg):
    """``params`` with every layer's selection bias ``b`` (the parameter
    ``noaux_tc`` has for this, which a trained checkpoint's balancing rule
    has moved and a random draw leaves at zero) set from one seeded sequence
    of ``BALANCE_TOKENS`` random ids walked through the layers in order
    (``_level``: the balancing rule itself, run until the sample's load is
    level).  With ``b = 0`` random weights give the tokens of a
    step a common favourite set (the mixers' SiLU and sigmoid outputs have a
    mean that ``W_o`` turns into one fixed direction), the 40 held experts'
    share of the load then goes by the draw (0.115-0.144 of a step's
    assignments over six seeds, 63-68% of them touched a step) and the
    cell's tokens/s with it, by 1.8% between seeds (my chip runs, PR 44).
    The gates stay the unbiased scores, as the equations have it."""
    import jax
    import jax.numpy as jnp
    F32 = jnp.float32
    kinds = cfg.layer_pattern
    doc = {
        "linear_attn_config": {
            "num_heads": cfg.linear_num_heads, "head_dim": cfg.linear_key_dim,
            "short_conv_kernel_size": cfg.linear_conv_width},
        "rms_norm_eps": cfg.norm_eps,
        "kda_allow_neg_eigval": cfg.linear_neg_eigval,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "use_gqa_gate": cfg.attn_output_gate,
        "num_experts_per_tok": cfg.experts_per_token,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "share": {"expert_start": cfg.expert_start}}
    eps, blocks = cfg.norm_eps, params["blocks"]
    tokens = jax.random.randint(jax.random.fold_in(key, 0xBA1),
                                (BALANCE_TOKENS,), 1, cfg.vocab_size)
    x = params["embed"]["tokens"][tokens].astype(F32)
    biases = {kind: [] for kind in set(kinds)}
    for layer in range(cfg.num_layers):
        kind = kinds[layer % len(kinds)]
        at = (layer // len(kinds), kinds[:layer % len(kinds)].count(kind))
        lp = jax.tree.map(lambda a: a[at].astype(F32), blocks[kind])
        if kind == "linear":
            h = x + _kda(_rms_norm(x, lp["mixer_norm"]["scale"], eps),
                         lp["mixer"], doc)
        else:
            h = x + _gqa(_rms_norm(x, lp["attn_norm"]["scale"], eps),
                         lp["attn"], doc)
        seen = _rms_norm(h, lp["mlp_norm"]["scale"], eps)
        bias = _level(jax.nn.sigmoid(seen @ lp["moe"]["router"]),
                      cfg.experts_per_token)
        biases[kind].append(bias)
        out, _ = expert_layer(seen, dict(lp["moe"], bias=bias),
                              blocks["experts"], layer, doc)
        x = h + out
    blocks = dict(blocks)
    for kind, rows in biases.items():
        old = blocks[kind]["moe"]["bias"]
        moe = dict(blocks[kind]["moe"],
                   bias=jnp.stack(rows).reshape(old.shape).astype(old.dtype))
        blocks[kind] = dict(blocks[kind], moe=moe)
    return dict(params, blocks=blocks)


def init_cache(cfg, num_slots: int, length: int, dtype):
    """Keys and values for the GQA layers, the delta rule's state and the
    convolution tail for the KDA layers, and the record of each token's
    routing that the comparison follows (``program_run``)."""
    from ray_tpu.models import decode
    return decode.init_kv_cache(cfg, num_slots, length, dtype,
                                expert_choices=True)


def prefill(params, cache, tokens, lengths, slots, cfg):
    from ray_tpu.models import decode
    return decode.prefill(params, cache, tokens, lengths, slots, cfg)


def decode_step(params, cache, tokens, active, cfg):
    from ray_tpu.models import decode
    return decode.decode_step(params, cache, tokens, active, cfg)


def program_run(params, tokens, doc: dict, prompt_len: int):
    """The program on ``tokens`` [S], run as the comparison runs it
    (``serve_app._check_reference``): a prefill of the first ``prompt_len``
    into a one-slot cache of its length, then a decode step for each of the
    rest.  Returns (the experts its routers chose [layers, S, k] int32, its
    logits at the last prompt position and after each step [1 + steps, V]).

    The comparison compiles its prefill and its decode step as programs of
    their own; here both are traced into the caller's jit, the steps as a
    scan's body.  Each is fenced in (``optimization_barrier`` around its
    arguments and its results), so that the compiler fuses, hoists and
    simplifies nothing across its edge and rounds inside it where it rounds
    in the program of its own, and the logits come back beside the choices,
    because a fence does not stop the compiler from pruning a result nobody
    reads: without them the head and the final norm left every step and the
    norms and shared experts before them were fused otherwise (17 of the
    step's 262 fusions had no twin, sandbox compile for the chip, PR 44).
    Unfenced, 292 of 2,048 (layer, step) pairs chose other experts than the
    compared run; fenced but pruned, a near-tie fell the other way about
    once a run (one held expert's output at one position: ``max_abs_diff``
    0.23-0.28 for 0.11-0.12); fenced and whole, PERF.md section 6 has the
    readings (my chip runs, PR 44)."""
    import jax
    import jax.numpy as jnp
    fence = jax.lax.optimization_barrier
    cfg, s = program_config(doc), tokens.shape[0]
    cache = init_cache(cfg, 1, -(-(s + 1) // 128) * 128, jnp.bfloat16)
    cache, first = fence(prefill(*fence((
        params, cache, tokens[None, :prompt_len],
        jnp.full((1,), prompt_len, jnp.int32), jnp.zeros((1,), jnp.int32))),
        cfg))

    def step(cache, token):
        return fence(decode_step(*fence((
            params, cache, token[None], jnp.ones((1,), bool))), cfg))

    cache, rest = jax.lax.scan(step, cache, tokens[prompt_len:])
    return (cache["expert_choices"][:, 0, :s],
            jnp.concatenate([first, rest[:, 0]]))


# ------------------------------------------------- 3. the plain reference
# The equations of the module's docstring, in float32 and under
# ``jax.default_matmul_precision("highest")``: the delta rule one token at a
# time (``lax.scan`` over positions, no chunks), attention a block of queries
# at a time over the whole row, no cache, no kernel; the held experts one at
# a time, every one on every token times its gate (zero where it was not
# chosen).  Weights are the program's parameter tree (``blocks.linear`` /
# ``blocks.full``, leaves [periods, layers of the kind in a period, ...];
# ``blocks.experts`` [layers, experts held, ...]), upcast a layer at a time.
#
# One thing it takes from the program, and only through ``logits``: which
# way a router's near-tie fell.  The top 8 of 320 scores is the one step of
# the equations that is not continuous; where the eighth and the next score
# lie closer than the program's bf16 compute moves them, either set is the
# equations' answer up to rounding, and the two answers differ by a whole
# expert's output where one of the two is held here.  So the reference is
# told the program's choices (``follow``) and takes a token's on two
# conditions, as ``models/xing4_0.py`` does and for its measured reasons
# (PERF.md section 6, PR 35), else it keeps its own set:
#   - every expert in it scores, by the reference's own float32 scores,
#     within ``FOLLOW_MARGIN`` of the reference's own k-th;
#   - the program's router (``ops.moe.route_sigmoid``), asked about the
#     reference's own input, gives the reference's own set, ties of
#     ``ROUTER_EXACT`` apart, at this token and at ``ROUTER_TRUSTED`` of the
#     layer's tokens: its arithmetic is the float32 the configuration states.
# The gates are always the reference's own scores.

QUERY_BLOCK = 512
FOLLOW_MARGIN = 0.02
ROUTER_EXACT = 1e-5
ROUTER_TRUSTED = 0.99


def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _swiglu(x, gate, up, down):
    import jax
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _delta_rule(q, k, v, alpha, beta):
    """q, k, alpha [S, H, d]; v [S, H, dv]; beta [S, H] -> o [S, H, dv]:
    ``S_t = (I - beta k k^T) Diag(alpha) S_{t-1} + beta k v^T; o = S^T q``,
    one token at a time."""
    import jax
    import jax.numpy as jnp

    def step(state, xs):                              # state [H, d, dv]
        q_t, k_t, v_t, a_t, b_t = xs
        state = a_t[:, :, None] * state
        seen = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + b_t[:, None, None] * k_t[:, :, None] \
            * (v_t - seen)[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    return jax.lax.scan(step, s0, (q, k, v, alpha, beta))[1]


def _kda(x, mp, doc):
    """x [S, H] (normed) -> the KDA mixer's output [S, H]."""
    import jax
    import jax.numpy as jnp
    s, la = x.shape[0], doc["linear_attn_config"]
    nh, d, width = la["num_heads"], la["head_dim"], \
        la["short_conv_kernel_size"]
    eps = doc["rms_norm_eps"]

    def conv(part):                  # W_q, W_k or W_v with its own taps
        cols = slice(part * nh * d, (part + 1) * nh * d)
        proj = jnp.concatenate([jnp.zeros((width - 1, nh * d), jnp.float32),
                                x @ mp["w_qkv"][:, cols]])
        return jax.nn.silu(sum(proj[j:j + s] * mp["conv_w"][j, cols]
                               for j in range(width))).reshape(s, nh, d)

    q, k, v = conv(0), conv(1), conv(2)
    q = q * jax.lax.rsqrt((q * q).sum(-1, keepdims=True) + L2_EPS) * d ** -0.5
    k = k * jax.lax.rsqrt((k * k).sum(-1, keepdims=True) + L2_EPS)
    g = -jnp.exp(mp["A_log"])[None, :, None] * jax.nn.softplus(
        (x @ mp["w_f_down"]) @ mp["w_f_up"] + mp["dt_bias"]).reshape(s, nh, d)
    beta = jax.nn.sigmoid(x @ mp["w_b"]) \
        * (2.0 if doc["kda_allow_neg_eigval"] else 1.0)
    o = _delta_rule(q, k, v, jnp.exp(g), beta)
    o = _rms_norm(o, mp["o_norm"]["scale"], eps).reshape(s, nh * d)
    return (o * jax.nn.sigmoid((x @ mp["w_g_down"]) @ mp["w_g_up"])) \
        @ mp["w_o"]


def _gqa(x, ap, doc):
    """x [S, H] (normed) -> gated causal softmax attention [S, H]."""
    import jax
    import jax.numpy as jnp
    s = x.shape[0]
    nh, nkv, d = doc["num_attention_heads"], doc["num_key_value_heads"], \
        doc["head_dim"]
    q = (x @ ap["wq"]).reshape(s, nh, d)
    k = jnp.repeat((x @ ap["wk"]).reshape(s, nkv, d), nh // nkv, axis=1)
    v = jnp.repeat((x @ ap["wv"]).reshape(s, nkv, d), nh // nkv, axis=1)
    outs = []
    for q0 in range(0, s, QUERY_BLOCK):
        q1 = min(q0 + QUERY_BLOCK, s)
        scores = jnp.einsum("qhd,khd->hqk", q[q0:q1], k[:q1]) * d ** -0.5
        seen = jnp.arange(q1)[None, :] <= jnp.arange(q0, q1)[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v[:q1]))
    attn = jnp.concatenate(outs).reshape(s, nh * d)
    if doc["use_gqa_gate"]:
        attn = attn * jax.nn.sigmoid(x @ ap["w_gate"])
    return attn @ ap["wo"]


def route(x, router, bias, doc, follow=None):
    """x [S, H] float32 -> (experts [S, k] among all the router's, gates
    [S, k], short [S]).  ``follow`` [S, k]: the program's choice, taken for
    a token on the two conditions of the section's head; ``short`` is how
    far below this router's k-th score + bias the lowest expert of that
    choice scores (0 where the sets are one, or with nothing to follow;
    infinite where there was no choice, or the program's router is not this
    one on this input)."""
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


def expert_layer(x, small, stacks, layer, doc, follow=None, shared=True):
    """x [S, H] float32 (normed); ``small`` this layer's router, bias and
    shared expert (float32); ``stacks`` the held experts' three matrices as
    stored, [layers, held, ...], of which this is ``layer``: experts
    ``share.expert_start ..`` of the router's.  The chosen experts that are
    not held add nothing; the gates are over all the chosen.  Returns (out,
    the router's ``short``)."""
    import jax
    import jax.numpy as jnp
    F32 = jnp.float32
    idx, gates, short = route(x, small["router"], small["bias"], doc, follow)
    start = expert_start(doc)

    def one(e, acc):
        gate, up, down = (jax.lax.dynamic_index_in_dim(
            jax.lax.dynamic_index_in_dim(stacks[n], layer, 0, False),
            e, 0, False).astype(F32) for n in ("w_gate", "w_in", "w_out"))
        weight = jnp.where(idx == start + e, gates, 0.0).sum(-1)
        return acc + weight[:, None] * _swiglu(x, gate, up, down)

    out = jax.lax.fori_loop(0, stacks["w_gate"].shape[1], one,
                            jnp.zeros_like(x))
    if shared and "shared_gate" in small:
        out = out + _swiglu(x, small["shared_gate"], small["shared_in"],
                            small["shared_out"])
    return out, short


def hidden_states(params, tokens, doc: dict, follow=None):
    """tokens [S] int32 -> (final normed hidden states [S, H] float32, the
    routers' ``short`` [layers, S]); ``follow`` [layers, S, k]: choices for
    ``route`` to follow."""
    import jax
    import jax.numpy as jnp
    F32 = jnp.float32
    kinds, eps = period(doc), doc["rms_norm_eps"]
    blocks = params["blocks"]

    def one_period(x, step):
        p, pp, chosen = step
        at, shorts = {"linear": 0, "full": 0}, []
        for j, kind in enumerate(kinds):
            lp = jax.tree.map(lambda a: a[at[kind]].astype(F32),
                              pp[kind])                  # this layer only
            at[kind] += 1
            if kind == "linear":
                mixed = _kda(_rms_norm(x, lp["mixer_norm"]["scale"], eps),
                             lp["mixer"], doc)
            else:
                mixed = _gqa(_rms_norm(x, lp["attn_norm"]["scale"], eps),
                             lp["attn"], doc)
            h = x + mixed
            out, short = expert_layer(
                _rms_norm(h, lp["mlp_norm"]["scale"], eps), lp["moe"],
                blocks["experts"], p * len(kinds) + j, doc,
                None if chosen is None else chosen[j])
            x = h + out
            shorts.append(short)
        return x, jnp.stack(shorts)

    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(F32)
        periods = doc["num_hidden_layers"] // len(kinds)
        if follow is not None:
            follow = follow.reshape((periods, len(kinds)) + follow.shape[1:])
        by_kind = {kind: blocks[kind] for kind in set(kinds)}
        x, short = jax.lax.scan(one_period, x,
                                (jnp.arange(periods), by_kind, follow))
        return (_rms_norm(x, params["final_norm"]["scale"].astype(F32), eps),
                short.reshape((-1,) + short.shape[2:]))


def logits(params, tokens, doc: dict, positions=None, follow="program"):
    """tokens [S] -> float32 logits over the held slice of the vocabulary
    [S, V], or [len(positions), V].

    ``follow``: the routers' choices to follow where they are tie-breaks
    (the section's head), [layers, S, k]; None for the reference on its own;
    by default the program's own, run as the harness's comparison runs it
    (``program_run``): a prefill up to the first of ``positions`` (concrete
    there), a decode step a token after it, or one prefill where none are
    given.  That run's own logits are read too, so that the compiler prunes
    nothing of it: where they are not finite, nothing here is a number
    either."""
    import jax
    import jax.numpy as jnp
    ran = None
    if isinstance(follow, str):
        import numpy as np
        follow, ran = program_run(
            params, tokens, doc, tokens.shape[0] if positions is None
            else int(np.asarray(positions)[0]) + 1)
    x, _ = hidden_states(params, tokens, doc, follow)
    if positions is not None:
        x = x[positions]
    with jax.default_matmul_precision("highest"):
        out = x @ params["lm_head"].astype(jnp.float32)
    if ran is not None:
        out = jnp.where(jnp.isfinite(ran).all(), out, jnp.nan)
    return out


def loss(params, tokens, doc: dict):
    """Mean next-token cross entropy of one sequence ``tokens`` [S + 1]."""
    import jax
    import jax.numpy as jnp
    lg = logits(params, tokens[:-1], doc, follow=None)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).mean()


# ------------------------------------------------ 4. operations and bytes
# From the published keys alone, of what this holder has and does: the held
# experts, the slice of the vocabulary.

CHUNK = 64               # of the chunked delta rule, the family's default


def _dims(doc: dict) -> dict:
    la, n = doc["linear_attn_config"], doc["num_hidden_layers"]
    full = len(set(doc["gqa_layers"]))
    return dict(
        h=doc["hidden_size"], v=doc["vocab_size"],
        nh=doc["num_attention_heads"], nkv=doc["num_key_value_heads"],
        hd=doc["head_dim"], lh=la["num_heads"], d=la["head_dim"],
        width=la["short_conv_kernel_size"], r=gate_rank(doc),
        em=doc["moe_intermediate_size"], e=router_experts(doc),
        held=doc["n_routed_experts"], k=doc["num_experts_per_tok"],
        sh=doc["n_shared_experts"], layers=n, full=full, linear=n - full)


def layer_matrix_params(doc: dict) -> dict:
    """Matrix parameters of the parts of a layer: the ``kda`` mixer, the
    ``gqa`` attention with its gate, one ``expert``, the ``shared``
    expert(s), the ``router``."""
    d = _dims(doc)
    ch = d["lh"] * d["d"]
    kda = (d["h"] * 3 * ch + ch * d["h"]
           + 2 * (d["h"] * d["r"] + d["r"] * ch) + d["h"] * d["lh"])
    wide = d["nh"] * d["hd"]
    gqa = (d["h"] * wide * (3 if doc["use_gqa_gate"] else 2)
           + 2 * d["h"] * d["nkv"] * d["hd"])
    return {"kda": kda, "gqa": gqa, "expert": 3 * d["h"] * d["em"],
            "shared": d["sh"] * 3 * d["h"] * d["em"],
            "router": d["h"] * d["e"]}


def _outside_experts(doc: dict) -> int:
    """Matrix parameters every decode step reads whatever it routes: the
    mixers, the shared expert and the router of every layer."""
    d, per = _dims(doc), layer_matrix_params(doc)
    return (d["linear"] * per["kda"] + d["full"] * per["gqa"]
            + d["layers"] * (per["shared"] + per["router"]))


def num_params(doc: dict) -> int:
    """Every parameter of the program's tree, of what this holder has: the
    matrices with the held experts, the slice's embedding and head, and the
    small ones (convolution taps, ``A_log``, ``dt_bias``, norm scales, the
    selection bias)."""
    d, per = _dims(doc), layer_matrix_params(doc)
    ch = d["lh"] * d["d"]
    kda_small = d["width"] * 3 * ch + d["lh"] + ch + d["d"]
    return (_outside_experts(doc)
            + d["layers"] * (d["held"] * per["expert"] + 2 * d["h"] + d["e"])
            + d["linear"] * kda_small + 2 * d["v"] * d["h"] + d["h"])


def state_bytes_per_slot(doc: dict) -> int:
    """Bytes of delta-rule state one sequence holds over all KDA layers
    (float32)."""
    d = _dims(doc)
    return d["linear"] * d["lh"] * d["d"] * d["d"] * 4


def kv_bytes_per_token(doc: dict, dtype_bytes: int = 2) -> int:
    """Bytes of K and V one token holds: the GQA layers only."""
    d = _dims(doc)
    return 2 * d["nkv"] * d["hd"] * dtype_bytes * d["full"]


def experts_touched(doc: dict, tokens: float) -> float:
    """Held experts of one layer that ``tokens`` tokens reach under uniform
    routing over all the router's experts."""
    d = _dims(doc)
    return d["held"] * (1.0 - (1.0 - d["k"] / d["e"]) ** tokens)


def _state_flops_per_token(doc: dict) -> float:
    """The recurrence's FLOPs a token: the decay a channel, S^T k, the
    rank-one update and S^T q, over all KDA layers."""
    d = _dims(doc)
    return d["linear"] * d["lh"] * 7.0 * d["d"] * d["d"]


def _met_here(doc: dict) -> float:
    """Of a token's chosen experts, how many are held here on average."""
    d = _dims(doc)
    return d["k"] * d["held"] / d["e"]


def train_flops_per_token(doc: dict, seq_len: int) -> float:
    """Forward plus backward FLOPs per token training would need here.  No
    cell trains this kind: a pattern has no backward pass."""
    d, per = _dims(doc), layer_matrix_params(doc)
    active = (_outside_experts(doc)
              + d["layers"] * _met_here(doc) * per["expert"])
    return (6.0 * (active + d["v"] * d["h"])
            + 6.0 * d["full"] * d["nh"] * d["hd"] * seq_len
            + 3.0 * _state_flops_per_token(doc))


def decode_step_bytes(doc: dict, active_slots: float, live_kv_tokens: float,
                      dtype_bytes: int = 2) -> float:
    """Bytes one decode step has to move, four terms: everything outside the
    experts and the head once; of each layer the held experts that
    ``active_slots`` tokens reach; the delta rule's state read and written
    once per active slot per KDA layer, at 4 bytes; K and V of the live
    tokens, GQA layers only."""
    d, per = _dims(doc), layer_matrix_params(doc)
    weights = (_outside_experts(doc) + d["v"] * d["h"]
               + d["layers"] * experts_touched(doc, active_slots)
               * per["expert"])
    return (weights * dtype_bytes
            + 2.0 * active_slots * state_bytes_per_slot(doc)
            + live_kv_tokens * kv_bytes_per_token(doc, dtype_bytes))


def decode_step_flops(doc: dict, active_slots: float,
                      live_kv_tokens: float) -> float:
    d, per = _dims(doc), layer_matrix_params(doc)
    a_token = (_outside_experts(doc) + d["v"] * d["h"]
               + d["layers"] * _met_here(doc) * per["expert"])
    return (2.0 * a_token * active_slots
            + _state_flops_per_token(doc) * active_slots
            + decode_attn_flops(doc, live_kv_tokens))


def kda_chunk_fwd_flops(doc: dict, tokens: float) -> float:
    """FLOPs the chunked (WY) form needs for ``tokens`` positions in every
    KDA layer, chunk 64: per chunk and head k k^T, q k^T and T [k beta]
    (2 c^2 d each), T [v beta] and the intra-chunk output (2 c^2 d each),
    the triangular solve (2 c^3 / 3), and the three products with the state
    (2 c d^2 each).  The decay a channel adds exponentials, not products."""
    d, c = _dims(doc), CHUNK
    per_token = 10.0 * c * d["d"] + 6.0 * d["d"] * d["d"] + 2.0 * c * c / 3
    return d["linear"] * d["lh"] * per_token * tokens


def kda_chunk_fwd_bytes(doc: dict, tokens: float,
                        dtype_bytes: int = 2) -> float:
    """Bytes the same call has to move: q, k, v read and o written per
    position, the cumulative log decay a channel and beta at 4 bytes (the
    final state, once a row, is left out)."""
    d = _dims(doc)
    per_token = 4 * d["d"] * dtype_bytes + 4 * d["d"] + 4
    return float(d["linear"] * d["lh"] * per_token * tokens)


def kda_recurrent_step_flops(doc: dict, active_slots: float) -> float:
    return _state_flops_per_token(doc) * active_slots


def kda_recurrent_step_bytes(doc: dict, active_slots: float,
                             dtype_bytes: int = 2) -> float:
    """The state read and written once per active slot per KDA layer, plus
    the step's q, k, v and o, the decay a channel and beta at 4 bytes."""
    d = _dims(doc)
    small = d["linear"] * d["lh"] * (4 * d["d"] * dtype_bytes
                                     + 4 * d["d"] + 4)
    return active_slots * (2.0 * state_bytes_per_slot(doc) + small)


def moe_gmm_flops(doc: dict, assignments: float) -> float:
    """FLOPs of the grouped matmuls for ``assignments`` (token, held
    expert) pairs: gate, up and down, 2 per multiply-add."""
    return 2.0 * layer_matrix_params(doc)["expert"] * assignments


def moe_gmm_bytes(doc: dict, assignments: float, experts_read: float,
                  dtype_bytes: int = 2) -> float:
    """Bytes the same calls have to move: the three matrices of each expert
    read (``experts_read``: experts with a token, summed over layers and
    calls), and an assignment's rows in and out of the two matmuls."""
    d, per = _dims(doc), layer_matrix_params(doc)
    rows = 2 * (d["h"] + d["em"])
    return (experts_read * per["expert"] + assignments * rows) * dtype_bytes


def decode_attn_flops(doc: dict, live_tokens: float) -> float:
    """FLOPs of decode attention over ``live_tokens`` cached positions
    (summed over slots), GQA layers: scores and values, 2 per
    multiply-add."""
    d = _dims(doc)
    return 4.0 * d["full"] * d["nh"] * d["hd"] * live_tokens


def decode_attn_bytes(doc: dict, live_tokens: float,
                      dtype_bytes: int = 2) -> float:
    return float(live_tokens * kv_bytes_per_token(doc, dtype_bytes))


def flash_attention_flops(doc: dict, batch: int, seq_len: int,
                          backward: bool = False) -> float:
    """FLOPs causal flash attention needs for ``batch`` rows in every GQA
    layer: QK^T and PV, 2 S^2 D a head each, halved by causality (5 more
    matmuls backward, which nothing here runs)."""
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
