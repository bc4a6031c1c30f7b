"""Block kind ``mellum``: JetBrains' Mellum 2 (HF ``model_type`` "mellum"; its
decoder is Qwen3-MoE's block with a pattern of sliding-window and full
attention layers and rotary settings by kind).  A train cell's kind: the four
chips of one host share each layer, its 64 experts 16 a chip with their
exchange over ``ep``, and nothing of a layer is left out.  The four groups of
``benchmark/README.md``, "A block kind":

1. published keys -> the program's configuration, with its refusals;
2. the program's entry points for this block kind (a train cell's: the
   serving ones raise and say why);
3. the plain float32 reference, written from the layer equations below and
   sharing nothing with ``ray_tpu.models`` or ``ray_tpu.ops``;
4. operations and bytes, the numerators of every roofline share, each for
   ONE chip's work of a step (a chip's own sequences through every layer,
   and the assignments that land on the experts it holds, which with every
   expert held by one of the chips are exactly ``tokens x
   num_experts_per_tok`` a layer on average).

The layer equations (``N`` an RMSNorm with a learned scale, eps
``rms_norm_eps``; the block is ``h = x + attn(N x)``, ``out = h + moe(N h)``;
a final norm and an untied head)::

    q = (x W_q) -> 32 heads of 128;  k, v = (x W_k), (x W_v) -> 4 heads of 128
    q_h = rope_kind(N_q q_h);  k_h = rope_kind(N_k k_h)    N over a head's 128
    score = q_h . k_g(h) * 128^-0.5        query head h reads KV head h // 8
    a "sliding_attention" layer's position t reads max(0, t - 1023) .. t,
    a "full_attention" layer's 0 .. t;  o = concat_h(softmax(score) v) W_o

    rope_kind: "sliding_attention" plain at rope_theta; "full_attention"
    YaRN: inverse frequencies blended between theta^-(2i/d) and the same
    over ``factor`` by a linear ramp between the dimensions that turn
    ``beta_fast`` and ``beta_slow`` times in ``original_max_position_
    embeddings``, cos and sin times ``attention_factor``; static (it applies
    at every length)

    p = softmax(x W_r) over all 64 in float32;  idx = the 8 largest
    g_i = p_i / sum of the chosen p         (``norm_topk_prob``)
    moe(x) = sum_i g_i W_down,i (silu(W_gate,i x) * W_up,i x)    at 896
    no shared expert, no capacity, no dropped token

    balance term of a layer over a sequence's T tokens (the family's
    ``load_balancing_loss_func``): 64 sum_e f_e P_e, f_e = assignments of
    expert e / T, P_e = mean_t p_t,e; the train step adds
    ``router_aux_loss_coef`` times its mean over layers and sequences to its
    total, and ``loss`` here, as the step's ``metrics["loss"]``, is without

Nothing here imports JAX while the file is loaded.  It refuses to load on a
tree whose program cannot train these layers (no exchange of tokens over
``ep``), so that a cell of this kind fails at once there.
"""

from __future__ import annotations

import importlib.util
import math
import os


def _require_program():
    spec = importlib.util.find_spec("ray_tpu")
    root = os.path.dirname(spec.origin) if spec and spec.origin else None
    try:
        with open(os.path.join(root, "ops", "moe.py")) as f:
            if "def moe_dropless_ep" in f.read():
                return
    except (OSError, TypeError):
        pass
    why = ("block kind mellum: this tree's ray_tpu/ops/moe.py has no exchange "
           "of tokens over ep (moe_dropless_ep), its train step refuses a "
           "layer_pattern and its banded flash kernel has no backward; the "
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
    "head_dim": "attn_head_dim",
    "intermediate_size": "mlp_size",
    "max_position_embeddings": "max_seq_len",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tied_embeddings",
    "sliding_window": "sliding_window",
    "num_experts": "num_experts",
    "num_experts_per_tok": "experts_per_token",
    "moe_intermediate_size": "expert_mlp_size",
}
_KINDS = {"sliding_attention": "window", "full_attention": "full"}


def layer_kinds(doc: dict) -> list:
    """The program's kind of every layer, in order."""
    return [_KINDS[t] for t in doc["layer_types"]]


def period(doc: dict) -> tuple:
    """One period of the layers' kinds: the shortest prefix of
    ``layer_types`` that, repeated, gives the whole list."""
    kinds = layer_kinds(doc)
    for n in range(1, len(kinds) + 1):
        if len(kinds) % n == 0 and kinds == kinds[:n] * (len(kinds) // n):
            return tuple(kinds[:n])
    raise AssertionError("a list is its own period")


def balance_weight(doc: dict) -> float:
    """``router_aux_loss_coef``: the published file has no such key, the
    configuration states the family's default (its ``assumed``)."""
    return float(doc.get("router_aux_loss_coef", 0.0))


def program_kwargs(doc: dict) -> dict:
    missing = [k for k in (*_KEYS, "layer_types", "mlp_layer_types",
                           "rope_parameters", "norm_topk_prob",
                           "use_sliding_window", "max_window_layers")
               if k not in doc]
    if missing:
        raise ValueError(f"configuration lacks published keys {missing}")
    ropes = doc["rope_parameters"]
    full = ropes.get("full_attention", {})
    slide = ropes.get("sliding_attention", {})
    refusals = (
        (len(doc["layer_types"]) != doc["num_hidden_layers"]
         or len(doc["mlp_layer_types"]) != doc["num_hidden_layers"],
         "layer_types / mlp_layer_types: one entry a layer"),
        (set(doc["layer_types"]) - set(_KINDS), "layer_types: "
         "'sliding_attention' or 'full_attention'"),
        (set(doc["mlp_layer_types"]) != {"sparse"}, "mlp_layer_types: every "
         "layer of this block has the experts beneath ('sparse')"),
        (doc.get("hidden_act", "silu") != "silu", "hidden_act: the experts "
         "are SiLU-gated"),
        (doc["tie_word_embeddings"], "tie_word_embeddings: the block has its "
         "own head"),
        (doc.get("attention_bias"), "attention_bias: the projections have "
         "none"),
        (not doc["norm_topk_prob"], "norm_topk_prob false: the block divides "
         "the gates by their sum"),
        (not doc["use_sliding_window"] or doc["max_window_layers"],
         "use_sliding_window / max_window_layers: layer_types says which "
         "layers slide (the published true and 0)"),
        (full.get("rope_type") != "yarn"
         or slide.get("rope_type") != "default", "rope_parameters: YaRN on "
         "the full layers and the plain table on the sliding ones"),
        (full.get("rope_theta") != slide.get("rope_theta"),
         "rope_parameters: one rope_theta for both kinds"),
        (full.get("rope_type") == "yarn" and abs(
            full.get("attention_factor", 0)
            - (0.1 * math.log(full["factor"]) + 1.0)) > 1e-9,
         "rope_parameters.full_attention.attention_factor: the program "
         "multiplies cos and sin by 0.1 ln(factor) + 1"),
    )
    for refused, why in refusals:
        if refused:
            raise ValueError(why)
    kw = {field: doc[key] for key, field in _KEYS.items()}
    kw.update(
        layer_pattern=period(doc), qk_head_norm=True, moe_dropless=True,
        moe_router="softmax", moe_balance_weight=balance_weight(doc),
        rope_theta=float(full["rope_theta"]), rope_yarn_kinds=("full",),
        rope_yarn_factor=float(full["factor"]),
        rope_yarn_original_max=int(full["original_max_position_embeddings"]),
        rope_yarn_beta_fast=float(full["beta_fast"]),
        rope_yarn_beta_slow=float(full["beta_slow"]),
        use_rope=True, use_rmsnorm=True, use_swiglu=True, use_qkv_bias=False,
        attention_impl="auto")
    return kw


def program_config(doc: dict):
    """What ``make_train_step``, ``state_shardings`` and ``init_params``
    take as ``cfg``."""
    from ray_tpu.models.config import TransformerConfig
    return TransformerConfig(**program_kwargs(doc))


# ---------------------- 2. the program's entry points for this block kind

#: what a drawn model's q norm scales are set to (``sharpened``)
ATTENTION_SHARPNESS = 4.0


def init_params(key, cfg, dtype):
    """The program's random parameters, with the attention's scores spread
    as a trained model's are (``sharpened``)."""
    from ray_tpu.models import transformer
    return sharpened(transformer.init_params(key, cfg, dtype=dtype))


def sharpened(params):
    """``params`` with every q norm's scale at ``ATTENTION_SHARPNESS`` where
    the draw leaves 1 (as the kind ``exaone_moe`` has it, and for its
    reason).  q and k are normed a head, so a drawn model's scores have unit
    spread whatever the weights: every softmax is near uniform over its band
    or its row, an attention layer hands on the AVERAGE of what it reads,
    neighbouring positions then look alike to the routers beneath, and the
    experts' load goes by the sequence, not by the token (on the chip, a
    drawn model's first step: the fullest expert 2.4 times the mean, the
    fullest chip 1.14; PERF.md section 6, PR 58).  A trained model's
    attention picks positions; at scores of spread 4 a position reads a few
    of its own."""
    def walk(tree):
        if not isinstance(tree, dict):
            return tree
        return {name: {"scale": (sub["scale"] * ATTENTION_SHARPNESS).astype(
            sub["scale"].dtype)} if name == "q_norm" else walk(sub)
            for name, sub in tree.items()}
    return walk(params)


def _train_only(what: str):
    raise NotImplementedError(
        f"block kind mellum: {what} is a serve cell's; this kind's "
        "configurations are trained (kind: train).  Served, the model is the "
        "window rings, K/V rows and dropless experts that the kind "
        "exaone_moe's cell already measures")


def init_cache(cfg, num_slots: int, length: int, dtype):
    _train_only("init_cache")


def prefill(params, cache, tokens, lengths, slots, cfg):
    _train_only("prefill")


def decode_step(params, cache, tokens, active, cfg):
    _train_only("decode_step")


# ------------------------------------------------- 3. the plain reference
# The equations of the module's docstring, in float32 and under
# ``jax.default_matmul_precision("highest")``: attention with its scores
# written out and masked, no kernel, no sort of the assignments and no
# exchange: every expert on every token, times its gate (zero where the
# router did not choose it).  So that one 8,192-token sequence fits beside a
# train state: attention a block of queries at a time, the experts one at a
# time (a scan over their stack), the head and the loss a block of positions
# at a time.  Weights are the program's parameter tree: ``blocks`` with an
# entry a kind, leaves [periods, layers of the kind a period, ...], and
# ``experts``, the routed experts [layers, experts, ...] in layer order.

QUERY_BLOCK = 512
LOSS_BLOCK = 1024
EXPERT_BLOCK = 1024


def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def rope_inverse_frequencies(doc: dict, layer_type: str):
    """(inverse frequencies [head_dim / 2] as a list of floats, what cos and
    sin are multiplied by) of a layer of ``layer_type``, from its section of
    ``rope_parameters``: the published YaRN (find the dimensions that turn
    ``beta_fast`` and ``beta_slow`` times over the original context, ramp
    linearly between them from extrapolation to interpolation) or the plain
    table."""
    rp, dim = doc["rope_parameters"][layer_type], doc["head_dim"]
    base = float(rp["rope_theta"])
    plain = [base ** -(2.0 * i / dim) for i in range(dim // 2)]
    if rp["rope_type"] == "default":
        return plain, 1.0
    factor, orig = float(rp["factor"]), rp["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rp["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rp["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i, f in enumerate(plain):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(f / factor * ramp + f * (1.0 - ramp))
    return out, float(rp["attention_factor"])


def _rope(x, doc, layer_type):
    """x [S, heads, D] at positions 0..S-1, rotated in halves."""
    import jax.numpy as jnp
    inv, mag = rope_inverse_frequencies(doc, layer_type)
    angles = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
              * jnp.asarray(inv, jnp.float32))
    cos, sin = (jnp.cos(angles) * mag)[:, None], (jnp.sin(angles) * mag)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(x, ap, doc, layer_type, window=None):
    """x [S, H] -> [S, H]; ``window``: the band of a sliding layer (the
    published ``sliding_window``; a control may give another)."""
    import jax
    import jax.numpy as jnp
    s, eps = x.shape[0], doc["rms_norm_eps"]
    nh, nkv, hd = (doc["num_attention_heads"], doc["num_key_value_heads"],
                   doc["head_dim"])
    q = _rms_norm((x @ ap["wq"]).reshape(s, nh, hd), ap["q_norm"]["scale"],
                  eps)
    k = _rms_norm((x @ ap["wk"]).reshape(s, nkv, hd), ap["k_norm"]["scale"],
                  eps)
    v = (x @ ap["wv"]).reshape(s, nkv, hd)
    q, k = _rope(q, doc, layer_type), _rope(k, doc, layer_type)
    k, v = (jnp.repeat(a, nh // nkv, axis=1) for a in (k, v))
    if layer_type == "sliding_attention":
        window = doc["sliding_window"] if window is None else window
    else:
        window = None
    # a block of queries against the ``span`` keys it can see (all of them
    # on a full layer, the last ``window - 1 + block`` on a sliding one,
    # masked by position); one body for every block, so that the compiler
    # sees one shape, and a gradient keeps a block's inputs, not its scores
    size = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    span = min(s, window - 1 + size) if window else s

    @jax.checkpoint
    def block(q0):
        k0 = jnp.clip(q0 + size - span, 0, s - span)
        qb = jax.lax.dynamic_slice_in_dim(q, q0, size)
        kb = jax.lax.dynamic_slice_in_dim(k, k0, span)
        vb = jax.lax.dynamic_slice_in_dim(v, k0, span)
        scores = jnp.einsum("qhd,khd->hqk", qb, kb) * hd ** -0.5
        qp = (q0 + jnp.arange(size))[:, None]
        kp = (k0 + jnp.arange(span))[None, :]
        seen = kp <= qp
        if window:
            seen = seen & (qp - kp < window)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, vb)

    out = jax.lax.map(block, jnp.arange(0, s, size))
    return out.reshape(s, nh * hd) @ ap["wo"]


def route(x, router, doc, follow=None):
    """x [S, H] float32 -> (experts [S, k], gates [S, k], probabilities
    [S, E]).  ``follow`` [S, k]: the experts of a run this one is compared
    with, taken for the 8 most probable (the gates are still this router's
    own probabilities of them): where two experts' probabilities lie closer
    than a lower precision's rounding, the compared run's choice is as right
    as this one's, and is data here (``tests/chip_mellum_check.py``)."""
    import jax
    import jax.numpy as jnp
    probs = jax.nn.softmax(x @ router, axis=-1)
    if follow is None:
        top, idx = jax.lax.top_k(probs, doc["num_experts_per_tok"])
    else:
        idx, top = follow, jnp.take_along_axis(probs, follow, axis=-1)
    return idx, top / top.sum(-1, keepdims=True), probs


def _experts(x, router, experts, doc, follow=None):
    """x [S, H] float32; ``experts``: one layer's three stacks [E, ...];
    ``follow`` [S, k] as ``route`` takes it.  Every expert on every token
    under its gate, an expert at a time, a block of ``EXPERT_BLOCK`` tokens
    at a time (a gradient keeps a block's inputs and replays it)."""
    import jax
    import jax.numpy as jnp
    n = experts["w_gate"].shape[0]

    @jax.checkpoint
    def block(part):
        xb, fb = part
        idx, gates, _ = route(xb, router, doc, fb)

        def one(acc, ew):
            e, gate, up, down = ew
            weight = jnp.where(idx == e, gates, 0.0).sum(-1)
            y = (jax.nn.silu(xb @ gate) * (xb @ up)) @ down
            return acc + weight[:, None] * y, None

        return jax.lax.scan(one, jnp.zeros_like(xb), (
            jnp.arange(n), experts["w_gate"], experts["w_in"],
            experts["w_out"]))[0]

    s = x.shape[0]
    size = EXPERT_BLOCK if s % EXPERT_BLOCK == 0 else s
    blocks = (x.reshape(s // size, size, -1),
              None if follow is None else follow.reshape(s // size, size, -1))
    return jax.lax.map(block, blocks).reshape(x.shape)


def experts_layer(x, router, experts, doc: dict, follow=None):
    """One layer's experts alone, x [S, H] float32 -> [S, H]: ``router`` [H,
    E], ``experts`` the layer's three stacks [E, ...], ``follow`` [S, k] as
    ``route`` takes it.  What ``tests/chip_mellum_check.py`` holds the
    program's layer against."""
    import jax
    with jax.default_matmul_precision("highest"):
        return _experts(x, router, experts, doc, follow)


def _layers(params, doc):
    """(layer_type, the layer's small leaves, its experts' stacks) of every
    layer in order, float32, out of the program's tree."""
    import jax
    import jax.numpy as jnp
    per, seen = len(period(doc)), {}
    for i, layer_type in enumerate(doc["layer_types"]):
        kind = _KINDS[layer_type]
        if i % per == 0:
            seen = {}
        n = seen[kind] = seen.get(kind, -1) + 1
        yield (layer_type,
               jax.tree.map(lambda a: a[i // per, n].astype(jnp.float32),
                            params["blocks"][kind]),
               jax.tree.map(lambda a: a[i].astype(jnp.float32),
                            params["blocks"]["experts"]))


def _walk(params, tokens, doc, window=None, follow=None):
    """tokens [S] -> (final normed hidden states [S, H] float32, the balance
    term of every layer [layers]).  ``follow`` [layers, S, k]: every layer's
    experts as ``route`` takes them."""
    import jax
    import jax.numpy as jnp
    eps, balances = doc["rms_norm_eps"], []

    def layer(x, lp, experts, told, layer_type):
        x = x + _attention(_rms_norm(x, lp["attn_norm"]["scale"], eps),
                           lp["attn"], doc, layer_type, window)
        y = _rms_norm(x, lp["mlp_norm"]["scale"], eps)
        idx, _, probs = route(y, lp["moe"]["router"], doc, told)
        n = probs.shape[-1]
        f = jnp.zeros((n,), jnp.float32).at[idx.reshape(-1)].add(
            1.0) / y.shape[0]
        balance = n * jnp.sum(jax.lax.stop_gradient(f) * probs.mean(0))
        return (x + _experts(y, lp["moe"]["router"], experts, doc, told),
                balance)

    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(jnp.float32)
        for i, (layer_type, lp, experts) in enumerate(_layers(params, doc)):
            # a layer's weights are read when its input is there (gathered
            # from a mesh's shards one layer at a time, not all ahead), and
            # a gradient keeps a layer's input and replays the layer
            x, lp, experts = jax.lax.optimization_barrier((x, lp, experts))
            x, balance = jax.checkpoint(layer, static_argnums=4)(
                x, lp, experts, None if follow is None else follow[i],
                layer_type)
            balances.append(balance)
        return (_rms_norm(x, params["final_norm"]["scale"].astype(jnp.float32),
                          eps), jnp.stack(balances))


def hidden_states(params, tokens, doc: dict):
    """tokens [S] int32 -> final normed hidden states [S, H] float32."""
    return _walk(params, tokens, doc)[0]


def balance_term(params, tokens, doc: dict):
    """Mean over the layers of the balance term of one sequence ``tokens``
    [S]: what the train step weighs by ``router_aux_loss_coef`` beside
    ``loss``."""
    return _walk(params, tokens, doc)[1].mean()


def logits(params, tokens, doc: dict, positions=None):
    """tokens [S] -> float32 logits [S, V], or [len(positions), V]."""
    import jax
    import jax.numpy as jnp
    x = hidden_states(params, tokens, doc)
    if positions is not None:
        x = x[positions]
    with jax.default_matmul_precision("highest"):
        return x @ params["lm_head"].astype(jnp.float32)


def _nll(x, targets, head):
    """Mean next-token cross entropy of hidden states x [S, H] against
    ``targets`` [S]; the head a block of positions at a time."""
    import jax
    import jax.numpy as jnp
    s = x.shape[0]
    block = LOSS_BLOCK if s % LOSS_BLOCK == 0 else s

    def nll(part):
        xb, tb = part
        with jax.default_matmul_precision("highest"):
            logp = jax.nn.log_softmax(xb @ head, axis=-1)
        return -jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0].sum()

    return jax.lax.map(nll, (x.reshape(s // block, block, -1),
                             targets.reshape(s // block, block))).sum() / s


def loss(params, tokens, doc: dict, window=None):
    """Mean next-token cross entropy of one sequence ``tokens`` [S + 1],
    without the balance term (as the step's ``metrics["loss"]``)."""
    import jax.numpy as jnp
    x, _ = _walk(params, tokens[:-1], doc, window)
    return _nll(x, tokens[1:], params["lm_head"].astype(jnp.float32))


def total_loss(params, tokens, doc: dict, follow=None):
    """``loss`` plus ``router_aux_loss_coef`` times ``balance_term``: what
    the train step differentiates, for one sequence; ``follow`` [layers, S,
    k] as ``_walk`` takes it."""
    import jax.numpy as jnp
    x, balances = _walk(params, tokens[:-1], doc, follow=follow)
    return (_nll(x, tokens[1:], params["lm_head"].astype(jnp.float32))
            + balance_weight(doc) * balances.mean())


# ------------------------------------------------ 4. operations and bytes
# For ONE chip's work of a step: ``batch`` is the sequences a chip has a
# step, ``tokens`` its tokens.  With every expert on one of the chips, the
# assignments that land on a chip's experts are ``tokens x k`` a layer on
# average over the chips, no expectation over the router.

def _dims(doc: dict) -> dict:
    kinds = layer_kinds(doc)
    return dict(
        h=doc["hidden_size"], v=doc["vocab_size"],
        nh=doc["num_attention_heads"], nkv=doc["num_key_value_heads"],
        hd=doc["head_dim"], em=doc["moe_intermediate_size"],
        e=doc["num_experts"], k=doc["num_experts_per_tok"],
        w=doc["sliding_window"], layers=len(kinds),
        full=kinds.count("full"), window=kinds.count("window"))


def layer_matrix_params(doc: dict) -> dict:
    """Matrix parameters of the parts of a layer: ``attention``, one
    ``expert``, the ``router``."""
    d = _dims(doc)
    return {"attention": (2 * d["h"] * d["nh"] * d["hd"]
                          + 2 * d["h"] * d["nkv"] * d["hd"]),
            "expert": 3 * d["h"] * d["em"], "router": d["h"] * d["e"]}


def num_params(doc: dict) -> int:
    """Every parameter of the configuration, over all the chips that share
    it: the matrices, every expert, the embedding and the head whole, and
    the small ones (two norm scales a layer, the two head norms, the
    router's unused selection bias, the final norm)."""
    d, per = _dims(doc), layer_matrix_params(doc)
    small = 2 * d["h"] + 2 * d["hd"] + d["e"]
    return (d["layers"] * (per["attention"] + per["router"]
                           + d["e"] * per["expert"] + small)
            + 2 * d["v"] * d["h"] + d["h"])


def chips(doc: dict) -> int:
    """The chips that share each layer: the configuration's ``ep``."""
    return int(doc["train"]["mesh"].get("ep", 1))


def band_mean(doc: dict, seq_len: int) -> float:
    """Mean number of positions a sliding layer's query reads over a
    sequence of ``seq_len``: t + 1 for the first ``window``, then
    ``window``."""
    w = min(_dims(doc)["w"], seq_len)
    return (w * (w + 1) / 2 + (seq_len - w) * w) / seq_len


def train_flops_per_token(doc: dict, seq_len: int) -> float:
    """Model FLOPs of a token, forward and backward: 6 per matrix parameter
    it meets (attention, the router, its ``num_experts_per_tok`` experts, the
    head), plus the score and value matmuls, 12 x heads x head_dim a key
    read: a full layer's query reads ``seq_len / 2`` keys on average, a
    sliding layer's its band (``band_mean``).  No recomputation counted."""
    d, per = _dims(doc), layer_matrix_params(doc)
    met = (d["layers"] * (per["attention"] + per["router"]
                          + d["k"] * per["expert"]) + d["v"] * d["h"])
    keys = d["full"] * seq_len / 2 + d["window"] * band_mean(doc, seq_len)
    return 6.0 * met + 12.0 * d["nh"] * d["hd"] * keys


def _replays(doc: dict) -> bool:
    """Whether the configuration's ``remat`` runs a layer's forward again in
    the backward: every policy but none does, and none keeps the expert
    layer's sorted rows."""
    return bool(doc.get("train", {}).get("remat"))


def _flash_fwd_calls(doc: dict) -> int:
    """Forward attention kernel calls a layer a step: kept by ``save_acts``
    (``attn_out``, ``attn_lse``), run again under ``full``."""
    return 2 if doc.get("train", {}).get("remat") in (True, "full") else 1


def flash_attention_flops(doc: dict, batch: float, seq_len: int,
                          backward: bool) -> float:
    """FLOPs causal attention needs for ``batch`` sequences in the FULL
    layers: 2 matmuls forward (QK^T, PV) and 5 backward (S again, dP, dV,
    dQ, dK), each 2 S^2 d a head, halved by causality."""
    d = _dims(doc)
    one = 2.0 * seq_len * seq_len * d["hd"] * d["nh"] / 2
    return d["full"] * batch * one * (
        2 * (_flash_fwd_calls(doc) if backward else 1)
        + (5 if backward else 0))


def _flash_bytes(doc: dict, layers: int, batch: float, seq_len: int,
                 backward: bool, dtype_bytes: int) -> float:
    """Bytes the attention kernels of ``layers`` layers have to move:
    forward reads Q, K, V and writes O; backward reads Q, K, V, O, dO and
    writes dQ, dK, dV."""
    d = _dims(doc)
    q = seq_len * d["nh"] * d["hd"] * dtype_bytes
    kv = seq_len * d["nkv"] * d["hd"] * dtype_bytes
    fwd = (2 * q + 2 * kv) * (_flash_fwd_calls(doc) if backward else 1)
    return layers * batch * (fwd + (4 * q + 4 * kv if backward else 0))


def flash_attention_bytes(doc: dict, batch: float, seq_len: int,
                          backward: bool, dtype_bytes: int = 2) -> float:
    """Bytes the FULL layers' calls have to move (``_flash_bytes``)."""
    return _flash_bytes(doc, _dims(doc)["full"], batch, seq_len, backward,
                        dtype_bytes)


def flash_window_train_flops(doc: dict, batch: float, seq_len: int) -> float:
    """FLOPs the band needs for ``batch`` sequences in the SLIDING layers of
    one train step: the same seven products over the keys a query reads
    (``band_mean``), the useful work of a band of ``sliding_window``.  The
    blocks a kernel computes beyond the band show as a lower share."""
    d = _dims(doc)
    one = 2.0 * seq_len * band_mean(doc, seq_len) * d["hd"] * d["nh"]
    return d["window"] * batch * one * (2 * _flash_fwd_calls(doc) + 5)


def flash_window_train_bytes(doc: dict, batch: float, seq_len: int,
                             dtype_bytes: int = 2) -> float:
    """Bytes the SLIDING layers' calls have to move: every row once a call,
    as the full layers' (``_flash_bytes``)."""
    return _flash_bytes(doc, _dims(doc)["window"], batch, seq_len, True,
                        dtype_bytes)


def moe_gmm_train_calls(doc: dict) -> dict:
    """Kernel calls of one expert layer in one train step on one chip, by
    kernel name: the exchange walks ``chips`` blocks of tokens past a chip's
    experts, and for each the forward's three grouped products (gate and up
    apart, the pass being differentiated, and down) run in the forward and
    again in the backward (each block's part is a checkpoint that keeps the
    block, not its sorted rows), the rows' gradient twice and the weights'
    three times.  A test counts them in the step compiled for the chip."""
    n = chips(doc)
    return {"moe_gmm": 6 * n, "moe_gmm_dx": 2 * n, "moe_gmm_dw": 3 * n}


def moe_gmm_train_passes(doc: dict) -> int:
    """Passes over an assignment's three matrices a step: forward, the
    forward again in the backward (a block's part keeps its inputs, not its
    sorted rows), the rows' gradient, the weights' gradient."""
    return 4


def moe_gmm_train_flops(doc: dict, tokens: float) -> float:
    """FLOPs of one step's grouped products on one chip whose own tokens
    are ``tokens``: ``tokens x k`` assignments a layer land on its experts
    (the mean over the chips), 2 per multiply-add, three matrices an
    assignment, every layer, every pass."""
    d, per = _dims(doc), layer_matrix_params(doc)
    return (2.0 * per["expert"] * tokens * d["k"] * d["layers"]
            * moe_gmm_train_passes(doc))


def moe_gmm_train_bytes(doc: dict, tokens: float,
                        dtype_bytes: int = 2) -> float:
    """Bytes the same calls have to move at the least: a pass over a block
    reads each held expert's three matrices once (so ``chips`` times a
    pass; the weights' gradient writes them in float32), and an
    assignment's rows go in and out of its three products."""
    d, per = _dims(doc), layer_matrix_params(doc)
    passes, n = moe_gmm_train_passes(doc), chips(doc)
    held = d["e"] / n
    weights = n * held * per["expert"] * ((passes - 1) * dtype_bytes + 4)
    rows = (tokens * d["k"] * (2 * d["h"] + 3 * d["em"]) * dtype_bytes
            * passes)
    return d["layers"] * (weights + rows)


def moe_ep_exchange_bytes(doc: dict, tokens: float,
                          dtype_bytes: int = 2) -> float:
    """Bytes one chip sends a train step for the exchange of its expert
    layers: a block of its ``tokens`` tokens (with 8 bytes a choice for the
    expert and the gate) on each of ``chips - 1`` hops and a float32 block
    of results back from as many, forward; the same transposed, backward;
    and the tokens' walk again where ``remat`` replays the layer."""
    d, n = _dims(doc), chips(doc)
    out = (n - 1) * tokens * (d["h"] * dtype_bytes + d["k"] * 8)
    back = (n - 1) * tokens * d["h"] * 4
    return d["layers"] * (2 * (out + back) + (out if _replays(doc) else 0))


def decode_step_bytes(doc: dict, active_slots: float, live_kv_tokens: float,
                      dtype_bytes: int = 2) -> float:
    _train_only("decode_step_bytes")


def decode_step_flops(doc: dict, active_slots: float,
                      live_kv_tokens: float) -> float:
    _train_only("decode_step_flops")
