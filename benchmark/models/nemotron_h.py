"""Block kind ``nemotron_h``: a decoder whose layers are each ONE sublayer
alone, of three kinds in a published order (``hybrid_override_pattern``): a
Mamba-2 state-space mixer (``M``), causal softmax attention without a
position embedding (``*``) and a mixture of squared-ReLU experts (``E``) (HF
``model_type`` "nemotron_h"; Nemotron-H, arXiv 2504.03624, and Mamba-2's
state-space dual, arXiv 2405.21060).  The four groups of
``benchmark/README.md``, "A block kind":

1. published keys -> the program's configuration, with its refusals;
2. the program's entry points for this block kind;
3. the plain float32 reference, written from the layer equations below and
   sharing nothing with ``ray_tpu.models`` or ``ray_tpu.ops``;
4. operations and bytes, the numerators of every roofline share, among them
   the kernels' under their names (``ssd_chunk_fwd``, ``ssd_recurrent_step``,
   ``moe_gmm``, ``decode_attn``, ``flash_attention``).

Every layer is ``x <- x + f(N(x))``, ``N`` an RMSNorm with a learned scale
(eps ``layer_norm_epsilon``); a final norm and an untied head follow.

An ``M`` layer (``mamba_num_heads`` H heads of ``mamba_head_dim`` P,
``n_groups`` G groups of ``ssm_state_size`` N, head ``h`` reads group ``h //
(H / G)``; ``conv_kernel`` taps, ``use_conv_bias``), for input ``u``::

    [z | xBC | dt~] = W_in u                 H P + (H P + 2 G N) + H columns
    xBC = silu(conv(xBC) + b_conv)           causal depthwise, over time
    dt_t = softplus(dt~_t + dt_bias)         in R^H, float32
    a_t = exp(-exp(A_log) * dt_t)            ONE DECAY A HEAD, in (0, 1]
    S_t = a_t S_{t-1} + dt_t x_t B_t^T       S in R^{P x N} a head, float32
    y_t = S_t C_t + D_h x_t
    out = W_out [ N_grouped( y_t * silu(z_t) ) ]    RMSNorm over the H P / G
                                             channels of a group, scale [H P]

A ``*`` layer is causal softmax attention, ``num_attention_heads`` query heads
over ``num_key_value_heads`` key / value heads of ``head_dim``, nothing
rotary, no gate, no bias: ``out = W_o attn(W_q x, W_k x, W_v x)``.

An ``E`` layer (``n_routed_experts`` experts of ``moe_intermediate_size``,
``num_experts_per_tok``, one shared expert of
``moe_shared_expert_intermediate_size``, ``mlp_hidden_act`` relu2)::

    s = sigmoid(x W_r)                    float32, over all the router's experts
    idx = top num_experts_per_tok of (s + b)        b the selection bias
    g = s[idx] / sum(s[idx]) * routed_scaling_factor        (norm_topk_prob)
    out = sum_i g_i E_idx_i(x) + S(x)     E, S: W_down relu(W_up x)^2

**The share.**  A configuration may hold a chip's share of each layer
(``share``, ``reduced``): ``n_routed_experts`` experts from
``share.expert_start`` on, of the ``reduced.n_routed_experts.published`` the
router scores, and a slice of the vocabulary.  The router keeps its width and
its experts a token; what the absent experts would add is left out, here and
in the program alike, and the gates are normalised over all the chosen.

Nothing here imports JAX while the file is loaded.  It refuses to load on a
tree whose program has no state-space kernels, so that a cell of this kind
fails at once there instead of inside a replica that never turns healthy.
"""

from __future__ import annotations

import importlib.util
import os


def _require_program():
    spec = importlib.util.find_spec("ray_tpu")
    root = os.path.dirname(spec.origin) if spec and spec.origin else None
    if root and os.path.isfile(os.path.join(root, "ops", "ssd.py")):
        return
    why = ("block kind nemotron_h: this tree's ray_tpu has no ops/ssd.py "
           "(the state-space mixer, layers that are one sublayer alone, "
           "experts of two matrices); the kind cannot run here")
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
    "layer_norm_epsilon": "norm_eps",
    "tie_word_embeddings": "tied_embeddings",
    "mamba_num_heads": "linear_num_heads",
    "mamba_head_dim": "linear_value_dim",
    "ssm_state_size": "linear_key_dim",
    "conv_kernel": "linear_conv_width",
    "n_groups": "ssm_groups",
    "n_routed_experts": "experts_held",
    "num_experts_per_tok": "experts_per_token",
    "moe_intermediate_size": "expert_mlp_size",
    "routed_scaling_factor": "routed_scaling_factor",
}
#: a letter of ``hybrid_override_pattern`` -> the program's kind of layer
_KINDS = {"M": "ssm", "*": "full", "E": "mlp"}


def kinds(doc: dict) -> tuple:
    """Every layer's kind in the program's names, in order."""
    pattern = doc["hybrid_override_pattern"]
    if "-" in pattern:
        raise ValueError(
            "hybrid_override_pattern with '-': the dense MLP layer of other "
            "Nemotron-H models beside expert layers would be a fifth kind "
            "of layer; the block has one MLP, the experts")
    if set(pattern) - set(_KINDS) or len(pattern) != doc["num_hidden_layers"]:
        raise ValueError(f"hybrid_override_pattern {pattern!r}: one of "
                         f"{sorted(_KINDS)} a layer, num_hidden_layers "
                         f"{doc['num_hidden_layers']} of them")
    return tuple(_KINDS[c] for c in pattern)


def period(doc: dict) -> tuple:
    """The shortest period the layers' kinds are whole repeats of (the
    published 52 are one period of 52)."""
    all_, n = kinds(doc), doc["num_hidden_layers"]
    return next(all_[:p] for p in range(1, n + 1)
                if n % p == 0 and all_ == all_[:p] * (n // p))


def router_experts(doc: dict) -> int:
    """The router's width: the published count of routed experts, of which
    ``n_routed_experts`` are held here."""
    cut = doc.get("reduced", {}).get("n_routed_experts")
    return int(cut["published"]) if cut else int(doc["n_routed_experts"])


def expert_start(doc: dict) -> int:
    return int(doc.get("share", {}).get("expert_start", 0))


def shared_width(doc: dict) -> int:
    return doc["n_shared_experts"] * doc["moe_shared_expert_intermediate_size"]


def program_kwargs(doc: dict) -> dict:
    missing = [k for k in (*_KEYS, "hybrid_override_pattern", "mlp_hidden_act",
                           "mamba_hidden_act", "use_conv_bias",
                           "n_shared_experts", "norm_topk_prob",
                           "moe_shared_expert_intermediate_size")
               if k not in doc]
    if missing:
        raise ValueError(f"configuration lacks published keys {missing}")
    refusals = (
        (doc["mlp_hidden_act"] != "relu2", "mlp_hidden_act: the block's "
         "experts are two matrices around a squared ReLU"),
        (doc["mamba_hidden_act"] != "silu", "mamba_hidden_act: the block's "
         "state-space mixer gates and convolves with SiLU"),
        (not doc["use_conv_bias"], "use_conv_bias false: the block's "
         "convolution has a bias"),
        (any(doc.get(k) for k in ("mamba_proj_bias", "mlp_bias", "use_bias",
                                  "attention_bias")),
         "mamba_proj_bias / mlp_bias / use_bias / attention_bias: the "
         "block's linear maps have none"),
        (doc["tie_word_embeddings"], "tie_word_embeddings: the block has "
         "its own head"),
        (doc.get("sliding_window") is not None, "sliding_window: the "
         "block's attention reads the whole row"),
        (not doc["norm_topk_prob"], "norm_topk_prob false: the block "
         "divides the gates by their sum"),
        (doc.get("n_group", 1) != 1 or doc.get("topk_group", 1) != 1,
         "n_group / topk_group: the block's router has no group limit"),
        (doc["mamba_num_heads"] % doc["n_groups"] != 0,
         "mamba_num_heads is not whole groups of n_groups"),
        (shared_width(doc) % doc["moe_intermediate_size"] != 0,
         "moe_shared_expert_intermediate_size: the program holds the shared "
         "expert's width as a whole multiple of the routed expert's"),
        (expert_start(doc) + doc["n_routed_experts"] > router_experts(doc),
         "share.expert_start + n_routed_experts is past the router's width"),
    )
    for refused, why in refusals:
        if refused:
            raise ValueError(why)
    kw = {field: doc[key] for key, field in _KEYS.items()}
    kw.update(layer_pattern=period(doc), mlp_act=doc["mlp_hidden_act"],
              num_experts=router_experts(doc),
              expert_start=expert_start(doc), moe_dropless=True,
              # two matrices and no gate: one expert of 2 m is two of m
              shared_experts=shared_width(doc) // doc["moe_intermediate_size"],
              use_rope=False, no_positions=True, use_rmsnorm=True,
              use_qkv_bias=False, attention_impl="auto")
    return kw


def program_config(doc: dict):
    """What ``LLMEngine`` and the entry points below take as ``cfg``."""
    from ray_tpu.models.config import TransformerConfig
    return TransformerConfig(**program_kwargs(doc))


# ---------------------- 2. the program's entry points for this block kind

#: the seeded sample the selection biases are balanced on, one row of
#: ``BALANCE_TOKENS`` random ids, and the balancing rule's rounds and step
#: (scores are sigmoids, in (0, 1))
BALANCE_TOKENS = 4096
LEVEL_ROUNDS, LEVEL_RATE = 400, 0.004


def init_params(key, cfg, dtype):
    """The program's random parameters, with each expert layer's selection
    bias set so that the router's load is level (``balanced``)."""
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


def _doc_of(cfg) -> dict:
    """The keys the reference's layers read, from the program's ``cfg``."""
    letters = {v: k for k, v in _KINDS.items()}
    return {
        "hybrid_override_pattern": "".join(
            letters[k] for k in cfg.layer_pattern) * cfg.num_periods,
        "num_hidden_layers": cfg.num_layers,
        "mamba_num_heads": cfg.linear_num_heads,
        "mamba_head_dim": cfg.linear_value_dim,
        "ssm_state_size": cfg.linear_key_dim, "n_groups": cfg.ssm_groups,
        "conv_kernel": cfg.linear_conv_width,
        "layer_norm_epsilon": cfg.norm_eps,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "num_experts_per_tok": cfg.experts_per_token,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "share": {"expert_start": cfg.expert_start}}


def balanced(params, key, cfg):
    """``params`` with every expert layer's selection bias ``b`` (the
    parameter ``noaux_tc`` has for this, which a trained checkpoint's
    balancing rule has moved and a random draw leaves at zero) set so that
    the routers' load is level on one seeded sequence of ``BALANCE_TOKENS``
    random ids walked through the layers in order (``_level``: the balancing
    rule itself, run until the sample's load is level), as
    ``models/solar_open2.balanced`` has it and for its reasons (with ``b =
    0`` random weights give the tokens of a step a common favourite set:
    PERF.md section 6, PR 44).  The gates stay the unbiased scores, as the
    equations have it.

    Random ids are what the cell's prompts hold, not what its greedy
    decode steps emit: random weights at the published widths, decoded
    greedily, fall into short cycles, on one seed of the fifteen tried into
    one token a row within 256 steps, and the experts a step touches then go
    by the seed (75-93% of the held 64 over a window; uniform routing gives
    95%).  Levelling on the program's own greedy continuations as well, 16
    to 64 rows of 256 to 768 tokens, did not mend that on every seed and is
    not done here (PERF.md section 6, PR 46)."""
    import jax
    import jax.numpy as jnp
    doc = _doc_of(cfg)
    tokens = jax.random.randint(jax.random.fold_in(key, 0xBA1),
                                (1, BALANCE_TOKENS), 1, cfg.vocab_size)

    def level(seen, small):
        return _level(jax.nn.sigmoid(seen[0] @ small["router"]),
                      cfg.experts_per_token)

    with jax.default_matmul_precision("highest"):
        _, biases = _walk(params, tokens, doc, level=level)
    old = params["blocks"]["mlp"]["moe"]["bias"]
    moe = dict(params["blocks"]["mlp"]["moe"], bias=jnp.stack(
        biases).reshape(old.shape).astype(old.dtype))
    mlp = dict(params["blocks"]["mlp"], moe=moe)
    return dict(params, blocks=dict(params["blocks"], mlp=mlp))


def init_cache(cfg, num_slots: int, length: int, dtype):
    """Keys and values for the attention layers, the state-space state and
    the convolution tail for the ``M`` layers, and the record of each
    token's routing (``expert_choices``): what a comparison that is handed
    the compared run's own choices as data reads (``logits(follow=)``;
    ``tests/chip_nano_check.py`` does, the harness cannot yet)."""
    from ray_tpu.models import decode
    return decode.init_kv_cache(cfg, num_slots, length, dtype,
                                expert_choices=True)


#: the engine's rows are whole buckets, every one whole blocks of this many
#: positions (512 .. 8192); so is the row ``prefill`` walks
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
# of queries at a time over the whole row, no cache, no kernel; the held
# experts one at a time, every one on every token times its gate (zero where
# it was not chosen).  Weights are the program's parameter tree (``blocks.ssm``
# / ``blocks.full`` / ``blocks.mlp``, leaves [periods, layers of the kind in
# a period, ...]; ``blocks.experts`` [expert layers, experts held, ...]),
# upcast a layer at a time.  Nothing of ``ray_tpu`` runs here: the section
# reads the parameter tree and calls ``jax`` alone.
#
# **Near-ties.**  The top 6 of 128 scores is the one step of the equations
# that is not continuous; where the sixth and the next score lie closer than
# the program's bf16 stream moves them, either set is the equations' answer
# up to rounding, and the two answers differ by a whole expert's output
# where one of the two is held here.  On its own (``follow=None``: what the
# harness's comparison calls, since it hands the reference nothing of the
# compared run) the reference routes by its own float32 scores, and every
# near-tie that fell the other way in the program stands in the difference:
# the configuration's ``check`` gives what that reads and what it hides.
# ``follow`` [expert layers, S, k] is DATA: the experts some run chose, as
# its cache recorded them (``expert_choices``).  A token's choice is taken
# where it is a tie-break by the reference's own account, every expert in it
# scoring, by the reference's own float32 scores, within ``FOLLOW_MARGIN`` of
# the reference's own k-th; else the reference keeps its own set.  The gates
# are always the reference's own scores.

QUERY_BLOCK = 512
FOLLOW_MARGIN = 0.02


def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _relu2_mlp(x, up, down):
    import jax.numpy as jnp
    return jnp.square(jnp.maximum(x @ up, 0.0)) @ down


def _mamba(u, mp, doc):
    """u [S, H] (normed) -> the state-space mixer's output [S, H]."""
    import jax
    import jax.numpy as jnp
    s = u.shape[0]
    nh, p, n, g = (doc["mamba_num_heads"], doc["mamba_head_dim"],
                   doc["ssm_state_size"], doc["n_groups"])
    width, inner = doc["conv_kernel"], nh * p
    proj = u @ mp["w_in"]
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * g * n],
                  proj[:, 2 * inner + 2 * g * n:])
    padded = jnp.concatenate([jnp.zeros((width - 1, xbc.shape[1]),
                                        jnp.float32), xbc])
    xbc = jax.nn.silu(sum(padded[j:j + s] * mp["conv_w"][j]
                          for j in range(width)) + mp["conv_b"])
    x = xbc[:, :inner].reshape(s, nh, p)
    # head h reads group h // (H / G)
    b, c = (jnp.repeat(xbc[:, lo:lo + g * n].reshape(s, g, n), nh // g, 1)
            for lo in (inner, inner + g * n))
    dt = jax.nn.softplus(dt + mp["dt_bias"])                      # [S, H]
    a = jnp.exp(-jnp.exp(mp["A_log"]) * dt)

    def step(state, xs):                              # state [H, P, N]
        x_t, b_t, c_t, dt_t, a_t = xs
        state = (a_t[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    y = jax.lax.scan(step, jnp.zeros((nh, p, n), jnp.float32),
                     (x, b, c, dt, a))[1] + mp["D"][None, :, None] * x
    y = y.reshape(s, inner) * jax.nn.silu(z)
    grouped = y.reshape(s, g, inner // g)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True)
        + doc["layer_norm_epsilon"])
    return (grouped.reshape(s, inner) * mp["o_norm"]["scale"]) @ mp["w_out"]


def _attention(x, ap, doc):
    """x [S, H] (normed) -> causal softmax attention [S, H], no positions."""
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
    return jnp.concatenate(outs).reshape(s, nh * d) @ ap["wo"]


def route(x, router, bias, doc, follow=None):
    """x [S, H] float32 -> (experts [S, k] among all the router's, gates
    [S, k], short [S]).  ``follow`` [S, k]: a recorded choice, taken for a
    token where it is a tie-break (the section's head); ``short`` is how far
    below this router's k-th score + bias the lowest expert of that choice
    scores (0 where the sets are one, or with nothing to follow; infinite
    where nothing was recorded, -1)."""
    import jax
    import jax.numpy as jnp
    k = doc["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ router)
    biased = scores + bias
    kth, idx = jax.lax.top_k(biased, k)
    short = jnp.zeros(x.shape[:1], jnp.float32)
    if follow is not None:
        short = jnp.where(
            (follow >= 0).all(-1), kth[:, -1] - jnp.take_along_axis(
                biased, jnp.maximum(follow, 0), axis=-1).min(-1), jnp.inf)
        idx = jnp.where((short <= FOLLOW_MARGIN)[:, None], follow, idx)
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, gates / gates.sum(-1, keepdims=True) \
        * doc["routed_scaling_factor"], short


def expert_layer(x, small, stacks, layer, doc, follow=None, shared=True):
    """x [S, H] float32 (normed); ``small`` this layer's router, bias and
    shared expert (float32); ``stacks`` the held experts' two matrices as
    stored, [expert layers, held, ...], of which this is ``layer``: experts
    ``share.expert_start ..`` of the router's.  The chosen experts that are
    not held add nothing; the gates are over all the chosen.  Returns (out,
    the router's ``short``)."""
    import jax
    import jax.numpy as jnp
    F32 = jnp.float32
    idx, gates, short = route(x, small["router"], small["bias"], doc, follow)
    start = expert_start(doc)

    def one(e, acc):
        up, down = (jax.lax.dynamic_index_in_dim(
            jax.lax.dynamic_index_in_dim(stacks[n], layer, 0, False),
            e, 0, False).astype(F32) for n in ("w_up", "w_out"))
        weight = jnp.where(idx == start + e, gates, 0.0).sum(-1)
        # the up projection lies as a linear map stores it, [M, H]
        return acc + weight[:, None] * _relu2_mlp(x, up.T, down)

    out = jax.lax.fori_loop(0, stacks["w_out"].shape[1], one,
                            jnp.zeros_like(x))
    if shared and "shared_in" in small:
        out = out + _relu2_mlp(x, small["shared_in"], small["shared_out"])
    return out, short


def _walk(params, tokens, doc: dict, follow=None, level=None):
    """The layers in order on rows of ``tokens`` [R, S] -> (the residual
    stream after the last [R, S, H] float32, a list with one entry an expert
    layer: its router's ``short`` [R S], or with ``level`` the selection
    bias ``level(seen [R, S, H], small)`` gave it, which the layer then
    routes with).  A mixer sees a row at a time, an expert layer the rows'
    tokens one after another (``follow`` [expert layers, R S, k])."""
    import jax
    import jax.numpy as jnp
    F32 = jnp.float32
    eps, blocks = doc["layer_norm_epsilon"], params["blocks"]
    pattern, at, said = period(doc), {}, []
    x = params["embed"]["tokens"][tokens].astype(F32)
    for kind in kinds(doc):
        i = at.get(kind, 0)
        at[kind] = i + 1
        count = pattern.count(kind)
        lp = jax.tree.map(lambda a: a[i // count, i % count].astype(F32),
                          blocks[kind])                  # this layer only
        if kind == "ssm":
            seen = _rms_norm(x, lp["mixer_norm"]["scale"], eps)
            out = jax.vmap(lambda u: _mamba(u, lp["mixer"], doc))(seen)
        elif kind == "full":
            seen = _rms_norm(x, lp["attn_norm"]["scale"], eps)
            out = jax.vmap(lambda u: _attention(u, lp["attn"], doc))(seen)
        else:
            seen, small = _rms_norm(x, lp["mlp_norm"]["scale"], eps), lp["moe"]
            if level is not None:
                small = dict(small, bias=level(seen, small))
            out, short = expert_layer(
                seen.reshape(-1, seen.shape[-1]), small, blocks["experts"],
                i, doc, None if follow is None else follow[i])
            out = out.reshape(x.shape)
            said.append(small["bias"] if level is not None else short)
        x = x + out
    return x, said


def hidden_states(params, tokens, doc: dict, follow=None):
    """tokens [S] int32 -> (final normed hidden states [S, H] float32, the
    routers' ``short`` [expert layers, S]); ``follow`` [expert layers, S,
    k]: choices for ``route`` to follow."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        x, short = _walk(params, tokens[None], doc, follow)
        return (_rms_norm(x[0], params["final_norm"]["scale"].astype(
            jnp.float32), doc["layer_norm_epsilon"]), jnp.stack(short))


def logits(params, tokens, doc: dict, positions=None, follow=None):
    """tokens [S] -> float32 logits over the held slice of the vocabulary
    [S, V], or [len(positions), V].  ``follow``: recorded choices to take
    where they are tie-breaks (the section's head), [expert layers, S, k];
    None, the harness's call, for the reference on its own."""
    import jax
    import jax.numpy as jnp
    x, _ = hidden_states(params, tokens, doc, follow)
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
# From the published keys alone, of what this holder has and does: the held
# experts, the slice of the vocabulary.

CHUNK = 128              # of the chunked state-space dual (``chunk_size``)


def _dims(doc: dict) -> dict:
    all_ = kinds(doc)
    nh, p = doc["mamba_num_heads"], doc["mamba_head_dim"]
    g, n = doc["n_groups"], doc["ssm_state_size"]
    return dict(
        h=doc["hidden_size"], v=doc["vocab_size"],
        nh=doc["num_attention_heads"], nkv=doc["num_key_value_heads"],
        hd=doc["head_dim"], lh=nh, p=p, g=g, n=n, inner=nh * p,
        mixed=nh * p + 2 * g * n, width=doc["conv_kernel"],
        em=doc["moe_intermediate_size"], sm=shared_width(doc),
        e=router_experts(doc), held=doc["n_routed_experts"],
        k=doc["num_experts_per_tok"], layers=len(all_),
        ssm=all_.count("ssm"), full=all_.count("full"),
        mlp=all_.count("mlp"))


def layer_matrix_params(doc: dict) -> dict:
    """Matrix parameters of the parts of a layer: the ``mamba`` mixer, the
    ``attention``, one ``expert`` (two matrices), the ``shared`` expert, the
    ``router``."""
    d = _dims(doc)
    return {"mamba": d["h"] * (d["inner"] + d["mixed"] + d["lh"])
            + d["inner"] * d["h"],
            "attention": 2 * d["h"] * d["nh"] * d["hd"]
            + 2 * d["h"] * d["nkv"] * d["hd"],
            "expert": 2 * d["h"] * d["em"], "shared": 2 * d["h"] * d["sm"],
            "router": d["h"] * d["e"]}


def _outside_experts(doc: dict) -> int:
    """Matrix parameters every decode step reads whatever it routes: the
    mixers, and the shared expert and the router of every expert layer."""
    d, per = _dims(doc), layer_matrix_params(doc)
    return (d["ssm"] * per["mamba"] + d["full"] * per["attention"]
            + d["mlp"] * (per["shared"] + per["router"]))


def num_params(doc: dict) -> int:
    """Every parameter of the program's tree, of what this holder has: the
    matrices with the held experts, the slice's embedding and head, and the
    small ones (convolution taps and bias, ``A_log``, ``D``, ``dt_bias``,
    norm scales, the selection bias)."""
    d, per = _dims(doc), layer_matrix_params(doc)
    ssm_small = (d["width"] + 1) * d["mixed"] + 3 * d["lh"] + d["inner"]
    return (_outside_experts(doc)
            + d["mlp"] * (d["held"] * per["expert"] + d["e"])
            + d["ssm"] * ssm_small + d["layers"] * d["h"]
            + 2 * d["v"] * d["h"] + d["h"])


def state_bytes_per_slot(doc: dict) -> int:
    """Bytes of state-space state one sequence holds over all ``M`` layers
    (float32)."""
    d = _dims(doc)
    return d["ssm"] * d["lh"] * d["p"] * d["n"] * 4


def kv_bytes_per_token(doc: dict, dtype_bytes: int = 2) -> int:
    """Bytes of K and V one token holds: the attention layers only."""
    d = _dims(doc)
    return 2 * d["nkv"] * d["hd"] * dtype_bytes * d["full"]


def experts_touched(doc: dict, tokens: float) -> float:
    """Held experts of one layer that ``tokens`` tokens reach under uniform
    routing over all the router's experts."""
    d = _dims(doc)
    return d["held"] * (1.0 - (1.0 - d["k"] / d["e"]) ** tokens)


def _state_flops_per_token(doc: dict) -> float:
    """The recurrence's FLOPs a token: the decay, the rank-one update and
    ``S C``, over all ``M`` layers."""
    d = _dims(doc)
    return d["ssm"] * d["lh"] * 5.0 * d["p"] * d["n"]


def _met_here(doc: dict) -> float:
    """Of a token's chosen experts, how many are held here on average."""
    d = _dims(doc)
    return d["k"] * d["held"] / d["e"]


def train_flops_per_token(doc: dict, seq_len: int) -> float:
    """Forward plus backward FLOPs per token training would need here.  No
    cell trains this kind: a pattern has no backward pass."""
    d, per = _dims(doc), layer_matrix_params(doc)
    active = (_outside_experts(doc)
              + d["mlp"] * _met_here(doc) * per["expert"])
    return (6.0 * (active + d["v"] * d["h"])
            + 6.0 * d["full"] * d["nh"] * d["hd"] * seq_len
            + 3.0 * _state_flops_per_token(doc))


def decode_step_bytes(doc: dict, active_slots: float, live_kv_tokens: float,
                      dtype_bytes: int = 2) -> float:
    """Bytes one decode step has to move, four terms: everything outside the
    experts and the head once; of each expert layer the held experts that
    ``active_slots`` tokens reach; the state-space state read and written
    once per active slot per ``M`` layer, at 4 bytes; K and V of the live
    tokens, attention layers only."""
    d, per = _dims(doc), layer_matrix_params(doc)
    weights = (_outside_experts(doc) + d["v"] * d["h"]
               + d["mlp"] * experts_touched(doc, active_slots)
               * per["expert"])
    return (weights * dtype_bytes
            + 2.0 * active_slots * state_bytes_per_slot(doc)
            + live_kv_tokens * kv_bytes_per_token(doc, dtype_bytes))


def decode_step_flops(doc: dict, active_slots: float,
                      live_kv_tokens: float) -> float:
    d, per = _dims(doc), layer_matrix_params(doc)
    a_token = (_outside_experts(doc) + d["v"] * d["h"]
               + d["mlp"] * _met_here(doc) * per["expert"])
    return (2.0 * a_token * active_slots
            + _state_flops_per_token(doc) * active_slots
            + decode_attn_flops(doc, live_kv_tokens))


def ssd_chunk_fwd_flops(doc: dict, tokens: float) -> float:
    """FLOPs the chunked form needs for ``tokens`` positions in every ``M``
    layer, chunk 128: per chunk ``C B^T`` once a group (2 c^2 N), and a head
    the decayed product with ``dt x`` (2 c^2 P), ``C S^T`` and the state's
    update (2 c P N each).  The decays are exponentials, not products."""
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
    """The state read and written once per active slot per ``M`` layer, plus
    the step's x and y, B and C of every group, and a head's decay and step
    at 4 bytes."""
    d = _dims(doc)
    small = d["ssm"] * ((2 * d["inner"] + 2 * d["g"] * d["n"]) * dtype_bytes
                        + 2 * d["lh"] * 4)
    return active_slots * (2.0 * state_bytes_per_slot(doc) + small)


def moe_gmm_flops(doc: dict, assignments: float) -> float:
    """FLOPs of the grouped matmuls for ``assignments`` (token, held
    expert) pairs: up and down, two matrices an expert, 2 per
    multiply-add."""
    return 2.0 * layer_matrix_params(doc)["expert"] * assignments


def moe_gmm_bytes(doc: dict, assignments: float, experts_read: float,
                  dtype_bytes: int = 2) -> float:
    """Bytes the same calls have to move: the two matrices of each expert
    read (``experts_read``: experts with a token, summed over layers and
    calls), and an assignment's rows in and out of the two matmuls."""
    d, per = _dims(doc), layer_matrix_params(doc)
    rows = 2 * (d["h"] + d["em"])
    return (experts_read * per["expert"] + assignments * rows) * dtype_bytes


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
