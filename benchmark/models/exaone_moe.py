"""Block kind ``exaone_moe``: a decoder of two kinds of attention layer in a
published order (``layer_types``): **sliding** layers, which rotate q and k
and read their last ``sliding_window`` positions, and **full** layers, which
add no positions and read the whole row; a dense SwiGLU MLP under the first
``first_k_dense_replace`` layers and dropless sigmoid-routed experts with a
shared expert under the others; and a **multi-token-prediction block** after
the last layer (HF ``model_type`` "exaone_moe"; K-EXAONE).  The four groups
of ``benchmark/README.md``, "A block kind":

1. published keys -> the program's configuration, with its refusals;
2. the program's entry points for this block kind (the decode step is the
   engine's speculative round with the next token forced: a verify step of
   two tokens, the draft rolled back, the block's pass);
3. the plain float32 reference of the forward pass and of the block's
   logits, written from the equations below and sharing nothing with
   ``ray_tpu.models`` or ``ray_tpu.ops``;
4. operations and bytes, the numerators of every roofline share, among them
   the kernels' under their names (``window_decode_attn``,
   ``flash_window_prefill``, ``decode_attn``, ``moe_gmm``,
   ``flash_attention``) and a verify-and-draft step's (``spec_step_bytes``).

Attention, both kinds (``num_attention_heads`` query heads over
``num_key_value_heads`` key / value heads of ``head_dim``, no bias)::

    q = W_q x;  k = W_k x;  v = W_v x
    q_h = RMSNorm_head(q_h);  k_h = RMSNorm_head(k_h)   one scale [head_dim],
                                                        shared by the heads
    sliding:  q, k rotated (theta ``rope_parameters.rope_theta``, the whole
              head, halves rotated against each other); position t attends
              to max(0, t - sliding_window + 1) .. t
    full:     no positions; position t attends to 0 .. t
    out = W_o softmax(q k^T / sqrt(head_dim)) v

The experts (``num_experts`` of ``moe_intermediate_size``,
``num_experts_per_tok``, ``num_shared_experts`` on every token)::

    s = sigmoid(x W_r)                   float32, over all the router's experts
    idx = top num_experts_per_tok of (s + b)         b the selection bias
    g = s[idx] / sum(s[idx]) * routed_scaling_factor         (norm_topk_prob)
    out = sum_i g_i E_idx_i(x) + S(x)    E, S: W_down (silu(W_gate x) * W_up x)

A block is ``h = x + N_a(attn(x)); out = h + N_m(mlp(h))``: no norm on a
sublayer's input, an RMSNorm on its output; a final RMSNorm before the untied
head.

The multi-token-prediction block, for position ``t`` with the model's last
hidden state ``h_t`` (before the final norm) and the next token ``x_{t+1}``::

    u_t = W_eh [N_e(E x_{t+1}) ; N_h(h_t)]       W_eh [2 hidden, hidden]
    one block of the form above, FULL attention over u_0 .. u_t, the experts
    logits for x_{t+2} = head(N_f(block(u)))     N_f the block's own norm,
                                                 E and the head the model's

**The share.**  A configuration may hold a chip's share of each layer
(``share``, ``reduced``): ``num_experts`` experts from ``share.expert_start``
on, of the ``reduced.num_experts.published`` the router scores, and a slice
of the vocabulary.  The router keeps its width and its experts a token; what
the absent experts would add is left out, here and in the program alike, and
the gates are normalised over all the chosen.

Nothing here imports JAX while the file is loaded.  It refuses to load on a
tree whose program has no window kind of layer, so that a cell of this kind
fails at once there instead of inside a replica that never turns healthy.
"""

from __future__ import annotations

import importlib.util
import os


def _require_program():
    spec = importlib.util.find_spec("ray_tpu")
    root = os.path.dirname(spec.origin) if spec and spec.origin else None
    try:
        with open(os.path.join(root, "models", "config.py")) as f:
            if "sliding_window" in f.read():
                return
    except (OSError, TypeError):
        pass
    why = ("block kind exaone_moe: this tree's ray_tpu has no 'window' kind "
           "of layer (models/config.py has no sliding_window: no ring, no "
           "windowed kernels, no multi-token-prediction block); the kind "
           "cannot run here")
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
    "num_experts": "experts_held",
    "num_experts_per_tok": "experts_per_token",
    "moe_intermediate_size": "expert_mlp_size",
    "num_shared_experts": "shared_experts",
    "routed_scaling_factor": "routed_scaling_factor",
    "sliding_window": "sliding_window",
    "first_k_dense_replace": "dense_prefix_layers",
    "num_nextn_predict_layers": "mtp_layers",
}
#: an entry of ``layer_types`` -> the program's kind of layer
_KINDS = {"sliding_attention": "window", "full_attention": "full"}


def kinds(doc: dict) -> tuple:
    """Every layer's kind in the program's names, in order."""
    types = doc["layer_types"]
    if set(types) - set(_KINDS) or len(types) != doc["num_hidden_layers"]:
        raise ValueError(f"layer_types: one of {sorted(_KINDS)} a layer, "
                         f"num_hidden_layers {doc['num_hidden_layers']} of "
                         "them")
    return tuple(_KINDS[t] for t in types)


def period(doc: dict) -> tuple:
    """The shortest period the layers' kinds are whole repeats of."""
    all_, n = kinds(doc), doc["num_hidden_layers"]
    return next(all_[:p] for p in range(1, n + 1)
                if n % p == 0 and all_ == all_[:p] * (n // p))


def router_experts(doc: dict) -> int:
    """The router's width: the published count of routed experts, of which
    ``num_experts`` are held here."""
    cut = doc.get("reduced", {}).get("num_experts")
    return int(cut["published"]) if cut else int(doc["num_experts"])


def expert_start(doc: dict) -> int:
    return int(doc.get("share", {}).get("expert_start", 0))


def program_kwargs(doc: dict) -> dict:
    missing = [k for k in (*_KEYS, "layer_types", "mlp_layer_types",
                           "sliding_windows", "hidden_act", "scoring_func",
                           "norm_topk_prob", "rope_parameters",
                           "mtp_layer_types")
               if k not in doc]
    if missing:
        raise ValueError(f"configuration lacks published keys {missing}")
    n, dense = doc["num_hidden_layers"], doc["first_k_dense_replace"]
    window, pattern = doc["sliding_window"], period(doc)
    refusals = (
        (doc["hidden_act"] != "silu", "hidden_act: the block's MLPs are "
         "SwiGLU"),
        (doc["scoring_func"] != "sigmoid", "scoring_func: the block's "
         "router scores with a sigmoid"),
        (not doc["norm_topk_prob"], "norm_topk_prob false: the block "
         "divides the gates by their sum"),
        (doc.get("n_group", 1) != 1 or doc.get("topk_group", 1) != 1,
         "n_group / topk_group: the block's router has no group limit"),
        (doc["tie_word_embeddings"], "tie_word_embeddings: the block has "
         "its own head"),
        (list(doc["mlp_layer_types"]) != ["dense"] * dense
         + ["sparse"] * (n - dense), "mlp_layer_types: first_k_dense_replace "
         "dense layers, then sparse ones"),
        (list(doc["sliding_windows"]) != [
            window if t == "sliding_attention" else 0
            for t in doc["layer_types"]], "sliding_windows: sliding_window "
         "for a sliding layer, 0 for a full one"),
        (doc["rope_parameters"].get("rope_type", "default") != "default",
         "rope_parameters.rope_type: the block's rotary embedding is the "
         "default one, unscaled"),
        (doc["num_nextn_predict_layers"] > 1
         or list(doc["mtp_layer_types"]) != ["full_attention"]
         * doc["num_nextn_predict_layers"], "num_nextn_predict_layers / "
         "mtp_layer_types: one multi-token-prediction block of full "
         "attention"),
        (expert_start(doc) + doc["num_experts"] > router_experts(doc),
         "share.expert_start + num_experts is past the router's width"),
    )
    for refused, why in refusals:
        if refused:
            raise ValueError(why)
    kw = {field: doc[key] for key, field in _KEYS.items()}
    kw.update(layer_pattern=pattern, num_experts=router_experts(doc),
              expert_start=expert_start(doc), moe_dropless=True,
              rope_theta=float(doc["rope_parameters"]["rope_theta"]),
              use_rope=True, rope_window_only=True, qk_head_norm=True,
              norm_on_output=True, use_rmsnorm=True, use_swiglu=True,
              use_qkv_bias=False, attention_impl="auto")
    return kw


def program_config(doc: dict):
    """What ``LLMEngine`` and the entry points below take as ``cfg``."""
    from ray_tpu.models.config import TransformerConfig
    return TransformerConfig(**program_kwargs(doc))


# ---------------------- 2. the program's entry points for this block kind

#: the seeded sample the routers are balanced on, ``BALANCE_ROWS`` rows of
#: ``BALANCE_TOKENS`` random ids each, of which the positions from
#: ``BALANCE_FROM`` on are counted, and the balancing rule's rounds and step
#: (scores are sigmoids, in (0, 1))
BALANCE_ROWS, BALANCE_TOKENS, BALANCE_FROM = 4, 4096, 1024
LEVEL_ROUNDS, LEVEL_RATE = 400, 0.004
#: new tokens a slot of the engine's step: the last emitted and one draft
VERIFY_WINDOW = 2


#: the scale the seeded weights give every q norm (``sharpened``)
ATTENTION_SHARPNESS = 4.0


def init_params(key, cfg, dtype):
    """The program's random parameters, with the attention's scores spread
    as a trained model's are (``sharpened``) and each expert layer's router
    set so that its load is level (``balanced``)."""
    from ray_tpu.models import transformer
    return balanced(sharpened(transformer.init_params(key, cfg, dtype=dtype)),
                    key, cfg)


def sharpened(params):
    """``params`` with every q norm's scale (the layers' and the block's)
    at ``ATTENTION_SHARPNESS`` where the draw leaves 1.  q and k are normed
    a head, so a drawn model's scores ``q k^T / sqrt(head_dim)`` have unit
    spread whatever the weights: every softmax is then near uniform over
    its window or its row, an attention layer hands on the AVERAGE of what
    it reads, which hardly moves from one position to the next and, once
    the rows decode greedily, pulls them all into the few cycles of one
    shared map (on the chip: 22-27 distinct tokens among a step's 48 rows,
    a row repeating 5-11 tokens; the routers then see a step's 96 tokens as
    two dozen, the held experts a step reads go by the seed, 76-89%, and a
    step's time with them: PERF.md section 6, PR 50, second round).  A
    trained model's attention picks positions; the q norm's scale is the
    parameter that says how sharply, and at 4 (scores of spread 4: the
    largest of a few thousand stands out) a row attends to its own context,
    greedy rows stay apart (45-46 distinct tokens among 48 rows, 86-91 in a
    row's last 100) and, with the routers ``balanced``, a step of the cell
    reads 99.6-99.7% of the held experts on each of twelve seeds, as the
    deployment's step does."""
    def walk(tree):
        if not isinstance(tree, dict):
            return tree
        return {name: {"scale": (sub["scale"] * ATTENTION_SHARPNESS).astype(
            sub["scale"].dtype)} if name == "q_norm" else walk(sub)
            for name, sub in tree.items()}
    return walk(params)


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
    names = {v: k for k, v in _KINDS.items()}
    return {
        "layer_types": [names[k] for k in cfg.layer_pattern]
        * cfg.num_periods,
        "num_hidden_layers": cfg.num_layers,
        "first_k_dense_replace": cfg.dense_prefix_layers,
        "sliding_window": cfg.sliding_window,
        "rms_norm_eps": cfg.norm_eps,
        "rope_parameters": {"rope_theta": cfg.rope_theta},
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "num_experts_per_tok": cfg.experts_per_token,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "share": {"expert_start": cfg.expert_start}}


def balanced(params, key, cfg):
    """``params`` with every expert layer's router, the multi-token-
    prediction block's too, set on ``BALANCE_ROWS`` seeded rows of
    ``BALANCE_TOKENS`` random ids each, walked through the layers side by
    side, every layer's setting from all the rows' tokens together:

    - **the router's columns scaled** so that the sample's scores
      ``x W_r`` have unit spread.  No norm stands ahead of a router here and
      the residual stream grows with every sublayer's normed output, so a
      drawn router's scores spread by 2 at the first expert layer and by 4 at
      the last: the sigmoids of the 8 largest of 128 then all lie within
      0.0025 of 1, closer together than bf16 keeps a bias, and no bias held
      in the served tree levels them (on the chip the deep layers read some
      experts never and others 7-11 times the mean, the held ones by the
      seed).  A trained router's scores are of order 1;
    - **the selection bias ``b``** (the parameter ``noaux_tc`` has for this,
      which a trained checkpoint's balancing rule has moved and a random draw
      leaves at zero) set so that the load is level (``_level``: the
      balancing rule itself, run until the sample's load is level), as
      ``models/solar_open2.balanced`` has it and for its reason: with
      ``b = 0`` random weights give the tokens of a step a common favourite
      set, and the experts a step reads are then the seed's, not the
      architecture's (PERF.md section 6, PR 44).  Long rows, counted from
      position ``BALANCE_FROM`` on: a step routes tokens at contexts of 512
      to 6,144, and in the deep layers an expert's load goes with the
      context's length (a bias levelled on rows of 256 left loads of 0.14 to
      1.9 times the mean at 1,000-2,000 positions; levelled here they read
      0.75-1.37 there and in the decoding).

    The gates stay the unbiased scores, as the equations have it (PERF.md
    section 6, PR 50, second round, has the readings)."""
    import jax
    import jax.numpy as jnp
    doc = _doc_of(cfg)
    tokens = jax.random.randint(jax.random.fold_in(key, 0xBA1),
                                (BALANCE_ROWS, BALANCE_TOKENS), 1,
                                cfg.vocab_size)

    def level(seen, small):            # one setting from all the rows' tokens
        seen = jax.lax.all_gather(seen, "rows")[:, BALANCE_FROM:]
        seen = seen.reshape(-1, seen.shape[-1])
        router = (small["router"] / (seen @ small["router"]).std()).astype(
            dtype).astype(jnp.float32)        # as the program will hold it
        return {"router": router, "bias": _level(
            jax.nn.sigmoid(seen @ router), cfg.experts_per_token)}

    def a_row(row):
        hidden, layers = _walk(params, row, doc, level=level)
        if "mtp" not in params:
            return layers, None
        return layers, _mtp_walk(params, hidden, row, doc, level=level)[1][0]

    dtype = params["blocks"]["moe"]["router"].dtype
    with jax.default_matmul_precision("highest"):
        layers, block = jax.tree.map(
            lambda a: a.astype(dtype),     # of all the rows: none's own
            jax.vmap(a_row, axis_name="rows", out_axes=None)(tokens))
    blocks = dict(params["blocks"])
    blocks["moe"] = dict(blocks["moe"], **jax.tree.map(
        lambda *a: jnp.stack(a), *layers))
    out = dict(params, blocks=blocks)
    if block is not None:
        mtp = params["mtp"]
        full = mtp["blocks"]["full"]
        moe = dict(full["moe"], **jax.tree.map(
            lambda new, old: new.reshape(old.shape), block,
            {k: full["moe"][k] for k in block}))
        out["mtp"] = dict(mtp, blocks=dict(
            mtp["blocks"], full=dict(full, moe=moe)))
    return out


def init_cache(cfg, num_slots: int, length: int, dtype, ring=None):
    """Rows for the full layers and for the block, rings for the window
    layers with the margin of a verify step's two tokens
    (``decode.ring_len``; ``ring``: another count of rows, for a control)."""
    from ray_tpu.models import decode
    return decode.init_kv_cache(
        cfg, num_slots, length, dtype,
        ring=ring or decode.ring_len(cfg, VERIFY_WINDOW))


#: the engine's rows are whole buckets, every one whole blocks of this many
#: positions (512 .. 4096); so is the row ``prefill`` walks
ROW_BLOCK = 512


def prefill(params, cache, tokens, lengths, slots, cfg):
    """The program's prefill on rows right-padded to whole ``ROW_BLOCK``s
    (or to the slot's length, where that is shorter), as the engine's admits
    are padded to its buckets: the comparison's prompt then runs what a
    request of that length runs, the flash kernels (banded for the window
    layers) from 1,024 positions up, the rings' rows gathered at the row's
    own length, and the block's pass over the row."""
    import jax.numpy as jnp
    from ray_tpu.models import decode
    tokens = jnp.asarray(tokens)
    s = tokens.shape[1]
    to = min(-(-s // ROW_BLOCK) * ROW_BLOCK, cache["k"].shape[2])
    return decode.prefill(params, cache,
                          jnp.pad(tokens, ((0, 0), (0, max(to - s, 0)))),
                          lengths, slots, cfg)


def decode_step(params, cache, tokens, active, cfg, compute_dtype=None):
    """One round of the engine's speculative step (``models/speculative.py``
    ``spec_state_round`` with ``block_drafter``) with the token after
    ``tokens`` forced by the caller: the model verifies the window [token,
    the block's draft] in ONE step of two tokens a slot, over rows and rings;
    the first position's logits are returned, the draft's row is rolled back
    by resetting ``length`` (what the engine does with a rejected draft:
    over random weights, all but one in ``vocab_size``), and the block runs
    over the window's two positions, each hidden state paired with the
    model's own greedy token, and leaves the next round's draft.
    ``compute_dtype``: bf16 where not given, as the engine runs it."""
    import jax.numpy as jnp
    from ray_tpu.models import decode
    dtype = compute_dtype or jnp.bfloat16
    len0 = cache["length"]
    window = jnp.stack([jnp.asarray(tokens), cache["draft"]], axis=1)
    cache, logits, hidden = decode.window_step(params, cache, window, active,
                                               cfg, dtype, hidden=True)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    cache, block_logits = decode.mtp_step(params, cache, hidden, greedy,
                                          len0, active, cfg, dtype)
    cache = dict(cache, length=jnp.where(active, len0 + 1, len0),
                 draft=jnp.argmax(block_logits[:, 0], -1).astype(jnp.int32))
    return cache, logits[:, 0]


# ------------------------------------------------- 3. the plain reference
# The equations of the module's docstring, in float32 and under
# ``jax.default_matmul_precision("highest")``: attention a block of queries
# at a time over the whole row under a whole-sequence mask (banded for a
# sliding layer), no cache, no kernel, no batching; the held experts one at
# a time, every one on every token times its gate (zero where it was not
# chosen).  Weights are the program's parameter tree (``blocks.window`` /
# ``blocks.full`` [periods, layers of the kind in a period, ...]; by layer
# ``blocks.dense`` [dense layers, ...], ``blocks.moe`` and ``blocks.experts``
# [expert layers, ...]; ``mtp``), upcast a layer at a time.  Nothing of
# ``ray_tpu`` runs here: the section reads the parameter tree and calls
# ``jax`` alone, told the share (``share.expert_start``, the held experts the
# stacks have) and nothing of the program's run.
#
# **Near-ties.**  The top 8 of 128 scores is the one step of the equations
# that is not continuous; where the eighth and the next score lie closer than
# the program's bf16 stream moves them, either set is the equations' answer
# up to rounding, and the two answers differ by a whole expert's output where
# one of the two is held here.  The reference routes by its own float32
# scores, so every near-tie that fell the other way in the program stands in
# the difference: the configuration's ``check`` gives what that reads.

QUERY_BLOCK = 512


def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _swiglu(x, gate, up, down):
    import jax
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _rotate(x, theta: float):
    """x [S, heads, D] at positions 0 .. S: the whole head rotated, its
    halves against each other."""
    import jax.numpy as jnp
    s, _, d = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(x, ap, doc, sliding: bool):
    """x [S, H] -> causal softmax attention [S, H]: a sliding layer rotates
    q and k and reads a band, a full layer adds no positions."""
    import jax
    import jax.numpy as jnp
    s = x.shape[0]
    nh, nkv, d = doc["num_attention_heads"], doc["num_key_value_heads"], \
        doc["head_dim"]
    eps = doc["rms_norm_eps"]
    q = _rms_norm((x @ ap["wq"]).reshape(s, nh, d), ap["q_norm"]["scale"],
                  eps)
    k = _rms_norm((x @ ap["wk"]).reshape(s, nkv, d), ap["k_norm"]["scale"],
                  eps)
    if sliding:
        theta = float(doc["rope_parameters"]["rope_theta"])
        q, k = _rotate(q, theta), _rotate(k, theta)
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat((x @ ap["wv"]).reshape(s, nkv, d), nh // nkv, axis=1)
    outs = []
    for q0 in range(0, s, QUERY_BLOCK):
        q1 = min(q0 + QUERY_BLOCK, s)
        scores = jnp.einsum("qhd,khd->hqk", q[q0:q1], k[:q1]) * d ** -0.5
        at, held = jnp.arange(q0, q1)[:, None], jnp.arange(q1)[None, :]
        seen = held <= at
        if sliding:
            seen = seen & (at - held < doc["sliding_window"])
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v[:q1]))
    return jnp.concatenate(outs).reshape(s, nh * d) @ ap["wo"]


def route(x, router, bias, doc):
    """x [S, H] float32 -> (experts [S, k] among all the router's, gates
    [S, k])."""
    import jax
    import jax.numpy as jnp
    scores = jax.nn.sigmoid(x @ router)
    _, idx = jax.lax.top_k(scores + bias, doc["num_experts_per_tok"])
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, gates / gates.sum(-1, keepdims=True) \
        * doc["routed_scaling_factor"]


def expert_layer(x, small, stacks, layer, doc, shared=True):
    """x [S, H] float32; ``small`` this layer's router, bias and shared
    expert (float32); ``stacks`` the held experts' three matrices, [expert
    layers, held, ...], of which this is ``layer``: experts
    ``share.expert_start ..`` of the router's.  The chosen experts that are
    not held add nothing; the gates are over all the chosen.  Returns (out,
    the chosen experts [S, k])."""
    import jax
    import jax.numpy as jnp
    F32 = jnp.float32
    idx, gates = route(x, small["router"], small["bias"], doc)
    start = expert_start(doc)

    def one(e, acc):
        gate, up, down = (jax.lax.dynamic_index_in_dim(
            jax.lax.dynamic_index_in_dim(stacks[n], layer, 0, False),
            e, 0, False).astype(F32) for n in ("w_gate", "w_in", "w_out"))
        weight = jnp.where(idx == start + e, gates, 0.0).sum(-1)
        return acc + weight[:, None] * _swiglu(x, gate, up, down)

    out = jax.lax.fori_loop(0, stacks["w_out"].shape[1], one,
                            jnp.zeros_like(x))
    if shared and "shared_in" in small:
        out = out + _swiglu(x, small["shared_gate"], small["shared_in"],
                            small["shared_out"])
    return out, idx


def _block(x, lp, mlp, doc, sliding: bool):
    """One block on x [S, H]: ``h = x + N_a(attn(x)); h + N_m(mlp(h))``."""
    eps = doc["rms_norm_eps"]
    h = x + _rms_norm(_attention(x, lp["attn"], doc, sliding),
                      lp["attn_norm"]["scale"], eps)
    return h + _rms_norm(mlp(h), lp["mlp_norm"]["scale"], eps)


def _walk(params, tokens, doc: dict, level=None):
    """The layers in order on ``tokens`` [S] -> (the residual stream after
    the last [S, H] float32, a list with one entry an expert layer: the
    experts it chose [S, k], or with ``level`` what ``level(seen [S, H],
    small)`` set of the layer's router, {"router", "bias"}, which the layer
    then routes with)."""
    import jax
    import jax.numpy as jnp
    F32 = jnp.float32
    blocks, dense = params["blocks"], doc["first_k_dense_replace"]
    pattern, at, said = period(doc), {}, []
    x = params["embed"]["tokens"][tokens].astype(F32)
    for layer, kind in enumerate(kinds(doc)):
        i = at.get(kind, 0)
        at[kind] = i + 1
        count = pattern.count(kind)
        lp = jax.tree.map(lambda a: a[i // count, i % count].astype(F32),
                          blocks[kind])                  # this layer only
        if layer < dense:
            ws = jax.tree.map(lambda a: a[layer].astype(F32), blocks["dense"])

            def mlp(h, ws=ws):
                return _swiglu(h, ws["w_gate"], ws["w_in"], ws["w_out"])
        else:
            rank = layer - dense
            small = jax.tree.map(lambda a: a[rank].astype(F32), blocks["moe"])

            def mlp(h, small=small, rank=rank):
                set_ = level and level(h, small)
                out, chosen = expert_layer(h, dict(small, **(set_ or {})),
                                           blocks["experts"], rank, doc)
                said.append(set_ or chosen)
                return out
        x = _block(x, lp, mlp, doc, kind == "window")
    return x, said


def _mtp_walk(params, hidden, tokens, doc: dict, level=None):
    """The multi-token-prediction block on the model's last hidden states
    ``hidden`` [S, H] (before its final norm) of ``tokens`` [S]: position t
    of 0 .. S - 2 pairs ``h_t`` with ``x_{t+1}``.  Returns (the block's
    output before its norm [S - 1, H], what ``_walk`` says of its expert
    layer)."""
    import jax
    import jax.numpy as jnp
    F32 = jnp.float32
    mp, eps, said = params["mtp"], doc["rms_norm_eps"], []
    emb = params["embed"]["tokens"][tokens[1:]].astype(F32)
    u = jnp.concatenate(
        [_rms_norm(emb, mp["embed_norm"]["scale"].astype(F32), eps),
         _rms_norm(hidden[:-1], mp["hidden_norm"]["scale"].astype(F32), eps)],
        -1) @ mp["proj"].astype(F32)
    lp = jax.tree.map(lambda a: a[0, 0].astype(F32), mp["blocks"]["full"])

    def mlp(h):
        set_ = level and level(h, lp["moe"])
        out, chosen = expert_layer(h, dict(lp["moe"], **(set_ or {})),
                                   mp["blocks"]["experts"], 0, doc)
        said.append(set_ or chosen)
        return out

    return _block(u, lp, mlp, doc, False), said


def hidden_states(params, tokens, doc: dict):
    """tokens [S] int32 -> the residual stream after the last layer [S, H]
    float32, before the final norm."""
    import jax
    with jax.default_matmul_precision("highest"):
        return _walk(params, tokens, doc)[0]


def logits(params, tokens, doc: dict, positions=None):
    """tokens [S] -> float32 logits over the held slice of the vocabulary
    [S, V], or [len(positions), V]."""
    import jax
    import jax.numpy as jnp
    F32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(hidden_states(params, tokens, doc),
                      params["final_norm"]["scale"].astype(F32),
                      doc["rms_norm_eps"])
        if positions is not None:
            x = x[positions]
        return x @ params["lm_head"].astype(F32)


def mtp_logits(params, tokens, doc: dict, positions=None):
    """tokens [S] -> the block's float32 logits [S - 1, V] (or
    [len(positions), V]): row t, from ``h_t`` and ``x_{t+1}``, is for
    ``x_{t+2}``."""
    import jax
    import jax.numpy as jnp
    F32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        out, _ = _mtp_walk(params, hidden_states(params, tokens, doc),
                           tokens, doc)
        x = _rms_norm(out, params["mtp"]["final_norm"]["scale"].astype(F32),
                      doc["rms_norm_eps"])
        if positions is not None:
            x = x[positions]
        return x @ params["lm_head"].astype(F32)


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

#: positions a step of the banded prefill kernel takes of K and V, and of
#: the queries (``ray_tpu/ops/flash_attention.py``: WINDOW_BLOCK_KV and the
#: default query block; spelled out here, the section imports nothing)
BAND_BLOCK_KV, BAND_BLOCK_Q = 128, 512


def _dims(doc: dict) -> dict:
    all_ = kinds(doc)
    dense = doc["first_k_dense_replace"]
    return dict(
        h=doc["hidden_size"], v=doc["vocab_size"],
        nh=doc["num_attention_heads"], nkv=doc["num_key_value_heads"],
        hd=doc["head_dim"], m=doc["intermediate_size"],
        em=doc["moe_intermediate_size"],
        sm=doc["num_shared_experts"] * doc["moe_intermediate_size"],
        e=router_experts(doc), held=doc["num_experts"],
        k=doc["num_experts_per_tok"], layers=len(all_),
        window=all_.count("window"), full=all_.count("full"), dense=dense,
        sparse=len(all_) - dense, mtp=doc["num_nextn_predict_layers"],
        span=doc["sliding_window"])


def layer_matrix_params(doc: dict) -> dict:
    """Matrix parameters of the parts of a layer: the ``attention``, the
    ``dense`` MLP, one ``expert`` (three matrices), the ``shared`` expert,
    the ``router``, and the block's projection ``mtp_proj``."""
    d = _dims(doc)
    return {"attention": 2 * d["h"] * d["nh"] * d["hd"]
            + 2 * d["h"] * d["nkv"] * d["hd"],
            "dense": 3 * d["h"] * d["m"], "expert": 3 * d["h"] * d["em"],
            "shared": 3 * d["h"] * d["sm"], "router": d["h"] * d["e"],
            "mtp_proj": 2 * d["h"] * d["h"]}


def _outside_experts(doc: dict, block: bool = True) -> int:
    """Matrix parameters every step reads whatever it routes: attention of
    every layer, the dense MLPs, the shared expert and the router of every
    expert layer and, with ``block``, the same of the
    multi-token-prediction block with its projection."""
    d, per = _dims(doc), layer_matrix_params(doc)
    sparse = per["shared"] + per["router"]
    out = (d["layers"] * per["attention"] + d["dense"] * per["dense"]
           + d["sparse"] * sparse)
    if block:
        out += d["mtp"] * (per["mtp_proj"] + per["attention"] + sparse)
    return out


def num_params(doc: dict) -> int:
    """Every parameter of the program's tree, of what this holder has: the
    matrices with the held experts (the block's among them), the slice's
    embedding and head, and the small ones (norm scales, the heads' q and k
    scales, the selection biases)."""
    d, per = _dims(doc), layer_matrix_params(doc)
    a_layer = 2 * d["h"] + 2 * d["hd"]          # two norms, q and k scales
    return (_outside_experts(doc)
            + (d["sparse"] + d["mtp"]) * (d["held"] * per["expert"] + d["e"])
            + (d["layers"] + d["mtp"]) * a_layer + d["mtp"] * 3 * d["h"]
            + 2 * d["v"] * d["h"] + d["h"])


def published_params(doc: dict) -> dict:
    """Matrix parameters of the published model, uncut (``reduced`` has the
    published depth, experts and vocabulary): ``total``, with the
    multi-token-prediction block (``total_with_block``), and those a token
    meets (``active``: its 8 experts of 128, the embedding and the head
    counted once each)."""
    cut = doc.get("reduced", {})

    def published(key):
        return int(cut[key]["published"]) if key in cut else int(doc[key])

    d, per = _dims(doc), layer_matrix_params(doc)
    layers, e, v = (published("num_hidden_layers"), published("num_experts"),
                    published("vocab_size"))
    sparse, emb = layers - d["dense"], 2 * v * d["h"]
    rest = per["shared"] + per["router"]
    total = (layers * per["attention"] + d["dense"] * per["dense"]
             + sparse * (e * per["expert"] + rest) + emb)
    block = d["mtp"] * (per["mtp_proj"] + per["attention"]
                        + e * per["expert"] + rest)
    active = (layers * per["attention"] + d["dense"] * per["dense"]
              + sparse * (d["k"] * per["expert"] + rest) + emb)
    return {"total": total, "total_with_block": total + block,
            "active": active}


def kv_bytes_per_token(doc: dict, dtype_bytes: int = 2) -> int:
    """Bytes of K and V one token holds in rows that grow with the context:
    the full layers' and the block's (a window layer's ring does not grow:
    ``ring_bytes_per_slot``)."""
    d = _dims(doc)
    return 2 * d["nkv"] * d["hd"] * dtype_bytes * (d["full"] + d["mtp"])


def ring_bytes_per_slot(doc: dict, rows: int, dtype_bytes: int = 2) -> int:
    """Bytes of K and V ``rows`` positions of every window layer hold."""
    d = _dims(doc)
    return 2 * d["nkv"] * d["hd"] * dtype_bytes * d["window"] * rows


def experts_touched(doc: dict, tokens: float) -> float:
    """Held experts of one layer that ``tokens`` tokens reach under uniform
    routing over all the router's experts."""
    d = _dims(doc)
    return d["held"] * (1.0 - (1.0 - d["k"] / d["e"]) ** tokens)


def _met_here(doc: dict) -> float:
    """Of a token's chosen experts, how many are held here on average."""
    d = _dims(doc)
    return d["k"] * d["held"] / d["e"]


def train_flops_per_token(doc: dict, seq_len: int) -> float:
    """Forward plus backward FLOPs per token training would need here.  No
    cell trains this kind: a pattern has no backward pass."""
    d, per = _dims(doc), layer_matrix_params(doc)
    active = (_outside_experts(doc, block=False)
              + d["sparse"] * _met_here(doc) * per["expert"])
    reach = d["full"] * seq_len + d["window"] * min(seq_len, 2 * d["span"])
    return (6.0 * (active + d["v"] * d["h"])
            + 6.0 * d["nh"] * d["hd"] * reach)


def spec_step_bytes(doc: dict, active_slots: float, live_kv_tokens: float,
                    window: int = VERIFY_WINDOW, dtype_bytes: int = 2,
                    experts_read: float = None) -> float:
    """Bytes one verify-and-draft step has to move: everything outside the
    experts once, the block's share of it among it, and the head twice (the
    model's logits, then the block's, which wait for them); of each expert
    layer, the block's too, the held experts the step reads
    (``experts_read`` a layer where the engine counted them; else those the
    step's ``window * active_slots`` tokens reach under uniform routing); K
    and V rows of the live tokens, full layers and the block's; and of each
    window layer the ``sliding_window + window - 1`` positions a slot's
    queries read."""
    d, per = _dims(doc), layer_matrix_params(doc)
    if experts_read is None:
        experts_read = experts_touched(doc, window * active_slots)
    weights = (_outside_experts(doc) + (1 + d["mtp"]) * d["v"] * d["h"]
               + (d["sparse"] + d["mtp"]) * experts_read * per["expert"])
    return (weights * dtype_bytes
            + live_kv_tokens * kv_bytes_per_token(doc, dtype_bytes)
            + active_slots * ring_bytes_per_slot(
                doc, d["span"] + window - 1, dtype_bytes))


def spec_step_flops(doc: dict, active_slots: float, live_kv_tokens: float,
                    window: int = VERIFY_WINDOW) -> float:
    d, per = _dims(doc), layer_matrix_params(doc)
    a_token = (_outside_experts(doc) + (1 + d["mtp"]) * d["v"] * d["h"]
               + (d["sparse"] + d["mtp"]) * _met_here(doc) * per["expert"])
    return (2.0 * a_token * window * active_slots
            + window * (decode_attn_flops(doc, live_kv_tokens)
                        + window_decode_attn_flops(doc, active_slots)))


def decode_step_bytes(doc: dict, active_slots: float, live_kv_tokens: float,
                      dtype_bytes: int = 2) -> float:
    """Bytes one plain decode step (the draft off: one token a slot, no
    pass of the block) has to move."""
    d, per = _dims(doc), layer_matrix_params(doc)
    weights = (_outside_experts(doc, block=False) + d["v"] * d["h"]
               + d["sparse"] * experts_touched(doc, active_slots)
               * per["expert"])
    return (weights * dtype_bytes
            + live_kv_tokens * 2 * d["nkv"] * d["hd"] * dtype_bytes
            * d["full"]
            + active_slots * ring_bytes_per_slot(doc, d["span"],
                                                 dtype_bytes))


def decode_step_flops(doc: dict, active_slots: float,
                      live_kv_tokens: float) -> float:
    d, per = _dims(doc), layer_matrix_params(doc)
    a_token = (_outside_experts(doc, block=False) + d["v"] * d["h"]
               + d["sparse"] * _met_here(doc) * per["expert"])
    return (2.0 * a_token * active_slots
            + 4.0 * d["full"] * d["nh"] * d["hd"] * live_kv_tokens
            + 4.0 * d["window"] * d["nh"] * d["hd"] * d["span"]
            * active_slots)


def moe_gmm_flops(doc: dict, assignments: float) -> float:
    """FLOPs of the grouped matmuls for ``assignments`` (token, held
    expert) pairs: gate, up and down, 2 per multiply-add."""
    return 2.0 * layer_matrix_params(doc)["expert"] * assignments


def moe_gmm_bytes(doc: dict, assignments: float, experts_read: float,
                  dtype_bytes: int = 2) -> float:
    """Bytes the same calls have to move: the three matrices of each expert
    read (``experts_read``: experts with a token, summed over layers and
    calls), and an assignment's rows in and out of the matmuls."""
    d, per = _dims(doc), layer_matrix_params(doc)
    rows = 2 * d["h"] + 3 * d["em"]
    return (experts_read * per["expert"] + assignments * rows) * dtype_bytes


def decode_attn_flops(doc: dict, live_tokens: float) -> float:
    """FLOPs of one query a slot over ``live_tokens`` cached positions
    (summed over slots), full layers and the block's: scores and values, 2
    per multiply-add."""
    d = _dims(doc)
    return 4.0 * (d["full"] + d["mtp"]) * d["nh"] * d["hd"] * live_tokens


def decode_attn_bytes(doc: dict, live_tokens: float,
                      dtype_bytes: int = 2) -> float:
    return float(live_tokens * kv_bytes_per_token(doc, dtype_bytes))


def window_decode_attn_flops(doc: dict, slot_steps: float,
                             window: int = VERIFY_WINDOW) -> float:
    """FLOPs of the ring kernel for ``slot_steps`` (live slot, step) pairs
    in every window layer: each of a step's ``window`` queries over its
    ``sliding_window`` positions, scores and values."""
    d = _dims(doc)
    return 4.0 * d["window"] * d["nh"] * d["hd"] * d["span"] * window \
        * slot_steps


def window_decode_attn_bytes(doc: dict, slot_steps: float,
                             window: int = VERIFY_WINDOW,
                             dtype_bytes: int = 2) -> float:
    """Bytes the same calls have to move: K and V of the ``sliding_window +
    window - 1`` positions a slot's queries read, once a step and window
    layer (the kernel fetches a slot's whole ring, which the margin rounds
    up to 256 rows here: what it fetches past this count is its own)."""
    d = _dims(doc)
    return float(slot_steps * ring_bytes_per_slot(
        doc, d["span"] + window - 1, dtype_bytes))


def _band_blocks(doc: dict, seq_len: int) -> int:
    """(query block, KV block) pairs the banded kernel computes for one row
    of ``seq_len`` positions (whole query blocks) and one head: a query
    block's first query reaches ``sliding_window - 1`` positions back, its
    last reads its own."""
    span, bq, bkv = doc["sliding_window"], BAND_BLOCK_Q, BAND_BLOCK_KV
    bq = min(bq, seq_len)
    pairs = 0
    for first in range(0, seq_len, bq):
        lo = max(first - (span - 1), 0) // bkv
        hi = -(-(first + bq) // bkv)
        pairs += hi - lo
    return pairs


def flash_window_prefill_flops(doc: dict, batch: int, seq_len: int) -> float:
    """FLOPs the banded flash forward needs for ``batch`` rows of
    ``seq_len`` in every window layer, counted by the blocks the band needs
    (a query block of 512 under a band of 128: 5 KV blocks of 128, not the
    diagonal's 128 positions a query): QK^T and PV of each computed pair."""
    d = _dims(doc)
    pair = 2.0 * 2 * min(BAND_BLOCK_Q, seq_len) * BAND_BLOCK_KV * d["hd"]
    return d["window"] * batch * d["nh"] * _band_blocks(doc, seq_len) * pair


def flash_window_prefill_bytes(doc: dict, batch: int, seq_len: int,
                               dtype_bytes: int = 2) -> float:
    """q read and o written for every query head, k and v for every KV
    head, once a row: every position is in some query's band."""
    d = _dims(doc)
    row = (2 * d["nh"] + 2 * d["nkv"]) * d["hd"] * dtype_bytes
    return float(d["window"] * batch * seq_len * row)


def flash_attention_flops(doc: dict, batch: int, seq_len: int,
                          backward: bool = False) -> float:
    """FLOPs causal flash attention needs for ``batch`` rows in every full
    layer and in the block: QK^T and PV, 2 S^2 D a head each, halved by
    causality (5 more matmuls backward, which nothing here runs)."""
    d = _dims(doc)
    one = 2.0 * seq_len * seq_len * d["hd"] * d["nh"] / 2
    return (d["full"] + d["mtp"]) * batch * one * (2 + (5 if backward else 0))


def flash_attention_bytes(doc: dict, batch: int, seq_len: int,
                          backward: bool = False,
                          dtype_bytes: int = 2) -> float:
    d = _dims(doc)
    row = (2 * d["nh"] + 2 * d["nkv"]) * d["hd"] * dtype_bytes
    return float((d["full"] + d["mtp"]) * batch * seq_len * row
                 * (1 + (2 if backward else 0)))
