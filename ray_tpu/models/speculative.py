"""Speculative decoding: a drafter's lookahead + one-shot target verify.  The
drafter is a cut-out model (``model_drafter``: the target's first layers,
with a cache of its own) or the target's own multi-token-prediction block
(``block_drafter``: it reads the target's last hidden state, shares its
embedding and head, keeps one layer's K/V rows in the target's cache tree
and drafts one token); one round function serves both.

Beyond-reference TPU-native addition (the reference serves LLMs by
pairing with an external engine; our serve stack owns its engine —
serve/llm.py — so the classic latency lever is implementable natively).

Why this is a TPU win: autoregressive decode is HBM-bound — every step
streams all weights for ONE matvec per slot. Speculation turns k of
those matvecs into ONE [slots, k]-token matmul (`verify_window`): same
weight traffic, k× the useful FLOPs, which is exactly the regime the
MXU wants. The draft model is small enough that its k sequential steps
cost less than the saved target steps whenever acceptance is decent.

Greedy acceptance keeps the output EXACTLY equal to vanilla greedy
decode (tests pin this): accept draft tokens while they match the
target's argmax at the same position, then emit the target's own token
at the first mismatch — ≥1 token per verify call, so worst case equals
vanilla decode plus the (cheap) draft work.

Everything is fixed-shape and jittable: the multi-round driver
(``spec_decode_state_loop``) is a ``lax.scan`` whose carry holds both
caches, the engine's device-resident decode state and per-slot emit buffers
— no host round-trip between rounds (cf. ``decode.decode_state_loop``).
The target's cache may be rows, pages, or rows beside rings (a pattern of
"full" and "window" layers): ``verify_window`` is the same
walk over the layers as every other serving forward (``decode.layer_stack``)
and the cache tree says which attention it gets; on rows and rings the W
tokens of a window ride the decode kernels (``ops/decode_attention.py``).  A
recurrent state and latent rows take no window of several tokens.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Tuple, Union

import jax
import jax.numpy as jnp

from .config import TransformerConfig
from .decode import (KVCache, Params, decode_step, mtp_step, sample_per_slot,
                     window_step)

__all__ = ["verify_window", "spec_state_round", "spec_decode_state_loop",
           "make_draft_params", "damp_block_outputs", "Drafter",
           "model_drafter", "block_drafter"]


def verify_window(params: Params, cache: KVCache, tokens: jnp.ndarray,
                  active: jnp.ndarray, cfg: TransformerConfig,
                  compute_dtype=jnp.bfloat16, hidden: bool = False
                  ) -> Tuple[KVCache, jnp.ndarray]:
    """Process a k-token window per slot in one forward, over rows or pages:
    ``decode_step`` at window k (``decode.window_step``; with k=1 it
    computes identical math).

    tokens: [slots, k] int32 — token j sits at cache position length+j
    active: [slots] bool
    Returns (cache, logits [slots, k, V] f32); K/V for all k positions
    are appended and ``length`` advances by k for active slots.  Callers
    roll ``length`` back to the accepted prefix afterwards — rollback is a
    length reset ONLY: the garbage tail beyond ``length`` is never read
    (same contract as prefill's padded tail) and the next round overwrites
    it.  On pages that is exact by construction too: every window position
    lands in a page the slot's block table already owns (private pages at
    index >= the shared-prefix boundary).  On a ring it is exact where the
    ring has the window's margin (``decode.ring_len``): no row a kept
    position still reads was replaced.
    """
    return window_step(params, cache, tokens, active, cfg, compute_dtype,
                       hidden)


# ---------------------------------------------------------------------------
# Drafters
# ---------------------------------------------------------------------------

class Drafter(NamedTuple):
    """What a round asks of whatever drafts for it.

    ``propose(target_params, target_cache, draft_params, draft_cache, last,
    active, k) -> (draft_cache, drafts [slots, k - 1])`` before the verify
    step; ``settle(target_params, target_cache, draft_params, draft_cache,
    seen) -> (target_cache, draft_cache)`` after it, with ``seen`` what the
    round learned: ``len0`` and ``new_len`` [slots], ``active``, ``emitted``
    [slots, k], ``emit_count`` [slots] and, where ``hidden`` is set, the
    target's last hidden states of the window, ``hidden`` [slots, k, H]."""
    propose: Callable
    settle: Callable
    hidden: bool = False


def model_drafter(draft_cfg: TransformerConfig,
                  compute_dtype=jnp.bfloat16) -> Drafter:
    """A cut-out model (``make_draft_params``) on a dense cache of its own:
    k - 1 greedy steps, and its rows rolled back with the target's."""
    def propose(_tp, _tc, draft_params, draft_cache, last, active, k):
        def draft_body(carry, _):
            dc, tok = carry
            dc, logits = decode_step(draft_params, dc, tok, active,
                                     draft_cfg, compute_dtype)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (dc, nxt), nxt

        (draft_cache, last_d), drafts = jax.lax.scan(
            draft_body, (draft_cache, last), None, length=k - 1)
        drafts = (drafts.T if k > 1
                  else jnp.zeros((last.shape[0], 0), jnp.int32))
        # one extra KV-only draft step: when every draft is accepted the
        # next round needs d_{k-1}'s row in the draft cache too (its logits
        # are discarded — this is the fixed price of fixed shapes)
        draft_cache, _ = decode_step(draft_params, draft_cache, last_d,
                                     active, draft_cfg, compute_dtype)
        return draft_cache, drafts

    def settle(_tp, target_cache, _dp, draft_cache, seen):
        return target_cache, dict(draft_cache, length=jnp.where(
            seen["active"], seen["new_len"], draft_cache["length"]))

    return Drafter(propose, settle)


def block_drafter(cfg: TransformerConfig,
                  compute_dtype=jnp.bfloat16) -> Drafter:
    """The target's own multi-token-prediction block (``cfg.mtp_layers``),
    one drafted token a round (k = 2).  Its state lies in the target's cache
    tree (``mtp_k`` / ``mtp_v`` rows, one ``length`` with the model's, and
    ``draft``, the token it proposed for the position after each slot's
    last); ``draft_params`` and ``draft_cache`` are empty.  After the verify
    step the block runs over the window's two positions, each hidden state
    paired with the token the round emitted after it: that fills the block's
    rows for every kept position, and its logits at the last kept position
    are the next round's draft.  A rejected draft's row lies past ``length``
    like the target's."""
    def propose(_tp, target_cache, _dp, draft_cache, _last, _active, k):
        if k != 2:
            raise ValueError(f"the block drafts one token a round: k={k}")
        return draft_cache, target_cache["draft"][:, None]

    def settle(target_params, target_cache, _dp, draft_cache, seen):
        active, count = seen["active"], seen["emit_count"]
        target_cache, logits = mtp_step(
            target_params, target_cache, seen["hidden"], seen["emitted"],
            seen["len0"], active, cfg, compute_dtype)
        kept = jnp.maximum(count - 1, 0)[:, None, None]
        nxt = jnp.argmax(jnp.take_along_axis(logits, kept, 1)[:, 0], -1)
        return dict(target_cache, draft=jnp.where(
            active & (count > 0), nxt.astype(jnp.int32),
            target_cache["draft"])), draft_cache

    return Drafter(propose, settle, hidden=True)


# ---------------------------------------------------------------------------
# Serving-engine integration: decode-state rounds (continuous batching)
# ---------------------------------------------------------------------------

def spec_state_round(target_params: Params, target_cache, draft_params:
                     Params, draft_cache: KVCache, state: Dict[str, Any],
                     k: int, target_cfg: TransformerConfig,
                     drafter: Union[Drafter, TransformerConfig],
                     top_k: int = 0, compute_dtype=jnp.bfloat16):
    """One draft→verify→accept round for every slot, against the engine's
    device-resident decode state (``decode.init_decode_state`` layout), run
    inside LLMEngine's scheduler thread.  ``drafter``: who drafts (a
    ``Drafter``; a cut-out model's configuration stands for
    ``model_drafter`` of it).

    Greedy acceptance: with drafts d_1..d_{k-1} and target logits
    l_0..l_{k-1} over window [last, d_1..d_{k-1}], accept d_{j+1} while
    d_{j+1} == argmax(l_j); then emit argmax(l_a) at the first mismatch
    (the "free" correction) — output identical to vanilla greedy.  Beyond
    that (tier-1 tests pin all three):

    * **Sampling-aware.**  Greedy slots (temperature 0) take the classic
      accept-while-matching path; sampled slots accept NO drafts and emit
      exactly one token drawn from the target's own first-position logits
      via ``sample_per_slot`` — the identical distribution a vanilla
      decode step would sample, so turning speculation on never changes
      sampling semantics (it just wastes the drafts for hot slots).
    * **Budget/EOS exact.**  ``emit_count`` is clamped to the remaining
      budget and truncated at the first emitted EOS (inclusive), then
      budget and active decay on device by the same predicate
      ``decode_state_loop`` applies per step — the host scheduling mirror
      stays byte-consistent with the plain decode path.
    * **Paged or dense target.**  Either way rollback is a length reset
      to ``len0 + emit_count`` (the cache then covers ``last,
      e_1..e_{cnt-1}`` and ``e_cnt`` is fed back next round).

    A cut-out model's draft cache is always DENSE (the paged HBM win
    matters for the big target; the draft is layers-sliced and small).
    Returns (target_cache, draft_cache, state, emitted [slots, k],
    emit_count [slots]).
    """
    if isinstance(drafter, TransformerConfig):
        drafter = model_drafter(drafter, compute_dtype)
    n_slots = state["tokens"].shape[0]
    last = state["tokens"]
    active = state["active"]
    temps = state["temps"]
    key = state["key"]

    # -- draft rollout: k-1 proposed tokens --------------------------------
    draft_cache, drafts = drafter.propose(
        target_params, target_cache, draft_params, draft_cache, last, active,
        k)

    # -- target verify: ONE k-token window ---------------------------------
    window = jnp.concatenate([last[:, None], drafts], axis=1)
    t_len0 = target_cache["length"]
    target_cache, logits, *hidden = verify_window(
        target_params, target_cache, window, active, target_cfg,
        compute_dtype, drafter.hidden)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # [slots, k]

    # -- acceptance --------------------------------------------------------
    match = (drafts == greedy[:, :-1]) if k > 1 \
        else jnp.zeros((n_slots, 0), bool)
    accepted = jnp.argmin(
        jnp.concatenate([match, jnp.zeros((n_slots, 1), bool)], 1), axis=1)
    is_greedy = temps <= 0.0
    accepted = jnp.where(is_greedy, accepted, 0)
    # sampled slots draw token 0 from the target's own next-token logits
    samp = sample_per_slot(logits[:, 0], jax.random.fold_in(key, 0xD1CE),
                           temps, top_k)
    correction = jnp.take_along_axis(greedy, accepted[:, None], 1)[:, 0]
    first_tok = jnp.where(is_greedy, correction, samp)
    drafts_pad = jnp.concatenate(
        [drafts, jnp.zeros((n_slots, 1), jnp.int32)], 1)
    emitted = jnp.where(jnp.arange(k)[None] < accepted[:, None],
                        drafts_pad, first_tok[:, None])     # [slots, k]

    # -- budget clamp + EOS truncation (device mirrors the host retire) ----
    emit_count = jnp.where(active, accepted + 1, 0)
    emit_count = jnp.minimum(emit_count, jnp.maximum(state["budget"], 0))
    in_window = jnp.arange(k)[None] < emit_count[:, None]
    eos_hits = (emitted == state["eos"][:, None]) & in_window
    has_eos = eos_hits.any(axis=1)
    emit_count = jnp.where(has_eos, jnp.argmax(eos_hits, axis=1) + 1,
                           emit_count)

    # -- roll caches back to the verified prefix ---------------------------
    # cache now ends with ...last, e_1..e_{cnt-1}; the last emitted token
    # (correction or budget-cut draft) is fed next round
    new_len = t_len0 + emit_count
    target_cache, draft_cache = drafter.settle(
        target_params, target_cache, draft_params, draft_cache,
        dict(len0=t_len0, new_len=new_len, active=active, emitted=emitted,
             emit_count=emit_count, hidden=hidden[0] if hidden else None))
    target_cache = dict(target_cache,
                        length=jnp.where(active, new_len, t_len0))

    new_last = jnp.take_along_axis(
        emitted, jnp.maximum(emit_count - 1, 0)[:, None], 1)[:, 0]
    new_last = jnp.where(active & (emit_count > 0), new_last, last)
    new_budget = jnp.where(active, state["budget"] - emit_count,
                           state["budget"])
    new_active = active & (new_budget > 0) & ~has_eos
    state = {"tokens": new_last, "active": new_active, "temps": temps,
             "budget": new_budget, "eos": state["eos"],
             "key": jax.random.fold_in(key, 0x5BEC)}
    return target_cache, draft_cache, state, emitted, emit_count


def spec_decode_state_loop(target_params: Params, target_cache,
                           draft_params: Params, draft_cache: KVCache,
                           state: Dict[str, Any], k: int, num_rounds: int,
                           target_cfg: TransformerConfig,
                           drafter: Union[Drafter, TransformerConfig],
                           top_k: int = 0,
                           compute_dtype=jnp.bfloat16) -> Dict[str, Any]:
    """``num_rounds`` decode-state spec rounds under one ``lax.scan`` —
    the engine's speculative twin of ``decode_state_loop`` (one dispatch,
    no host sync between rounds).

    Returns {tokens: [slots, num_rounds*k] (per-slot emit buffer; entries
    beyond counts are garbage), counts: [slots], emit_counts:
    [num_rounds, slots] (per-round acceptance accounting — the host
    derives drafted/accepted/rollback tallies from these alone),
    target_cache, draft_cache, state} and, for a target with dropless
    experts, moe_counts: [2], what they did over these rounds (assignments,
    experts touched: ``decode.decode_state_loop``'s).
    """
    if "moe_counts" in target_cache:
        target_cache = dict(target_cache, moe_counts=jnp.zeros_like(
            target_cache["moe_counts"]))
    n_slots = state["tokens"].shape[0]
    out = jnp.zeros((n_slots, num_rounds * k), jnp.int32)
    counts = jnp.zeros((n_slots,), jnp.int32)
    row = jnp.arange(n_slots)[:, None]

    def body(carry, _):
        tc, dc, st, out, counts = carry
        tc, dc, st, emitted, n_emit = spec_state_round(
            target_params, tc, draft_params, dc, st, k, target_cfg,
            drafter, top_k, compute_dtype)
        idx = jnp.minimum(counts[:, None] + jnp.arange(k)[None],
                          out.shape[1] - 1)
        keep = jnp.arange(k)[None] < n_emit[:, None]
        out = out.at[row, idx].set(jnp.where(keep, emitted, out[row, idx]))
        counts = counts + n_emit
        return (tc, dc, st, out, counts), n_emit

    (target_cache, draft_cache, state, out, counts), emits = jax.lax.scan(
        body, (target_cache, draft_cache, state, out, counts), None,
        length=num_rounds)
    return {"tokens": out, "counts": counts, "emit_counts": emits,
            "target_cache": target_cache, "draft_cache": draft_cache,
            "state": state,
            **({"moe_counts": target_cache["moe_counts"]}
               if "moe_counts" in target_cache else {})}


# ---------------------------------------------------------------------------
# Draft-model construction
# ---------------------------------------------------------------------------

def make_draft_params(params: Params, num_layers: int) -> Params:
    """Layers-sliced draft: the leading ``num_layers`` blocks of the
    stacked target params, SHARING embed/final_norm/lm_head (no copy —
    block params are stacked [L, ...] for the layer scan, so a slice is
    one gather).  This is the zero-training draft the serving engine
    defaults to: acceptance then measures how far the truncated trunk
    agrees with the full one, and greedy acceptance keeps the output
    exact regardless."""
    import jax as _jax
    return {key: (_jax.tree_util.tree_map(lambda a: a[:num_layers], val)
                  if key == "blocks" else val)
            for key, val in params.items()}


def damp_block_outputs(params: Params, scale: float = 0.05,
                       from_layer: int = 0, output_norms: bool = False
                       ) -> Params:
    """Benchmark/test param surgery for SYNTHETIC (randomly initialized)
    weights: scale the output projections (attention ``wo``, MLP
    ``w_out`` + their biases) of every block with index >= ``from_layer``
    by ``scale`` (a pattern's blocks are stacked by kind: ``from_layer``
    then counts periods).  With ``from_layer = draft_layers`` the target's deep
    tail contributes only a small residual perturbation on top of the
    layers a sliced draft shares, so the pair agrees at the acceptance
    rates a TRAINED draft/target pair exhibits — while the target still
    pays its full depth per step, which is the cost speculation saves.
    Untrained random blocks otherwise give a sliced draft ~chance
    acceptance, which benchmarks the overhead of speculation but none of
    its win.  The acceptance rate is recorded honestly either way, the
    SAME damped model runs in BOTH arms of the perf A/B (fair
    comparison), and this is never applied to real checkpoints."""
    import jax as _jax
    import jax.numpy as _jnp

    def _scale(keypath, leaf):
        path = "/".join(str(getattr(p, "key", p)) for p in keypath)
        tail = path.rsplit("/", 1)[-1]
        # (a block that norms its sublayers' OUTPUTS, ``norm_on_output``,
        # undoes a scaled projection: ``output_norms`` scales those norms'
        # scales instead)
        if path.endswith(("attn_norm/scale", "mlp_norm/scale",
                          "mixer_norm/scale")
                         if output_norms else
                         ("wo", "bo", "w_out", "b_out")):
            # stacked block params carry the leading layer dim
            mult = _jnp.where(_jnp.arange(leaf.shape[0]) >= from_layer,
                              _jnp.asarray(scale, leaf.dtype),
                              _jnp.asarray(1.0, leaf.dtype))
            return leaf * mult.reshape((-1,) + (1,) * (leaf.ndim - 1))
        return leaf
    out = dict(params)
    out["blocks"] = _jax.tree_util.tree_map_with_path(
        _scale, params["blocks"])
    return out
