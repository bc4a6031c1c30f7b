"""Model configs: one TransformerConfig covers the GPT-2, Llama-3 and Mixtral
families (BASELINE.json configs #1-#3).

The reference delegates model definitions to torch/HF; here models are first-class and
TPU-first: static shapes, stacked-layer params for ``lax.scan``, bf16 compute, and
explicit sharding rules (see ``ray_tpu/models/sharding.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

#: Per-chip peak bf16 matmul FLOP/s by device kind — the denominator of
#: every MFU number this repo reports (bench.py headline, the runtime
#: train-observability plane's running MFU, MULTICHIP captures).
PEAK_BF16_FLOPS = {
    "v5 lite": 197e12,   # v5e
    "v5e": 197e12,
    "v5p": 459e12,
    "v5": 459e12,
    "v4": 275e12,
    "v6 lite": 918e12,   # trillium
    "v6e": 918e12,
    "cpu": 1e12,         # nominal; an explicit row so CPU tests have an MFU
}


def detect_peak_flops(device) -> float:
    """Peak bf16 FLOP/s of one device, keyed on ``device_kind``.  A device
    that is not in the table raises: an MFU over an assumed peak is a
    number about no machine."""
    kind = str(device.device_kind).lower()
    for key, val in PEAK_BF16_FLOPS.items():
        if key in kind:
            return val
    raise ValueError(
        f"no peak bf16 FLOP/s known for device_kind={device.device_kind!r}; "
        f"add it to PEAK_BF16_FLOPS (known: {sorted(PEAK_BF16_FLOPS)})")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int
    num_layers: int
    hidden_size: int
    num_heads: int
    num_kv_heads: int           # < num_heads => GQA (Llama-3/Mixtral)
    mlp_size: int
    max_seq_len: int
    # architecture flags
    use_rope: bool = True       # False => learned positional embeddings (GPT-2)
    rope_theta: float = 500_000.0
    use_rmsnorm: bool = True    # False => LayerNorm with bias (GPT-2)
    use_qkv_bias: bool = False  # True => biases on Q/K/V only (Qwen-2)
    use_swiglu: bool = True     # False => GELU MLP (GPT-2)
    # "relu2": the MLPs, the routed and the shared experts alike, are two
    # matrices and no gate, ``W_out relu(W_in x)^2`` (a layer_pattern's
    # only: models/hybrid.py builds them); "": what ``use_swiglu`` says
    mlp_act: str = ""
    tied_embeddings: bool = False
    # MoE (Mixtral): num_experts > 1 enables the sparse MLP
    num_experts: int = 1
    experts_per_token: int = 2
    expert_capacity_factor: float = 1.25
    # numerics
    norm_eps: float = 1e-5
    # attention
    causal: bool = True
    attn_logit_softcap: float = 0.0
    #: "auto" (mha dispatcher: the flash kernel on TPU where the shape
    #: tiles, plain elsewhere), "plain", "flash" (ops/flash_attention), or
    #: "splash" (the pallas splash kernel with explicit backward block
    #: sizes).  An explicit "flash"/"splash" that cannot run for the shape
    #: raises; only "auto" chooses.
    attention_impl: str = "auto"
    # layers of several kinds (models/hybrid.py).  ``layer_pattern`` is one
    # period of kinds: "linear" (a gated-delta-rule mixer with a recurrent
    # state per sequence), "ssm" (a state-space mixer, Mamba-2's, with one
    # too), "full" (the dense attention above), "window" (the same attention
    # over the last ``sliding_window`` positions, its K/V on a ring a slot:
    # models/decode.py) and "mlp" (the feed-forward
    # alone: dense, or the dropless experts where ``moe_dropless``); the
    # model is ``num_layers / len(layer_pattern)`` such periods.  Empty:
    # every layer is a dense block, the path of every other preset.
    # A pattern with an "mlp" kind is one whose layers are each ONE sublayer
    # alone, ``x + f(norm(x))`` (``sublayers_alone``): a mixer kind carries
    # no MLP beneath and the MLPs are the pattern's "mlp" layers; without
    # one an MLP lies under every mixer.
    layer_pattern: Tuple[str, ...] = ()
    # a stack of more than one pattern: ``((pattern, periods), ...)``, each
    # segment whole periods of its own pattern, one after another (SambaY's
    # ``(("ssm1", "window"), 8), (("ssm1", "full"), 1), (("gmu", "cross"),
    # 7)``).  Given in place of ``layer_pattern``, which then reads every
    # layer's kind in order (one period, the whole stack); empty: the one
    # segment ``(layer_pattern, num_periods)``.  The kinds that need it:
    # "ssm1" (Mamba-1's selective scan: a decay a channel AND a state
    # column, ``ssm1_*`` below; the last one before a "gmu" hands its scan's
    # output, before the gate, down the stack as the memory), "gmu" (a gated
    # memory unit, ``W_out(memory * silu(W_in x))``: no state of its own)
    # and "cross" (attention with a query and an output projection alone,
    # over the K/V rows of the model's one "full" layer)
    layer_segments: Tuple[Tuple[Tuple[str, ...], int], ...] = ()
    # positions a "window" layer's query reads, its own among them
    sliding_window: int = 0
    # the "ssm1" kind: inner channels, state columns a channel, and the rank
    # its step ``dt`` is projected through (the convolution's width is
    # ``linear_conv_width``)
    ssm1_inner: int = 0
    ssm1_state: int = 0
    ssm1_dt_rank: int = 0
    # differential attention (arXiv 2410.05258) in every attention layer
    # ("full", "window", "cross"): heads in pairs, two softmax maps over
    # values two heads wide, ``(1 - lam0) rmsnorm([A1 v1 | A1 v2] - lam [A2
    # v1 | A2 v2])`` with ``lam0 = 0.8 - 0.6 exp(-0.3 depth)``
    # (models/decode.py ``diff_combine``)
    diff_attn: bool = False
    # (the "ssm" kind reads the same four: its heads, the state's width N
    # as the key's, the head's width P as the value's, its convolution)
    linear_num_heads: int = 0       # key heads = value heads of the mixer
    linear_key_dim: int = 0         # per head
    linear_value_dim: int = 0       # per head
    linear_conv_width: int = 4      # causal depthwise convolution over time
    # groups of the "ssm" kind: B and C (its keys and queries) are shared
    # by the ``linear_num_heads / ssm_groups`` heads of a group
    ssm_groups: int = 0
    linear_neg_eigval: bool = False  # beta in (0, 2) instead of (0, 1)
    # the linear kind's variant (ops/kda.py, Kimi Delta Attention): a decay
    # a key channel instead of one a head and a sigmoid output gate instead
    # of SiLU, the decay's and the gate's projections through a bottleneck
    # of the published rank
    linear_decay_per_channel: bool = False
    linear_gate_rank: int = 0
    # an RMSNorm on q and k before the heads attend: ``qk_norm`` over the
    # whole row (all heads' channels one vector, a scale a channel);
    # ``qk_head_norm`` over each head's channels alone, one scale vector of
    # ``head_dim`` shared by the heads
    qk_norm: bool = False
    qk_head_norm: bool = False
    norm_on_output: bool = False    # x + norm(f(x)); else x + f(norm(x))
    # the full kind under a pattern: the published width of a head where it
    # is not hidden / heads (0: it is), and an elementwise sigmoid gate on
    # the attention's output from a projection of the layer's input
    attn_head_dim: int = 0
    attn_output_gate: bool = False
    no_positions: bool = False      # neither rotary nor learned positions
    # rotary positions by kind: the "window" layers rotate q and k, the
    # "full" layers add none (``use_rope`` alone: every attention layer does)
    rope_window_only: bool = False
    # YaRN by kind: the kinds of a pattern whose rotary table is YaRN's
    # (``rope_yarn_*`` below; Mellum's "full" layers), the other rotary
    # kinds plain at ``rope_theta``.  Static: it applies at every length.
    # The train step's (``transformer.rope_table``); serving refuses it
    rope_yarn_kinds: Tuple[str, ...] = ()
    # multi-token-prediction blocks after the last layer (DeepSeek-V3's
    # ``num_nextn_predict_layers``; models/speculative.py drafts with one):
    # a block is ``W_eh [norm(E x_{t+1}); norm(h_t)]``, one "full" layer of
    # the model's own form with K/V rows of its own, a norm, and the
    # model's head
    mtp_layers: int = 0
    # four published scalars of a pattern (Granite's): on the embedding, on
    # every sublayer's output before it joins the residual stream, the
    # attention's scale in place of ``head_dim ** -0.5``, and what the
    # logits are divided by.  0: absent, and skipped in Python, not
    # multiplied by 1 (models/decode.py applies each in one place)
    embedding_multiplier: float = 0.0
    residual_multiplier: float = 0.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 0.0
    # latent attention (models/latent.py; DeepSeek-V2's MLA, whose keys these
    # are): ``kv_lora_rank`` > 0 turns it on.  Queries and keys have heads
    # of ``qk_nope_head_dim + qk_rope_head_dim``, values of ``v_head_dim``;
    # a token caches one row of ``kv_lora_rank + qk_rope_head_dim`` a layer,
    # shared by all heads.  ``head_dim`` is then not hidden / heads.
    # ``q_lora_rank`` 0: the queries are projected directly (one matrix, no
    # compression and no norm), as the published file says with ``null``.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN on the rotary dimensions (``rope_yarn_factor`` > 0)
    rope_yarn_factor: float = 0.0
    rope_yarn_original_max: int = 0
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    rope_yarn_mscale: float = 1.0
    rope_yarn_mscale_all_dim: float = 0.0
    # dropless expert layers (ops/moe.py ``moe_dropless``): no capacity, a
    # token's output does not depend on its batch.  ``num_experts`` routed
    # experts of width ``expert_mlp_size`` with sigmoid scores and a
    # selection bias, ``shared_experts`` more on every token; the first
    # ``dense_prefix_layers`` layers keep a dense MLP of ``mlp_size``.
    moe_dropless: bool = False
    # the dropless layer's router: "sigmoid" (``noaux_tc``: sigmoid scores,
    # a selection bias, the chosen scores over their sum times
    # ``routed_scaling_factor``) or "softmax" (Qwen3-MoE's: softmax over all
    # experts in float32, the top k, the chosen probabilities over their sum;
    # no bias, no scaling), and the weight of the softmax router's balance
    # term in the train step's total (``ops.moe.balance_term``; 0: none)
    moe_router: str = "sigmoid"
    moe_balance_weight: float = 0.0
    expert_mlp_size: int = 0
    shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    dense_prefix_layers: int = 0
    # a chip's share of the dropless experts: the router keeps its
    # ``num_experts`` outputs and this holder has the weights of experts
    # ``expert_start .. expert_start + experts_held`` (given as 0: all of
    # them, and the field then reads ``num_experts``).  Assignments to the
    # others are left out of its part of the result (``moe_dropless``);
    # nothing here exchanges tokens or stands in for the other holders.
    # ``share_by_position``: the held weights stand for another group of
    # ``experts_held`` router outputs at each position of a sequence, group
    # ``expert_start / experts_held + position`` modulo the number of
    # groups, so that the share of a step's assignments kept here is
    # ``experts_held / num_experts`` whatever the router learns.  With one
    # fixed group and the others' part left out, a router under training
    # learns to send its tokens past the held experts (PERF.md, PR 39).
    # The train step's; the serving path refuses it.
    expert_start: int = 0
    experts_held: int = 0
    share_by_position: bool = False
    # ``hc_mult`` > 1 residual streams mixed by manifold-constrained
    # hyper-connections (models/latent.py ``hc_*``; arXiv 2512.24880)
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: float = 30.0

    #: the fields whose parameters are ``models/latent.py``'s tree
    #: (``prefix`` / ``blocks``) and whose serving state is its cache
    LATENT_TREE = ("kv_lora_rank", "moe_dropless", "dense_prefix_layers",
                   "hc_mult")
    #: of them, what only the serving path's walk over the layers
    #: (models/decode.py ``layer_stack``) knows: the train step wires one
    #: residual stream
    SERVED_ONLY = ("hc_mult",)

    #: a pattern's kinds of layer
    KINDS = ("linear", "ssm", "ssm1", "gmu", "full", "window", "cross",
             "mlp")
    #: of them, the kinds with a recurrent state a slot (one a model)
    RECURRENT_KINDS = ("linear", "ssm", "ssm1")
    #: of them, the kinds the train step walks (``transformer.apply_trunk``):
    #: attention layers that differ in their span and their rotary table
    TRAINED_KINDS = ("full", "window")
    #: what a pattern may not switch on to be trained: each is wired by the
    #: serving path's block alone (models/decode.py ``layer_stack``)
    PATTERN_SERVED_ONLY = ("layer_segments", "diff_attn",
                           "qk_norm", "norm_on_output", "attn_output_gate",
                           "no_positions", "rope_window_only", "mtp_layers",
                           "mlp_act", "dense_prefix_layers",
                           "embedding_multiplier", "residual_multiplier",
                           "attention_multiplier", "logits_scaling")
    #: the published scalars, 0 where absent
    MULTIPLIERS = ("embedding_multiplier", "residual_multiplier",
                   "attention_multiplier", "logits_scaling")

    def __post_init__(self):
        if self.layer_segments:
            flat = tuple(k for seg, n in self.layer_segments
                         for k in tuple(seg) * n)
            if self.layer_pattern not in ((), flat) or not all(
                    seg and n > 0 for seg, n in self.layer_segments):
                raise ValueError(
                    f"layer_segments {self.layer_segments}: whole periods "
                    "of one pattern a segment, given in place of "
                    "layer_pattern (which then reads the whole stack)")
            object.__setattr__(self, "layer_pattern", flat)
        pat = self.layer_pattern
        self._check_latent_tree()
        if not pat:
            only = [f for f in ("qk_norm", "qk_head_norm", "norm_on_output",
                                "attn_head_dim", "attn_output_gate",
                                "linear_decay_per_channel",
                                "linear_gate_rank", "ssm_groups", "mlp_act",
                                "sliding_window", "rope_window_only",
                                "rope_yarn_kinds", "mtp_layers",
                                "ssm1_inner", "ssm1_state", "ssm1_dt_rank",
                                "diff_attn", *self.MULTIPLIERS)
                    if getattr(self, f)]
            if only:
                raise ValueError(f"{only} are wired for a layer_pattern only "
                                 "(models/hybrid.py builds those blocks)")
            return
        if set(pat) - set(self.KINDS):
            raise ValueError(f"layer_pattern {pat}: kinds are "
                             f"{', '.join(map(repr, self.KINDS))}")
        if self.num_layers % len(pat):
            raise ValueError(f"num_layers {self.num_layers} is not whole "
                             f"periods of {pat}")
        if len(set(pat) & set(self.RECURRENT_KINDS)) > 1:
            raise ValueError(
                f"layer_pattern {pat}: one recurrent kind a model, 'linear', "
                "'ssm' or 'ssm1' (they keep the cache tree's one state, the "
                "first two by the same linear_* sizes)")
        self._check_segment_kinds(pat)
        if ("linear" in pat or "ssm" in pat) and not (
                self.linear_num_heads and self.linear_key_dim
                and self.linear_value_dim):
            raise ValueError("a 'linear' or 'ssm' layer needs "
                             "linear_num_heads, linear_key_dim and "
                             "linear_value_dim")
        if ("ssm" in pat) != bool(self.ssm_groups) or (
                self.ssm_groups and self.linear_num_heads % self.ssm_groups):
            raise ValueError(
                f"ssm_groups {self.ssm_groups}: the groups of an 'ssm' "
                f"layer's {self.linear_num_heads} heads, whole heads a "
                "group, and no other kind's")
        if ("window" in pat) != (self.sliding_window > 0):
            raise ValueError(
                f"sliding_window {self.sliding_window}: the positions a "
                "'window' layer reads, 1 or more, and no other kind's")
        if self.qk_norm and self.qk_head_norm:
            raise ValueError("qk_norm (the whole row) or qk_head_norm (a "
                             "head), not both")
        if self.rope_window_only and not (self.use_rope and "window" in pat):
            raise ValueError("rope_window_only: rotary 'window' layers "
                             "beside 'full' layers without positions needs "
                             "use_rope and a 'window' kind")
        rotary = {k for k in ("window", "full") if k in pat and self.use_rope
                  and (k == "window" or not self.rope_window_only)}
        if set(self.rope_yarn_kinds) - rotary or bool(
                self.rope_yarn_kinds) != bool(self.rope_yarn_factor):
            raise ValueError(
                f"rope_yarn_kinds {self.rope_yarn_kinds}: the rotary kinds "
                f"of {pat} ({sorted(rotary)}) whose table is YaRN's, given "
                "with rope_yarn_factor and not without")
        if self.mtp_layers not in (0, 1):
            raise ValueError(f"mtp_layers {self.mtp_layers}: models/"
                             "speculative.py drafts one token with one block")
        if self.mtp_layers and set(pat) - {"full", "window"}:
            raise ValueError(
                f"mtp_layers under {pat}: a rejected draft is rolled out of "
                "rows and rings by their length, not out of a recurrent "
                "state, and the block is a layer with its MLP beneath")
        if any(getattr(self, f) < 0 for f in self.MULTIPLIERS):
            raise ValueError(f"{self.MULTIPLIERS}: 0 (absent) or more")
        if self.residual_multiplier and self.norm_on_output:
            raise ValueError(
                "residual_multiplier scales a pre-norm sublayer's output "
                "(x + m f(norm(x))), not one normed on its way out "
                "(norm_on_output)")
        if self.mlp_act not in ("", "relu2"):
            raise ValueError(f"mlp_act {self.mlp_act!r}: '' or 'relu2'")
        if self.linear_gate_rank < 0 or self.attn_head_dim < 0:
            raise ValueError("linear_gate_rank and attn_head_dim: 0 or more")
        if self.linear_decay_per_channel != bool(self.linear_gate_rank):
            raise ValueError(
                "linear_decay_per_channel and linear_gate_rank go together: "
                "the decay a channel and its sigmoid gate are projected "
                "through linear_gate_rank (models/hybrid.py has no full "
                "[hidden, heads x key_dim] projection of either, and no "
                "bottleneck for the mixer with a decay a head)")

    def _check_segment_kinds(self, pat):
        """The kinds a stack of segments brings ("ssm1", "gmu", "cross") and
        the differential form of its attention."""
        new = [k for k in ("ssm1", "gmu", "cross") if k in pat]
        if new and not self.layer_segments:
            raise ValueError(
                f"layer_pattern {pat}: {new} are kinds of a stack given as "
                "layer_segments")
        sizes = (self.ssm1_inner, self.ssm1_state, self.ssm1_dt_rank)
        if ("ssm1" in pat) != all(sizes) or (
                "ssm1" not in pat and any(sizes)) or self.ssm1_inner % 128:
            raise ValueError(
                f"ssm1_inner {self.ssm1_inner}, ssm1_state "
                f"{self.ssm1_state}, ssm1_dt_rank {self.ssm1_dt_rank}: the "
                "sizes of an 'ssm1' layer, all three and no other kind's, "
                "the inner channels whole tiles of 128 lanes (the state "
                "lies [columns, channels / 128, 128]: ops/selective_scan.py)")
        for kind, source in (("gmu", "ssm1"), ("cross", "full")):
            if kind in pat and source not in pat[:pat.index(kind)]:
                raise ValueError(
                    f"layer_pattern {pat}: a {kind!r} layer reads what a "
                    f"{source!r} layer above it leaves (the memory, the K/V "
                    "rows)")
        if "cross" in pat and (pat.count("full") != 1 or "gmu" not in pat
                               or not self.no_positions or self.qk_norm
                               or self.qk_head_norm or self.use_qkv_bias):
            raise ValueError(
                f"layer_pattern {pat}: 'cross' layers read the rows of the "
                "model's ONE 'full' layer beside 'gmu' layers (a "
                "cross-decoder), with a query projection alone: no "
                "positions, no bias and no norm on it")
        if self.layer_segments and (self.moe_dropless or self.mtp_layers
                                    or self.sublayers_alone):
            raise ValueError(
                "layer_segments: every layer a mixer with its dense MLP "
                "beneath (no experts, no 'mlp' layers, no "
                "multi-token-prediction block: models/decode.py layer_stack "
                "counts those through one pattern)")
        if self.diff_attn and (
                self.num_kv_heads % 2
                or self.num_heads % (2 * self.num_kv_heads)
                or not set(pat) & {"full", "window", "cross"}
                or self.attn_output_gate or self.attn_logit_softcap):
            raise ValueError(
                f"diff_attn: heads in pairs ({self.num_heads} query heads "
                f"over {self.num_kv_heads} K/V heads: whole pairs of both, "
                "as many query pairs a K/V pair as query heads a K/V head) "
                "in an attention kind, with no output gate and no softcap")

    def _check_latent_tree(self):
        if self.layer_pattern:
            # a pattern's blocks are models/hybrid.py's: full attention
            # over K/V rows and the recurrent mixers, one residual stream,
            # with a dense MLP or dropless experts under every mixer or as
            # layers of their own
            on = [f for f in ("kv_lora_rank", "hc_mult") if getattr(self, f)]
            if on:
                raise ValueError(
                    f"{on} do not combine with a layer_pattern: "
                    "models/hybrid.py's full layers cache K/V rows, not "
                    "latent ones, and its blocks carry one residual stream")
            if self.dense_prefix_layers and (
                    self.sublayers_alone
                    or self.dense_prefix_layers > len(self.layer_pattern)):
                raise ValueError(
                    f"dense_prefix_layers {self.dense_prefix_layers} under "
                    f"{self.layer_pattern}: the dense layers lie in the "
                    "first period, which models/decode.py walks ahead of "
                    "its scan, and every layer has its MLP beneath (no "
                    "'mlp' kind)")
        if self.kv_lora_rank:
            if not (self.qk_nope_head_dim and self.qk_rope_head_dim
                    and self.v_head_dim) or self.q_lora_rank < 0:
                raise ValueError(
                    "latent attention (kv_lora_rank) needs qk_nope_head_dim, "
                    "qk_rope_head_dim and v_head_dim, and a q_lora_rank of "
                    "0 (queries projected directly) or more")
            if self.num_kv_heads != self.num_heads:
                raise ValueError("latent attention has one key and one "
                                 "value head a query head: num_kv_heads "
                                 f"{self.num_kv_heads} != num_heads")
            if not (self.use_rope and self.use_rmsnorm) \
                    or self.use_qkv_bias or self.attn_logit_softcap:
                raise ValueError("latent attention is rotary, RMS-normed, "
                                 "without biases or softcap")
            if self.qk_rope_head_dim % 2:
                raise ValueError("qk_rope_head_dim must be even")
        elif self.q_lora_rank or (self.rope_yarn_factor
                                  and not self.rope_yarn_kinds):
            raise ValueError(
                "q_lora_rank is read by latent attention only (models/"
                "latent.py), rope_yarn_factor by it or by a pattern's "
                "rope_yarn_kinds")
        if self.rope_yarn_factor and not self.rope_yarn_original_max:
            raise ValueError("rope_yarn_factor needs rope_yarn_original_max")
        if self.moe_dropless:
            if not (self.num_experts > 1 and self.expert_mlp_size
                    and (self.use_swiglu or self.mlp_act)):
                raise ValueError("moe_dropless needs num_experts > 1, "
                                 "expert_mlp_size and a SwiGLU MLP (or "
                                 "mlp_act 'relu2' under a layer_pattern)")
            if self.moe_router not in ("sigmoid", "softmax"):
                raise ValueError(f"moe_router {self.moe_router!r}: "
                                 "'sigmoid' or 'softmax'")
            if self.moe_balance_weight and self.moe_router != "softmax":
                raise ValueError(
                    "moe_balance_weight weighs the softmax router's balance "
                    "term; the sigmoid router balances by its selection "
                    "bias, which no rule here moves")
            if not 0 < self.experts_per_token <= self.num_experts:
                raise ValueError(
                    f"experts_per_token {self.experts_per_token} of "
                    f"{self.num_experts} experts")
            if not 0 <= self.dense_prefix_layers < self.num_layers:
                raise ValueError(
                    f"dense_prefix_layers {self.dense_prefix_layers} of "
                    f"{self.num_layers} layers leaves no expert layer")
            if not self.experts_held:
                object.__setattr__(self, "experts_held", self.num_experts)
            if self.expert_start < 0 or self.experts_held < 0 or \
                    self.expert_start + self.experts_held > self.num_experts:
                raise ValueError(
                    f"experts {self.expert_start} to {self.expert_start} + "
                    f"{self.experts_held} are not among the router's "
                    f"{self.num_experts}")
            if self.share_by_position and (
                    self.num_experts % self.experts_held
                    or self.expert_start % self.experts_held):
                raise ValueError(
                    f"share_by_position: the router's {self.num_experts} "
                    f"outputs in groups of experts_held {self.experts_held}"
                    f", expert_start {self.expert_start} the first of one")
        elif self.dense_prefix_layers or self.shared_experts \
                or self.expert_mlp_size or self.expert_start \
                or self.experts_held or self.share_by_position \
                or self.moe_router != "sigmoid" or self.moe_balance_weight:
            raise ValueError("dense_prefix_layers, shared_experts, "
                             "expert_mlp_size, expert_start, experts_held, "
                             "share_by_position, moe_router and "
                             "moe_balance_weight belong to moe_dropless")
        if self.hc_mult == 1 or self.hc_mult < 0:
            raise ValueError(f"hc_mult {self.hc_mult}: 0 (one residual "
                             "stream) or at least 2")

    @property
    def head_dim(self) -> int:
        if self.kv_lora_rank:
            raise AttributeError(
                "latent attention has no one head_dim: qk_head_dim for "
                "queries and keys, v_head_dim for values")
        return self.attn_head_dim or self.hidden_size // self.num_heads

    @property
    def attn_scale(self) -> float:
        """What the scores ``q k^T`` are multiplied by: the published
        ``attention_multiplier``, or ``head_dim ** -0.5``."""
        return self.attention_multiplier or self.head_dim ** -0.5

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """Width of the one row a token caches a layer under latent
        attention: the compressed keys and values, then the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_tree(self) -> Tuple[str, ...]:
        """The mechanisms of this configuration that make its parameters
        ``models/latent.py``'s tree and its serving state that file's cache
        (no pages, no window of several tokens, no mesh).  None under a
        ``layer_pattern``, whose tree is ``models/hybrid.py``'s whatever its
        layers' MLP."""
        if self.layer_pattern:
            return ()
        return tuple(f for f in self.LATENT_TREE if getattr(self, f))

    @property
    def served_only(self) -> Tuple[str, ...]:
        """The mechanisms of this configuration that only the serving path
        runs (the train step has no block for them)."""
        return tuple(f for f in self.SERVED_ONLY if getattr(self, f))

    @property
    def pattern_untrained(self) -> Tuple[str, ...]:
        """What of this configuration's pattern the train step has no
        backward or no wiring for: the recurrent kinds (their kernels are
        forward only), "mlp" layers of their own, and the fields of
        ``PATTERN_SERVED_ONLY``.  Empty: ``apply_trunk`` walks it."""
        kinds = tuple(k for k in dict.fromkeys(self.layer_pattern)
                      if k not in self.TRAINED_KINDS)
        return kinds + tuple(f for f in self.PATTERN_SERVED_ONLY
                             if self.layer_pattern and getattr(self, f))

    @property
    def sublayers_alone(self) -> bool:
        """Every layer is one sublayer alone: the pattern has "mlp" layers,
        so no mixer carries an MLP beneath."""
        return "mlp" in self.layer_pattern

    @property
    def mlp_layers(self) -> int:
        """Layers with a feed-forward: a pattern's "mlp" layers where its
        layers are sublayers alone, else every layer."""
        if self.sublayers_alone:
            return self.num_periods * self.layer_pattern.count("mlp")
        return self.num_layers

    @property
    def expert_layers(self) -> int:
        return (self.mlp_layers - self.dense_prefix_layers
                if self.moe_dropless else 0)

    @property
    def learned_positions(self) -> bool:
        return not self.use_rope and not self.no_positions

    @property
    def num_periods(self) -> int:
        return self.num_layers // len(self.layer_pattern)

    @property
    def segments(self) -> Tuple[Tuple[Tuple[str, ...], int], ...]:
        """The stack as ``decode.layer_stack`` walks it: ``(pattern,
        periods)`` a segment; one where the model has one pattern (without
        a pattern: full layers, one a period)."""
        return self.layer_segments or (
            (self.layer_pattern or ("full",),
             self.num_periods if self.layer_pattern else self.num_layers),)

    @property
    def cross_segment(self) -> int:
        """The first segment with a layer that reads what the layers above
        it left ("gmu", "cross"): from it on a prefill walks a prompt's last
        token alone (the cross-decoder).  0: no such segment."""
        return next((j for j, (seg, _) in enumerate(self.segments)
                     if set(seg) & {"gmu", "cross"}), 0)

    def depths(self, kind: str) -> Tuple[int, ...]:
        """The depth in the stack of each layer of ``kind``, in the order
        its stack of weights holds them."""
        return tuple(j for j, k in enumerate(self.layer_pattern) if k == kind)

    def _layers_of(self, kind: str) -> int:
        return (self.num_periods * self.layer_pattern.count(kind)
                if self.layer_pattern else 0)

    @property
    def linear_layers(self) -> int:
        return self._layers_of("linear")

    @property
    def ssm_layers(self) -> int:
        return self._layers_of("ssm")

    @property
    def ssm1_layers(self) -> int:
        return self._layers_of("ssm1")

    @property
    def cross_layers(self) -> int:
        return self._layers_of("cross")

    @property
    def recurrent_layers(self) -> int:
        return sum(self._layers_of(k) for k in self.RECURRENT_KINDS)

    @property
    def full_layers(self) -> int:
        return (self._layers_of("full") if self.layer_pattern
                else self.num_layers)

    @property
    def window_layers(self) -> int:
        return self._layers_of("window")

    @property
    def mtp_cfg(self) -> "TransformerConfig":
        """The multi-token-prediction block as a model of one "full" layer
        of this model's form (its experts where the model has them), which
        ``decode.layer_stack`` walks on the block's own K/V rows."""
        return dataclasses.replace(
            self, num_layers=1, layer_pattern=("full",), sliding_window=0,
            rope_window_only=False,
            no_positions=self.no_positions or self.rope_window_only,
            use_rope=self.use_rope and not self.rope_window_only,
            dense_prefix_layers=0, mtp_layers=0)

    @property
    def ssm_channels(self) -> Tuple[int, int]:
        """(inner channels heads x head width, channels the convolution
        mixes: those and B and C of every group) of the "ssm" kind."""
        inner = self.linear_num_heads * self.linear_value_dim
        return inner, inner + 2 * self.ssm_groups * self.linear_key_dim

    def num_params(self) -> int:
        """Approximate parameter count (for MFU math): what this holder
        has, where it holds a share of the experts."""
        h, v, L = self.hidden_size, self.vocab_size, self.num_layers
        if self.latent_tree:
            return self._latent_tree_params(self.experts_held)
        if self.layer_pattern:
            return self._pattern_params(self.experts_held)
        attn = h * h + 2 * h * (self.num_kv_heads * self.head_dim) + h * h
        if self.num_experts > 1:
            mlp = self.num_experts * 3 * h * self.mlp_size + h * self.num_experts
        else:
            mlp = (3 if self.use_swiglu else 2) * h * self.mlp_size
        emb = v * h * (1 if self.tied_embeddings else 2)
        return L * (attn + mlp) + emb

    def _pattern_params(self, routed: float) -> float:
        """Matrix parameters of ``models/hybrid.py``'s tree with ``routed``
        routed experts an expert layer (``_latent_tree_params``'s two
        uses)."""
        h = self.hidden_size
        lh, r = self.linear_num_heads, self.linear_gate_rank
        kd, vd = lh * self.linear_key_dim, lh * self.linear_value_dim
        wide = self.num_heads * self.head_dim
        attn = (h * wide * (3 if self.attn_output_gate else 2)
                + 2 * h * self.num_kv_heads * self.head_dim)
        decay = h * r + r * kd if r else h * lh
        gate = h * r + r * vd if r else h * vd
        mixer = h * (2 * kd + vd) + vd * h + decay + gate + h * lh
        inner, mixed = self.ssm_channels
        ssm = h * (inner + mixed + lh) + inner * h
        c1 = self.ssm1_inner
        ssm1 = (3 * h * c1 + c1 * (self.ssm1_dt_rank + 2 * self.ssm1_state)
                + self.ssm1_dt_rank * c1)
        mats = 2 if self.mlp_act else 3
        mlp = mats * h * self.mlp_size
        if self.moe_dropless:
            mlp = (mats * h * self.expert_mlp_size
                   * (routed + self.shared_experts) + h * self.num_experts)
        dense = mats * h * self.mlp_size
        # a multi-token-prediction block: its projection of [embedding;
        # hidden state] and one layer with the experts' MLP
        block = self.mtp_layers * (2 * h * h + attn + mlp)
        return (self.linear_layers * mixer + self.ssm_layers * ssm
                + self.ssm1_layers * ssm1
                + self._layers_of("gmu") * 2 * h * c1
                + self.cross_layers * 2 * h * wide
                + (self.full_layers + self.window_layers) * attn
                + (self.mlp_layers - self.dense_prefix_layers) * mlp
                + self.dense_prefix_layers * dense + block
                + self.vocab_size * h * (1 if self.tied_embeddings else 2))

    def _latent_tree_params(self, routed: float) -> float:
        """Matrix parameters of ``models/latent.py``'s tree (latent
        attention, dropless experts, a dense prefix or hyper-connections)
        with ``routed`` routed experts a layer: those held, for what a
        holder has; those a token meets here, for its FLOPs."""
        h, nh, L = self.hidden_size, self.num_heads, self.num_layers
        if self.kv_lora_rank:
            qr = self.q_lora_rank
            attn = ((h * qr + qr * nh * self.qk_head_dim if qr
                     else h * nh * self.qk_head_dim)
                    + h * self.latent_row
                    + self.kv_lora_rank * nh * (self.qk_nope_head_dim
                                                + self.v_head_dim)
                    + nh * self.v_head_dim * h)
        else:
            attn = 2 * h * h + 2 * h * self.num_kv_heads * (h // nh)
        dense = 3 * h * self.mlp_size
        sparse = dense
        if self.moe_dropless:
            sparse = (3 * h * self.expert_mlp_size
                      * (routed + self.shared_experts)
                      + h * self.num_experts)
        n = self.hc_mult
        hc = 2 * n * h * (2 * n + n * n) if n else 0
        return (L * (attn + hc) + self.dense_prefix_layers * dense
                + (L - self.dense_prefix_layers) * sparse
                + self.vocab_size * h * (1 if self.tied_embeddings else 2))

    def flops_per_token(self, seq_len: Optional[int] = None) -> float:
        """Training FLOPs/token ≈ 6*N_active + attention quadratic term."""
        h, L = self.hidden_size, self.num_layers
        if self.served_only:
            raise NotImplementedError(
                f"{self.served_only} are served, not trained: no training "
                "FLOPs a token")
        if self.latent_tree:
            # what this holder does for a token: 6 a parameter the token
            # meets here (its routed experts at the expected share that
            # lands on the experts held), the head without the embedding's
            # lookup, and the score and value matmuls at the heads' own
            # widths (not the kernel's padded ones), halved by causality
            s = seq_len or self.max_seq_len
            met = self.experts_per_token * self.experts_held \
                / self.num_experts if self.moe_dropless else 0.0
            emb = self.vocab_size * h * (1 if self.tied_embeddings else 2)
            heads = (self.qk_head_dim + self.v_head_dim if self.kv_lora_rank
                     else 2 * (h // self.num_heads))
            return (6.0 * (self._latent_tree_params(met) - emb
                           + self.vocab_size * h)
                    + 3.0 * L * self.num_heads * heads * s)
        if self.layer_pattern:
            # the quadratic term for the full layers only; a recurrent
            # mixer's state update and read are 4 * key_dim * value_dim a
            # head a token; of a token's routed experts the expected share
            # that lands on the experts held
            s = seq_len or self.max_seq_len
            state = ((self.linear_layers + self.ssm_layers)
                     * self.linear_num_heads * 4
                     * self.linear_key_dim * self.linear_value_dim)
            met = (self.experts_per_token * self.experts_held
                   / self.num_experts if self.moe_dropless else 0.0)
            emb = self.vocab_size * h * (1 if self.tied_embeddings else 2)
            return (6.0 * (self._pattern_params(met) - emb
                           + self.vocab_size * h)
                    + 6.0 * self.full_layers * 2 * s
                    * self.num_heads * self.head_dim + 3.0 * state)
        attn = L * (h * h + 2 * h * self.num_kv_heads * self.head_dim + h * h)
        if self.num_experts > 1:
            mlp = L * self.experts_per_token * 3 * h * self.mlp_size
        else:
            mlp = L * (3 if self.use_swiglu else 2) * h * self.mlp_size
        emb = self.vocab_size * h
        n_active = attn + mlp + emb
        s = seq_len or self.max_seq_len
        attn_quad = L * 2 * s * h  # 2*s*h per token for QK^T + AV (causal halves it)
        return 6.0 * n_active + 6.0 * attn_quad


# ---------------------------------------------------------------------------
# Presets
# ---------------------------------------------------------------------------

def gpt2_small(max_seq_len: int = 1024) -> TransformerConfig:
    """GPT-2 124M (BASELINE config #1). Vocab padded to a multiple of 128 for
    MXU-friendly embedding/logit matmuls."""
    return TransformerConfig(
        vocab_size=50304, num_layers=12, hidden_size=768, num_heads=12,
        num_kv_heads=12, mlp_size=3072, max_seq_len=max_seq_len,
        use_rope=False, use_rmsnorm=False, use_swiglu=False,
        tied_embeddings=True, norm_eps=1e-5)


def llama3_8b(max_seq_len: int = 8192) -> TransformerConfig:
    return TransformerConfig(
        vocab_size=128256, num_layers=32, hidden_size=4096, num_heads=32,
        num_kv_heads=8, mlp_size=14336, max_seq_len=max_seq_len,
        rope_theta=500_000.0)


def llama3_70b(max_seq_len: int = 8192) -> TransformerConfig:
    return TransformerConfig(
        vocab_size=128256, num_layers=80, hidden_size=8192, num_heads=64,
        num_kv_heads=8, mlp_size=28672, max_seq_len=max_seq_len,
        rope_theta=500_000.0)


def llama_1b(max_seq_len: int = 2048) -> TransformerConfig:
    """~0.89B Llama-style model (``num_params()`` = 889M).  With f32 Adam its
    train state alone is ~14 GB, so it trains sharded over several chips
    (the four-chip config of chip_smoke.py); one 16 GB chip holds it for
    inference."""
    return TransformerConfig(
        vocab_size=32768, num_layers=16, hidden_size=2048, num_heads=16,
        num_kv_heads=8, mlp_size=5632, max_seq_len=max_seq_len)


def llama_400m(max_seq_len: int = 2048) -> TransformerConfig:
    """~0.41B Llama-style model: fits a single 16 GB chip *with* f32 Adam state
    (llama-1b's state alone is ~14 GB — see bench.py's memory model).  The
    single-chip bench config."""
    return TransformerConfig(
        vocab_size=32768, num_layers=12, hidden_size=1536, num_heads=12,
        num_kv_heads=6, mlp_size=4096, max_seq_len=max_seq_len)


def mixtral_8x7b(max_seq_len: int = 8192) -> TransformerConfig:
    return TransformerConfig(
        vocab_size=32000, num_layers=32, hidden_size=4096, num_heads=32,
        num_kv_heads=8, mlp_size=14336, max_seq_len=max_seq_len,
        rope_theta=1_000_000.0, num_experts=8, experts_per_token=2)


def gemma2_2b(max_seq_len: int = 8192) -> TransformerConfig:
    """Gemma-2-2B-class: GQA, GeGLU-family MLP, attention logit softcapping
    (the architectural marker of the family), tied embeddings."""
    return TransformerConfig(
        vocab_size=256128, num_layers=26, hidden_size=2304, num_heads=8,
        num_kv_heads=4, mlp_size=9216, max_seq_len=max_seq_len,
        rope_theta=10_000.0, attn_logit_softcap=50.0, tied_embeddings=True)


def qwen2_7b(max_seq_len: int = 8192) -> TransformerConfig:
    """Qwen-2-7B-class: Llama-like with QKV biases (use_qkv_bias marker)."""
    return TransformerConfig(
        vocab_size=152064, num_layers=28, hidden_size=3584, num_heads=28,
        num_kv_heads=4, mlp_size=18944, max_seq_len=max_seq_len,
        rope_theta=1_000_000.0, use_qkv_bias=True)


def tiny(vocab: int = 256, layers: int = 2, hidden: int = 64, heads: int = 4,
         seq: int = 64, experts: int = 1) -> TransformerConfig:
    """Test-size config (CPU mesh)."""
    return TransformerConfig(
        vocab_size=vocab, num_layers=layers, hidden_size=hidden, num_heads=heads,
        num_kv_heads=max(1, heads // 2), mlp_size=hidden * 3, max_seq_len=seq,
        num_experts=experts, experts_per_token=min(2, experts))


PRESETS = {
    "gpt2-124m": gpt2_small,
    "llama3-8b": llama3_8b,
    "llama3-70b": llama3_70b,
    "llama-1b": llama_1b,
    "llama-400m": llama_400m,
    "mixtral-8x7b": mixtral_8x7b,
    "gemma2-2b": gemma2_2b,
    "qwen2-7b": qwen2_7b,
    "tiny": tiny,
}
