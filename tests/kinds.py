"""The kinds of model the benchmark serves or trains, a row each, and the
builders the tests of the model path share (a module, no tests).

A row says what the tests of a kind otherwise spell out each for itself: the
kind's file under ``benchmark/models/``, its tiny configuration under
``benchmark/tests/tiny/configs/`` and its cell's under ``benchmark/configs/``
(all read, none edited), the prompt / decode split and the tolerance of its
parity test, its engine's sizes and gauges, the scopes and kernel names its
programs carry, the limits of its cell's programs, the refusals it expects
and the counts of its published configuration.  ``tests/contract.py`` holds
the tests every served kind passes; they take a row.

The builders remember what they built for the life of the process: a tiny
model's parameters are made once (``init_params`` under ``jax.jit``), a
program is compiled once (``programs``), an engine is started once
(``engine``).  Under ``--dist loadfile`` a kind's file is one worker's, so a
kind's programs are compiled in that process only.
"""

import atexit
import copy
import dataclasses
import functools
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")

@dataclasses.dataclass(frozen=True)
class Kind:
    name: str                 # benchmark/models/<name>.py
    tiny: object              # a file of benchmark/tests/tiny/configs, or the
    #                           document itself where the kind has no file
    cell: str                 # benchmark/configs/<cell>.json
    # the parity test: prompts of ``lens`` drawn by ``draw(rng)`` admitted to
    # ``slots`` of ``n_slots`` x ``max_len`` in one ``bucket``, then ``steps``
    # decode steps, held to the reference's logits (called with ``ref_kw``)
    # within ``atol``
    parity: dict = None
    # the engine test: ``kw`` of LLMEngine, prompts of ``lens`` drawn from
    # ``seed``, ``max_tokens`` each, and the gauges the engine then reports
    engine: dict = None
    # shares of the routed experts: how many, of how many held, and where an
    # expert layer's small weights lie in ``params["blocks"]``
    shares: dict = None
    # scopes its serve programs carry (both / decode only / neither), and
    # the counters and gauges its engine reports
    scopes: dict = None
    # the Pallas kernels' names as a device trace shows them (``<name>
    # [pallas]``): (module of ray_tpu.ops, its constant, the name), and what
    # the benchmark's readers spell out for themselves: (file of
    # benchmark/layer_metrics, its constant, the value)
    kernels: tuple = ()
    readers: tuple = ()
    # its cell's programs on a described chip: (program, temporaries' limit
    # in GB, kernel calls), the stacks held in place and what may not leave
    # its stack
    cell_programs: tuple = ()
    stacks: tuple = ()
    held_in_place: tuple = ()
    # refusals: (id, LLMEngine kwargs, match), what the train step says,
    # (id, change to the tiny document, match), and (a base of
    # TransformerConfig kwargs or None for the tiny configuration's,
    # (id, kwargs, match))
    engine_refusals: tuple = ()
    engine_refusal_names: tuple = ()
    train_refusal: str = ""
    kind_refusals: tuple = ()
    config_refusals: tuple = (None, ())
    # counts of the cell's configuration: ``num_params``, the matrices a
    # layer, and the gauges of the cache the engine holds for it
    counts: dict = None


def _PAGED_SPEC_TP(paged, spec, tp):
    """What the engine says of each mode a kind's cache cannot serve."""
    return (("paged", dict(paged=True), paged),
            ("speculative", dict(spec_decode_enabled=True), spec),
            ("tp", dict(tp=2), tp))


def _whole_tiles(gauges):
    """A cell's cache as the chip stores it: every published state fills
    whole tiles of 128 lanes (the hybrid's heads of 192 packed two a tile
    since PR 57), so no lane of it holds nothing."""
    return dict(gauges, cache_state_hbm_bytes=gauges["cache_state_bytes"])


_PATTERN_BASE = dict(vocab_size=8, hidden_size=8, num_heads=1, num_kv_heads=1,
                     mlp_size=8, max_seq_len=8, num_layers=4,
                     linear_num_heads=1, linear_key_dim=4, linear_value_dim=4,
                     layer_pattern=("linear", "full"))
_EXPERTS = dict(moe_dropless=True, num_experts=4, experts_per_token=2,
                expert_mlp_size=8)
_MOE_COUNTERS = ("moe_assignments", "moe_experts_touched",
                 "moe_expert_layer_steps", "moe_assignments_prefill")

KINDS = {k.name: k for k in (
    Kind(
        name="mistral", tiny="tiny-serve.json",
        cell="mistral-7b-v0.3-serve-l14",
        parity=dict(draw=lambda rng: [rng.integers(1, 256, 35)], lens=[29],
                    slots=[1], n_slots=3, max_len=64, bucket=32, steps=6,
                    atol=2e-4, ref_kw={}),
        engine=dict(kw=dict(num_slots=3, max_len=64, buckets=(16, 32),
                            steps_per_dispatch=2),
                    seed=1, lens=(11,), max_tokens=6,
                    gauges={"linear_layers": 0, "full_layers": 2,
                            "cache_state_bytes": 0,
                            "cache_kv_bytes": 2 * 2 * 4 * 64 * 2 * 16 * 4,
                            "cache_latent_bytes": 0, "experts_held": 0,
                            "expert_layers": 0}),
        kind_refusals=(
            ("a-window", dict(sliding_window=128), "sliding_window"),
            ("another-activation", dict(hidden_act="gelu"), "hidden_act")),
        # (this kind counts its matrices alone, not the norms' scales)
        counts=dict(slots=33, num_params=3_321_888_768, matrices_alone=True)),
    Kind(
        name="olmo_hybrid",
        tiny=dict(
            model_type="olmo_hybrid", vocab_size=256, hidden_size=64,
            intermediate_size=192, num_hidden_layers=8, num_attention_heads=4,
            num_key_value_heads=4, hidden_act="silu",
            max_position_embeddings=256, attention_bias=False,
            rms_norm_eps=1e-6, tie_word_embeddings=False,
            layer_types=(["linear_attention"] * 3 + ["full_attention"]) * 2,
            linear_num_key_heads=4, linear_num_value_heads=4,
            linear_key_head_dim=8, linear_value_head_dim=16,
            linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
            rope_parameters={"rope_theta": None}),
        cell="olmo-hybrid-7b-serve-l12",
        # 5e-4 on logits of std 1 covers the CPU's default float32 matmuls
        # through 8 layers; a wrong pad position, tail or state lands at 1e-1
        parity=dict(draw=lambda rng: [rng.integers(1, 256, size=n + 12)
                                      for n in (37, 61)],
                    lens=[37, 61], slots=[2, 0], n_slots=4, max_len=128,
                    bucket=64, steps=12, atol=5e-4, ref_kw={}),
        # 4 cache rows (3 slots + scratch): K and V of 2 full layers, the
        # state and the convolution tail of 6 linear ones, float32 here
        engine=dict(kw=dict(num_slots=3, max_len=96, buckets=(32, 64),
                            prefill_batch=2),
                    seed=2, lens=(19, 40, 7), max_tokens=10, min_gap=1e-3,
                    gauges={"linear_layers": 6, "full_layers": 2,
                            "cache_kv_bytes": 2 * 2 * 4 * 96 * 64 * 4,
                            "cache_state_bytes":
                                6 * 4 * (4 * 8 * 16 + 3 * 128) * 4}),
        scopes=dict(
            both={"attn", "mlp", "norm", "lm_head", "kv_write", "gdn",
                  "gdn_conv", "state_write"},
            decode={"kv_read", "state_read"}, neither=set(),
            stats=("cache_kv_bytes", "cache_state_bytes", "linear_layers",
                   "full_layers")),
        train_refusal="layer_pattern",
        engine_refusals=_PAGED_SPEC_TP("paged", "spec_decode_enabled",
                                       "tp=2"),
        kernels=(("gated_delta", "KERNEL_CHUNK_FWD", "gdn_chunk_fwd"),
                 ("gated_delta", "KERNEL_RECURRENT_STEP",
                  "gdn_recurrent_step")),
        readers=(("_gdn.py", "CHUNK_FWD", "gdn_chunk_fwd"),
                 ("_gdn.py", "RECURRENT_STEP", "gdn_recurrent_step")),
        kind_refusals=(
            ("layers-not-whole-periods", dict(
                layer_types=["linear_attention"] * 5 + ["full_attention"] * 3
                + ["linear_attention"], num_hidden_layers=9),
             "whole periods"),
            ("another-activation", dict(hidden_act="gelu"), "hidden_act"),
            ("tied-head", dict(tie_word_embeddings=True),
             "tie_word_embeddings"),
            ("rotary", dict(rope_parameters={"rope_theta": 500000.0}),
             "rope_theta"),
            ("a-window", dict(layer_types=["linear_attention"] * 7
                              + ["sliding_attention"]), "sliding_attention")),
        config_refusals=(dict(_PATTERN_BASE, norm_on_output=True), (
            ("half-a-period", dict(num_layers=5), "whole periods"),
            ("unknown-kind", dict(layer_pattern=("linear", "sink")),
             "kinds"),
            ("decay-a-channel-without-its-rank",
             dict(linear_decay_per_channel=True), "linear_gate_rank"),
            ("no-mixer-sizes", dict(linear_key_dim=0), "linear_key_dim"),
            ("norms-without-a-pattern", dict(layer_pattern=()),
             "layer_pattern only"))),
        # the hybrid's largest bucket (PR 32): K/V of the three full layers,
        # 2.36 GB each, and the float32 state, carried through the loop over
        # the admit's rows (4.7 + 0.7 GB: a copy would not fit)
        stacks=("bf16[3,25,4096,3840]", "f32[9,25,15,96,384]"),
        counts=dict(slots=25, num_params=3_268_268_508,
                    per={"linear": 215_516_160, "full": 185_794_560},
                    gauges=lambda kind, doc: _whole_tiles({
                        "cache_kv_bytes": 25 * 4096 * 46_080,
                        "cache_state_bytes": 25 * (
                            kind.state_bytes_per_slot(doc)
                            + 9 * 3 * 11520 * 2),
                        "linear_layers": 9, "full_layers": 3,
                        # no latent rows and no experts here (PR 35's gauges)
                        "cache_latent_bytes": 0, "expert_layers": 0,
                        "experts_held": 0}))),
    Kind(
        name="xing4_0", tiny="tiny-latent.json",
        cell="xing4.0-29b-a4b-serve-l7",
        # float32 both sides; 2e-4 is rounding through 3 layers of 20
        # Sinkhorn rounds
        parity=dict(seed=1, draw=lambda rng: [rng.integers(1, 512, 35)],
                    lens=[29], slots=[1], n_slots=3, max_len=64, bucket=32,
                    steps=6, atol=2e-4, ref_kw=dict(follow=None),
                    choices=True),
        engine=dict(kw=dict(num_slots=3, max_len=64, buckets=(16, 32),
                            steps_per_dispatch=4),
                    seed=4, lens=(11,), max_tokens=6,
                    gauges={"cache_latent_bytes": 3 * 4 * 64 * (32 + 8) * 4,
                            "cache_kv_bytes": 0, "cache_state_bytes": 0,
                            "experts_held": 8, "expert_layers": 2}),
        scopes=dict(
            both={"attn", "mlp", "norm", "lm_head", "mla_down", "mla_up",
                  "latent_write", "moe_route", "moe_sort", "moe_experts",
                  "moe_shared", "moe_combine", "hc_coeff", "hc_mix"},
            decode={"latent_read"}, neither=set(),
            stats=("cache_latent_bytes", "experts_held", "expert_layers")
            + _MOE_COUNTERS),
        # (latent attention and dropless experts train since PR 39; the tiny
        # configuration's four residual streams do not)
        train_refusal="one residual stream",
        engine_refusals=_PAGED_SPEC_TP("paged=True", "spec_decode_enabled",
                                       "tp=2"),
        engine_refusal_names=("kv_lora_rank", ":"),
        kernels=(("moe", "KERNEL_MOE_GMM", "moe_gmm"),
                 ("decode_attention", "KERNEL_MLA_DECODE_ATTN",
                  "mla_decode_attn")),
        readers=(("moe_gmm_roofline.py", "MOE_GMM", "moe_gmm"),
                 ("mla_decode_attn_roofline.py", "MLA_DECODE_ATTN",
                  "mla_decode_attn"),
                 ("moe_mla_kernels_device_share.py", "KERNELS",
                  ("moe_gmm", "mla_decode_attn"))),
        kind_refusals=(
            ("softmax-scores", dict(scoring_func="softmax"), "sigmoid"),
            ("router-groups", dict(n_group=8), "group limit"),
            ("unnormalised-gates", dict(norm_topk_prob=False),
             "norm_topk_prob"),
            ("tied-head", dict(tie_word_embeddings=True), "own head"),
            ("linear-rope-scaling", dict(rope_scaling={"type": "linear"}),
             "YaRN"),
            ("a-clamp-off-centre", dict(mhc_h_res_clamp_min=-10),
             "symmetrically"),
            ("expert-parallel", dict(ep_size=4), "ep_size"),
            ("experts-every-other-layer", dict(moe_layer_freq=2),
             "moe_layer_freq"),
            ("another-activation", dict(hidden_act="gelu"), "SiLU")),
        config_refusals=(None, (
            ("a-pattern", dict(layer_pattern=("linear", "full"),
                               norm_on_output=True, linear_num_heads=2,
                               linear_key_dim=8, linear_value_dim=8),
             "layer_pattern"),
            ("a-negative-rank", dict(q_lora_rank=-1), "a q_lora_rank of 0"),
            ("grouped-heads", dict(num_kv_heads=2),
             "one key and one value head"),
            ("biases", dict(use_qkv_bias=True), "without biases"),
            ("yarn-without-latent-rows", dict(kv_lora_rank=0),
             "rope_yarn_factor"),
            ("experts-not-dropless", dict(moe_dropless=False),
             "belong to moe_dropless"),
            ("a-prefix-of-every-layer", dict(dense_prefix_layers=3),
             "leaves no expert layer"),
            ("more-a-token-than-experts", dict(experts_per_token=9),
             "experts_per_token"),
            ("one-stream", dict(hc_mult=1), "hc_mult"))),
        # readings 0.163, 0.500 and 1.712 GB of temporaries beside 13.26 GB
        # of arguments (sandbox compile, PR 35; 1.712 too with the four
        # streams carried in bf16: their mixing is float32 either way):
        # under 15.0 GiB, as ISSUE 35 asks of the largest program.  Since PR
        # 47 the 8,192 program walks a row in chunks of 2,048 and holds a
        # chunk's temporaries: reading 0.534 GB.  The kernels:
        # mla_decode_attn, flash_fwd or flash_fwd_rows, twice each (the dense
        # layer, the scan's body), and the two grouped matmuls
        cell_programs=(("decode", 0.3, 4), ("prefill-2048", 0.7, 4),
                       ("prefill-8192", 1.0, 4)),
        stacks=("bf16[7,33,8192,512]", "bf16[7,33,64,8192]"),
        # no layer's experts leave their stack: [64, 3584, 1024] is 0.47 GB
        held_in_place=(r"= bf16\[(1,)?64,(3584,1024|1024,3584)\]\S* "
                       r"(dynamic-slice|copy|fusion)\(",),
        counts=dict(slots=33, num_params=5_537_859_578)),
    Kind(
        name="solar_open2", tiny="tiny-solar.json",
        cell="solar-open2-250b-serve-l4-e40",
        # a padded prefill (29 of a bucket of 64: no multiple of the chunk)
        # then 11 decode steps through the cache
        parity=dict(draw=lambda rng: [rng.integers(1, 256, size=40)],
                    lens=[29], slots=[1], n_slots=2, max_len=128, bucket=64,
                    steps=11, atol=2e-4, ref_kw=dict(follow=None),
                    choices=True),
        engine=dict(kw=dict(num_slots=3, max_len=64, buckets=(16, 32),
                            steps_per_dispatch=2),
                    seed=1, lens=(11,), max_tokens=6,
                    gauges={"experts_held": 4, "expert_layers": 8,
                            "linear_layers": 6, "full_layers": 2,
                            "cache_state_bytes": 6 * 4 * (
                                4 * 16 * 16 * 4 + 3 * 3 * 4 * 16 * 4),
                            "cache_kv_bytes": 2 * 2 * 4 * 64 * 2 * 32 * 4,
                            "cache_latent_bytes": 0},
                    # an admit's assignments: the held quarter of 11 tokens
                    # x 4 x 8 layers
                    admitted=11 * 4 * 8 // 4),
        shares=dict(n=4, held=4, moe_at=("linear", (0, 1))),
        # the KDA mixer's pieces under ``kda`` / ``kda_conv`` / ``kda_gate``
        # (state reads and writes keep ``state_read`` / ``state_write``), the
        # gated full layer's gate under ``attn``, the experts' under
        # ``moe_*``; none of the scalar-decay mixer's ``gdn`` scopes
        scopes=dict(
            both={"attn", "norm", "lm_head", "kv_write", "kda", "kda_conv",
                  "kda_gate", "state_write", "moe_route", "moe_sort",
                  "moe_experts", "moe_shared", "moe_combine"},
            decode={"kv_read", "state_read"}, neither={"gdn", "gdn_conv"},
            # the gate's sigmoid sits under the full layer's ``attn``
            decode_text=r"attn/logistic",
            stats=("cache_kv_bytes", "cache_state_bytes", "linear_layers",
                   "full_layers", "experts_held", "expert_layers")
            + _MOE_COUNTERS),
        train_refusal="layer_pattern",
        engine_refusals=_PAGED_SPEC_TP("page arena", "rolled out",
                                       "sharding rule"),
        kernels=(("kda", "KERNEL_KDA_CHUNK_FWD", "kda_chunk_fwd"),
                 ("kda", "KERNEL_KDA_RECURRENT_STEP", "kda_recurrent_step")),
        readers=(("_kda.py", "CHUNK_FWD", "kda_chunk_fwd"),
                 ("_kda.py", "RECURRENT_STEP", "kda_recurrent_step"),
                 ("_kda.py", "MOE_GMM", "moe_gmm"),
                 ("kda_moe_kernels_device_share.py", "KERNELS",
                  ("kda_chunk_fwd", "kda_recurrent_step", "moe_gmm"))),
        kind_refusals=(
            ("rotary", dict(use_rope=True), "use_rope"),
            ("full-projections", dict(kda_use_full_proj=True),
             "kda_use_full_proj"),
            ("a-dense-layer", dict(first_k_dense_replace=1),
             "first_k_dense_replace"),
            ("unnormalised-gates", dict(norm_topk_prob=False),
             "norm_topk_prob"),
            ("router-groups", dict(n_group=2), "n_group"),
            ("tied-head", dict(tie_word_embeddings=True),
             "tie_word_embeddings"),
            ("a-layer-past-the-depth", dict(gqa_layers=[0, 9]),
             "of 8 layers"),
            ("a-share-past-the-end", dict(share=dict(expert_start=14)),
             "past the router")),
        config_refusals=(_PATTERN_BASE, (
            ("latent-attention", dict(kv_lora_rank=4, qk_nope_head_dim=4,
                                      qk_rope_head_dim=2, v_head_dim=4),
             "latent"),
            ("residual-streams", dict(hc_mult=2), "residual stream"),
            ("a-dense-prefix-past-the-first-period",
             dict(**_EXPERTS, dense_prefix_layers=3), "first period"),
            ("a-decay-a-channel-without-its-rank",
             dict(linear_decay_per_channel=True), "linear_gate_rank"),
            ("a-rank-without-a-decay-a-channel", dict(linear_gate_rank=4),
             "go together"),
            ("a-gate-without-a-pattern",
             dict(layer_pattern=(), attn_output_gate=True),
             "layer_pattern only"),
            ("a-rank-without-a-pattern",
             dict(layer_pattern=(), linear_gate_rank=4),
             "layer_pattern only"))),
        # under 15.0 GiB, as ISSUE 44 asks of the largest program (readings
        # in PERF.md section 4).  decode: decode_attn, three recurrent steps
        # and two grouped matmuls a layer; prefill: flash_fwd, three chunked
        # forwards and two grouped matmuls a layer
        # (a chunk's 1,024 tokens lay 8,192 assignments out for the 40 of
        # 320 experts held: the rows are walked, PR 59, and each expert
        # layer's walk in takes its buffer from ``moe_rows_blank``; a decode
        # step's layout is the plain gathers')
        cell_programs=(("decode", 0.3, 1 + 3 + 2 * 4),
                       ("prefill-1024", 0.6, 1 + 3 + 2 * 4 + 4)),
        stacks=("bf16[1,65,4096,1024]", "f32[3,65,64,128,128]"),
        # no layer's experts leave their stack ([40, 4096, 1280] is 0.42
        # GB), no layer's [slots, 64, 128, 128] slab is sliced out of the
        # state
        held_in_place=(r"= bf16\[(1,)?40,(4096,1280|1280,4096)\]\S* "
                       r"(dynamic-slice|copy|fusion)\(",
                       r"= f32\[(1,)?65,64,128,128\]\S* "
                       r"(dynamic-slice|copy)\("),
        counts=dict(slots=65, num_params=3_308_353_344,
                    per={"kda": 137_625_600, "gqa": 109_051_904,
                         "expert": 15_728_640, "shared": 15_728_640,
                         "router": 1_310_720},
                    gauges=lambda kind, doc: _whole_tiles({
                        "cache_kv_bytes": 65 * 4096 * 4096,
                        "cache_state_bytes": 65 * (
                            kind.state_bytes_per_slot(doc)
                            + 3 * 3 * 24576 * 2),
                        "linear_layers": 3, "full_layers": 1,
                        "cache_latent_bytes": 0, "expert_layers": 4,
                        "experts_held": 40}))),
    Kind(
        name="nemotron_h", tiny="tiny-nemotron.json",
        cell="nemotron-3-nano-30b-a3b-serve-l9-e64",
        # two rows of other lengths into slots 2 and 0, then decode steps
        # with an idle slot between
        parity=dict(seed=1,
                    draw=lambda rng: list(rng.integers(1, 256, (2, 40))),
                    lens=[29, 18], slots=[2, 0], n_slots=3, max_len=64,
                    bucket=32, steps=8, atol=2e-4, ref_kw={}),
        engine=dict(kw=dict(num_slots=4, max_len=64, buckets=(32, 64),
                            steps_per_dispatch=2),
                    seed=1, lens=(11,), max_tokens=6,
                    gauges={"experts_held": 8, "expert_layers": 4,
                            "linear_layers": 0, "ssm_layers": 4,
                            "full_layers": 1,
                            "cache_state_bytes": 4 * 5 * (
                                4 * 16 * 32 * 4
                                + 3 * (4 * 16 + 2 * 2 * 32) * 4),
                            "cache_kv_bytes": 2 * 1 * 5 * 64 * 2 * 32 * 4,
                            "cache_latent_bytes": 0},
                    # an admit's assignments: 11 tokens x 3 x 4 expert
                    # layers (of which the held half is computed)
                    admitted=11 * 3 * 4 // 2),
        shares=dict(n=2, held=8, moe_at=("mlp", (0, 2))),
        # the state-space mixer's pieces under ``ssm`` / ``ssm_conv`` (state
        # reads and writes keep ``state_read`` / ``state_write``), the expert
        # layer's under ``moe_*`` as under any layer
        scopes=dict(
            both={"attn", "norm", "lm_head", "kv_write", "ssm", "ssm_conv",
                  "state_write", "moe_route", "moe_sort", "moe_experts",
                  "moe_shared", "moe_combine"},
            decode={"kv_read", "state_read"}, neither=set(),
            stats=("cache_kv_bytes", "cache_state_bytes", "linear_layers",
                   "ssm_layers", "full_layers", "experts_held",
                   "expert_layers") + _MOE_COUNTERS),
        train_refusal="layer_pattern",
        engine_refusals=_PAGED_SPEC_TP("page arena", "rolled out",
                                       "sharding rule"),
        kernels=(("ssd", "KERNEL_SSD_CHUNK_FWD", "ssd_chunk_fwd"),
                 ("ssd", "KERNEL_SSD_RECURRENT_STEP", "ssd_recurrent_step")),
        readers=(("_ssd.py", "CHUNK_FWD", "ssd_chunk_fwd"),
                 ("_ssd.py", "RECURRENT_STEP", "ssd_recurrent_step"),
                 ("_ssd.py", "MOE_GMM", "moe_gmm"),
                 ("ssm_moe_kernels_device_share.py", "KERNELS",
                  ("ssd_chunk_fwd", "ssd_recurrent_step", "moe_gmm"))),
        kind_refusals=(
            ("a-dense-mlp-layer", dict(hybrid_override_pattern="MEMEM-EME"),
             "fifth kind"),
            ("a-pattern-of-another-depth",
             dict(hybrid_override_pattern="MEMEM*EM"), "num_hidden_layers"),
            ("gated-experts", dict(mlp_hidden_act="silu"), "mlp_hidden_act"),
            ("another-mixer-activation", dict(mamba_hidden_act="gelu"),
             "mamba_hidden_act"),
            ("no-conv-bias", dict(use_conv_bias=False), "use_conv_bias"),
            ("biases", dict(mlp_bias=True), "mlp_bias"),
            ("unnormalised-gates", dict(norm_topk_prob=False),
             "norm_topk_prob"),
            ("router-groups", dict(n_group=2), "n_group"),
            ("tied-head", dict(tie_word_embeddings=True),
             "tie_word_embeddings"),
            ("a-window", dict(sliding_window=128), "sliding_window"),
            ("a-shared-width-between",
             dict(moe_shared_expert_intermediate_size=40), "whole multiple"),
            ("a-share-past-the-end", dict(share=dict(expert_start=14)),
             "past the router")),
        config_refusals=(
            dict(_PATTERN_BASE, linear_num_heads=2, ssm_groups=1,
                 layer_pattern=("ssm", "full")), (
            ("layers-not-whole-periods",
             dict(layer_pattern=("ssm", "mlp", "full")), "whole periods"),
            ("a-kind-it-does-not-know", dict(layer_pattern=("ssm", "moe")),
             "kinds are"),
            ("two-recurrent-kinds", dict(layer_pattern=("ssm", "linear")),
             "one recurrent kind"),
            ("no-groups", dict(ssm_groups=0), "ssm_groups"),
            ("heads-not-whole-groups",
             dict(linear_num_heads=3, ssm_groups=2), "ssm_groups"),
            ("groups-without-ssm", dict(layer_pattern=("linear", "full")),
             "ssm_groups"),
            ("another-activation", dict(mlp_act="gelu"), "mlp_act"),
            ("relu2-without-a-pattern",
             dict(layer_pattern=(), ssm_groups=0, mlp_act="relu2"),
             "layer_pattern only"),
            ("ungated-experts-of-no-activation",
             dict(**_EXPERTS, use_swiglu=False), "SwiGLU"),
            ("a-dense-prefix-past-the-first-period",
             dict(**_EXPERTS, dense_prefix_layers=3), "first period"))),
        # under 15.0 GiB, as ISSUE 46 asks of the largest program (readings
        # 0.004 and 0.92 GB; PERF.md section 4).  decode: a recurrent step a
        # state-space layer, decode_attn, two grouped matmuls an expert
        # layer; prefill: a chunked forward a state-space layer, flash_fwd,
        # two grouped matmuls an expert layer
        cell_programs=(("decode", 0.1, 4 + 1 + 2 * 4),
                       ("prefill-8192", 1.2, 4 + 1 + 2 * 4)),
        stacks=("bf16[1,65,8192,256]", "f32[4,65,64,64,128]"),
        # no layer's experts leave their stack ([64, 1856, 2688] is 0.64
        # GB) and no stack is copied into another layout; no layer's [slots,
        # 64, 64, 128] slab is sliced out of the state
        held_in_place=(r"= bf16\[(4,|1,)?64,1856,2688\]\S* "
                       r"(dynamic-slice|copy|fusion)\(",
                       r"= f32\[(1,)?65,64,64,128\]\S* "
                       r"(dynamic-slice|copy)\("),
        counts=dict(slots=65, num_params=3_166_244_352,
                    per={"mamba": 38_707_200, "attention": 23_396_352,
                         "expert": 9_977_856, "shared": 19_955_712,
                         "router": 344_064},
                    gauges=lambda kind, doc: _whole_tiles({
                        "cache_kv_bytes": 65 * 8192 * 1024,
                        "cache_state_bytes": 65 * (
                            kind.state_bytes_per_slot(doc)
                            + 4 * 3 * 6144 * 2),
                        "linear_layers": 0, "ssm_layers": 4,
                        "full_layers": 1, "cache_latent_bytes": 0,
                        "expert_layers": 4, "experts_held": 64}))),
    Kind(
        name="exaone_moe", tiny="tiny-exaone.json",
        cell="k-exaone-236b-a23b-serve-l8-e8",
        # window 8 on a ring of 16: prompts on both sides of the window and
        # of the ring, then decode past two wraps of the ring
        parity=dict(seed=2,
                    draw=lambda rng: [rng.integers(1, 256, n + 40)
                                      for n in (5, 8, 9, 23)],
                    lens=[5, 8, 9, 23], slots=[3, 0, 4, 1], n_slots=5,
                    max_len=128, bucket=32, steps=36, atol=2e-4, ref_kw={}),
        engine=dict(kw=dict(num_slots=3, max_len=64, buckets=(16, 32),
                            steps_per_dispatch=2),
                    seed=1, lens=(11,), max_tokens=6,
                    # 4 cache rows (3 slots + scratch), float32: K and V of 2
                    # full layers and of the block's one, rings of 16 rows
                    # (window 8, a step of one token) for 6 window layers
                    gauges={"experts_held": 8, "expert_layers": 7,
                            "linear_layers": 0, "window_layers": 6,
                            "full_layers": 2, "cache_state_bytes": 0,
                            "cache_kv_bytes": 2 * 3 * 4 * 64 * 2 * 16 * 4,
                            "cache_ring_bytes": 2 * 6 * 4 * 16 * 2 * 16 * 4,
                            "cache_latent_bytes": 0},
                    # an admit's assignments: 11 tokens x 3 x 7 expert
                    # layers (of which the held half is computed)
                    admitted=11 * 3 * 7 // 2),
        shares=dict(n=2, held=8, moe_at=(None, 2), key="num_experts"),
        # the window layers' band under ``window_attn``, their rings under
        # ``ring_write`` / ``ring_read``; the dense layer's MLP under
        # ``mlp``, the experts' under ``moe_*``; the block's projection
        # (``mtp_proj``) is the admit's and the speculative program's
        scopes=dict(
            both={"attn", "norm", "lm_head", "mlp", "kv_write", "ring_write",
                  "moe_route", "moe_sort", "moe_experts", "moe_shared",
                  "moe_combine"},
            decode={"kv_read", "ring_read"}, neither=set(),
            stats=("cache_kv_bytes", "cache_ring_bytes", "window_layers",
                   "full_layers", "experts_held", "expert_layers")
            + _MOE_COUNTERS),
        train_refusal="layer_pattern",
        engine_refusals=(("paged", dict(paged=True), "no ring"),
                         ("tp", dict(tp=2), "sharding rule")),
        kernels=(("decode_attention", "KERNEL_WINDOW_DECODE_ATTN",
                  "window_decode_attn"),
                 ("flash_attention", "KERNEL_FLASH_WINDOW",
                  "flash_window_prefill"),
                 ("decode_attention", "KERNEL_DECODE_ATTN", "decode_attn")),
        readers=(("window_decode_attn_roofline.py", "WINDOW_DECODE_ATTN",
                  "window_decode_attn"),
                 ("flash_window_prefill_roofline.py", "FLASH_WINDOW",
                  "flash_window_prefill"),
                 ("swa_moe_kernels_device_share.py", "KERNELS",
                  ("window_decode_attn", "flash_window_prefill",
                   "decode_attn", "moe_gmm")),
                 ("spec_step_device_ms.batch.py", "SPEC_PROGRAM",
                  "engine_spec_decode")),
        kind_refusals=(
            ("softmax-scores", dict(scoring_func="softmax"), "sigmoid"),
            ("router-groups", dict(n_group=2), "n_group"),
            ("unnormalised-gates", dict(norm_topk_prob=False),
             "norm_topk_prob"),
            ("tied-head", dict(tie_word_embeddings=True),
             "tie_word_embeddings"),
            ("another-activation", dict(hidden_act="gelu"), "hidden_act"),
            ("scaled-rotary", dict(rope_parameters={
                "rope_theta": 1e6, "rope_type": "yarn"}), "rope_type"),
            ("two-blocks", dict(num_nextn_predict_layers=2,
                                mtp_layer_types=["full_attention"] * 2),
             "one multi-token-prediction block"),
            ("a-sliding-block", dict(mtp_layer_types=["sliding_attention"]),
             "mtp_layer_types"),
            ("a-sparse-layer-first", dict(
                mlp_layer_types=["sparse"] + ["dense"] + ["sparse"] * 6),
             "mlp_layer_types"),
            ("windows-that-disagree", dict(sliding_windows=[8] * 8),
             "sliding_windows"),
            ("a-kind-it-does-not-know", dict(
                layer_types=["chunked_attention"] * 8), "layer_types"),
            ("a-share-past-the-end", dict(share=dict(expert_start=14)),
             "past the router")),
        config_refusals=(None, (
            ("a-window-without-its-kind", dict(layer_pattern=("full",)),
             "sliding_window"),
            ("a-kind-without-its-window", dict(sliding_window=0),
             "sliding_window"),
            ("both-norms", dict(qk_norm=True), "not both"),
            ("rotary-by-kind-without-rotary", dict(use_rope=False),
             "rope_window_only"),
            ("two-blocks", dict(mtp_layers=2), "one block"),
            ("a-block-over-a-recurrent-state",
             dict(layer_pattern=("linear", "window", "window", "full"),
                  linear_num_heads=2, linear_key_dim=8, linear_value_dim=8),
             "rolled out"),
            ("a-prefix-past-the-first-period", dict(dense_prefix_layers=5),
             "first period"))),
        stacks=("bf16[2,49,6144,1024]", "bf16[6,49,256,1024]",
                "bf16[1,49,6144,1024]"),
        # no layer's experts leave their stack: [8, 6144, 2048] is 0.2 GB
        held_in_place=(r"= bf16\[(1,)?8,(6144,2048|2048,6144)\]\S* "
                       r"(dynamic-slice|copy)\(",),
        counts=dict(slots=49, num_params=4_394_720_512,
                    per={"attention": 113_246_208, "dense": 339_738_624,
                         "expert": 37_748_736, "shared": 37_748_736,
                         "router": 786_432, "mtp_proj": 75_497_472},
                    gauges=lambda kind, doc: _whole_tiles({
                        "cache_kv_bytes": 49 * 6144 * kind.kv_bytes_per_token(
                            doc),
                        "cache_ring_bytes": 49 * kind.ring_bytes_per_slot(
                            doc, 256),
                        "cache_state_bytes": 0,
                        "linear_layers": 0, "window_layers": 6,
                        "full_layers": 2, "cache_latent_bytes": 0,
                        "expert_layers": 7, "experts_held": 8}))),
    Kind(
        name="granitemoehybrid", tiny="tiny-granite.json",
        cell="granite-4.0-h-micro-serve-l40",
        # two rows of other lengths into slots 2 and 0, then decode steps
        # with an idle slot between; float32 both sides, the four
        # multipliers no powers of two
        parity=dict(seed=1,
                    draw=lambda rng: list(rng.integers(1, 256, (2, 40))),
                    lens=[29, 18], slots=[2, 0], n_slots=3, max_len=64,
                    bucket=32, steps=8, atol=2e-4, ref_kw={}),
        # 5 cache rows (4 slots + scratch), float32: the state and the
        # convolution tail of 6 state-space layers (one group of 8 heads),
        # K and V of 2 attention layers of 2 heads of 16
        engine=dict(kw=dict(num_slots=4, max_len=64, buckets=(32, 64),
                            steps_per_dispatch=2),
                    seed=1, lens=(11,), max_tokens=6,
                    gauges={"experts_held": 0, "expert_layers": 0,
                            "linear_layers": 0, "ssm_layers": 6,
                            "full_layers": 2,
                            "cache_state_bytes": 6 * 5 * (
                                8 * 16 * 32 * 4
                                + 3 * (8 * 16 + 2 * 1 * 32) * 4),
                            "cache_kv_bytes": 2 * 2 * 5 * 64 * 2 * 16 * 4,
                            "cache_latent_bytes": 0}),
        # the state-space mixer's pieces under ``ssm`` / ``ssm_conv``, the
        # dense MLP beneath every mixer under ``mlp``; no expert's scope
        scopes=dict(
            both={"attn", "norm", "lm_head", "mlp", "kv_write", "ssm",
                  "ssm_conv", "state_write"},
            decode={"kv_read", "state_read"},
            neither={"moe_route", "moe_experts"},
            stats=("cache_kv_bytes", "cache_state_bytes", "linear_layers",
                   "ssm_layers", "full_layers")),
        train_refusal="layer_pattern",
        engine_refusals=_PAGED_SPEC_TP("page arena", "rolled out",
                                       "sharding rule"),
        kernels=(("ssd", "KERNEL_SSD_CHUNK_FWD", "ssd_chunk_fwd"),
                 ("ssd", "KERNEL_SSD_RECURRENT_STEP", "ssd_recurrent_step"),
                 ("decode_attention", "KERNEL_DECODE_ATTN", "decode_attn"),
                 ("flash_attention", "KERNEL_FLASH_FWD", "flash_fwd")),
        readers=(("_ssd.py", "CHUNK_FWD", "ssd_chunk_fwd"),
                 ("_ssd.py", "RECURRENT_STEP", "ssd_recurrent_step"),
                 ("decode_attn_roofline.py", "DECODE_ATTN", "decode_attn"),
                 ("ssm_dense_kernels_device_share.py", "KERNELS",
                  ("ssd_chunk_fwd", "ssd_recurrent_step", "decode_attn",
                   "flash_fwd"))),
        kind_refusals=(
            ("routed-experts", dict(num_local_experts=4,
                                    num_experts_per_tok=2),
             "no routed experts"),
            ("another-activation", dict(hidden_act="gelu"), "hidden_act"),
            ("no-conv-bias", dict(mamba_conv_bias=False), "mamba_conv_bias"),
            ("biases", dict(attention_bias=True), "attention_bias"),
            ("an-untied-head", dict(tie_word_embeddings=False),
             "tie_word_embeddings"),
            ("rotary", dict(position_embedding_type="rope"),
             "position_embedding_type"),
            ("scaled-rotary", dict(rope_scaling={"type": "linear"}),
             "rope_scaling"),
            ("a-kind-it-does-not-know", dict(
                layer_types=["mamba", "sliding"] * 4), "layer_types"),
            ("layers-of-another-depth", dict(num_hidden_layers=9),
             "num_hidden_layers"),
            ("heads-not-whole-groups", dict(mamba_n_groups=3),
             "whole groups"),
            ("an-inner-width-that-is-not-the-heads", dict(mamba_expand=3),
             "mamba_expand"),
            ("a-multiplier-of-zero", dict(residual_multiplier=0.0),
             "positive")),
        config_refusals=(None, (
            ("a-negative-multiplier", dict(logits_scaling=-1.0),
             "or more"),
            ("a-residual-multiplier-on-a-normed-output",
             dict(norm_on_output=True), "norm_on_output"),
            ("multipliers-without-a-pattern", dict(layer_pattern=()),
             "layer_pattern only"),
            ("two-recurrent-kinds",
             dict(layer_pattern=("ssm", "linear", "full", "ssm")),
             "one recurrent kind"))),
        # under 15.0 GiB (readings 13.83 and 14.29 GiB in place).  The
        # temporaries' readings 1.308 and 1.800 GB, of which 1.255 GB is
        # the chip's copy of the ``W_in`` stack [4, 9, 2048, 8512], which
        # it lays out with the 2,048 rows minor (8,512 is no multiple of
        # 128 lanes).  decode: a recurrent step a state-space layer of the
        # period and decode_attn; prefill: a chunked forward each and
        # flash_fwd
        cell_programs=(("decode", 1.5, 9 + 1), ("prefill-4096", 2.0, 9 + 1)),
        stacks=("bf16[4,65,4096,512]", "f32[36,65,64,64,128]"),
        # no layer's [slots, 64, 64, 128] slab is sliced out of the state
        held_in_place=(r"= f32\[(1,)?65,64,64,128\]\S* "
                       r"(dynamic-slice|copy)\(",),
        counts=dict(slots=65, num_params=3_191_396_096,
                    per={"mamba": 25_821_184, "attention": 10_485_760,
                         "mlp": 50_331_648},
                    gauges=lambda kind, doc: _whole_tiles({
                        "cache_kv_bytes": 65 * 4096 * 8192,
                        "cache_state_bytes": 65 * (
                            kind.state_bytes_per_slot(doc)
                            + 36 * 3 * 4352 * 2),
                        "linear_layers": 0, "ssm_layers": 36,
                        "full_layers": 4, "cache_latent_bytes": 0,
                        "expert_layers": 0, "experts_held": 0}))),
    # SambaY (PR 60): a stack of three segments, the float32 selective-scan
    # state [layers, slots, 16, 40, 128], rows of ONE full layer that seven
    # cross layers read, rings for eight window layers
    Kind(
        name="phi4flash", tiny="tiny-phi4flash.json",
        cell="phi-4-mini-flash-reasoning-serve-l32",
        # two rows of other lengths into slots 2 and 0, then decode steps
        # with an idle slot between, past the window of 16: float32 both
        # sides
        parity=dict(seed=1,
                    draw=lambda rng: list(rng.integers(1, 256, (2, 48))),
                    lens=[29, 18], slots=[2, 0], n_slots=3, max_len=64,
                    bucket=32, steps=10, atol=2e-4, ref_kw={}),
        # 5 cache rows (4 slots + scratch), float32: the state [16, 1, 128]
        # and the tail [3, 128] of 4 Mamba layers, K and V of ONE
        # full layer of 4 heads of 8, rings of 16 rows for 3 window layers
        engine=dict(kw=dict(num_slots=4, max_len=64, buckets=(32, 64),
                            steps_per_dispatch=2),
                    seed=1, lens=(11,), max_tokens=6,
                    gauges={"experts_held": 0, "expert_layers": 0,
                            "linear_layers": 0, "ssm1_layers": 4,
                            "cross_layers": 2, "window_layers": 3,
                            "full_layers": 1,
                            "cache_state_bytes": 4 * 5 * (
                                16 * 128 * 4 + 3 * 128 * 4),
                            "cache_state_hbm_bytes": 4 * 5 * (
                                16 * 128 * 4 + 3 * 128 * 4),
                            "cache_kv_bytes": 2 * 1 * 5 * 64 * 4 * 8 * 4,
                            "cache_shared_kv_bytes":
                                2 * 1 * 5 * 64 * 4 * 8 * 4,
                            "cache_ring_bytes": 2 * 3 * 5 * 16 * 4 * 8 * 4,
                            "cache_latent_bytes": 0}),
        scopes=dict(
            both={"attn", "norm", "lm_head", "mlp", "kv_write",
                  "selective_scan", "selective_scan_conv", "gmu",
                  "diff_attn", "cross", "state_write", "ring_write"},
            decode={"kv_read", "ring_read", "state_read"},
            neither={"moe_route", "moe_experts", "ssm", "gdn"},
            stats=("cache_kv_bytes", "cache_state_bytes",
                   "cache_shared_kv_bytes", "cache_ring_bytes",
                   "ssm1_layers", "cross_layers", "window_layers",
                   "full_layers", "prefill_self_tokens",
                   "prefill_cross_tokens", "shared_kv_positions_read")),
        train_refusal="layer_pattern",
        engine_refusals=_PAGED_SPEC_TP("page arena", "rolled out",
                                       "sharding rule"),
        engine_refusal_names=("ssm1", "cross"),
        kernels=(("selective_scan", "KERNEL_CHUNK_FWD",
                  "selective_scan_chunk_fwd"),
                 ("selective_scan", "KERNEL_STEP", "selective_scan_step"),
                 ("decode_attention", "KERNEL_DECODE_ATTN", "decode_attn"),
                 ("decode_attention", "KERNEL_WINDOW_DECODE_ATTN",
                  "window_decode_attn"),
                 ("flash_attention", "KERNEL_FLASH_WINDOW",
                  "flash_window_prefill"),
                 ("flash_attention", "KERNEL_FLASH_FWD", "flash_fwd")),
        readers=(("_sambay.py", "CHUNK_FWD", "selective_scan_chunk_fwd"),
                 ("_sambay.py", "STEP", "selective_scan_step"),
                 ("_sambay.py", "WINDOW_DECODE_ATTN", "window_decode_attn"),
                 ("sambay_kernels_device_share.py", "KERNELS",
                  ("selective_scan_chunk_fwd", "selective_scan_step",
                   "decode_attn", "window_decode_attn",
                   "flash_window_prefill", "flash_fwd"))),
        kind_refusals=(
            ("another-activation", dict(hidden_act="gelu"), "hidden_act"),
            ("a-mamba-layer-every-third", dict(mb_per_layer=3),
             "mb_per_layer"),
            ("biases", dict(mlp_bias=True), "mlp_bias"),
            ("an-untied-head", dict(tie_word_embeddings=False),
             "tie_word_embeddings"),
            ("no-window", dict(sliding_window=0), "sliding_window"),
            ("layers-not-whole-pairs", dict(num_hidden_layers=10),
             "num_hidden_layers"),
            ("heads-not-in-pairs", dict(num_key_value_heads=1),
             "whole pairs"),
            ("channels-not-whole-tiles", dict(hidden_size=72,
                                              num_attention_heads=4,
                                              num_key_value_heads=2),
             "128 lanes"),
            ("dropout", dict(resid_pdrop=0.1), "resid_pdrop")),
        config_refusals=(None, (
            ("segments-beside-a-pattern",
             dict(layer_pattern=("ssm1", "full")), "in place of"),
            # (the tiny configuration's fields carry its stack flattened too)
            ("a-cross-layer-above-the-full-layer", dict(layer_segments=(
                (("ssm1", "window"), 1), (("gmu", "cross"), 5)),
                layer_pattern=()), "reads what"),
            ("two-full-layers", dict(layer_segments=(
                (("ssm1", "full"), 2), (("gmu", "cross"), 4)),
                layer_pattern=(), sliding_window=0), "ONE 'full' layer"),
            ("a-scan-without-its-sizes", dict(ssm1_dt_rank=0),
             "ssm1_inner"),
            ("channels-that-are-no-whole-tiles", dict(ssm1_inner=96),
             "128 lanes"),
            ("two-recurrent-kinds", dict(layer_segments=(
                (("ssm1", "window"), 3), (("ssm1", "full"), 1),
                (("ssm", "full"), 2)), layer_pattern=(), ssm_groups=1,
                linear_num_heads=2, linear_key_dim=4, linear_value_dim=4),
             "one recurrent kind"),
            ("differential-attention-with-odd-heads",
             dict(num_kv_heads=1), "pairs"),
            ("experts-under-segments", dict(
                moe_dropless=True, num_experts=4, experts_per_token=2,
                expert_mlp_size=8), "layer_segments"),
            ("segments-kinds-without-segments", dict(
                layer_segments=(), layer_pattern=("ssm1", "full", "gmu",
                                                  "cross") * 3),
             "layer_segments"))),
        # decode 0.238 GB of temporaries, the 4,096 admit 0.632 GB (sandbox
        # compile, PR 60); decode: a selective-scan step a segment with a
        # Mamba layer, the ring's kernel, decode_attn for the full layer and
        # for a cross layer; the 4,096 admit: a chunked scan a segment, the
        # banded flash forward and the full layer's
        cell_programs=(("decode", 0.3, 5), ("prefill-512", 0.25, 2),
                       ("prefill-1024", 0.35, 4), ("prefill-2048", 0.5, 4),
                       ("prefill-4096", 0.75, 4)),
        stacks=("bf16[1,65,8192,1280]", "bf16[8,65,512,1280]",
                "f32[9,65,16,40,128]"),
        # no layer's [slots, 16, 40, 128] slab is sliced out of the state
        held_in_place=(r"= f32\[(1,)?65,16,40,128\]\S* "
                       r"(dynamic-slice|copy)\(",),
        counts=dict(slots=65, num_params=3_852_457_984,
                    per={"mamba": 41_123_840, "attention": 19_660_800,
                         "gmu": 26_214_400, "cross": 13_107_200,
                         "mlp": 78_643_200},
                    gauges=lambda kind, doc: _whole_tiles({
                        "cache_kv_bytes": 65 * 8192 * 5120,
                        "cache_shared_kv_bytes": 65 * 8192 * 5120,
                        "cache_ring_bytes": 65 * 512 * 5120 * 8,
                        "cache_state_bytes": 65 * (
                            kind.state_bytes_per_slot(doc)
                            + kind.conv_bytes_per_slot(doc)),
                        "linear_layers": 0, "ssm1_layers": 9,
                        "cross_layers": 7, "window_layers": 8,
                        "full_layers": 1, "cache_latent_bytes": 0,
                        "expert_layers": 0, "experts_held": 0}))),
    # trained, not served: the share train cell's kind.  The backward's two
    # grouped kernels beside the forward's; the reader's list still holds
    # ``flash_dq``, a kernel that is gone since PR 48 (the backward is
    # ``flash_dkv`` alone): it sums what it finds, and the entry is a
    # ``benchmark`` PR's to drop
    Kind(name="kimi_vl", tiny="tiny-kimi.json",
         cell="kimi-vl-a3b-train-l6-e8",
         kernels=(("moe", "KERNEL_MOE_GMM", "moe_gmm"),
                  ("moe", "KERNEL_MOE_GMM_DX", "moe_gmm_dx"),
                  ("moe", "KERNEL_MOE_GMM_DW", "moe_gmm_dw")),
         readers=(("_moe_train.py", "MOE_GMM_TRAIN",
                   ("moe_gmm", "moe_gmm_dx", "moe_gmm_dw")),
                  ("_moe_train.py", "FLASH_TRAIN",
                   ("flash_fwd", "flash_dq", "flash_dkv")))),
    # trained over four chips that share each layer (PR 58): the band's
    # forward and its backward beside the full layer's two and the grouped
    # products' three, and the kind of collective the exchange is told by
    Kind(name="mellum", tiny="tiny-mellum.json",
         cell="mellum2-12b-a2.5b-train-l4",
         kernels=(("moe", "KERNEL_MOE_GMM", "moe_gmm"),
                  ("moe", "KERNEL_MOE_GMM_DX", "moe_gmm_dx"),
                  ("moe", "KERNEL_MOE_GMM_DW", "moe_gmm_dw"),
                  ("flash_attention", "KERNEL_FLASH_FWD", "flash_fwd"),
                  ("flash_attention", "KERNEL_FLASH_WINDOW",
                   "flash_window_prefill"),
                  ("flash_attention", "KERNEL_FLASH_WINDOW_BWD",
                   "flash_window_bwd")),
         readers=(("flash_full_train_roofline.py", "FLASH_FULL_TRAIN",
                   ("flash_fwd", "flash_dkv")),
                  ("flash_window_train_roofline.py", "FLASH_WINDOW_TRAIN",
                   ("flash_window_prefill", "flash_window_bwd")),
                  ("swa_attn_train_kernels_device_share.py", "SWA_ATTN_TRAIN",
                   ("flash_fwd", "flash_dkv", "flash_window_prefill",
                    "flash_window_bwd")),
                  ("moe_ep_exchange_device_share.py", "EXCHANGE",
                   r"^collective-permute(-start|-done)?$"))),
)}


# --------------------------------------------------------------- builders

@functools.lru_cache(maxsize=None)
def load(name):
    """The kind's module (``benchmark/models/<name>.py``)."""
    from benchmark.lib.manifest import load_model
    return load_model(os.path.join(BENCH, "models", name + ".py"))


def _read(path):
    with open(path) as f:
        return json.load(f)


def doc(name) -> dict:
    """The kind's tiny configuration (a fresh copy: tests edit theirs)."""
    tiny = KINDS[name].tiny
    if isinstance(tiny, dict):
        return copy.deepcopy(tiny)
    return _read(os.path.join(BENCH, "tests", "tiny", "configs", tiny))


def cell_doc(name, cell=None) -> dict:
    """The configuration file of the kind's cell (or of ``cell``, another
    cell of its kind)."""
    return _read(os.path.join(BENCH, "configs",
                              (cell or KINDS[name].cell) + ".json"))


def cell_cfg(name, cell=None, **changes):
    """The program's configuration of the kind's cell, at published sizes."""
    cfg = load(name).program_config(cell_doc(name, cell))
    return dataclasses.replace(cfg, **changes) if changes else cfg


#: the kinds whose cells run ``ops/moe.py``'s dropless experts
EXPERT_KINDS = ("xing4_0", "exaone_moe", "solar_open2", "nemotron_h",
                "kimi_vl", "mellum")


def expert_shapes(name):
    """Of the kind's cell at published sizes: (hidden width, expert width,
    experts held, whether an expert is of two matrices, its up projection
    stored transposed under a squared ReLU)."""
    cfg = cell_cfg(name)
    return (cfg.hidden_size, cfg.expert_mlp_size, cfg.experts_held,
            cfg.mlp_act == "relu2")


@functools.lru_cache(maxsize=None)
def init(init_params, cfg, dtype=jnp.float32, seed=0):
    """``init_params(PRNGKey(seed), cfg, dtype)`` under ``jax.jit``, once a
    process for each model."""
    return jax.jit(lambda key: init_params(key, cfg, dtype))(
        jax.random.PRNGKey(seed))


def tiny(name, dtype=jnp.float32, seed=3):
    """(cfg, params) of the kind's tiny configuration, seeded."""
    kind = load(name)
    cfg = kind.program_config(doc(name))
    return cfg, init(kind.init_params, cfg, dtype, seed)


@functools.lru_cache(maxsize=None)
def programs(cfg, dtype=jnp.float32, **prefill_kw):
    """The jitted ``decode.prefill`` (params, cache, tokens, lengths, slots)
    and ``decode.decode_step`` (params, cache, tokens, active) of a
    configuration: two tests that need the same program share one compile."""
    from ray_tpu.models import decode
    return SimpleNamespace(
        prefill=jax.jit(lambda p, c, t, n, s: decode.prefill(
            p, c, t, n, s, cfg, dtype, **prefill_kw)),
        step=jax.jit(lambda p, c, t, a: decode.decode_step(
            p, c, t, a, cfg, dtype)))


def reference(name, params, toks, start, **kw):
    """The kind's plain reference under ``jax.jit``: logits at positions
    ``start ..`` of ``toks``, on the tiny configuration."""
    kind, d, toks = load(name), doc(name), jnp.asarray(toks, jnp.int32)
    at = jnp.arange(start, len(toks))
    return np.asarray(jax.jit(lambda p, t: kind.logits(p, t, d, at, **kw))(
        params, toks))


# a kind's file says which row is its own (``ROW``); its tests then ask for
# the kind's module, its tiny configuration and (cfg, params) by these names

@pytest.fixture(scope="module")
def kind(request):
    return load(request.module.ROW.name)


@pytest.fixture(scope="module")
def tiny_doc(request):
    return doc(request.module.ROW.name)


@pytest.fixture(scope="module", name="tiny")
def tiny_model(request):
    return tiny(request.module.ROW.name)


_engines = {}


def engine(cfg, params=None, **kw):
    """An ``LLMEngine`` started once a process for these arguments, for
    tests that submit to it and read it; it is shut down at exit."""
    from ray_tpu.serve.llm import LLMEngine
    key = (cfg, id(params), tuple(sorted(kw.items())))
    if key not in _engines:
        _engines[key] = (LLMEngine(cfg, params=params, **kw), params)
    return _engines[key][0]


@atexit.register
def _shutdown_engines():
    while _engines:
        _engines.popitem()[1][0].shutdown()


def padded(rows, bucket):
    out = np.zeros((len(rows), bucket), np.int32)
    for i, r in enumerate(rows):
        out[i, :len(r)] = r
    return out


def _through_the_cache(cfg, params, toks, lens, slots, n_slots, max_len,
                       bucket, steps, choices=False):
    """Logits of a prefill of each row's first ``lens[i]`` tokens (right-
    padded in one bucket, into ``slots``) and of ``steps`` decode steps that
    feed each row its next token, the other slots idle, in float32: for each
    row [1 + steps, V], and the cache they leave."""
    from ray_tpu.models import decode
    run = programs(cfg)
    cache = decode.init_kv_cache(cfg, n_slots, max_len, jnp.float32,
                                 expert_choices=choices)
    cache, lg = run.prefill(
        params, cache, padded([t[:n] for t, n in zip(toks, lens)], bucket),
        np.array(lens, np.int32), np.array(slots, np.int32))
    got = [[np.asarray(lg[i])] for i in range(len(toks))]
    active = np.isin(np.arange(n_slots), slots)
    for s in range(steps):
        fed = np.zeros(n_slots, np.int32)
        for t, n, slot in zip(toks, lens, slots):
            fed[slot] = t[n + s]
        cache, lg = run.step(params, cache, fed, active)
        for i, slot in enumerate(slots):
            got[i].append(np.asarray(lg[slot]))
    return [np.stack(g) for g in got], cache


@functools.lru_cache(maxsize=None)
def parity_run(name):
    """The kind's parity run, made once a process: (tokens a row, logits a
    row, the cache it left)."""
    p = KINDS[name].parity
    toks = [np.asarray(t, np.int32)
            for t in p["draw"](np.random.default_rng(p.get("seed", 0)))]
    got, cache = _through_the_cache(
        *tiny(name), toks, p["lens"], p["slots"], p["n_slots"], p["max_len"],
        p["bucket"], p["steps"], p.get("choices", False))
    return toks, got, cache


def _scans(jaxpr):
    """Every ``scan`` of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)
