"""The block kind ``xing4_0`` as files (``models/xing4_0.py``, the
configuration ``xing4.0-29b-a4b-serve-l7``, its cell, traffic and readers):
the lookup by ``model_type``, the published widths against the catalog's,
the refusal to load over a program without latent attention, the counts and
the four readers on a made-up context.  A file of its own: a ``model_config``
PR adds files to the benchmark and edits none (ISSUE 35 asked for cases of
``test_models.py`` and ``test_manifest.py``, which stand as they were).  The
comparison of the program with the kind's reference is
``tests/test_latent.py`` (tier-1)."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib.manifest import MODEL_API, Cell, load_model
from benchmark.tests.test_runners import REPO, run_cell

BENCH = os.path.join(REPO, "benchmark")
KIND = os.path.join(BENCH, "models", "xing4_0.py")
CELL = "serve-mla-moe-longctx-closed"
#: the published file's numbers (the model-configs catalog's row)
PUBLISHED = dict(
    first_k_dense_replace=2, hidden_size=3584, intermediate_size=9216,
    kv_lora_rank=512, max_position_embeddings=262144,
    moe_intermediate_size=1024, n_group=1, n_routed_experts=64,
    n_shared_experts=1, num_attention_heads=32, num_experts_per_tok=4,
    num_hidden_layers=40, num_key_value_heads=32, num_nextn_predict_layers=1,
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30, q_lora_rank=768, qk_nope_head_dim=128,
    qk_rope_head_dim=64, rms_norm_eps=1e-6, rope_theta=10000,
    routed_scaling_factor=2, topk_group=1, v_head_dim=128,
    vocab_size=131072, ep_size=1, moe_layer_freq=1)


@pytest.fixture(scope="module")
def cell():
    return Cell(os.path.join(REPO, "BENCHMARK.json"), CELL)


def test_the_cell_resolves_to_the_kinds_files(cell):
    assert cell.model_path == KIND and cell.chips == 1
    assert all(callable(getattr(cell.model, f)) for f in MODEL_API)
    for m in cell.metrics("per_layer"):
        assert callable(cell.reader(m["name"]))
    assert {m["name"] for m in cell.metrics("per_layer")} >= {
        "moe_gmm_roofline", "mla_decode_attn_roofline",
        "moe_mla_kernels_device_share", "moe_experts_touched_share",
        "decode_step_batch_roofline", "device_idle_share.serve"}
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "latency_per_token_p95_ms", "serve_out_tokens_per_s", "setup_s"}
    t, dep = cell.traffic, cell.config["serve"]
    assert (t["loop"], t["clients"], t["ingress"], t["order"],
            t["shape_seed"]) == ("closed", 32, "handle_stream", "fixed", 35)
    assert t["clients"] == dep["num_slots"]
    assert t["prompt"]["hi"] + t["output"]["hi"] <= dep["max_len"] == 8192
    assert dep["buckets"] == [512, 1024, 2048, 4096, 8192]
    assert (dep["check"]["prompt_len"], dep["check"]["decode_steps"]) == (
        4093, 64)


def test_every_width_is_the_published_one(cell):
    doc, entry = cell.config, cell.config_entry
    assert entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace"]
    assert sorted(doc["reduced"]) == sorted(entry["reduced"])
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert doc["reduced"][key]["published"] == value
            assert doc[key] == doc["reduced"][key]["here"] != value
        else:
            assert doc[key] == value, key
    assert doc["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert (doc["num_hidden_layers"], doc["first_k_dense_replace"]) == (7, 1)
    for key in ("hyper_connection_parameters",
                "hyper_connection_start_and_end", "hc_eps_place", "clamp",
                "selection_bias", "gate_in_float32",
                "residual_streams_float32"):
        assert key in doc["assumed"]
    said = " ".join(doc["departures"])
    for word in ("random", "tokenizer", "prediction head"):
        assert word in said
    assert "pipeline stage" in doc["stands_for"]
    assert entry["source"] == doc["source"] and "Xing4.0-29B-A4B" in doc["source"]


def test_the_kind_refuses_to_load_over_a_program_without_latent(tmp_path):
    """As on the parent of PR 35: the cell has to fail at once there, with
    the harness's own error, in the process that resolves its files."""
    fake = tmp_path / "ray_tpu"
    (fake / "models").mkdir(parents=True)
    (fake / "__init__.py").write_text("")
    (fake / "models" / "__init__.py").write_text("")
    p = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, sys.argv[2]); "
         "sys.path.insert(0, sys.argv[1]); "
         "from benchmark.lib.manifest import Cell, ManifestError\n"
         "try: Cell(sys.argv[3], sys.argv[4])\n"
         "except ManifestError as e: print('REFUSED', e); sys.exit(1)",
         str(tmp_path), REPO, os.path.join(REPO, "BENCHMARK.json"), CELL],
        capture_output=True, text=True, cwd=str(tmp_path),
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert p.returncode == 1, p.stderr
    assert "REFUSED" in p.stdout and "no models/latent.py" in p.stdout
    assert "jax" not in (p.stdout + p.stderr).lower()


def test_loading_the_kind_imports_no_jax():
    p = subprocess.run(
        [sys.executable, "-c",
         "import sys; from benchmark.lib.manifest import load_model; "
         "load_model(sys.argv[1]); assert 'jax' not in sys.modules", KIND],
        capture_output=True, text=True, cwd=REPO)
    assert p.returncode == 0, p.stderr


def test_the_cell_refuses_to_run_without_its_chip():
    p = run_cell(os.path.join(REPO, "BENCHMARK.json"), CELL, seconds=1)
    assert p.returncode != 0 and "needs 1 TPU chip" in p.stderr


@pytest.mark.parametrize("count,value", [
    ("num_params", 5_537_859_578),
    ("kv_bytes_per_token", 8064),
])
def test_counts_of_the_l7_configuration(cell, count, value):
    assert getattr(cell.model, count)(cell.config) == value


def test_a_decode_step_reads_the_experts_its_tokens_reach(cell):
    m, doc = cell.model, cell.config
    per = m.layer_matrix_params(doc)
    assert round(m.experts_touched(doc, 32), 1) == 55.9
    assert round(m.experts_touched(doc, 26), 1) == 52.0
    assert m.experts_touched(doc, 1) == pytest.approx(4.0)
    outside = (m.decode_step_bytes(doc, 26, 0)
               - 6 * m.experts_touched(doc, 26) * per["expert"] * 2)
    # attention, hyper-connections, shared expert, router of every layer,
    # the dense MLP and the head: 0.96 GB + 0.94 GB of head
    assert outside == pytest.approx(2 * (7 * (per["attention"] + per["hc"])
                                         + per["mlp"] + 6 * (per["shared"]
                                                             + per["router"])
                                         + 131072 * 3584))
    assert m.decode_step_bytes(doc, 26, 1000) - m.decode_step_bytes(
        doc, 26, 0) == 1000 * 8064
    assert m.decode_step_flops(doc, 1, 0) == pytest.approx(2.0 * (
        outside / 2 + 6 * 4 * per["expert"]))


def _ctx(cell, ops, stats0, stats1, busy=2.0):
    sample = types.SimpleNamespace(prompt_len=4000, token_times=[0.0] * 400,
                                   t_fired=-1.0, t_end=99.0)
    return {"model": cell.model, "config": cell.config,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "trace": {"ops": ops, "programs": [], "busy_s": busy},
            "span": {"t0": 0.0, "t1": 5.0, "stats0": stats0,
                     "stats1": stats1},
            "stats0": stats0, "stats1": stats1, "samples": [sample]}


def test_the_four_readers_on_a_made_up_span(cell):
    """One second of ``moe_gmm`` for work whose least time is half a second
    reads 50%; a span without the kernels, or a program without the
    counters (the parent's), reads nothing and raises nothing."""
    from benchmark.lib import trace
    m, doc = cell.model, cell.config
    zero = dict(moe_assignments=0, moe_experts_touched=0,
                moe_assignments_prefill=0, admitted_requests=0, steps=0,
                admit_batches=0, tokens_out=0, moe_expert_layer_steps=0,
                kv_positions_live=0, expert_layers=6, experts_held=64)
    per = m.layer_matrix_params(doc)["expert"] * 2
    touched = int(0.5 * 819e9 / per)            # half a second of weights
    live = 1000 * 26 * 4200
    after = dict(zero, moe_experts_touched=touched, steps=1000,
                 tokens_out=26000, moe_expert_layer_steps=6000,
                 kv_positions_live=live)
    ops = [["moe_gmm" + trace.PALLAS_TAG, 1.0, 9],
           ["mla_decode_attn" + trace.PALLAS_TAG, 0.2, 9]]
    ctx = _ctx(cell, ops, zero, after)
    read = {name: cell.reader(name)(ctx) for name in (
        "moe_gmm_roofline", "mla_decode_attn_roofline",
        "moe_mla_kernels_device_share", "moe_experts_touched_share")}
    assert read["moe_gmm_roofline"] == pytest.approx(50.0, rel=1e-3)
    assert read["mla_decode_attn_roofline"] == pytest.approx(
        100 * live * 8064 / 819e9 / 0.2)
    assert read["moe_mla_kernels_device_share"] == pytest.approx(60.0)
    assert read["moe_experts_touched_share"] == pytest.approx(
        100 * touched / (6000 * 64))
    bare = {k: 0 for k in ("steps", "admit_batches", "tokens_out",
                           "admitted_requests")}
    no_kernels, no_counters = (_ctx(cell, [], zero, after),
                               _ctx(cell, ops, bare, bare))
    for name in ("moe_gmm_roofline", "mla_decode_attn_roofline",
                 "moe_mla_kernels_device_share"):
        assert cell.reader(name)(no_kernels) is None
    for name in ("moe_gmm_roofline", "mla_decode_attn_roofline",
                 "moe_experts_touched_share"):
        assert cell.reader(name)(no_counters) is None
