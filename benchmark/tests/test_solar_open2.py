"""The block kind ``solar_open2`` as files (``models/solar_open2.py``, the
configuration ``solar-open2-250b-serve-l4-e40``, its cell, traffic and
readers): the lookup by ``model_type``, the published widths against the
catalog's, the refusal to load over a program without the KDA kernels, the
program against the kind's reference through the cache on the tests' tiny
configuration, and the three readers on a made-up context.  A file of its
own: a ``model_config`` PR adds files to the benchmark and edits none.  The
kernels against the recurrence, the shares adding up and the counts are
``tests/test_kda.py`` (tier-1)."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib.manifest import MODEL_API, Cell, load_model
from benchmark.tests.test_runners import REPO, run_cell

BENCH = os.path.join(REPO, "benchmark")
KIND = os.path.join(BENCH, "models", "solar_open2.py")
TINY = os.path.join(BENCH, "tests", "tiny", "configs", "tiny-solar.json")
CELL = "serve-kda-moe-reasoning-closed"
#: the published file's numbers (the model-configs catalog's row)
PUBLISHED = dict(
    partial_rotary_factor=1, hidden_size=4096, num_hidden_layers=48,
    num_attention_heads=64, head_dim=128, num_key_value_heads=8,
    vocab_size=196608, intermediate_size=10240, moe_intermediate_size=1280,
    rms_norm_eps=1e-5, rope_theta=10000, max_position_embeddings=1048576,
    first_k_dense_replace=0, gqa_interval=3, n_routed_experts=320,
    n_shared_experts=1, routed_scaling_factor=1, num_experts_per_tok=8,
    gqa_layers=list(range(0, 48, 4)))


@pytest.fixture(scope="module")
def cell():
    return Cell(os.path.join(REPO, "BENCHMARK.json"), CELL)


def test_the_cell_resolves_to_the_kinds_files(cell):
    assert cell.model_path == KIND and cell.chips == 1
    assert all(callable(getattr(cell.model, f)) for f in MODEL_API)
    for m in cell.metrics("per_layer"):
        assert callable(cell.reader(m["name"]))
    # judged on the whole answer's latency a token alone: tokens/s is counted
    # an answer at a time here and moves by 2.8% with three answers at the
    # window's edge (PERF.md section 6, PR 44), so the cell reports neither
    # it nor the per-layer metrics that move it; of those, decode attention's
    # share of its roofline and the steps' occupancy are read under names of
    # their own that move the latency (``.batch``)
    assert {m["name"] for m in cell.metrics("per_layer")} == {
        "kda_recurrent_step_roofline", "kda_chunk_fwd_roofline",
        "kda_moe_kernels_device_share", "decode_step_batch_roofline",
        "decode_step_device_ms.batch", "moe_gmm_roofline",
        "moe_experts_touched_share", "stream_admit_stall_share.batch",
        "decode_attn_roofline.batch", "decode_slot_occupancy.batch"}
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "latency_per_token_p95_ms", "setup_s"}
    t, dep = cell.traffic, cell.config["serve"]
    assert (t["loop"], t["clients"], t["ingress"], t["order"],
            t["shape_seed"], t["requests_per_client"], t["preroll_s"],
            t["drain_grace_s"], t["request_timeout_s"]) == (
        "closed", 64, "handle_stream", "fixed", 44, 16, 20, 90, 300)
    assert t["prompt"] == dict(dist="lognormal", median=512, sigma=0.5,
                               lo=256, hi=1024)
    assert t["output"] == dict(dist="lognormal", median=1536, sigma=0.4,
                               lo=768, hi=3072)
    assert t["clients"] == dep["num_slots"]
    assert t["prompt"]["hi"] + t["output"]["hi"] <= dep["max_len"] == 4096
    assert dep["buckets"] == [256, 512, 1024] and dep["paged"] is False
    assert dep["check"]["prompt_len"] % 64 and \
        dep["check"]["decode_steps"] >= 256


def test_every_width_is_the_published_one_and_the_cut_is_stated(cell):
    doc, entry = cell.config, cell.config_entry
    assert sorted(entry["reduced"]) == sorted(
        ["num_hidden_layers", "n_routed_experts", "vocab_size",
         "gqa_layers"]) == sorted(doc["reduced"])
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert doc["reduced"][key]["published"] == value
            assert doc[key] == doc["reduced"][key]["here"] != value
        else:
            assert doc[key] == value, key
    assert doc["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert (doc["use_rope"], doc["use_gqa_gate"], doc["kda_use_full_proj"],
            doc["kda_allow_neg_eigval"], doc["norm_topk_prob"],
            doc["tie_word_embeddings"]) == (False, True, False, True, True,
                                            False)
    assert (doc["num_hidden_layers"], doc["gqa_layers"],
            doc["n_routed_experts"], doc["vocab_size"]) == (4, [0], 40, 24576)
    assert doc["share"]["chips"] == 8 and doc["share"]["expert_start"] == 0
    assert doc["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert doc["n_routed_experts"] * 8 == PUBLISHED["n_routed_experts"]
    for key in ("kda_low_rank", "gqa_gate", "router", "norm_placement",
                "no_qk_norm", "biases"):
        assert key in doc["assumed"]
    said = " ".join(doc["departures"])
    for word in ("random", "tokenizer", "A_log"):
        assert word in said
    assert "8 pipeline stages x 8 chips" in doc["stands_for"]
    assert entry["source"] == doc["source"] and "Solar-Open2-250B" in \
        doc["source"]
    assert cell.model.period(doc) == ("full", "linear", "linear", "linear")


def test_the_kind_refuses_to_load_over_a_program_without_kda(tmp_path):
    """As on the parent of PR 44: the cell has to fail at once there, with
    the harness's own error, in the process that resolves its files."""
    fake = tmp_path / "ray_tpu"
    (fake / "ops").mkdir(parents=True)
    (fake / "__init__.py").write_text("")
    (fake / "ops" / "__init__.py").write_text("")
    (fake / "ops" / "gated_delta.py").write_text("")
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
    assert "REFUSED" in p.stdout and "no ops/kda.py" in p.stdout
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


def test_prefill_then_decode_through_the_cache_equals_the_reference():
    """The harness's own comparison (``serve_app._check_reference``: the
    kind's entry points, a prefill then decode steps, against ``logits``
    told the program's routing) on the tests' tiny configuration, in
    float32 weights and bf16 compute as a cell runs it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    model = load_model(KIND)
    with open(TINY) as f:
        doc = json.load(f)
    cfg = model.program_config(doc)
    params = model.init_params(jax.random.PRNGKey(2), cfg, jnp.float32)
    toks = np.random.default_rng(3).integers(1, 256, size=48).astype(np.int32)
    n_prompt = 37
    pos = jnp.arange(n_prompt - 1, 48)
    ref = np.asarray(model.logits(params, toks, doc, pos))
    alone = np.asarray(model.logits(params, toks, doc, pos, follow=None))
    cache = model.init_cache(cfg, 1, 128, jnp.bfloat16)
    cache, lg = model.prefill(params, cache, toks[None, :n_prompt],
                              np.array([n_prompt], np.int32),
                              np.array([0], np.int32), cfg)
    got = [np.asarray(lg)[0]]
    for i in range(n_prompt, 48):
        cache, lg = model.decode_step(params, cache, toks[i:i + 1],
                                      np.ones((1,), bool), cfg)
        got.append(np.asarray(lg)[0])
    diff = np.stack(got) - ref
    assert np.isfinite(diff).all() and ref.std() > 0.5
    # bf16 compute at hidden 64: a few percent of the logits' deviation;
    # told the program's routing the reference is no further than on its own
    assert np.sqrt((diff ** 2).mean()) < 0.3
    assert np.sqrt((diff ** 2).mean()) <= np.sqrt(
        ((np.stack(got) - alone) ** 2).mean()) + 1e-6


def _ctx(cell, ops, stats0, stats1, busy=2.0):
    sample = types.SimpleNamespace(prompt_len=600, token_times=[0.0] * 1500,
                                   t_fired=-1.0, t_end=99.0)
    return {"model": cell.model, "config": cell.config,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "trace": {"ops": ops, "programs": [], "busy_s": busy},
            "span": {"t0": 0.0, "t1": 5.0, "stats0": stats0,
                     "stats1": stats1},
            "stats0": stats0, "stats1": stats1, "samples": [sample]}


def test_the_three_readers_on_a_made_up_span(cell):
    """Kernel seconds equal to twice the least time read 50%; a span without
    the kernels, or a program without the counters (the parent's), reads
    nothing and raises nothing."""
    from benchmark.lib import trace
    m, doc = cell.model, cell.config
    zero = dict(steps=0, admit_batches=0, tokens_out=0, admit_tokens_real=0)
    after = dict(steps=1010, admit_batches=10, tokens_out=64 * 1010,
                 admit_tokens_real=20000)
    step_s = m.kda_recurrent_step_bytes(doc, 64 * 1000) / 819e9
    chunk = max(m.kda_chunk_fwd_bytes(doc, 20000) / 819e9,
                m.kda_chunk_fwd_flops(doc, 20000) / 197e12)
    ops = [["kda_recurrent_step" + trace.PALLAS_TAG, 2 * step_s, 9],
           ["kda_chunk_fwd" + trace.PALLAS_TAG, 4 * chunk, 9],
           ["moe_gmm" + trace.PALLAS_TAG, 0.5, 9]]
    busy = 4 * (2 * step_s + 4 * chunk + 0.5)
    ctx = _ctx(cell, ops, zero, after, busy)
    names = ("kda_recurrent_step_roofline", "kda_chunk_fwd_roofline",
             "kda_moe_kernels_device_share")
    read = {name: cell.reader(name)(ctx) for name in names}
    assert read["kda_recurrent_step_roofline"] == pytest.approx(50.0)
    assert read["kda_chunk_fwd_roofline"] == pytest.approx(25.0)
    assert read["kda_moe_kernels_device_share"] == pytest.approx(25.0)
    for name in names:
        assert cell.reader(name)(_ctx(cell, [], zero, after)) is None
    bare = dict(tokens_out=0)
    for name in names[:2]:
        assert cell.reader(name)(_ctx(cell, ops, bare, bare)) is None
