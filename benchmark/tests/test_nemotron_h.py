"""The block kind ``nemotron_h`` as files (``models/nemotron_h.py``, the
configuration ``nemotron-3-nano-30b-a3b-serve-l9-e64``, its cell, traffic
and readers): the lookup by ``model_type``, the published widths against the
catalog's, the refusal to load over a program without the state-space
kernels, the program against the kind's reference through the cache on the
tests' tiny configuration, and the three readers on a made-up context.  A
file of its own: a ``model_config`` PR adds files to the benchmark and edits
none.  The kernels against the recurrence, the shares adding up and the
counts are ``tests/test_ssd.py`` (tier-1)."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib.manifest import MODEL_API, Cell, load_model
from benchmark.tests.test_runners import REPO, run_cell

BENCH = os.path.join(REPO, "benchmark")
KIND = os.path.join(BENCH, "models", "nemotron_h.py")
TINY = os.path.join(BENCH, "tests", "tiny", "configs", "tiny-nemotron.json")
CELL = "serve-ssm-moe-mixedlen-closed"
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
#: the published file's numbers and settings (the model-configs catalog's row)
PUBLISHED = dict(
    attention_bias=False, chunk_size=128, conv_kernel=4, expand=2,
    head_dim=128, hidden_size=2688, hybrid_override_pattern=PATTERN,
    intermediate_size=1856, layer_norm_epsilon=1e-5, mamba_head_dim=64,
    mamba_hidden_act="silu", mamba_num_heads=64, mamba_proj_bias=False,
    max_position_embeddings=262144, mlp_bias=False, mlp_hidden_act="relu2",
    model_type="nemotron_h", moe_intermediate_size=1856,
    moe_shared_expert_intermediate_size=3712, n_group=1, n_groups=8,
    n_routed_experts=128, n_shared_experts=1, norm_eps=1e-5,
    norm_topk_prob=True, num_attention_heads=32, num_experts_per_tok=6,
    num_hidden_layers=52, num_key_value_heads=2, num_logits_to_keep=1,
    partial_rotary_factor=1, rescale_prenorm_residual=True,
    residual_in_fp32=False, rope_theta=10000, routed_scaling_factor=2.5,
    sliding_window=None, ssm_state_size=128, tie_word_embeddings=False,
    time_step_floor=0.0001, time_step_max=0.1, time_step_min=0.001,
    topk_group=1, use_bias=False, use_conv_bias=True, use_mamba_kernels=True,
    vocab_size=131072)


@pytest.fixture(scope="module")
def cell():
    return Cell(os.path.join(REPO, "BENCHMARK.json"), CELL)


def test_the_cell_resolves_to_the_kinds_files(cell):
    assert cell.model_path == KIND and cell.chips == 1
    assert all(callable(getattr(cell.model, f)) for f in MODEL_API)
    for m in cell.metrics("per_layer"):
        assert callable(cell.reader(m["name"]))
    assert {m["name"] for m in cell.metrics("per_layer")} == {
        "ssd_recurrent_step_roofline", "ssd_chunk_fwd_roofline",
        "ssm_moe_kernels_device_share", "decode_step_batch_roofline",
        "decode_step_device_ms.batch", "moe_gmm_roofline",
        "moe_experts_touched_share", "stream_admit_stall_share.batch",
        "decode_attn_roofline.batch", "decode_slot_occupancy.batch",
        "prefill_ms_per_admitted_ktoken.batch"}
    # decoded greedily, tokens/s goes with the seed's draw (over the 0.5%
    # at which ISSUE 46 leaves the cell off that list): not judged, and the
    # readers are the ``.batch`` ones (PERF.md section 6, PR 46)
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "latency_per_token_p95_ms", "setup_s"}
    t, dep = cell.traffic, cell.config["serve"]
    assert (t["loop"], t["clients"], t["ingress"], t["order"],
            t["shape_seed"], t["requests_per_client"], t["preroll_s"],
            t["drain_grace_s"], t["request_timeout_s"]) == (
        "closed", 64, "handle_stream", "fixed", 46, 32, 20, 60, 300)
    assert t["prompt"] == dict(dist="lognormal", median=1024, sigma=0.7,
                               lo=256, hi=6144)
    assert t["output"] == dict(dist="lognormal", median=384, sigma=0.5,
                               lo=128, hi=1024)
    # greedy, as ISSUE 46 lists it; 4 steps a dispatch where the program
    # has 8 (at 8 the cell's p95 spread over the 2% a new cell may have:
    # the file's ``departures``), and that setting is said there
    assert t["temperature"] == 0.0
    assert dep["engine_kwargs"] == {"steps_per_dispatch": 4}
    assert any("steps_per_dispatch 4" in d for d in cell.config["departures"])
    assert t["clients"] == dep["num_slots"]
    assert t["prompt"]["hi"] + t["output"]["hi"] <= dep["max_len"] == 8192
    assert dep["buckets"] == [512, 1024, 2048, 4096, 8192]
    assert dep["paged"] is False
    assert dep["check"]["prompt_len"] % 128 and \
        dep["check"]["decode_steps"] >= 256


def test_every_width_is_the_published_one_and_the_cut_is_stated(cell):
    doc, entry = cell.config, cell.config_entry
    assert sorted(entry["reduced"]) == sorted(
        ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
         "vocab_size"]) == sorted(doc["reduced"])
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert doc["reduced"][key]["published"] == value
            assert doc[key] == doc["reduced"][key]["here"] != value
        else:
            assert doc[key] == value, key
    assert (doc["num_hidden_layers"], doc["hybrid_override_pattern"],
            doc["n_routed_experts"], doc["vocab_size"]) == (
        9, PATTERN[:9], 64, 65536)
    assert (PATTERN.count("M"), PATTERN.count("E"), PATTERN.count("*")) == (
        23, 23, 6)
    assert doc["share"]["chips"] == 2 and doc["share"]["expert_start"] == 0
    assert doc["vocab_size"] * 2 == PUBLISHED["vocab_size"]
    assert doc["n_routed_experts"] * 2 == PUBLISHED["n_routed_experts"]
    for key in ("d_inner", "no_position_embedding", "gated_norm", "dt",
                "router", "experts", "state_dtype", "biases"):
        assert key in doc["assumed"]
    said = " ".join(doc["departures"])
    for word in ("random", "tokenizer", "A_log", "w_up"):
        assert word in said
    assert "4 pipeline stages x 2 chips" in doc["stands_for"]
    assert entry["source"] == doc["source"] and \
        "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16" in doc["source"]
    assert cell.model.period(doc) == (
        "ssm", "mlp", "ssm", "mlp", "ssm", "full", "mlp", "ssm", "mlp")


def test_the_kind_refuses_to_load_over_a_program_without_ssd(tmp_path):
    """As on the parent of PR 46: the cell has to fail at once there, with
    the harness's own error, in the process that resolves its files."""
    fake = tmp_path / "ray_tpu"
    (fake / "ops").mkdir(parents=True)
    (fake / "__init__.py").write_text("")
    (fake / "ops" / "__init__.py").write_text("")
    (fake / "ops" / "kda.py").write_text("")
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
    assert "REFUSED" in p.stdout and "no ops/ssd.py" in p.stdout
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
    kind's entry points, a prefill then decode steps, against ``logits`` on
    its own) on the tests' tiny configuration, whose expert width (24) is no
    multiple of the lane width, in float32 weights and bf16 compute as a
    cell runs it; and the reference handed that run's recorded routing as
    data, which is no further from it than the reference on its own."""
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
    alone = np.asarray(model.logits(params, toks, doc, pos))
    cache = model.init_cache(cfg, 1, 128, jnp.bfloat16)
    cache, lg = model.prefill(params, cache, toks[None, :n_prompt],
                              np.array([n_prompt], np.int32),
                              np.array([0], np.int32), cfg)
    got = [np.asarray(lg)[0]]
    for i in range(n_prompt, 48):
        cache, lg = model.decode_step(params, cache, toks[i:i + 1],
                                      np.ones((1,), bool), cfg)
        got.append(np.asarray(lg)[0])
    chosen = cache["expert_choices"][:, 0, :48]
    assert (np.asarray(chosen) >= 0).all()
    told = np.asarray(model.logits(params, toks, doc, pos, follow=chosen))
    rms = lambda d: float(np.sqrt((d ** 2).mean()))      # noqa: E731
    assert np.isfinite(np.stack(got)).all() and alone.std() > 0.5
    # bf16 compute at hidden 64: a few percent of the logits' deviation
    assert rms(np.stack(got) - alone) < 0.3
    assert rms(np.stack(got) - told) <= rms(np.stack(got) - alone) + 1e-6


def test_the_reference_runs_nothing_of_the_program():
    """Section 3 reads the program's parameter tree and calls ``jax`` alone:
    no function from the head of the section to the counts imports or names
    ``ray_tpu``, and ``logits`` asks for no run of the program."""
    import ast
    import inspect
    with open(KIND) as f:
        source = f.read()
    start = source.index("# ------------------------------------------------- "
                         "3. the plain reference")
    end = source.index("# ------------------------------------------------ "
                       "4. operations and bytes")
    tree = ast.parse(source[start:end])
    names = {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)} | {
        a.name.split(".")[0] for n in ast.walk(tree)
        if isinstance(n, (ast.Import, ast.ImportFrom))
        for a in n.names} | {
        (n.module or "").split(".")[0] for n in ast.walk(tree)
        if isinstance(n, ast.ImportFrom)}
    assert "ray_tpu" not in names and not {
        "prefill", "decode_step", "init_cache", "program_config"} & names
    model = load_model(KIND)
    assert inspect.signature(model.logits).parameters["follow"].default is None
    assert not hasattr(model, "program_run")


def _ctx(cell, ops, stats0, stats1, busy=2.0):
    sample = types.SimpleNamespace(prompt_len=1400, token_times=[0.0] * 400,
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
    step_s = m.ssd_recurrent_step_bytes(doc, 64 * 1000) / 819e9
    chunk = max(m.ssd_chunk_fwd_bytes(doc, 20000) / 819e9,
                m.ssd_chunk_fwd_flops(doc, 20000) / 197e12)
    ops = [["ssd_recurrent_step" + trace.PALLAS_TAG, 2 * step_s, 9],
           ["ssd_chunk_fwd" + trace.PALLAS_TAG, 4 * chunk, 9],
           ["moe_gmm" + trace.PALLAS_TAG, 0.5, 9]]
    busy = 4 * (2 * step_s + 4 * chunk + 0.5)
    ctx = _ctx(cell, ops, zero, after, busy)
    names = ("ssd_recurrent_step_roofline", "ssd_chunk_fwd_roofline",
             "ssm_moe_kernels_device_share")
    read = {name: cell.reader(name)(ctx) for name in names}
    assert read["ssd_recurrent_step_roofline"] == pytest.approx(50.0)
    assert read["ssd_chunk_fwd_roofline"] == pytest.approx(25.0)
    assert read["ssm_moe_kernels_device_share"] == pytest.approx(25.0)
    for name in names:
        assert cell.reader(name)(_ctx(cell, [], zero, after)) is None
    bare = dict(tokens_out=0)
    for name in names[:2]:
        assert cell.reader(name)(_ctx(cell, ops, bare, bare)) is None
