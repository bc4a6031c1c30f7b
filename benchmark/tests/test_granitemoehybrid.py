"""The block kind ``granitemoehybrid`` as files
(``models/granitemoehybrid.py``, the configuration
``granite-4.0-h-micro-serve-l40``, its cell, traffic and readers): the
lookup by ``model_type``, every published key against the catalog's row
with nothing cut, the refusal to load over a program without
the four multipliers, the counts at the published keys, each multiplier in
the reference, the program against the reference through the cache on the
tests' tiny configuration, and the two new readers on a made-up context.  A
file of its own: a ``model_config`` PR adds files to the benchmark and edits
none.  The contract every served kind passes, the kernels at one group wider
than a grid step and the counts are ``tests/test_granitemoehybrid.py`` and
``tests/test_ssd.py`` (tier-1)."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib.manifest import MODEL_API, Cell, load_model
from benchmark.tests.test_runners import REPO, run_cell

BENCH = os.path.join(REPO, "benchmark")
KIND = os.path.join(BENCH, "models", "granitemoehybrid.py")
TINY = os.path.join(BENCH, "tests", "tiny", "configs", "tiny-granite.json")
CELL = "serve-ssm-dense-agents-closed"
TYPES = (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4
#: the published file's numbers and settings (the model-configs catalog's row)
PUBLISHED = dict(
    attention_bias=False, attention_multiplier=0.015625,
    embedding_multiplier=12, hidden_act="silu", hidden_size=2048,
    intermediate_size=8192, layer_types=TYPES, logits_scaling=8,
    mamba_chunk_size=256, mamba_conv_bias=True, mamba_d_conv=4,
    mamba_d_head=64, mamba_d_state=128, mamba_expand=2, mamba_n_groups=1,
    mamba_n_heads=64, mamba_proj_bias=False, max_position_embeddings=131072,
    model_type="granitemoehybrid", normalization_function="rmsnorm",
    num_attention_heads=32, num_experts_per_tok=0, num_hidden_layers=40,
    num_key_value_heads=8, num_local_experts=0,
    position_embedding_type="nope", residual_multiplier=0.22,
    rms_norm_eps=1e-5, rope_scaling=None, rope_theta=10000,
    shared_intermediate_size=8192, tie_word_embeddings=True,
    vocab_size=100352)
MULTIPLIERS = ("embedding_multiplier", "residual_multiplier",
               "attention_multiplier", "logits_scaling")


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
        "ssm_dense_kernels_device_share", "decode_state_stream_share",
        "decode_step_batch_roofline", "decode_step_device_ms.batch",
        "stream_admit_stall_share.batch", "decode_attn_roofline.batch",
        "decode_slot_occupancy.batch",
        "prefill_ms_per_admitted_ktoken.batch"}
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "latency_per_token_p95_ms", "setup_s"}
    t, dep = cell.traffic, cell.config["serve"]
    # ISSUE 53's traffic, letter for letter
    assert (t["loop"], t["clients"], t["ingress"], t["order"],
            t["shape_seed"], t["requests_per_client"], t["preroll_s"],
            t["drain_grace_s"], t["request_timeout_s"]) == (
        "closed", 64, "handle_stream", "fixed", 53, 32, 20, 60, 300)
    assert t["prompt"] == dict(dist="lognormal", median=768, sigma=0.6,
                               lo=256, hi=3072)
    assert t["output"] == dict(dist="lognormal", median=512, sigma=0.5,
                               lo=256, hi=1024)
    assert t["prefix"] == dict(pool=0, len=0) and t["temperature"] == 0.0
    assert t["clients"] == dep["num_slots"]
    assert t["prompt"]["hi"] + t["output"]["hi"] <= dep["max_len"] == 4096
    assert dep["buckets"] == [512, 1024, 2048, 4096]
    assert dep["paged"] is False
    for word in ("paged", "spec_decode_enabled", "tp > 1", "training"):
        assert word in dep["refuses"]
    # a setting that differs from the program's default is a departure
    for key, value in dep["engine_kwargs"].items():
        assert any(f"{key} {value}" in d for d in cell.config["departures"])
    assert dep["check"]["prompt_len"] % 128 and \
        dep["check"]["decode_steps"] >= 512


def test_every_key_is_the_published_one_and_nothing_is_cut(cell):
    doc, entry = cell.config, cell.config_entry
    assert entry["reduced"] == [] and doc["reduced"] == {}
    assert "nothing is cut" in doc["reduced_why"]
    for key, value in PUBLISHED.items():
        assert doc[key] == value, key
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "granite-4.0-h-micro"]
    assert row["config"] == PUBLISHED and entry["source"] == row["source_url"]
    assert entry["source"] == doc["source"]
    for key in ("state_dtype", "chunk", "in_proj", "gated_norm", "dt", "mlp",
                "norm_placement", "multipliers", "positions", "head_dim"):
        assert key in doc["assumed"]
    said = " ".join(doc["departures"])
    for word in ("random", "tokenizer", "A_log", "N(0, 0.1)"):
        assert word in said
    assert "whole model" in doc["stands_for"]
    assert cell.model.period(doc) == ("ssm",) * 5 + ("full",) + ("ssm",) * 4
    assert doc["params"]["held"] == cell.model.num_params(doc)


def test_the_counts_at_the_published_keys(cell):
    """ISSUE 53's table: 3.19B parameters, 6.38 GB in bf16; a slot's state
    75.5 MB, K/V 8 KB a token; the cache the engine holds 4.91 + 0.06 + 2.18
    GB, the arguments 13.5 GB, 80% of the chip."""
    m, doc = cell.model, cell.config
    per = m.layer_matrix_params(doc)
    assert per == {"mamba": 2048 * 8512 + 4096 * 2048,
                   "attention": 2 * 2048 * 2048 + 2 * 2048 * 512,
                   "mlp": 3 * 2048 * 8192}
    n = m.num_params(doc)
    assert n == 3_191_396_096 and round(n / 1e9, 2) == 3.19
    assert round(2 * n / 1e9, 2) == 6.38
    assert m.state_bytes_per_slot(doc) == 75_497_472
    assert m.kv_bytes_per_token(doc) == 8192
    slots, max_len = doc["serve"]["num_slots"] + 1, doc["serve"]["max_len"]
    state = slots * m.state_bytes_per_slot(doc)
    tails = slots * 36 * 3 * 4352 * 2
    kv = slots * max_len * m.kv_bytes_per_token(doc)
    assert (round(state / 1e9, 2), round(tails / 1e9, 2),
            round(kv / 1e9, 2)) == (4.91, 0.06, 2.18)
    arguments = 2 * n + state + tails + kv
    assert round(arguments / 1e9, 1) == 13.5
    assert 0.79 < arguments / 16.9e9 < 0.81


def test_the_kind_refuses_to_load_over_a_program_without_the_multipliers(
        tmp_path):
    """As on the parent of PR 53, whose ``models/config.py`` has no field
    for them: the cell has to fail at once there, with the harness's own
    error, in the process that resolves its files."""
    fake = tmp_path / "ray_tpu"
    (fake / "models").mkdir(parents=True)
    (fake / "ops").mkdir()
    (fake / "__init__.py").write_text("")
    (fake / "models" / "__init__.py").write_text("")
    (fake / "models" / "config.py").write_text("tied_embeddings = False\n")
    (fake / "ops" / "__init__.py").write_text("")
    (fake / "ops" / "ssd.py").write_text("")
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
    assert "REFUSED" in p.stdout and "no embedding_multiplier" in p.stdout
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


def test_keys_become_the_programs_configuration_with_its_refusals():
    model = load_model(KIND)
    cfg = model.program_config(PUBLISHED)
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.mlp_size, cfg.vocab_size) == (
        40, 2048, 32, 8, 64, 8192, 100352)
    assert (cfg.linear_num_heads, cfg.linear_value_dim, cfg.linear_key_dim,
            cfg.linear_conv_width, cfg.ssm_groups) == (64, 64, 128, 4, 1)
    assert cfg.tied_embeddings and cfg.no_positions and not cfg.use_rope
    assert not (cfg.moe_dropless or cfg.sublayers_alone)
    assert [getattr(cfg, f) for f in MULTIPLIERS] == [12, 0.22, 0.015625, 8]
    for change, match in (
            (dict(num_local_experts=8), "no routed experts"),
            (dict(tie_word_embeddings=False), "tie_word_embeddings"),
            (dict(position_embedding_type="rope"), "nope"),
            (dict(mamba_n_groups=3), "whole groups"),
            (dict(logits_scaling=0), "positive"),
            (dict(layer_types=TYPES[:-1]), "num_hidden_layers")):
        with pytest.raises(ValueError, match=match):
            model.program_config({**PUBLISHED, **change})
    lacking = {k: v for k, v in PUBLISHED.items()
               if k != "residual_multiplier"}
    with pytest.raises(ValueError, match="residual_multiplier"):
        model.program_config(lacking)


@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp
    model = load_model(KIND)
    with open(TINY) as f:
        doc = json.load(f)
    cfg = model.program_config(doc)
    return model, doc, cfg, model.init_params(jax.random.PRNGKey(2), cfg,
                                              jnp.float32)


@pytest.mark.parametrize("key", MULTIPLIERS)
def test_each_multiplier_moves_the_references_logits(tiny, key):
    import numpy as np
    model, doc, _, params = tiny
    assert all(doc[k] > 0 and np.log2(doc[k]) % 1 for k in MULTIPLIERS)
    toks = np.random.default_rng(4).integers(1, 256, size=40).astype(np.int32)
    want = np.asarray(model.logits(params, toks, doc))
    moved = np.asarray(model.logits(params, toks,
                                    {**doc, key: 1.25 * doc[key]}))
    assert want.std() > 0.5 and np.abs(moved - want).max() > 0.01
    # the loss is the logits' mean next-token cross entropy
    logp = want[:-1] - np.log(np.exp(want[:-1]).sum(-1, keepdims=True))
    assert float(model.loss(params, toks, doc)) == pytest.approx(
        -logp[np.arange(39), toks[1:]].mean(), rel=1e-5)


def test_prefill_then_decode_through_the_cache_equals_the_reference(tiny):
    """The harness's own comparison (``serve_app._check_reference``: the
    kind's entry points, a prefill then decode steps, against ``logits``) on
    the tests' tiny configuration, in float32 weights and bf16 compute as a
    cell runs it."""
    import jax.numpy as jnp
    import numpy as np
    model, doc, cfg, params = tiny
    toks = np.random.default_rng(3).integers(1, 256, size=48).astype(np.int32)
    n_prompt = 37
    want = np.asarray(model.logits(params, toks, doc,
                                   jnp.arange(n_prompt - 1, 48)))
    cache = model.init_cache(cfg, 1, 128, jnp.bfloat16)
    cache, lg = model.prefill(params, cache, toks[None, :n_prompt],
                              np.array([n_prompt], np.int32),
                              np.array([0], np.int32), cfg)
    got = [np.asarray(lg)[0]]
    for i in range(n_prompt, 48):
        cache, lg = model.decode_step(params, cache, toks[i:i + 1],
                                      np.ones((1,), bool), cfg)
        got.append(np.asarray(lg)[0])
    diff = np.stack(got) - want
    assert np.isfinite(np.stack(got)).all() and want.std() > 0.5
    # bf16 compute at hidden 64: a few percent of the logits' deviation
    assert float(np.sqrt((diff ** 2).mean())) < 0.03


def test_the_reference_runs_nothing_of_the_program():
    """Section 3 reads the program's parameter tree and calls ``jax`` alone:
    no function from the head of the section to the counts imports or names
    ``ray_tpu``, and ``logits`` asks for nothing of a compared run."""
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
    assert list(inspect.signature(model.logits).parameters) == [
        "params", "tokens", "doc", "positions"]


def _ctx(cell, ops, stats0, stats1, busy=2.0):
    sample = types.SimpleNamespace(prompt_len=900, token_times=[0.0] * 500,
                                   t_fired=-1.0, t_end=99.0)
    return {"model": cell.model, "config": cell.config,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "trace": {"ops": ops, "programs": [], "busy_s": busy},
            "span": {"t0": 0.0, "t1": 5.0, "stats0": stats0,
                     "stats1": stats1},
            "stats0": stats0, "stats1": stats1, "samples": [sample]}


def test_the_readers_on_a_made_up_span(cell):
    """The four kernels' seconds over the busy time; the state's bytes over
    a step's at the span's mean active slots and live positions; the two
    state-space rooflines with this kind's counts; a span without the
    kernels, a program without the counters (the parent's) or a kind
    without a per-slot state's count reads nothing and raises nothing."""
    from benchmark.lib import trace
    m, doc = cell.model, cell.config
    zero = dict(steps=0, admit_batches=0, tokens_out=0, admit_tokens_real=0,
                kv_positions_live=0)
    after = dict(steps=1010, admit_batches=10, tokens_out=60 * 1010,
                 admit_tokens_real=20000, kv_positions_live=1000 * 60 * 1150)
    step_s = m.ssd_recurrent_step_bytes(doc, 60 * 1000) / 819e9
    chunk = max(m.ssd_chunk_fwd_bytes(doc, 20000) / 819e9,
                m.ssd_chunk_fwd_flops(doc, 20000) / 197e12)
    ops = [["ssd_recurrent_step" + trace.PALLAS_TAG, 2 * step_s, 9],
           ["ssd_chunk_fwd" + trace.PALLAS_TAG, 4 * chunk, 9],
           ["decode_attn" + trace.PALLAS_TAG, 0.25, 9],
           ["flash_fwd" + trace.PALLAS_TAG, 0.25, 9],
           ["flash_fwd_rows" + trace.PALLAS_TAG, 7.0, 9]]
    busy = 4 * (2 * step_s + 4 * chunk + 0.5)
    ctx = _ctx(cell, ops, zero, after, busy)
    read = {name: cell.reader(name)(ctx) for name in (
        "ssd_recurrent_step_roofline", "ssd_chunk_fwd_roofline",
        "ssm_dense_kernels_device_share", "decode_state_stream_share")}
    assert read["ssd_recurrent_step_roofline"] == pytest.approx(50.0)
    assert read["ssd_chunk_fwd_roofline"] == pytest.approx(25.0)
    assert read["ssm_dense_kernels_device_share"] == pytest.approx(25.0)
    state = 2 * 60 * 75_497_472
    assert read["decode_state_stream_share"] == pytest.approx(
        100 * state / (2 * 3_190_292_480 + state + 60 * 1150 * 8192))
    assert 55 < read["decode_state_stream_share"] < 58
    share = cell.reader("ssm_dense_kernels_device_share")
    stream = cell.reader("decode_state_stream_share")
    assert share(_ctx(cell, [], zero, after)) is None
    bare = dict(tokens_out=0)
    assert stream(_ctx(cell, ops, bare, bare)) is None
    assert stream(_ctx(cell, ops, zero, zero)) is None
    other = dict(ctx, model=types.SimpleNamespace())
    assert stream(other) is None
