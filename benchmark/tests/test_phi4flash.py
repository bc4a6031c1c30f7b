"""The block kind ``phi4flash`` as files (``models/phi4flash.py``, the
configuration ``phi-4-mini-flash-reasoning-serve-l32``, its cell, traffic and
readers): the lookup by ``model_type``, every published key against the
catalog's row with nothing cut, the refusal to load over a program without a
stack of segments, the counts at the published keys against the parameter
tree and the bytes functions against ``cache_gauges``, the program against
the reference through the cache on the tests' tiny configuration, and the new
readers on a made-up context.  A file of its own: a ``model_config`` PR adds
files to the benchmark and edits none.  The contract every served kind
passes and the kernels are ``tests/test_phi4flash.py`` and
``tests/test_selective_scan.py`` (tier-1)."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib.manifest import MODEL_API, Cell, load_model
from benchmark.tests.test_runners import REPO, run_cell

BENCH = os.path.join(REPO, "benchmark")
KIND = os.path.join(BENCH, "models", "phi4flash.py")
TINY = os.path.join(BENCH, "tests", "tiny", "configs", "tiny-phi4flash.json")
CELL = "serve-sambay-longcot-closed"
#: the published file's numbers and settings (the model-configs catalog's row)
PUBLISHED = dict(
    embd_pdrop=0, hidden_act="silu", hidden_size=2560,
    intermediate_size=10240, layer_norm_eps=1e-05,
    max_position_embeddings=262144, mb_per_layer=2, model_type="phi4flash",
    num_attention_heads=40, num_hidden_layers=32, num_key_value_heads=20,
    resid_pdrop=0, sliding_window=512, tie_word_embeddings=True,
    mlp_bias=False, lm_head_bias=False, vocab_size=200064)
BATCH = {
    "decode_step_batch_roofline", "decode_step_device_ms.batch",
    "decode_slot_occupancy.batch", "prefill_ms_per_admitted_ktoken.batch",
    "stream_admit_stall_share.batch", "chip_unbound_share.batch",
    "slot_unfed_share.batch", "slot_queued_share.batch",
    "slot_prefill_share.batch", "slot_tail_share.batch",
    "ingress_wait_ms.batch", "ingress_transit_ms.batch",
    "finish_deliver_ms.batch", "stream_first_chunk_wait_ms.batch"}
NEW = {"selective_scan_chunk_fwd_roofline", "selective_scan_step_roofline",
       "window_decode_attn_roofline.batch", "shared_kv_read_share",
       "prefill_cross_token_share", "sambay_kernels_device_share"}


@pytest.fixture(scope="module")
def cell():
    return Cell(os.path.join(REPO, "BENCHMARK.json"), CELL)


def test_the_cell_resolves_to_the_kinds_files(cell):
    assert cell.model_path == KIND and cell.chips == 1
    assert all(callable(getattr(cell.model, f)) for f in MODEL_API)
    for m in cell.metrics("per_layer"):
        assert callable(cell.reader(m["name"]))
    # (at least these: a later PR's reader of this cell does not redden it)
    assert {m["name"] for m in cell.metrics("per_layer")} >= BATCH | NEW | {
        "decode_attn_roofline.batch", "flash_window_prefill_roofline"}
    assert {m["name"] for m in cell.metrics("end_to_end")} >= {
        "latency_per_token_p95_ms", "setup_s"}
    # a new reader is this cell's alone
    for m in cell.metrics("per_layer"):
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
    t, dep = cell.traffic, cell.config["serve"]
    # ISSUE 60's traffic, letter for letter
    assert (t["loop"], t["clients"], t["ingress"], t["order"],
            t["shape_seed"], t["requests_per_client"], t["preroll_s"],
            t["drain_grace_s"], t["request_timeout_s"]) == (
        "closed", 64, "handle_stream", "fixed", 60, 16, 40, 120, 300)
    assert t["prompt"] == dict(dist="lognormal", median=2048, sigma=0.5,
                               lo=512, hi=4096)
    assert t["output"] == dict(dist="lognormal", median=1536, sigma=0.4,
                               lo=768, hi=3072)
    assert t["prefix"] == dict(pool=0, len=0) and t["temperature"] == 0.0
    assert t["clients"] == dep["num_slots"]
    assert t["prompt"]["hi"] + t["output"]["hi"] == 7168 < dep["max_len"]
    assert t["prompt"]["hi"] <= max(dep["buckets"])
    assert dep["buckets"] == [512, 1024, 2048, 4096] and dep["max_len"] == 8192
    assert dep["paged"] is False
    for word in ("paged", "spec_decode_enabled", "tp > 1", "training",
                 "largest bucket"):
        assert word in dep["refuses"]
    for key, value in dep["engine_kwargs"].items():
        assert any(f"{key} {value}" in d for d in cell.config["departures"])
    chk = dep["check"]
    assert chk["prompt_len"] % 128 and chk["prompt_len"] % 512 \
        and chk["decode_steps"] >= 512


def test_every_key_is_the_published_one_and_nothing_is_cut(cell):
    doc, entry = cell.config, cell.config_entry
    assert entry["reduced"] == [] and doc["reduced"] == {}
    assert "nothing is cut" in doc["reduced_why"]
    for key, value in PUBLISHED.items():
        assert doc[key] == value, key
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "Phi-4-mini-flash-reasoning"]
    assert row["config"] == PUBLISHED and entry["source"] == row["source_url"]
    assert entry["source"] == doc["source"]
    for key in ("layer_kinds", "mamba", "memory", "differential_attention",
                "head_pairing", "cross_attention", "norms", "positions",
                "head_dim", "state_dtype", "chunk"):
        assert key in doc["assumed"]
    said = " ".join(doc["departures"])
    for word in ("random", "tokenizer", "A_log", "N(0, 1/hidden)"):
        assert word in said
    assert "whole model" in doc["stands_for"]
    kinds = cell.model.kinds(doc)
    assert kinds[:16] == ("ssm1", "window") * 8
    assert kinds[16:18] == ("ssm1", "full")
    assert kinds[18:] == ("gmu", "cross") * 7
    assert cell.model.segments(doc) == (
        (("ssm1", "window"), 8), (("ssm1", "full"), 1), (("gmu", "cross"), 7))
    assert doc["params"]["held"] == cell.model.num_params(doc)


def test_the_counts_at_the_published_keys(cell):
    """ISSUE 60's table, redone from the built tree: a Mamba layer 119.8M,
    an attention layer 98.3M, a unit's 104.9M, a cross layer 91.75M, the
    embedding 512.2M: 3,852M parameters, 7.70 GB in bf16; rows 2.73 GB,
    rings 1.36, state and tails 0.21; the arguments 12.0 GB, 75% of the
    chip; and the bytes functions are ``cache_gauges``' numbers."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import decode
    m, doc = cell.model, cell.config
    per = m.layer_matrix_params(doc)
    assert per == {"mamba": 2560 * 10240 + 5120 * 192 + 160 * 5120
                   + 5120 * 2560,
                   "attention": 2 * 2560 * 2560 + 2 * 2560 * 1280,
                   "gmu": 2 * 2560 * 5120, "cross": 2 * 2560 * 2560,
                   "mlp": 3 * 2560 * 10240}
    assert [round((per[k] + per["mlp"]) / 1e6, 2) for k in (
        "mamba", "attention", "gmu", "cross")] == [119.77, 98.3, 104.86,
                                                   91.75]
    n = m.num_params(doc)
    assert n == 3_852_457_984 and round(2 * n / 1e9, 2) == 7.70
    cfg = m.program_config(doc)
    tree = jax.eval_shape(lambda k: m.init_params(k, cfg, jnp.bfloat16),
                          jax.random.PRNGKey(0))
    assert n == sum(a.size for a in jax.tree.leaves(tree))
    slots, max_len = doc["serve"]["num_slots"] + 1, doc["serve"]["max_len"]
    cache = jax.eval_shape(lambda: m.init_cache(cfg, slots, max_len,
                                                jnp.bfloat16))
    g = decode.cache_gauges(cfg, cache)
    assert g["cache_kv_bytes"] == g["cache_shared_kv_bytes"] == \
        slots * max_len * m.kv_bytes_held_per_token(doc)
    assert m.kv_bytes_held_per_token(doc) == 5120
    assert m.kv_bytes_per_token(doc) == 8 * 5120       # once a reading layer
    assert g["cache_ring_bytes"] == slots * m.ring_bytes_per_slot(doc, 512)
    assert g["cache_state_bytes"] == g["cache_state_hbm_bytes"] == slots * (
        m.state_bytes_per_slot(doc) + m.conv_bytes_per_slot(doc))
    assert cache["state"].shape == (9, slots, 16, 40, 128)
    assert (round(g["cache_kv_bytes"] / 1e9, 2),
            round(g["cache_ring_bytes"] / 1e9, 2),
            round(g["cache_state_bytes"] / 1e9, 2)) == (2.73, 1.36, 0.21)
    arguments = 2 * n + sum(v for k, v in g.items() if k in (
        "cache_kv_bytes", "cache_ring_bytes", "cache_state_bytes"))
    assert round(arguments / 1e9, 1) == 12.0 and arguments / 16e9 > 0.6
    # a decode step at 64 slots and 3,100 live positions a slot: the shared
    # rows read by eight layers are its largest stream
    step = m.decode_step_bytes(doc, 64, 64 * 3100)
    shared = m.decode_shared_kv_bytes(doc, 64 * 3100)
    assert round(step / 1e9, 2) == 17.55 and round(shared / 1e9, 2) == 8.13
    assert m.decode_state_bytes(doc, 64) == 2 * 64 * 9 * 16 * 5120 * 4
    # a 2,048 row: 18 layers over the row, 14 over its last token
    assert 8.0e12 < m.prefill_row_flops(doc, 2048) < 8.6e12


def test_the_kind_refuses_to_load_over_a_program_without_segments(tmp_path):
    """As on the parent of PR 60, whose ``models/config.py`` has no stack of
    segments: the cell has to fail at once there, with the harness's own
    error, in the process that resolves its files."""
    fake = tmp_path / "ray_tpu"
    (fake / "models").mkdir(parents=True)
    (fake / "__init__.py").write_text("")
    (fake / "models" / "__init__.py").write_text("")
    (fake / "models" / "config.py").write_text("layer_pattern = ()\n")
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
    assert "REFUSED" in p.stdout and "no layer_segments" in p.stdout
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
            cfg.head_dim, cfg.mlp_size, cfg.vocab_size, cfg.sliding_window
            ) == (32, 2560, 40, 20, 64, 10240, 200064, 512)
    assert (cfg.ssm1_inner, cfg.ssm1_state, cfg.ssm1_dt_rank,
            cfg.linear_conv_width) == (5120, 16, 160, 4)
    assert cfg.tied_embeddings and cfg.no_positions and not cfg.use_rope
    assert cfg.diff_attn and not cfg.use_rmsnorm and cfg.cross_segment == 2
    assert (cfg.ssm1_layers, cfg.window_layers, cfg.full_layers,
            cfg.layer_pattern.count("gmu"), cfg.layer_pattern.count("cross")
            ) == (9, 8, 1, 7, 7)
    for change, match in (
            (dict(hidden_act="gelu"), "hidden_act"),
            (dict(tie_word_embeddings=False), "tie_word_embeddings"),
            (dict(mb_per_layer=4), "mb_per_layer"),
            (dict(num_hidden_layers=30), "multiple of 4"),
            (dict(num_key_value_heads=5), "whole pairs")):
        with pytest.raises(ValueError, match=match):
            model.program_config({**PUBLISHED, **change})
    lacking = {k: v for k, v in PUBLISHED.items() if k != "sliding_window"}
    with pytest.raises(ValueError, match="sliding_window"):
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


def test_prefill_then_decode_through_the_cache_equals_the_reference(tiny):
    """The harness's own comparison (``serve_app._check_reference``: the
    kind's entry points, a prefill then decode steps, against ``logits``) on
    the tests' tiny configuration, in float32 weights and bf16 compute as a
    cell runs it; and the loss is the logits' cross entropy."""
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
    # bf16 compute at hidden 64 through 12 layers: some percent of the
    # logits' deviation (0.062 here; a wrong tail, ring or memory reads 0.3)
    assert float(np.sqrt((diff ** 2).mean())) < 0.1
    whole = np.asarray(model.logits(params, toks, doc))
    logp = whole[:-1] - np.log(np.exp(whole[:-1]).sum(-1, keepdims=True))
    assert float(model.loss(params, toks, doc)) == pytest.approx(
        -logp[np.arange(47), toks[1:]].mean(), rel=1e-5)


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
    sample = types.SimpleNamespace(prompt_len=2000, token_times=[0.0] * 1500,
                                   t_fired=-1.0, t_end=99.0)
    return {"model": cell.model, "config": cell.config,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "trace": {"ops": ops, "programs": [], "busy_s": busy},
            "span": {"t0": 0.0, "t1": 5.0, "stats0": stats0,
                     "stats1": stats1},
            "stats0": stats0, "stats1": stats1, "samples": [sample]}


def test_the_readers_on_a_made_up_span(cell):
    """The six kernels' seconds over the busy time; the shared rows' bytes
    over a step's at the span's mean active slots and counted positions; the
    three rooflines with this kind's counts; the cross-decoder's tokens over
    the self-decoder's; a span without the kernels, a program without the
    counters (the parent's) or a kind without the counts reads nothing and
    raises nothing."""
    from benchmark.lib import trace
    m, doc = cell.model, cell.config
    zero = dict(steps=0, admit_batches=0, tokens_out=0, admit_tokens_real=0,
                shared_kv_positions_read=0, prefill_self_tokens=0,
                prefill_cross_tokens=0)
    after = dict(steps=1010, admit_batches=10, tokens_out=60 * 1010,
                 admit_tokens_real=20000, prefill_self_tokens=20000,
                 prefill_cross_tokens=10,
                 shared_kv_positions_read=8 * 1000 * 60 * 3000)
    step_s = m.selective_scan_step_bytes(doc, 60 * 1000) / 819e9
    chunk = max(m.selective_scan_chunk_fwd_bytes(doc, 20000) / 819e9,
                m.selective_scan_chunk_fwd_flops(doc, 20000) / 197e12)
    ring = m.window_decode_attn_bytes(doc, 60 * 1000) / 819e9
    ops = [["selective_scan_step" + trace.PALLAS_TAG, 2 * step_s, 9],
           ["selective_scan_chunk_fwd" + trace.PALLAS_TAG, 5 * chunk, 9],
           ["window_decode_attn" + trace.PALLAS_TAG, 4 * ring, 9],
           ["decode_attn" + trace.PALLAS_TAG, 0.25, 9],
           ["flash_window_prefill" + trace.PALLAS_TAG, 0.125, 9],
           ["flash_fwd" + trace.PALLAS_TAG, 0.125, 9],
           ["flash_fwd_rows" + trace.PALLAS_TAG, 7.0, 9]]
    busy = 4 * (2 * step_s + 5 * chunk + 4 * ring + 0.5)
    ctx = _ctx(cell, ops, zero, after, busy)
    read = {name: cell.reader(name)(ctx) for name in NEW}
    assert read["selective_scan_step_roofline"] == pytest.approx(50.0)
    assert read["selective_scan_chunk_fwd_roofline"] == pytest.approx(20.0)
    assert read["window_decode_attn_roofline.batch"] == pytest.approx(25.0)
    assert read["sambay_kernels_device_share"] == pytest.approx(25.0)
    assert read["prefill_cross_token_share"] == pytest.approx(0.05)
    shared = 60 * 3000 * 8 * 5120
    assert read["shared_kv_read_share"] == pytest.approx(
        100 * shared / m.decode_step_bytes(doc, 60, 60 * 3000))
    assert 43 < read["shared_kv_read_share"] < 47
    for name in NEW:
        reader = cell.reader(name)
        bare = dict(tokens_out=0)
        assert reader(_ctx(cell, [], bare, bare)) is None
        assert reader(dict(_ctx(cell, [], zero, zero),
                           model=types.SimpleNamespace())) is None
