"""The block kind ``exaone_moe`` as files (``models/exaone_moe.py``, the
configuration ``k-exaone-236b-a23b-serve-l8-e8``, its cell, traffic and
readers): the lookup by ``model_type``, the published widths against the
catalog's, the counts of the published model from the file's own keys, the
refusal to load over a program without the window kind, the harness's own
comparison on the tests' tiny configuration, the reference's independence of
the program, and the six readers on a made-up context.  A file of its own: a
``model_config`` PR adds files to the benchmark and edits none.  The kernels
against their twins, the verify window on the ring, the block against the
reference and the shares adding up are ``tests/test_exaone_moe.py``
(tier-1)."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib.manifest import MODEL_API, Cell, load_model
from benchmark.tests.test_runners import REPO, run_cell

BENCH = os.path.join(REPO, "benchmark")
KIND = os.path.join(BENCH, "models", "exaone_moe.py")
TINY = os.path.join(BENCH, "tests", "tiny", "configs", "tiny-exaone.json")
CELL = "serve-swa-moe-mtp-longreason-closed"
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
#: the published file's numbers and settings (the model-configs catalog's row)
PUBLISHED = dict(
    first_k_dense_replace=1, head_dim=128, hidden_act="silu",
    hidden_size=6144, intermediate_size=18432, layer_types=PERIOD * 12,
    max_position_embeddings=262144,
    mlp_layer_types=["dense"] + ["sparse"] * 47, model_type="exaone_moe",
    moe_intermediate_size=2048, mtp_layer_types=["full_attention"],
    mtp_sliding_windows=[0], n_group=1, norm_topk_prob=True,
    num_attention_heads=64, num_experts=128, num_experts_per_tok=8,
    num_hidden_layers=48, num_key_value_heads=8, num_nextn_predict_layers=1,
    num_shared_experts=1, rms_norm_eps=1e-5,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    routed_scaling_factor=2.5, scoring_func="sigmoid", sliding_window=128,
    sliding_window_pattern="LLLG", sliding_windows=[128, 128, 128, 0] * 12,
    tie_word_embeddings=False, topk_group=1, vocab_size=153600)
CUT = ("num_hidden_layers", "layer_types", "mlp_layer_types",
       "sliding_windows", "num_experts", "vocab_size")


@pytest.fixture(scope="module")
def cell():
    return Cell(os.path.join(REPO, "BENCHMARK.json"), CELL)


def test_the_cell_resolves_to_the_kinds_files(cell):
    assert cell.model_path == KIND and cell.chips == 1
    assert all(callable(getattr(cell.model, f)) for f in MODEL_API)
    for m in cell.metrics("per_layer"):
        assert callable(cell.reader(m["name"]))
    assert {m["name"] for m in cell.metrics("per_layer")} == {
        "spec_step_device_ms.batch", "spec_step_batch_roofline",
        "mtp_accept_share", "window_decode_attn_roofline",
        "flash_window_prefill_roofline", "swa_moe_kernels_device_share",
        "moe_gmm_roofline", "moe_experts_touched_share",
        "stream_admit_stall_share.batch", "decode_attn_roofline.batch",
        "prefill_ms_per_admitted_ktoken.batch"}
    # a step that is not ``engine_decode``: the two readers of that program
    # are not this cell's, nor the occupancy whose tokens a slot-step can
    # pass one
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "latency_per_token_p95_ms", "setup_s"}
    t, dep = cell.traffic, cell.config["serve"]
    assert (t["loop"], t["clients"], t["ingress"], t["order"],
            t["shape_seed"], t["requests_per_client"], t["preroll_s"]) == (
        "closed", 48, "handle_stream", "fixed", 50, 16, 20)
    assert t["prompt"] == dict(dist="lognormal", median=2048, sigma=0.5,
                               lo=512, hi=4096)
    assert t["output"] == dict(dist="lognormal", median=1024, sigma=0.4,
                               lo=512, hi=2048)
    assert t["temperature"] == 0.0 and t["prefix"] == {"pool": 0, "len": 0}
    assert t["clients"] == dep["num_slots"] == 48
    assert t["prompt"]["hi"] + t["output"]["hi"] <= dep["max_len"] == 6144
    assert dep["buckets"] == [512, 1024, 2048, 4096]
    assert dep["paged"] is False
    # the model's own block drafts one token a round, fixed
    kw = dep["engine_kwargs"]
    assert kw["spec_decode_enabled"] is True and kw["spec_adaptive"] is False
    assert any("spec_decode_enabled" in d for d in cell.config["departures"])
    assert any("by chance" in d for d in cell.config["departures"])
    # a prompt of a few windows that is no multiple of 128 or of a flash
    # block, the flash kernels' path (1,024 up), and rounds that wrap the
    # ring of 256 twice
    chk = dep["check"]
    assert chk["prompt_len"] % 128 and chk["prompt_len"] > 1024
    assert chk["decode_steps"] >= 2 * 256 >= 300


def test_every_width_is_the_published_one_and_the_cut_is_stated(cell):
    doc, entry = cell.config, cell.config_entry
    assert sorted(entry["reduced"]) == sorted(CUT) == sorted(doc["reduced"])
    for key, value in PUBLISHED.items():
        if key in CUT:
            assert doc[key] != value
            assert doc[key] == (value[:8] if isinstance(value, list)
                                else doc["reduced"][key]["here"])
        else:
            assert doc[key] == value, key
    assert [doc["reduced"][k]["published"] for k in (
        "num_hidden_layers", "num_experts", "vocab_size")] == [
            48, 128, 153600]
    assert (doc["num_hidden_layers"], doc["num_experts"],
            doc["vocab_size"]) == (8, 8, 19200)
    assert doc["layer_types"] == PERIOD * 2
    assert doc["share"]["chips"] == 16 and doc["share"]["expert_start"] == 0
    assert doc["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert doc["num_experts"] * 16 == PUBLISHED["num_experts"]
    for key in ("qk_norm", "rotary_by_kind", "window", "block", "router",
                "mtp", "dense_layer"):
        assert key in doc["assumed"]
    assert "modeling_exaone4.py" in doc["assumed"]["qk_norm"]
    said = " ".join(doc["departures"])
    for word in ("random", "tokenizer", "selection bias", "ring of 256"):
        assert word in said
    assert "4 pipeline stages x 16 chips" in doc["stands_for"]
    assert "a sixteenth" in doc["stands_for"]
    assert entry["source"] == doc["source"] and \
        "K-EXAONE-236B-A23B" in doc["source"]
    assert cell.model.period(doc) == ("window", "window", "window", "full")


def test_the_counts_of_the_published_model(cell):
    """236B in all and about 23B a token, from the file's own keys and its
    ``reduced``; what is held here, and the bytes the file states."""
    m, doc = cell.model, cell.config
    pub = m.published_params(doc)
    assert 236.0e9 < pub["total"] < 237.0e9
    assert 23.0e9 < pub["active"] < 24.0e9
    # the block, which the published count leaves out, is a layer and W_eh
    assert pub["total_with_block"] - pub["total"] == pytest.approx(
        5.06e9, rel=0.01)
    held = m.num_params(doc)
    assert held == doc["params"]["held"] == 4_394_720_512
    per = m.layer_matrix_params(doc)
    assert per["attention"] + per["dense"] == pytest.approx(453.0e6, rel=1e-3)
    assert (per["attention"] + per["shared"] + per["router"]
            + 8 * per["expert"]) == pytest.approx(453.8e6, rel=1e-3)
    # rows of 48 + 1 slots x 6,144 for 2 full layers and the block, rings of
    # 256 for 6 window layers: with the weights, three quarters of the chip
    rows = 49 * 6144 * m.kv_bytes_per_token(doc)
    rings = 49 * m.ring_bytes_per_slot(doc, 256)
    assert rows == pytest.approx(3.70e9, rel=0.01)
    assert rings == pytest.approx(0.31e9, rel=0.02)
    assert 0.70 < (2 * held + rows + rings) / 16.9e9 < 0.80
    # a step's bytes at the cell's mean context: weights read once, all 8
    # experts touched by 96 tokens, 1.7 GB of rows and 0.15 GB of windows
    step = m.spec_step_bytes(doc, 48, 48 * 2800)
    assert 10.0e9 < step < 11.0e9
    assert m.experts_touched(doc, 96) > 7.98
    assert m.window_decode_attn_bytes(doc, 48) == pytest.approx(
        48 * 6 * 129 * 4096)


def test_the_kind_refuses_to_load_over_a_program_without_windows(tmp_path):
    """As on the parent of PR 50: the cell has to fail at once there, with
    the harness's own error, in the process that resolves its files."""
    fake = tmp_path / "ray_tpu"
    (fake / "models").mkdir(parents=True)
    (fake / "__init__.py").write_text("")
    (fake / "models" / "__init__.py").write_text("")
    (fake / "models" / "config.py").write_text("KINDS = ('linear', 'full')\n")
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
    assert "REFUSED" in p.stdout and "no 'window' kind" in p.stdout
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


def test_the_harness_comparison_on_the_tiny_configuration():
    """The harness's own comparison (``serve_app._check_reference``: the
    kind's entry points, a prefill then decode steps, against ``logits``) on
    the tests' tiny configuration, in float32 weights and bf16 compute as a
    cell runs it: the decode step is the verify step of two tokens with the
    draft rolled back, on a ring with the window's margin."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    model = load_model(KIND)
    with open(TINY) as f:
        doc = json.load(f)
    cfg = model.program_config(doc)
    params = model.init_params(jax.random.PRNGKey(2), cfg, jnp.float32)
    n_prompt, n = 37, 80
    toks = np.random.default_rng(3).integers(1, 256, size=n).astype(np.int32)
    pos = jnp.arange(n_prompt - 1, n)
    want = np.asarray(jax.jit(lambda p, t: model.logits(p, t, doc, pos))(
        params, toks))
    cache = model.init_cache(cfg, 1, 128, jnp.bfloat16)
    assert cache["wk"].shape == (6, 1, 16, 32)
    cache, lg = jax.jit(lambda p, c, t, ln, sl: model.prefill(
        p, c, t, ln, sl, cfg))(params, cache, toks[None, :n_prompt],
                               np.array([n_prompt], np.int32),
                               np.array([0], np.int32))
    got = [np.asarray(lg)[0]]
    step = jax.jit(lambda p, c, t, a: model.decode_step(p, c, t, a, cfg))
    for i in range(n_prompt, n):
        cache, lg = step(params, cache, toks[i:i + 1], np.ones((1,), bool))
        got.append(np.asarray(lg)[0])
    assert int(cache["length"][0]) == n
    rms = float(np.sqrt(((np.stack(got) - want) ** 2).mean()))
    assert np.isfinite(np.stack(got)).all() and want.std() > 0.5
    # bf16 compute at hidden 64 with near-ties of 3 of 16 and scores of
    # spread 4 (``sharpened``): about half the logits' deviation at most
    assert rms < 0.6 * want.std(), (rms, want.std())


def test_the_reference_runs_nothing_of_the_program():
    """Section 3 reads the program's parameter tree and calls ``jax`` alone:
    no function from the head of the section to the counts imports or names
    ``ray_tpu`` or an entry point, and the reference is told the share and
    nothing of a run."""
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
    assert list(inspect.signature(model.mtp_logits).parameters) == [
        "params", "tokens", "doc", "positions"]


def test_the_references_band_is_the_window():
    """A sliding layer's output at position t moves with position t - 127's
    input... and not with t - 128's: here at the tiny window of 8, on one
    sliding layer alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    model = load_model(KIND)
    with open(TINY) as f:
        doc = json.load(f)
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    ap = {"wq": jax.random.normal(ks[0], (64, 64)) / 8,
          "wk": jax.random.normal(ks[1], (64, 32)) / 8,
          "wv": jax.random.normal(ks[2], (64, 32)) / 8,
          "wo": jax.random.normal(ks[3], (64, 64)) / 8,
          "q_norm": {"scale": jnp.ones((16,))},
          "k_norm": {"scale": jnp.ones((16,))}}
    x = jax.random.normal(ks[4], (24, 64))
    out = model._attention(x, ap, doc, True)
    far = model._attention(x.at[12].add(1.0), ap, doc, True)
    moved = np.abs(np.asarray(far - out)).max(-1) > 1e-6
    assert moved[12:20].all() and not moved[:12].any()
    assert not moved[20:].any()                 # 12 + 8 on: out of the band
    full = model._attention(x.at[12].add(1.0), ap, doc, False) \
        - model._attention(x, ap, doc, False)
    assert (np.abs(np.asarray(full)).max(-1) > 1e-6)[12:].all()


def _ctx(cell, ops, programs, stats0, stats1, busy=2.0):
    sample = types.SimpleNamespace(prompt_len=2300, token_times=[0.0] * 1000,
                                   t_fired=-1.0, t_end=99.0)
    return {"model": cell.model, "config": cell.config,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "trace": {"ops": ops, "programs": programs, "busy_s": busy,
                      "devices": [{}]},
            "span": {"t0": 0.0, "t1": 5.0, "stats0": stats0,
                     "stats1": stats1},
            "stats0": stats0, "stats1": stats1, "samples": [sample]}


def test_the_six_readers_on_a_made_up_span(cell):
    """Device seconds equal to twice the least time read 50%; a span without
    the kernels or the program, or a program without the counters (the
    parent's), reads nothing and raises nothing."""
    from benchmark.lib import trace
    m, doc = cell.model, cell.config
    zero = dict(steps=0, admit_batches=0, tokens_out=0, admit_tokens_real=0,
                admit_tokens_padded=0, admitted_requests=0, spec_rounds=0,
                spec_drafted=0, spec_accepted=0, window_layers=6,
                moe_experts_touched=0, moe_expert_layer_steps=0)
    after = dict(moe_experts_touched=8 * 8000, moe_expert_layer_steps=8000,
                 steps=1010, admit_batches=10, tokens_out=48 * 1000 + 10,
                 admit_tokens_real=30000, admit_tokens_padded=10960,
                 admitted_requests=10, spec_rounds=48 * 1000,
                 spec_drafted=48 * 1000, spec_accepted=480, window_layers=6)
    ring_s = m.window_decode_attn_bytes(doc, 48 * 1000) / 819e9
    band_s = max(m.flash_window_prefill_flops(doc, 1, 4096) / 197e12,
                 m.flash_window_prefill_bytes(doc, 1, 4096) / 819e9) * 10
    step_s = m.spec_step_bytes(doc, 48, 48 * 2800) / 819e9
    ops = [["window_decode_attn" + trace.PALLAS_TAG, 2 * ring_s, 6000],
           ["flash_window_prefill" + trace.PALLAS_TAG, 4 * band_s, 60],
           ["decode_attn" + trace.PALLAS_TAG, 0.3, 9],
           ["moe_gmm" + trace.PALLAS_TAG, 0.2, 9]]
    programs = [["jit_engine_spec_decode", 2 * step_s * 1000, 250]]
    busy = 4 * (2 * ring_s + 4 * band_s + 0.5)
    ctx = _ctx(cell, ops, programs, zero, after, busy)
    read = {name: cell.reader(name)(ctx) for name in (
        "spec_step_device_ms.batch", "spec_step_batch_roofline",
        "mtp_accept_share", "window_decode_attn_roofline",
        "flash_window_prefill_roofline", "swa_moe_kernels_device_share")}
    assert read["spec_step_device_ms.batch"] == pytest.approx(
        2 * step_s * 1000.0)
    assert read["spec_step_batch_roofline"] == pytest.approx(50.0, rel=1e-3)
    # experts that were not read are not a round's least bytes: half of the
    # held 8 read a layer takes 8 x 4 x 75.5 MB off the 10.6 GB
    less = dict(after, moe_experts_touched=4 * 8000)
    assert cell.reader("spec_step_batch_roofline")(
        _ctx(cell, ops, programs, zero, less, busy)) == pytest.approx(
            50.0 * m.spec_step_bytes(doc, 48, 48 * 2800, experts_read=4.0)
            / m.spec_step_bytes(doc, 48, 48 * 2800), rel=1e-3)
    assert read["mtp_accept_share"] == pytest.approx(1.0)
    assert read["window_decode_attn_roofline"] == pytest.approx(50.0)
    # 10 rows of 4,096 walked, every one through the kernel in 6 layers
    assert read["flash_window_prefill_roofline"] == pytest.approx(25.0)
    assert read["swa_moe_kernels_device_share"] == pytest.approx(25.0)
    # rows under 1,024 positions take plain attention: half the rows' calls,
    # half the positions counted
    ops[1][2] = 30
    assert cell.reader("flash_window_prefill_roofline")(ctx) == \
        pytest.approx(12.5)
    for name in read:
        if name != "mtp_accept_share":
            assert cell.reader(name)(_ctx(cell, [], [], zero, after)) is None
    bare = dict(tokens_out=0, steps=0, admit_batches=0)
    for name in read:           # (the share reads the trace alone)
        if name != "swa_moe_kernels_device_share":
            assert cell.reader(name)(_ctx(cell, ops, programs, bare,
                                          bare)) is None
    # the reader of the rows' kernel holds at a window of two: a round is
    # one call a full layer and the block, each over the live rows once
    assert m.kv_bytes_per_token(doc) == 3 * 2 * 8 * 128 * 2
