"""The block kind ``mellum`` as files (``models/mellum.py``, the configuration
``mellum2-12b-a2.5b-train-l4``, its cell and its five readers): the lookup
by ``model_type``, the published widths against the catalog's, the cut as
the file states it, the refusal to load over a program without the exchange,
the program against the kind's reference at the tiny size through the train
runner on four virtual devices, the reference's own band and YaRN, the
counts against hand sums and the readers on a made-up trace.  A file of its
own: a ``model_config`` PR adds files to the benchmark and edits none.  The
comparison of the program's loss and gradients with the kind's reference is
``tests/test_mellum_grads.py`` (tier-1)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.lib.manifest import MODEL_API, Cell, load_model
from benchmark.tests.test_runners import REPO, TINY, last_json, run_cell

BENCH = os.path.join(REPO, "benchmark")
KIND = os.path.join(BENCH, "models", "mellum.py")
CELL = "train-swa-moe-ep4-s8192"
NEW = ("flash_full_train_roofline", "flash_window_train_roofline",
       "moe_ep_exchange_device_share", "swa_attn_train_kernels_device_share",
       "moe_ep_chip_wait_spread")
#: the published file's numbers (the model-configs catalog's row)
PUBLISHED = dict(
    attention_bias=False, head_dim=128, hidden_act="silu", hidden_size=2304,
    intermediate_size=7168, max_position_embeddings=131072,
    max_window_layers=0, model_type="mellum", moe_intermediate_size=896,
    norm_topk_prob=True, num_attention_heads=32, num_experts=64,
    num_experts_per_tok=8, num_hidden_layers=28, num_key_value_heads=4,
    rms_norm_eps=1e-6, sliding_window=1024, tie_word_embeddings=False,
    vocab_size=98304, use_sliding_window=True)


@pytest.fixture(scope="module")
def cell():
    return Cell(os.path.join(REPO, "BENCHMARK.json"), CELL)


def test_the_cell_resolves_to_the_kinds_files(cell):
    assert cell.model_path == KIND and cell.chips == 4
    assert all(callable(getattr(cell.model, f)) for f in MODEL_API)
    for m in cell.metrics("per_layer"):
        assert callable(cell.reader(m["name"]))
    assert {m["name"] for m in cell.metrics("per_layer")} == {
        "device_idle_share.train", "train_step_ms", "train_step_mfu",
        "train_step_device_ms", "collective_exposed_share",
        "moe_gmm_train_roofline", "moe_train_kernels_device_share", *NEW}
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "train_tokens_per_s_per_chip", "setup_s"}
    tr = cell.config["train"]
    assert cell.entry["traffic"] == "pretrain-s8192"
    assert (cell.traffic["loop"], cell.traffic["data"]) == (
        "train", "uniform_tokens")
    assert (tr["global_batch"], tr["sequence_length"], tr["mesh"]) == (
        4, 8192, {"fsdp": 1, "ep": 4})
    # a run past its warm-up: the window's loss falls by more than its noise
    assert tr["optimizer"] == {"learning_rate": 4e-4, "warmup_steps": 1}
    assert tr["remat"] == "save_acts"


def test_every_width_is_the_published_one_and_the_cut_is_stated(cell):
    doc, entry = cell.config, cell.config_entry
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "mlp_layer_types"]
    assert sorted(doc["reduced"]) == sorted(entry["reduced"])
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert doc["reduced"][key]["published"] == value
            assert doc[key] == doc["reduced"][key]["here"] != value
        else:
            assert doc[key] == value, key
    assert doc["layer_types"] == ["sliding_attention"] * 3 + [
        "full_attention"]
    assert doc["mlp_layer_types"] == ["sparse"] * 4
    assert doc["rope_parameters"] == {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
    for word in ("block", "q and k norms", "experts", "router_aux_loss_coef"):
        assert "modeling_qwen3_moe.py" in doc["assumed"][word] \
            or "Qwen3MoeConfig" in doc["assumed"][word], word
    said = " ".join(doc["departures"])
    for word in ("MTP head", "selection bias", "no capacity", "random"):
        assert word in said, word
    assert "pipeline stage" in doc["stands_for"] and "40%" in doc["stands_for"]
    assert entry["source"] == doc["source"] and "Mellum2-12B" in doc["source"]
    kw = cell.model.program_kwargs(doc)
    assert (kw["num_experts"], kw["experts_per_token"], kw["layer_pattern"],
            kw["moe_router"], kw["moe_balance_weight"], kw["attn_head_dim"],
            kw["rope_yarn_kinds"], kw["rope_yarn_factor"]) == (
        64, 8, ("window",) * 3 + ("full",), "softmax", 0.001, 128,
        ("full",), 16.0)
    # nothing of a layer is cut: every expert, the whole vocabulary
    assert "num_experts" not in doc["reduced"] \
        and "vocab_size" not in doc["reduced"]


@pytest.mark.parametrize("change,match", [
    (dict(mlp_layer_types=["dense"] + ["sparse"] * 3), "mlp_layer_types"),
    (dict(layer_types=["chunked_attention"] * 4), "layer_types"),
    (dict(layer_types=["full_attention"] * 3), "one entry a layer"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(max_window_layers=2), "max_window_layers"),
    (dict(rope_parameters={
        "full_attention": {"rope_type": "default", "rope_theta": 5e5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 5e5}}),
     "YaRN on the full layers"),
])
def test_the_kind_refuses_what_the_block_cannot_express(cell, change, match):
    with pytest.raises(ValueError, match=match):
        cell.model.program_kwargs(dict(cell.config, **change))


def test_an_attention_factor_that_is_not_the_formulas_is_refused(cell):
    rp = json.loads(json.dumps(cell.config["rope_parameters"]))
    rp["full_attention"]["attention_factor"] = 1.0
    with pytest.raises(ValueError, match="attention_factor"):
        cell.model.program_kwargs(dict(cell.config, rope_parameters=rp))


@pytest.mark.parametrize("call", [
    lambda m, doc: m.init_cache(None, 1, 1, None),
    lambda m, doc: m.prefill(None, None, None, None, None, None),
    lambda m, doc: m.decode_step(None, None, None, None, None),
    lambda m, doc: m.decode_step_bytes(doc, 1, 1),
    lambda m, doc: m.decode_step_flops(doc, 1, 1),
], ids=["init_cache", "prefill", "decode_step", "decode_step_bytes",
        "decode_step_flops"])
def test_what_a_train_cell_never_calls_says_so(cell, call):
    with pytest.raises(NotImplementedError, match="serve cell's"):
        call(cell.model, cell.config)


def test_the_kind_refuses_to_load_over_a_program_without_the_exchange(
        tmp_path):
    """As on the parent of PR 58 (its ``ops/moe.py`` has the grouped
    products' backward and no ``moe_dropless_ep``): the cell has to fail at
    once there, with the harness's own error, in the process that resolves
    its files."""
    fake = tmp_path / "ray_tpu"
    (fake / "ops").mkdir(parents=True)
    (fake / "__init__.py").write_text("")
    (fake / "ops" / "__init__.py").write_text("")
    (fake / "ops" / "moe.py").write_text("KERNEL_MOE_GMM_DW = 'moe_gmm_dw'\n")
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
    assert "REFUSED" in p.stdout and "no exchange" in p.stdout
    assert "jax" not in (p.stdout + p.stderr).lower()


def test_loading_the_kind_imports_no_jax_and_nothing_of_the_programs_models():
    p = subprocess.run(
        [sys.executable, "-c",
         "import sys; from benchmark.lib.manifest import load_model; "
         "load_model(sys.argv[1]); assert 'jax' not in sys.modules; "
         "assert not [m for m in sys.modules if m.startswith('ray_tpu')]",
         KIND], capture_output=True, text=True, cwd=REPO)
    assert p.returncode == 0, p.stderr
    # the reference (group 3) names nothing of the program: only groups 1
    # and 2 import it, inside their functions
    text = open(KIND).read()
    ref = text[text.index("# ------------------------------------------------- "
                          "3. the plain reference"):]
    assert "ray_tpu" not in ref.replace("``ray_tpu.models``", "").replace(
        "``ray_tpu.ops``", "")


def test_the_cell_refuses_to_run_without_its_chips():
    p = run_cell(os.path.join(REPO, "BENCHMARK.json"), CELL, seconds=1,
                 devices=4)
    assert p.returncode != 0 and "needs 4 tpu device" in p.stderr


def test_the_tiny_period_trains_through_the_runner_beside_its_reference(
        tmp_path):
    """The train runner on four virtual CPU devices with the tiny ``mellum``
    configuration (one period, eight experts two a device, exchanged): the
    first step's loss beside the kind's reference, a loss that falls, the
    new readers silent without a device trace."""
    root = tmp_path / "bench"
    shutil.copytree(TINY, root)
    m = json.load(open(root / "BENCHMARK.json"))
    m["paths"] = [".", BENCH]
    m["configs"].append({"name": "tiny-mellum", "source": "tests",
                         "file": "configs/tiny-mellum.json", "reduced": [],
                         "why": "toy"})
    m["workloads"].append({"name": "tiny-mellum4", "config": "tiny-mellum",
                           "traffic": "tiny-job", "chips": 4, "why": "x"})
    for e in m["end_to_end"] + m["per_layer"]:
        if "tiny-train4" in e.get("workloads", []):
            e["workloads"].append("tiny-mellum4")
    for name in NEW:
        m["per_layer"].append({
            "name": name, "unit": "%", "better": "higher",
            "source": "device_trace", "layer": "kernels",
            "moves": "train_tokens_per_s_per_chip",
            "workloads": ["tiny-mellum4"]})
    json.dump(m, open(root / "BENCHMARK.json", "w"))
    out = last_json(run_cell(str(root / "BENCHMARK.json"), "tiny-mellum4",
                             trace=1, devices=4))
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 3
    assert out["device"]["count"] == 4
    assert out["compared"]["first_loss_gap"]["value"] < 0.1
    assert "train_step_ms" in out["metrics"]
    assert not set(NEW) & set(out["metrics"])


# ------------------------------------------------- the reference's own band

def test_the_references_band_and_tables_are_the_published_ones(cell):
    """A sliding layer's position reads its last ``sliding_window``
    positions and no more; the two kinds' rotary tables are the published
    sections'."""
    import jax
    import jax.numpy as jnp
    m = cell.model
    doc = json.load(open(os.path.join(BENCH, "tests", "tiny", "configs",
                                      "tiny-mellum.json")))
    cfg = m.program_config(doc)
    params = m.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    ap = jax.tree.map(lambda a: a[0, 0], params["blocks"]["window"]["attn"])
    x = jax.random.normal(jax.random.PRNGKey(1), (64, doc["hidden_size"]))
    w = doc["sliding_window"]
    base = m._attention(x, ap, doc, "sliding_attention")
    moved = m._attention(x.at[10].add(1.0), ap, doc, "sliding_attention")
    changed = jnp.abs(moved - base).max(-1) > 1e-6
    assert bool(changed[10 + w - 1]) and not bool(changed[10 + w:].any())
    assert not bool(changed[:10].any())
    full = m._attention(x.at[10].add(1.0), ap, doc, "full_attention") \
        - m._attention(x, ap, doc, "full_attention")
    assert bool((jnp.abs(full).max(-1) > 1e-6)[10 + w:].all())
    inv, mag = m.rope_inverse_frequencies(cell.config, "full_attention")
    plain, one = m.rope_inverse_frequencies(cell.config, "sliding_attention")
    assert (mag, one) == (1.2772588722239782, 1.0)
    assert plain[0] == inv[0] == 1.0            # the fastest: extrapolated
    assert inv[-1] == pytest.approx(plain[-1] / 16)    # the slowest: / 16
    assert all(a <= b for a, b in zip(inv, plain))


# ----------------------------------------------------- counts by hand

H, EM, V = 2304, 896, 98304
ATTENTION = 2 * H * 32 * 128 + 2 * H * 4 * 128
EXPERT = 3 * H * EM


def test_counts_of_the_l4_configuration(cell):
    m, doc = cell.model, cell.config
    assert m.layer_matrix_params(doc) == {
        "attention": ATTENTION, "expert": EXPERT, "router": H * 64}
    assert (ATTENTION, EXPERT) == (21_233_664, 6_193_152)
    small = 4 * (2 * H + 2 * 128 + 64) + H
    assert m.num_params(doc) == (
        4 * (ATTENTION + H * 64 + 64 * EXPERT) + 2 * V * H + small
    ) == doc["params"]["whole"] == 2_123_978_240
    assert m.chips(doc) == 4 and m.period(doc) == ("window",) * 3 + ("full",)
    band = m.band_mean(doc, 8192)
    assert band == pytest.approx((1024 * 1025 / 2 + 7168 * 1024) / 8192)
    met = 4 * (ATTENTION + H * 64 + 8 * EXPERT) + V * H
    assert m.train_flops_per_token(doc, 8192) == pytest.approx(
        6 * met + 12 * 32 * 128 * (4096 + 3 * band))
    assert m.train_flops_per_token(doc, 8192) == pytest.approx(3.404e9,
                                                               rel=1e-3)


def test_the_kernels_counts_follow_the_passes_the_program_runs(cell):
    m, doc = cell.model, cell.config
    assert m.moe_gmm_train_calls(doc) == {"moe_gmm": 24, "moe_gmm_dx": 8,
                                          "moe_gmm_dw": 12}
    assert m.moe_gmm_train_passes(doc) == 4
    # a chip's tokens x 8 assignments a layer land on its experts, the mean
    assert m.moe_gmm_train_flops(doc, 8192) == pytest.approx(
        4 * 2 * EXPERT * 8192 * 8 * 4)
    assert m.moe_gmm_train_bytes(doc, 8192) == pytest.approx(
        4 * (4 * 16 * EXPERT * (3 * 2 + 4)
             + 4 * 8192 * 8 * (2 * H + 3 * EM) * 2))
    assert m.flash_attention_flops(doc, 1, 8192, True) == pytest.approx(
        7 * 2 * 8192 ** 2 * 128 * 32 / 2)
    assert m.flash_attention_flops(doc, 1, 8192, False) == pytest.approx(
        2 * 2 * 8192 ** 2 * 128 * 32 / 2)
    assert m.flash_attention_bytes(doc, 1, 8192, True) == pytest.approx(
        8192 * 128 * 2 * (6 * 32 + 6 * 4))
    assert m.flash_window_train_flops(doc, 1, 8192) == pytest.approx(
        3 * 7 * 2 * 8192 * m.band_mean(doc, 8192) * 128 * 32)
    assert m.flash_window_train_bytes(doc, 1, 8192) == pytest.approx(
        3 * m.flash_attention_bytes(doc, 1, 8192, True))
    full = dict(doc, train=dict(doc["train"], remat="full"))
    assert m.flash_window_train_flops(full, 1, 8192) == pytest.approx(
        9 / 7 * m.flash_window_train_flops(doc, 1, 8192))
    assert m.moe_ep_exchange_bytes(doc, 8192) == pytest.approx(
        4 * (3 * 3 * 8192 * (H * 2 + 64) + 2 * 3 * 8192 * H * 4))
    assert m.moe_ep_exchange_bytes(doc, 8192) == pytest.approx(3.19e9,
                                                               rel=1e-3)


def _ctx(cell, ops, devices=None, steps=5, busy=4.0):
    devices = devices or [{"collective_exposed_s": 0.1}] * 4
    return {"model": cell.model, "config": cell.config, "chips": 4,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "trace": {"ops": ops, "programs": [], "busy_s": busy,
                      "window_s": 4.0, "devices": devices},
            "span": {"steps": steps, "seconds": 4.0}}


def test_the_new_readers_on_a_made_up_trace(cell):
    """Kernel self times that are twice the least time the chip could take
    read 50%; the exchange is read off the collective-permutes alone; the
    spread off the chips' exposed waits; a trace without the kernels, or a
    kind without the counts, reads nothing and raises nothing."""
    from benchmark.lib import trace
    m, doc = cell.model, cell.config
    tag = trace.PALLAS_TAG
    full = m.flash_attention_flops(doc, 1, 8192, True) / 197e12
    band = m.flash_window_train_flops(doc, 1, 8192) / 197e12
    ops = [["flash_fwd" + tag, 4 * full, 5], ["flash_dkv" + tag, 6 * full, 5],
           ["flash_window_prefill" + tag, 3 * band, 15],
           ["flash_window_bwd" + tag, 7 * band, 15],
           ["moe_gmm" + tag, 0.2, 480], ["moe_gmm_dx" + tag, 0.1, 160],
           ["moe_gmm_dw" + tag, 0.1, 240],
           ["collective-permute-start", 0.04, 600],
           ["collective-permute-done", 0.06, 600],
           ["all-gather-start", 0.5, 100], ["all-reduce", 0.5, 10],
           ["fusion", 1.0, 1000]]
    devices = [{"collective_exposed_s": s} for s in (0.10, 0.30, 0.18, 0.22)]
    ctx = _ctx(cell, ops, devices)
    read = {n: cell.reader(n)(ctx) for n in NEW}
    assert read["flash_full_train_roofline"] == pytest.approx(50.0)
    assert read["flash_window_train_roofline"] == pytest.approx(50.0)
    assert read["moe_ep_exchange_device_share"] == pytest.approx(2.5)
    assert read["swa_attn_train_kernels_device_share"] == pytest.approx(
        100 * (10 * full + 10 * band) / 4.0)
    # the grouped products are the accepted reader's, which lists the cell
    assert cell.reader("moe_train_kernels_device_share")(
        ctx) == pytest.approx(100 * 0.4 / 4.0)
    assert read["moe_ep_chip_wait_spread"] == pytest.approx(5.0)
    bare = _ctx(cell, [["fusion", 1.0, 1000], ["all-gather", 0.2, 90]])
    for name in NEW[:4]:
        assert cell.reader(name)(bare) is None
    other = dict(ctx, model=load_model(os.path.join(BENCH, "models",
                                                    "mistral.py")))
    for name in ("flash_full_train_roofline", "flash_window_train_roofline",
                 "moe_ep_exchange_device_share", "moe_ep_chip_wait_spread"):
        assert cell.reader(name)(other) is None
