"""The block kind ``kimi_vl`` as files (``models/kimi_vl.py``, the
configuration ``kimi-vl-a3b-train-l6-e8``, its cell, traffic and readers):
the lookup by ``model_type``, the published widths against the catalog's,
the cut as the file states it, the refusal to load over a program whose
train step cannot run it, the program against the kind's reference at the
tiny size through the train runner, the counts against hand sums and the
three readers on a made-up trace.  A file of its own: a ``model_config`` PR
adds files to the benchmark and edits none.  The comparison of the
program's loss and gradients with the kind's reference is
``tests/test_moe_train.py`` (tier-1)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.lib.manifest import MODEL_API, Cell, load_model
from benchmark.tests.test_runners import REPO, TINY, last_json, run_cell

BENCH = os.path.join(REPO, "benchmark")
KIND = os.path.join(BENCH, "models", "kimi_vl.py")
CELL = "train-moe-share-s8192"
#: the published file's numbers (the model-configs catalog's row)
PUBLISHED = dict(
    vocab_size=163840, max_position_embeddings=131072, hidden_size=2048,
    intermediate_size=11264, moe_intermediate_size=1408,
    num_hidden_layers=27, num_attention_heads=16, n_shared_experts=2,
    n_routed_experts=64, ep_size=1, routed_scaling_factor=2.446,
    kv_lora_rank=512, q_lora_rank=None, qk_rope_head_dim=64, v_head_dim=128,
    qk_nope_head_dim=128, n_group=1, topk_group=1, num_experts_per_tok=6,
    moe_layer_freq=1, first_k_dense_replace=1, num_key_value_heads=16,
    rms_norm_eps=1e-5, rope_theta=800000, rope_scaling=None)


@pytest.fixture(scope="module")
def cell():
    return Cell(os.path.join(REPO, "BENCHMARK.json"), CELL)


def test_the_cell_resolves_to_the_kinds_files(cell):
    assert cell.model_path == KIND and cell.chips == 1
    assert all(callable(getattr(cell.model, f)) for f in MODEL_API)
    for m in cell.metrics("per_layer"):
        assert callable(cell.reader(m["name"]))
    assert {m["name"] for m in cell.metrics("per_layer")} == {
        "device_idle_share.train", "train_step_ms", "train_step_mfu",
        "train_step_device_ms", "moe_gmm_train_roofline",
        "mla_flash_train_roofline", "moe_train_kernels_device_share"}
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "train_tokens_per_s_per_chip", "setup_s"}
    tr = cell.config["train"]
    assert (cell.traffic["loop"], cell.traffic["data"]) == (
        "train", "uniform_tokens")
    assert (tr["global_batch"], tr["sequence_length"], tr["mesh"]) == (
        2, 8192, {"fsdp": -1})
    assert tr["optimizer"] == {} and tr["remat"] == "save_acts"


def test_every_width_is_the_published_one_and_the_cut_is_stated(cell):
    doc, entry = cell.config, cell.config_entry
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert sorted(doc["reduced"]) == sorted(entry["reduced"])
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert doc["reduced"][key]["published"] == value
            assert doc[key] == doc["reduced"][key]["here"] != value
        else:
            assert doc[key] == value, key
    assert (doc["num_hidden_layers"], doc["n_routed_experts"],
            doc["vocab_size"]) == (6, 8, 163840 // 8)
    assert doc["share"]["chips"] == 8 and doc["share"]["expert_start"] == 0
    assert "model_type" in doc["assumed"]
    said = " ".join(doc["departures"])
    for word in ("vision tower", "selection bias", "balance term",
                 "no capacity", "random"):
        assert word in said, word
    assert "pipeline stage" in doc["stands_for"]
    assert entry["source"] == doc["source"] and "Kimi-VL-A3B" in doc["source"]
    cfg = cell.model.program_kwargs(doc)
    assert (cfg["num_experts"], cfg["experts_held"], cfg["expert_start"],
            cfg["q_lora_rank"], cfg["experts_per_token"]) == (64, 8, 0, 0, 6)


@pytest.mark.parametrize("change,match", [
    (dict(q_lora_rank=1536), "q_lora_rank"),
    (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
    (dict(scoring_func="softmax"), "sigmoid"),
    (dict(n_group=8), "n_group"),
    (dict(ep_size=8), "ep_size"),
    (dict(share={"chips": 8, "expert_start": 60}), "past the router"),
])
def test_the_kind_refuses_what_the_block_cannot_express(cell, change, match):
    with pytest.raises(ValueError, match=match):
        cell.model.program_kwargs(dict(cell.config, **change))


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


def test_the_kind_refuses_to_load_over_a_program_that_cannot_train_it(
        tmp_path):
    """As on the parent of PR 39: the cell has to fail at once there, with
    the harness's own error, in the process that resolves its files."""
    fake = tmp_path / "ray_tpu"
    (fake / "ops").mkdir(parents=True)
    (fake / "__init__.py").write_text("")
    (fake / "ops" / "__init__.py").write_text("")
    (fake / "ops" / "moe.py").write_text("KERNEL_MOE_GMM = 'moe_gmm'\n")
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
    assert "REFUSED" in p.stdout and "no backward" in p.stdout
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
    assert p.returncode != 0 and "needs 1 tpu device" in p.stderr


def test_the_tiny_share_trains_through_the_runner_beside_its_reference(
        tmp_path):
    """The train runner on the CPU with the tiny ``kimi_vl`` configuration
    (a share: experts 4-7 of 16): the first step's loss beside the kind's
    reference, a loss that falls, the three new readers silent without a
    device trace."""
    root = tmp_path / "bench"
    shutil.copytree(TINY, root)
    m = json.load(open(root / "BENCHMARK.json"))
    m["paths"] = [".", BENCH]
    m["configs"].append({"name": "tiny-kimi", "source": "tests",
                         "file": "configs/tiny-kimi.json", "reduced": [],
                         "why": "toy"})
    m["workloads"].append({"name": "tiny-kimi1", "config": "tiny-kimi",
                           "traffic": "tiny-job", "chips": 1, "why": "x"})
    for e in m["end_to_end"] + m["per_layer"]:
        if "tiny-train4" in e.get("workloads", []):
            e["workloads"].append("tiny-kimi1")
    for name in ("moe_gmm_train_roofline", "mla_flash_train_roofline",
                 "moe_train_kernels_device_share"):
        m["per_layer"].append({
            "name": name, "unit": "%", "better": "higher",
            "source": "device_trace", "layer": "kernels",
            "moves": "train_tokens_per_s_per_chip",
            "workloads": ["tiny-kimi1"]})
    json.dump(m, open(root / "BENCHMARK.json", "w"))
    out = last_json(run_cell(str(root / "BENCHMARK.json"), "tiny-kimi1",
                             trace=1))
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 3
    assert out["compared"]["first_loss_gap"]["value"] < 0.05
    assert "train_step_ms" in out["metrics"]
    assert not {"moe_gmm_train_roofline", "mla_flash_train_roofline",
                "moe_train_kernels_device_share"} & set(out["metrics"])


# ----------------------------------------------------- counts by hand

H, EM, M, V = 2048, 1408, 11264, 20480
ATTENTION = H * 16 * 192 + H * 576 + 512 * 16 * 256 + 16 * 128 * H
EXPERT = 3 * H * EM


def test_counts_of_the_l6_e8_configuration(cell):
    m, doc = cell.model, cell.config
    per = m.layer_matrix_params(doc)
    assert per == {"attention": ATTENTION, "expert": EXPERT,
                   "shared": 2 * EXPERT, "router": H * 64, "mlp": 3 * H * M}
    assert ATTENTION == 13_762_560 and EXPERT == 8_650_752
    small = 6 * (2 * H + 512) + 5 * 64 + H
    assert m.num_params(doc) == (
        6 * ATTENTION + 3 * H * M + 5 * (8 * EXPERT + 2 * EXPERT + H * 64)
        + 2 * V * H + small) == doc["params"]["held"] == 668_890_432
    # a token meets 6 * 8 / 64 = 0.75 routed experts a layer here
    assert m.assignments_held(doc, 16384) == 12288
    met = (6 * ATTENTION + 3 * H * M
           + 5 * (2 * EXPERT + H * 64 + 0.75 * EXPERT) + V * H)
    assert m.train_flops_per_token(doc, 8192) == pytest.approx(
        6 * met + 3 * 6 * 16 * (192 + 128) * 8192)
    assert m.train_flops_per_token(doc, 8192) == pytest.approx(2.635e9,
                                                               rel=1e-3)


def test_the_kernels_counts_follow_the_passes_the_program_runs(cell):
    m, doc = cell.model, cell.config
    assert m.moe_gmm_train_calls(doc) == {"moe_gmm": 6, "moe_gmm_dx": 2,
                                          "moe_gmm_dw": 3}
    assert m.moe_gmm_train_passes(doc) == 4
    assert m.moe_gmm_train_flops(doc, 16384) == pytest.approx(
        4 * 2 * EXPERT * 12288 * 5)
    assert m.moe_gmm_train_bytes(doc, 16384) == pytest.approx(
        5 * (8 * EXPERT * (3 * 2 + 4)
             + 4 * 12288 * (2 * H + 3 * EM) * 2))
    no_replay = dict(doc, train=dict(doc["train"], remat=False))
    assert m.moe_gmm_train_passes(no_replay) == 3
    assert m.moe_gmm_train_calls(no_replay)["moe_gmm"] == 3
    assert m.moe_gmm_train_flops(no_replay, 16384) == pytest.approx(
        0.75 * m.moe_gmm_train_flops(doc, 16384))
    # attention: 2 products forward, 5 backward, at heads of 192 / 128
    assert m.mla_flash_train_calls(doc) == {"flash_fwd": 1, "flash_dq": 1,
                                            "flash_dkv": 1}
    assert m.mla_flash_train_flops(doc, 2, 8192) == pytest.approx(
        6 * 2 * 16 * 8192 ** 2 * (192 + 128 + 3 * 192 + 2 * 128))
    full = dict(doc, train=dict(doc["train"], remat="full"))
    assert m.mla_flash_train_calls(full)["flash_fwd"] == 2
    assert m.mla_flash_train_bytes(doc, 2, 8192) == pytest.approx(
        6 * 2 * 8192 * 16 * 2 * (2 * 192 + 2 * 128 + 4 * 192 + 4 * 128))


def _ctx(cell, ops, steps=5, busy=4.0):
    return {"model": cell.model, "config": cell.config, "chips": 1,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "trace": {"ops": ops, "programs": [], "busy_s": busy},
            "span": {"steps": steps, "seconds": 4.0}}


def test_the_three_readers_on_a_made_up_trace(cell):
    """Kernel self times that are twice the least time the chip could take
    read 50%; a trace without the kernels (the parent's train step), or a
    kind without the counts, reads nothing and raises nothing."""
    from benchmark.lib import trace
    m, doc = cell.model, cell.config
    tag = trace.PALLAS_TAG
    gmm_least = m.moe_gmm_train_flops(doc, 16384) / 197e12
    flash_least = m.mla_flash_train_flops(doc, 2, 8192) / 197e12
    assert gmm_least > m.moe_gmm_train_bytes(doc, 16384) / 819e9
    ops = [["moe_gmm" + tag, 5 * gmm_least, 150],
           ["moe_gmm_dx" + tag, 3 * gmm_least, 50],
           ["moe_gmm_dw" + tag, 2 * gmm_least, 75],
           ["flash_fwd" + tag, 4 * flash_least, 30],
           ["flash_dq" + tag, 3 * flash_least, 30],
           ["flash_dkv" + tag, 3 * flash_least, 30],
           ["fusion", 1.0, 1000], ["moe_gmm_other", 9.0, 1]]
    ctx = _ctx(cell, ops)
    read = {n: cell.reader(n)(ctx) for n in (
        "moe_gmm_train_roofline", "mla_flash_train_roofline",
        "moe_train_kernels_device_share")}
    assert read["moe_gmm_train_roofline"] == pytest.approx(50.0)
    assert read["mla_flash_train_roofline"] == pytest.approx(50.0)
    assert read["moe_train_kernels_device_share"] == pytest.approx(
        100 * 10 * gmm_least / 4.0)
    bare = _ctx(cell, [["fusion", 1.0, 1000],
                       ["closed_call" + tag, 0.2, 90]])
    other = dict(ctx, model=load_model(os.path.join(BENCH, "models",
                                                    "mistral.py")))
    for name in read:
        assert cell.reader(name)(bare) is None
    for name in ("moe_gmm_train_roofline", "mla_flash_train_roofline"):
        assert cell.reader(name)(other) is None
