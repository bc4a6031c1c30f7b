"""The block kind ``olmo_hybrid`` as files (``models/olmo_hybrid.py``, the
configuration ``olmo-hybrid-7b-serve-l12``, its cell and readers): the counts
against literals worked out by hand from the published keys, the lookup by
``model_type``, and the refusal to load over a program without layers of two
kinds.  The comparison of the program with the kind's reference is
``tests/test_hybrid.py`` (tier-1)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib.manifest import MODEL_API, Cell, load_model
from benchmark.tests.test_runners import REPO

BENCH = os.path.join(REPO, "benchmark")
KIND = os.path.join(BENCH, "models", "olmo_hybrid.py")
CELL = "serve-hybrid-longgen-closed"


@pytest.fixture(scope="module")
def cell():
    return Cell(os.path.join(REPO, "BENCHMARK.json"), CELL)


def test_the_cell_resolves_to_the_kinds_files(cell):
    assert cell.model_path == KIND
    assert all(callable(getattr(cell.model, f)) for f in MODEL_API)
    assert cell.config["model_type"] == "olmo_hybrid"
    assert sorted(cell.config["reduced"]) == ["layer_types",
                                              "num_hidden_layers"]
    # published widths, unchanged
    doc = cell.config
    assert (doc["hidden_size"], doc["intermediate_size"], doc["vocab_size"],
            doc["num_attention_heads"], doc["num_key_value_heads"],
            doc["linear_num_key_heads"], doc["linear_key_head_dim"],
            doc["linear_value_head_dim"], doc["linear_conv_kernel_dim"]) == (
        3840, 11008, 100352, 30, 30, 30, 96, 192, 4)
    assert doc["layer_types"] == (["linear_attention"] * 3
                                  + ["full_attention"]) * 3
    for m in cell.metrics("per_layer"):
        assert callable(cell.reader(m["name"]))
    assert {m["name"] for m in cell.metrics("per_layer")} >= {
        "gdn_chunk_fwd_roofline", "gdn_recurrent_step_roofline",
        "gdn_kernels_device_share"}
    t = cell.traffic
    assert (t["loop"], t["clients"], t["ingress"], t["order"]) == (
        "closed", 24, "handle_stream", "fixed")
    assert t["prompt"]["hi"] + t["output"]["hi"] <= doc["serve"]["max_len"]


def test_counts_from_the_published_keys(cell):
    m, doc = cell.model, cell.config
    mixer = 3840 * (2880 + 2880 + 5760 + 5760) + 5760 * 3840 + 2 * 3840 * 30
    mlp = 3 * 3840 * 11008
    assert m.layer_matrix_params(doc) == {
        "linear": mixer + mlp, "full": 4 * 3840 * 3840 + mlp}
    assert mixer == 88_704_000 and mlp == 126_812_160
    small = 9 * (4 * 11520 + 2 * 30 + 192 + 2 * 3840) \
        + 3 * (2 * 3840 + 2 * 3840) + 3840
    assert m.num_params(doc) == 9 * (mixer + mlp) + 3 * (
        4 * 3840 * 3840 + mlp) + 2 * 100352 * 3840 + small == 3_268_268_508
    weights = 2 * (m.num_params(doc) - small - 100352 * 3840)
    assert m.decode_step_bytes(doc, 0, 0) == weights == 5_764_761_600
    assert m.state_bytes_per_slot(doc) == 19_906_560
    assert m.kv_bytes_per_token(doc) == 46_080
    assert m.decode_step_bytes(doc, 24, 24 * 1900) == (
        weights + 2 * 24 * 19_906_560 + 24 * 1900 * 46_080)
    assert m.decode_step_flops(doc, 24, 24 * 1900) == (
        24 * weights + 24 * 9 * 30 * 6 * 96 * 192
        + 4 * 3 * 30 * 128 * 24 * 1900)
    assert m.gdn_recurrent_step_flops(doc, 24) == 24 * 9 * 30 * 6 * 96 * 192
    assert m.gdn_recurrent_step_bytes(doc, 1) == 2 * 19_906_560 + 311_040
    assert m.gdn_chunk_fwd_bytes(doc, 4096) == 9 * 30 * 1160 * 4096
    assert m.train_flops_per_token(doc, 4096) > 6 * weights / 2


def test_the_kind_refuses_to_load_over_a_program_without_hybrid(tmp_path):
    """As on the parent of PR 29: the cell has to fail at once there, in
    the process that resolves its files, and not inside a replica."""
    fake = tmp_path / "ray_tpu"
    (fake / "models").mkdir(parents=True)
    (fake / "__init__.py").write_text("")
    (fake / "models" / "__init__.py").write_text("")
    p = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, sys.argv[2]); "
         "sys.path.insert(0, sys.argv[1]); "
         "from benchmark.lib.manifest import load_model; "
         "load_model(sys.argv[3])", str(tmp_path), REPO, KIND],
        capture_output=True, text=True, cwd=str(tmp_path),
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert p.returncode != 0
    assert "no models/hybrid.py" in p.stderr and "jax" not in p.stderr.lower()


def test_loading_the_kind_imports_no_jax():
    p = subprocess.run(
        [sys.executable, "-c",
         "import sys; from benchmark.lib.manifest import load_model; "
         "load_model(sys.argv[1]); assert 'jax' not in sys.modules", KIND],
        capture_output=True, text=True, cwd=REPO)
    assert p.returncode == 0, p.stderr
