"""The block kind ``olmo_hybrid`` as files (``models/olmo_hybrid.py``, the
configuration ``olmo-hybrid-7b-serve-l12``, its cell and readers): the
lookup by ``model_type`` and the refusal to load over a program without
layers of two kinds.  Its counts are cases of ``test_models.py``'s one test
of counts, its widths of ``test_manifest.py``'s.  The comparison of the
program with the kind's reference is ``tests/test_hybrid.py`` (tier-1)."""

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
    doc = cell.config
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
