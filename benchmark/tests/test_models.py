"""A block kind is files: ``lib/manifest.Cell`` finds a configuration's
mapping, entry points, plain reference and counts by its ``model_type``.
The counts of each kind's file, cases of one test: ``models/mistral.py``
against literals taken from ``lib/costs.py`` at the parent of PR 28 (the
file it was moved from), ``models/olmo_hybrid.py`` against literals worked
out by hand from the published keys; a second kind added as files in another ``paths`` directory; the failures of
the lookup; and the rule that nothing else in ``benchmark/`` names a model.
"""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark.lib.manifest import MODEL_API, Cell, ManifestError, load_model
from benchmark.tests.test_runners import REPO, TINY, last_json, run_cell

BENCH = os.path.join(REPO, "benchmark")
MISTRAL = os.path.join(BENCH, "models", "mistral.py")
OLMO = os.path.join(BENCH, "models", "olmo_hybrid.py")

# decode at 8.25 active slots of 700.5 live tokens each, train and the flash
# kernels at the train cell's 4096 tokens and one sequence a chip (a float
# there: ``global_batch / chips``), the forward kernel alone at 2 x 512
PINNED = {
    "configs/mistral-7b-v0.3-serve-l14.json": dict(
        layer_params=218103808, num_params=3321888768,
        train_flops_per_token=21944598528.0, kv_bytes_per_token=57344,
        decode_step_bytes=6706740224.0, decode_step_flops=53922164736.0,
        flash_attention_flops=6734508720128.0,
        flash_attention_bytes=3523215360.0,
        flash_attention_flops_fwd=60129542144.0,
        flash_attention_bytes_fwd=293601280),
    "configs/mistral-7b-v0.3-train-l8.json": dict(
        layer_params=218103808, num_params=2013265920,
        train_flops_per_token=12884901888.0, kv_bytes_per_token=32768,
        decode_step_bytes=3947466752.0, decode_step_flops=31761776640.0,
        flash_attention_flops=3848290697216.0,
        flash_attention_bytes=2013265920.0,
        flash_attention_flops_fwd=34359738368.0,
        flash_attention_bytes_fwd=167772160),
    "tests/tiny/configs/tiny-serve.json": dict(
        layer_params=49152, num_params=131072,
        train_flops_per_token=6979584.0, kv_bytes_per_token=256,
        decode_step_bytes=1708832.0, decode_step_flops=4851264.0,
        flash_attention_flops=15032385536.0, flash_attention_bytes=9437184.0,
        flash_attention_flops_fwd=134217728.0,
        flash_attention_bytes_fwd=786432),
}
PINNED["tests/tiny/configs/tiny-train.json"] = \
    PINNED["tests/tiny/configs/tiny-serve.json"]


def mistral_counts(m, doc):
    live = 8.25 * 700.5
    got = dict(
        layer_params=m.layer_params(doc), num_params=m.num_params(doc),
        train_flops_per_token=m.train_flops_per_token(doc, 4096),
        kv_bytes_per_token=m.kv_bytes_per_token(doc),
        decode_step_bytes=m.decode_step_bytes(doc, 8.25, live),
        decode_step_flops=m.decode_step_flops(doc, 8.25, live),
        flash_attention_flops=m.flash_attention_flops(doc, 1.0, 4096, True),
        flash_attention_bytes=m.flash_attention_bytes(doc, 1.0, 4096, True),
        flash_attention_flops_fwd=m.flash_attention_flops(doc, 2, 512, False),
        flash_attention_bytes_fwd=m.flash_attention_bytes(doc, 2, 512, False))
    # a dense block holds nothing per slot beyond its keys and values
    assert m.decode_step_bytes(doc, 0, live) == got["decode_step_bytes"]
    return got


# Olmo-Hybrid-7B at 12 layers (9 linear + 3 full), worked out by hand from
# the published keys: hidden 3840, MLP 11008, vocab 100352 untied, 30 heads
# of 128, linear layers with 30 key heads of 96 and 30 value heads of 192
_MIXER = 3840 * (2880 + 2880 + 5760 + 5760) + 5760 * 3840 + 2 * 3840 * 30
_MLP = 3 * 3840 * 11008
_SMALL = 9 * (4 * 11520 + 2 * 30 + 192 + 2 * 3840) \
    + 3 * (2 * 3840 + 2 * 3840) + 3840
_WEIGHTS = 5_764_761_600          # bf16 bytes a decode step reads
OLMO_PINNED = dict(
    mixer=88_704_000, mlp=126_812_160,
    layer_matrix_params={"linear": _MIXER + _MLP,
                         "full": 4 * 3840 * 3840 + _MLP},
    num_params=3_268_268_508,
    weights=2 * (3_268_268_508 - _SMALL - 100352 * 3840),
    decode_step_bytes_empty=_WEIGHTS,
    state_bytes_per_slot=19_906_560, kv_bytes_per_token=46_080,
    decode_step_bytes=_WEIGHTS + 2 * 24 * 19_906_560 + 24 * 1900 * 46_080,
    decode_step_flops=24 * _WEIGHTS + 24 * 9 * 30 * 6 * 96 * 192
    + 4 * 3 * 30 * 128 * 24 * 1900,
    gdn_recurrent_step_flops=24 * 9 * 30 * 6 * 96 * 192,
    gdn_recurrent_step_bytes=2 * 19_906_560 + 311_040,
    gdn_chunk_fwd_bytes=9 * 30 * 1160 * 4096,
    train_flops_above_six_a_weight=True)


def olmo_counts(m, doc):
    return dict(
        mixer=_MIXER, mlp=_MLP,
        layer_matrix_params=m.layer_matrix_params(doc),
        num_params=m.num_params(doc),
        weights=_WEIGHTS,
        decode_step_bytes_empty=m.decode_step_bytes(doc, 0, 0),
        state_bytes_per_slot=m.state_bytes_per_slot(doc),
        kv_bytes_per_token=m.kv_bytes_per_token(doc),
        decode_step_bytes=m.decode_step_bytes(doc, 24, 24 * 1900),
        decode_step_flops=m.decode_step_flops(doc, 24, 24 * 1900),
        gdn_recurrent_step_flops=m.gdn_recurrent_step_flops(doc, 24),
        gdn_recurrent_step_bytes=m.gdn_recurrent_step_bytes(doc, 1),
        gdn_chunk_fwd_bytes=m.gdn_chunk_fwd_bytes(doc, 4096),
        train_flops_above_six_a_weight=(
            m.train_flops_per_token(doc, 4096) > 6 * _WEIGHTS / 2))


#: configuration file -> (its kind's file, the counts taken, their values)
COUNTS = {config: (MISTRAL, mistral_counts, want)
          for config, want in PINNED.items()}
COUNTS["configs/olmo-hybrid-7b-serve-l12.json"] = (OLMO, olmo_counts,
                                                   OLMO_PINNED)


@pytest.mark.parametrize("config", sorted(COUNTS))
def test_counts_from_the_published_keys(config):
    kind, counts, want = COUNTS[config]
    doc = json.load(open(os.path.join(BENCH, config)))
    assert counts(load_model(kind), doc) == want


def test_the_mapping_refuses_what_the_dense_block_cannot_express():
    m = load_model(MISTRAL)
    doc = json.load(open(os.path.join(TINY, "configs", "tiny-serve.json")))
    assert m.program_config(doc).num_layers == 2
    for change in ({"sliding_window": 4096}, {"hidden_act": "gelu"},
                   {"head_dim": 32}):
        with pytest.raises(ValueError):
            m.program_kwargs({**doc, **change})
    lacking = {k: v for k, v in doc.items() if k != "rope_theta"}
    with pytest.raises(ValueError, match="rope_theta"):
        m.program_kwargs(lacking)


def test_loading_the_file_imports_no_jax():
    """The parent of a serve cell loads it for the counts and may not hold
    the chip (``runners/serve.Replica`` fails the run otherwise)."""
    code = ("import sys; from benchmark.lib.manifest import load_model; "
            f"m = load_model({MISTRAL!r}); m.num_params; "
            "sys.exit('jax' in sys.modules)")
    assert subprocess.run([sys.executable, "-c", code], cwd=REPO).returncode == 0


# ------------------------------------------------- a second kind, as files

#: cell -> the end-to-end metric its mark's entry has to name
MARKED = {"tiny-open": "serve_out_tokens_per_s",
          "tiny-train4": "train_tokens_per_s_per_chip"}


def second_kind(tmp_path, kind="othermistral", edit=lambda text: text):
    """A second ``paths`` directory beside the tests' tiny one: a block
    kind's file (``models/mistral.py``'s text under another name, with a
    mark that only a reader of that file can return), the two tiny
    configurations with that ``model_type``, a reader and a manifest."""
    assert not os.path.exists(os.path.join(BENCH, "models", kind + ".py"))
    root = tmp_path / "more"
    for sub in ("models", "configs", "layer_metrics"):
        (root / sub).mkdir(parents=True)
    (root / "models" / (kind + ".py")).write_text(
        edit(open(MISTRAL).read()) + "\nMARK = 28.0\n")
    m = json.load(open(os.path.join(TINY, "BENCHMARK.json")))
    m["paths"] = [str(root), TINY, BENCH]
    for c in m["configs"]:
        doc = json.load(open(os.path.join(TINY, c["file"])))
        doc["model_type"] = kind
        c["file"] = os.path.join("configs", c["name"] + ".json")
        json.dump(doc, open(root / c["file"], "w"))
    for cell, e2e in MARKED.items():
        (root / "layer_metrics" / f"block_kind_mark.{cell}.py").write_text(
            "def read(ctx):\n    return getattr(ctx['model'], 'MARK', None)\n")
        m["per_layer"].append({
            "name": f"block_kind_mark.{cell}", "unit": "mark",
            "better": "higher", "source": "program_counter",
            "layer": "device", "moves": e2e, "workloads": [cell]})
    json.dump(m, open(root / "BENCHMARK.json", "w"))
    return str(root / "BENCHMARK.json")


def tree_of(path):
    return {os.path.join(d, f): os.path.getmtime(os.path.join(d, f))
            for d, _, files in os.walk(path) for f in files
            if "__pycache__" not in d}


@pytest.mark.parametrize("workload,devices", zip(MARKED, (1, 4)))
def test_a_second_block_kind_is_files_alone(tmp_path, workload, devices):
    before = tree_of(BENCH)
    manifest = second_kind(tmp_path)
    cell = Cell(manifest, workload)
    assert cell.model_path == str(tmp_path / "more" / "models"
                                  / "othermistral.py")
    out = last_json(run_cell(manifest, workload, trace=1, devices=devices,
                             seed=2_900_000_021))
    assert out["correct"] is True and out["failed"] == 0
    assert out["metrics"][f"block_kind_mark.{workload}"] == {
        "value": 28.0, "unit": "mark"}
    assert tree_of(BENCH) == before


def test_the_replica_compares_with_the_kinds_own_reference(tmp_path):
    """The same second kind with its reference's logits shifted: the
    program is sound, so only the file's own reference can say otherwise."""
    manifest = second_kind(tmp_path, edit=lambda text: text.replace(
        "        return x @ _head(params, doc)\n",
        "        return x @ _head(params, doc) + 1.0\n"))
    out = last_json(run_cell(manifest, "tiny-open", seed=2_900_000_021))
    assert out["correct"] is False and out["failed"] == 0


# ------------------------------------------------ the lookup's own failures

#: a published ``model_type`` no directory of ``paths`` has a file for
NO_KIND = "a_kind_with_no_file"


def broken(tmp_path, change):
    root = tmp_path / "bench"
    shutil.copytree(TINY, root)
    m = json.load(open(root / "BENCHMARK.json"))
    m["paths"] = [str(root), BENCH]
    json.dump(m, open(root / "BENCHMARK.json", "w"))
    path = root / "configs" / "tiny-serve.json"
    doc = json.load(open(path))
    change(doc)
    json.dump(doc, open(path, "w"))
    return root


def test_a_model_type_with_no_file_names_the_paths_tried(tmp_path):
    assert not os.path.exists(os.path.join(BENCH, "models", NO_KIND + ".py"))
    root = broken(tmp_path, lambda d: d.update(model_type=NO_KIND))
    with pytest.raises(ManifestError) as e:
        Cell(str(root / "BENCHMARK.json"), "tiny-open")
    assert str(root / "models" / (NO_KIND + ".py")) in str(e.value)
    assert os.path.join(BENCH, "models", NO_KIND + ".py") in str(e.value)
    # and through the command: no result line
    p = run_cell(str(root / "BENCHMARK.json"), "tiny-open")
    assert p.returncode != 0 and NO_KIND + ".py" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


@pytest.mark.parametrize("model_type", [None, "", "../mistral", 7])
def test_no_model_type_names_the_paths_tried(tmp_path, model_type):
    def change(doc):
        if model_type is None:
            del doc["model_type"]
        else:
            doc["model_type"] = model_type
    root = broken(tmp_path, change)
    with pytest.raises(ManifestError, match="model_type") as e:
        Cell(str(root / "BENCHMARK.json"), "tiny-open")
    assert str(root / "models") in str(e.value)
    assert os.path.join(BENCH, "models") in str(e.value)


def test_a_kinds_file_that_lacks_a_function_is_refused(tmp_path):
    path = tmp_path / "half.py"
    path.write_text("def program_config(doc):\n    return doc\n")
    with pytest.raises(ManifestError, match="init_params") as e:
        load_model(str(path))
    assert all(f in str(e.value) for f in MODEL_API[1:])


# ------------------------------------ nothing else in benchmark/ names a model

NAMES_A_MODEL = re.compile(
    r"ray_tpu\.models|TransformerConfig"
    r"|lib import .*(modelcfg|reference|costs)")


def test_only_a_kinds_file_names_the_programs_models():
    hits = []
    for d, _, files in os.walk(BENCH):
        rel = os.path.relpath(d, BENCH)
        if rel.split(os.sep)[0] in ("models", "tests"):
            continue
        for f in files:
            if f.endswith(".py"):
                for n, line in enumerate(open(os.path.join(d, f)), 1):
                    if NAMES_A_MODEL.search(line):
                        hits.append(f"{os.path.join(rel, f)}:{n}: {line}")
    assert not hits, "".join(hits)
    for gone in ("modelcfg", "reference", "costs"):
        assert not os.path.exists(os.path.join(BENCH, "lib", gone + ".py"))
