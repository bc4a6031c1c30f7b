"""The committed ``BENCHMARK.json`` against the limits of its contract that
a file check can see, and every name in it resolved to its file."""

import json
import os
import re

from benchmark.lib.manifest import Cell
from benchmark.lib.peaks import peaks_for

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PATH = os.path.join(REPO, "BENCHMARK.json")
M = json.load(open(PATH))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MISTRAL_7B = dict(hidden_size=4096, intermediate_size=14336,
                  num_attention_heads=32, num_key_value_heads=8,
                  vocab_size=32768, rope_theta=1000000.0)
#: configuration -> the widths its source publishes (a new configuration
#: adds its line here)
WIDTHS = {
    "mistral-7b-v0.3-serve-l14": MISTRAL_7B,
    "mistral-7b-v0.3-train-l8": MISTRAL_7B,
    "olmo-hybrid-7b-serve-l12": dict(
        hidden_size=3840, intermediate_size=11008, vocab_size=100352,
        num_attention_heads=30, num_key_value_heads=30,
        linear_num_key_heads=30, linear_key_head_dim=96,
        linear_value_head_dim=192, linear_conv_kernel_dim=4),
}


def test_keys_names_and_sizes():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(PATH) <= 64 * 1024
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in M[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(tuple(p + "/" for p in M["paths"]))
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(1, len(M["workloads"]) // 4)
    metric_names = [m["name"] for g in ("end_to_end", "per_layer")
                    for m in M[g]]
    assert len(metric_names) == len(set(metric_names))


def test_metrics_follow_the_contract():
    cells = {w["name"] for w in M["workloads"]}
    e2e = {m["name"]: m for m in M["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in M["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in M["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        where = set(m.get("workloads", cells))
        moved = e2e[m["moves"]]
        assert where <= set(moved.get("workloads", cells)), m["name"]
    for m in M["end_to_end"] + M["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for c in cells:
        has = [m for m in M["end_to_end"] if c in m.get("workloads", cells)]
        assert len(has) >= 2
        assert any(c in m.get("workloads", cells) for m in M["per_layer"])


def test_every_name_resolves_to_its_file():
    for w in M["workloads"]:
        cell = Cell(PATH, w["name"])
        entry, doc = cell.config_entry, cell.config
        assert doc["source"] == entry["source"]
        assert sorted(doc["reduced"]) == sorted(entry["reduced"])
        for key in ("assumed", "departures", "stands_for", "kind"):
            assert key in doc
        # the configuration's own published widths, unchanged
        for key, value in WIDTHS[entry["name"]].items():
            assert doc[key] == value, (entry["name"], key)
        for m in cell.metrics("per_layer"):
            assert callable(cell.reader(m["name"]))


def test_peaks_table_knows_the_v5e_and_nothing_by_default():
    import pytest
    p = peaks_for("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["hbm_bytes_per_s"], p["hbm_bytes"],
            p["ici_bits_per_s"]) == (197e12, 819e9, 16e9, 1600e9)
    with pytest.raises(KeyError):
        peaks_for("cpu")
