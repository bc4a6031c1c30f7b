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
        # Mistral-7B-v0.3's published widths, unchanged
        assert (doc["hidden_size"], doc["intermediate_size"],
                doc["num_attention_heads"], doc["num_key_value_heads"],
                doc["vocab_size"], doc["rope_theta"]) == \
            (4096, 14336, 32, 8, 32768, 1000000.0)
        for m in cell.metrics("per_layer"):
            assert callable(cell.reader(m["name"]))


def test_peaks_table_knows_the_v5e_and_nothing_by_default():
    import pytest
    p = peaks_for("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["hbm_bytes_per_s"], p["hbm_bytes"],
            p["ici_bits_per_s"]) == (197e12, 819e9, 16e9, 1600e9)
    with pytest.raises(KeyError):
        peaks_for("cpu")
