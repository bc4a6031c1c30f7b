"""End-to-end rehearsals on the CPU with the tests' own tiny manifest,
configurations and traffic (``tests/tiny``): each runner once, the result
line's keys, a real cell refusing to run without its chips, and a second
tiny cell added by files and entries alone."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "tiny")


def run_cell(manifest, workload, trace=0, seconds=3, devices=1, seed=7):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--manifest", manifest,
         "--workload", workload, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", str(trace)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    return p


def last_json(p):
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    # each number that decided ``correct`` beside its limit: the line's last
    # key, and the run's last lines on standard error
    assert list(out)[-1] == "compared" and out["compared"]
    tail = p.stderr.strip().splitlines()[-len(out["compared"]):]
    for line, (name, c) in zip(tail, out["compared"].items()):
        assert set(c) == {"value", "limit"}
        assert line.startswith(f"compared {name}: "), line
    return out


@pytest.mark.parametrize("workload", ["tiny-open", "tiny-closed"])
def test_serve_runner_end_to_end(workload):
    manifest = json.load(open(os.path.join(TINY, "BENCHMARK.json")))
    p = run_cell(os.path.join(TINY, "BENCHMARK.json"), workload,
                 seed=3_000_000_019)
    out = last_json(p)
    # no measured request is a caller process's first
    assert "sent their first request" in p.stdout
    want = {m["name"] for m in manifest["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(out["metrics"]) == want and "setup_s" in want
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["device"]["platform"] == "cpu"
    assert all(m["value"] > 0 for m in out["metrics"].values())


def test_train_runner_traced_on_four_virtual_devices():
    out = last_json(run_cell(os.path.join(TINY, "BENCHMARK.json"),
                             "tiny-train4", trace=1, devices=4))
    assert out["correct"] is True and out["device"]["count"] == 4
    # no device plane on the CPU: the trace readers return nothing and are
    # left out; the host-clock reader stays
    assert set(out["metrics"]) == {"train_step_ms"}
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("workload,devices", [("serve-chat-steady", 1),
                                              ("train-fsdp4-s4096", 4)])
def test_a_real_cell_fails_on_the_cpu_with_no_result_line(workload, devices):
    p = run_cell("BENCHMARK.json", workload, devices=devices)
    assert p.returncode != 0 and "FAILED" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_a_second_cell_is_files_and_entries_alone(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(TINY, root)
    m = json.load(open(root / "BENCHMARK.json"))
    # the real harness's readers stay reachable through an absolute path
    m["paths"] = [".", os.path.join(REPO, "benchmark")]
    traffic = json.load(open(root / "traffic" / "tiny-open.json"))
    traffic["prefix"] = {"pool": 2, "len": 16}
    traffic["rate_per_s"] = 3.0
    json.dump(traffic, open(root / "traffic" / "tiny-prefix.json", "w"))
    (root / "layer_metrics").mkdir()
    (root / "layer_metrics" / "admit_batches.py").write_text(
        "def read(ctx):\n"
        "    return float(ctx['stats1']['admit_batches']"
        " - ctx['stats0']['admit_batches'])\n")
    m["workloads"].append({"name": "tiny-prefix", "config": "tiny-serve",
                           "traffic": "tiny-prefix", "chips": 1, "why": "x"})
    for e in m["end_to_end"]:
        if "tiny-open" in e.get("workloads", []):
            e["workloads"].append("tiny-prefix")
    m["per_layer"].append({
        "name": "admit_batches", "unit": "batches", "better": "lower",
        "source": "program_counter", "layer": "LLM engine (host loop)",
        "moves": "ttft_p95_ms", "workloads": ["tiny-prefix"]})
    json.dump(m, open(root / "BENCHMARK.json", "w"))
    out = last_json(run_cell(str(root / "BENCHMARK.json"), "tiny-prefix",
                             trace=1))
    assert out["metrics"]["admit_batches"]["value"] > 0
    assert out["metrics"]["admit_batches"]["unit"] == "batches"
