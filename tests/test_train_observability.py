"""Train-plane observability tests (ISSUE 10): per-step decomposition
sums stay inside the step wall clock, first-call compile splits out,
MFU/goodput arithmetic, the train_metrics_enabled kill switch sheds
every ``raytpu_train_*`` series, the loop monitor lands in train
workers, and a 2-node training run yields a connected
chief -> worker -> step chrome trace, a non-empty /api/metrics/history
with derived rates, ``raytpu top --once`` with train MFU/goodput next
to the node columns, and an on-demand profiler artifact."""

import json
import os
import time
import types

import pytest

import ray_tpu
from ray_tpu import train
from ray_tpu.train import DataParallelTrainer, RunConfig, ScalingConfig

# ---------------------------------------------------------------- units


def test_step_tracker_decomposition_and_compile_split():
    from ray_tpu.train.observability import StepTracker

    t = StepTracker(0, trial="unit")
    t.SNAPSHOT_PERIOD_S = 0.0  # fresh snapshot per report (no cache lag)
    t.start()
    with t.phase("data_wait"):
        time.sleep(0.002)
    with t.phase("step_compute"):
        time.sleep(0.02)
    snap = t.on_report()
    t.on_resume()
    # first step: compute is COMPILE, not a step sample
    assert snap["steps"] == 1
    assert snap["compile_s"] >= 0.02
    assert snap["compile_s"] == snap["last_step"]["phases"]["step_compute"]
    assert snap["step_time_s"] is None
    waited = snap["last_step"]["phases"]["data_wait"]
    steps = []
    for _ in range(3):
        with t.phase("data_wait"):
            time.sleep(0.001)
        with t.phase("step_compute"):
            time.sleep(0.004)
        snap = t.on_report()
        t.on_resume()
        # decomposition sums <= the step wall clock (satellite gate)
        last = snap["last_step"]
        assert sum(last["phases"].values()) <= last["wall_s"] + 1e-6
        steps.append(last)
    assert snap["steps"] == 4
    # compile stayed split out, whatever a loaded box made of the sleeps:
    # 3 step samples, the longest of them the longest of the three steps,
    # and the stages' totals the three steps' parts (the first step's wait
    # with them, its compute with the compile and nowhere else)
    assert snap["step_time_s"]["count"] == 3
    assert snap["step_time_s"]["max"] == pytest.approx(
        max(s["wall_s"] for s in steps))
    assert snap["stage_totals_s"]["step_compute"] == pytest.approx(
        sum(s["phases"]["step_compute"] for s in steps))
    assert snap["stage_totals_s"]["data_wait"] == pytest.approx(
        waited + sum(s["phases"]["data_wait"] for s in steps))
    assert snap["productive_s"] == pytest.approx(
        snap["stage_totals_s"]["step_compute"])
    assert 0.0 < snap["goodput"] <= 1.0


def test_step_tracker_mfu_math():
    from ray_tpu.train.observability import StepTracker

    t = StepTracker(1)
    t.SNAPSHOT_PERIOD_S = 0.0
    # 100 tokens/step at 1e6 flops/token against a 1e9 flops/s "chip":
    # a 0.1 s step is exactly MFU 1.0
    t.set_model(flops_per_token=1e6, tokens_per_step=100, peak_flops=1e9)
    t.start()
    t.on_report()  # compile step
    t.on_resume()
    t0 = time.perf_counter()
    with t.phase("step_compute"):
        time.sleep(0.1)
    snap = t.on_report()
    # against the step as it really lasted: under a loaded box a 0.1 s sleep
    # takes longer, and the MFU is lower by exactly that
    took = time.perf_counter() - t0
    assert snap["mfu"] == pytest.approx(0.1 / took, rel=0.25)
    assert snap["tokens_total"] == 100
    # model-config path: flops_per_token comes from the config object
    from ray_tpu.models import tiny
    t2 = StepTracker(2).set_model(tiny(), seq_len=32, tokens_per_step=64,
                                  peak_flops=1e12)
    assert t2._flops_per_token == tiny().flops_per_token(32)


def test_step_tracker_collective_bytes_and_opt_gauge():
    """set_collectives wires the step builders' wire/HBM accounting into
    raytpu_train_collective_bytes_total{op,dtype} (counted per completed
    step, compile excluded) and the opt-state gauge, and both ride the
    snapshot to the driver (ISSUE 20 satellite)."""
    from ray_tpu.train.observability import StepTracker
    from ray_tpu.util.metrics import get_metric

    t = StepTracker(555)
    t.SNAPSHOT_PERIOD_S = 0.0
    t.set_collectives({("reduce_scatter", "int8"): 1000,
                       ("all_gather", "float32"): 64},
                      opt_state_bytes=4096)
    t.start()
    t.on_report()  # compile step: no collective counts
    t.on_resume()
    for _ in range(3):
        t.on_report()
        t.on_resume()
    snap = t.snapshot()
    assert snap["collective_bytes_per_step"] == {
        "reduce_scatter/int8": 1000, "all_gather/float32": 64}
    assert snap["opt_state_bytes"] == 4096

    key_rs = tuple(sorted((("rank", "555"), ("op", "reduce_scatter"),
                           ("dtype", "int8"))))
    key_ag = tuple(sorted((("rank", "555"), ("op", "all_gather"),
                           ("dtype", "float32"))))
    vals = get_metric("raytpu_train_collective_bytes_total") \
        .snapshot()["values"]
    assert vals[key_rs] == 3000 and vals[key_ag] == 192
    gauge = get_metric("raytpu_train_opt_state_bytes").snapshot()["values"]
    assert gauge[(("rank", "555"),)] == 4096
    # the driver rollup sums resident optimizer HBM across ranks
    from ray_tpu.train.observability import aggregate
    roll = aggregate({0: snap, 1: dict(snap, opt_state_bytes=4096)})
    assert roll["opt_state_bytes"] == 8192


def test_kill_switch_sheds_all_train_series():
    """train_metrics_enabled=False => zero raytpu_train_* series for this
    tracker's rank, no snapshot piggyback; flipping back on records."""
    from ray_tpu.core.config import Config, reset_config, set_config
    from ray_tpu.train.observability import StepTracker
    from ray_tpu.util.metrics import get_metric

    key = (("rank", "777"),)
    try:
        set_config(Config(train_metrics_enabled=False))
        t = StepTracker(777)
        t.SNAPSHOT_PERIOD_S = 0.0
        t.set_model(flops_per_token=1.0, tokens_per_step=1,
                    peak_flops=1.0)
        t.start()
        with t.phase("step_compute"):
            pass
        assert t.on_report() is None
        assert t.snapshot() is None
        for name in ("raytpu_train_steps_total", "raytpu_train_mfu",
                     "raytpu_train_step_seconds",
                     "raytpu_train_compile_seconds"):
            m = get_metric(name)
            if m is not None:
                snap = m.snapshot()
                vals = snap.get("values") or snap.get("count") or {}
                assert key not in vals, (name, vals)

        set_config(Config(train_metrics_enabled=True))
        t.start()
        t.on_report()   # compile
        t.on_resume()
        snap = t.on_report()
        assert snap is not None and snap["steps"] == 2
        assert get_metric(
            "raytpu_train_steps_total").snapshot()["values"][key] == 2
    finally:
        reset_config()


def test_train_worker_installs_loop_monitor():
    """Satellite: train workers run the event-loop stall detector,
    tagged process=train_worker:<rank> (only RPC loops and serve
    processes were watched before)."""
    from ray_tpu.core.config import Config, reset_config, set_config
    from ray_tpu.train.worker_group import TrainWorker
    from ray_tpu.util.loop_monitor import LoopMonitor

    try:
        set_config(Config(loop_monitor_enabled=True))
        w = TrainWorker(3)
        w.init_session(world_rank=3, world_size=4, local_rank=0,
                       local_world_size=1, node_rank=0,
                       experiment_name="e", trial_name="t", trial_id="i",
                       trial_dir="/tmp/t", checkpoint_path=None,
                       dataset_shards=None, mesh_spec=None)
        mon = w._train_loop_monitor
        assert isinstance(mon, LoopMonitor)
        assert mon.source == "train_worker:3"
        mon.stop()
    finally:
        reset_config()


def test_aggregate_rollup():
    from ray_tpu.train.observability import aggregate

    snap = {"steps": 5, "compile_s": 2.0, "mfu": 0.4, "goodput": 0.8,
            "tokens_total": 100,
            "step_time_s": {"count": 4, "p50": 0.1}}
    other = dict(snap, mfu=0.6, compile_s=3.0, tokens_total=50,
                 step_time_s={"count": 4, "p50": 0.3})
    roll = aggregate({0: snap, 1: other, 2: None})
    assert roll["n_workers"] == 2
    assert roll["mfu"] == pytest.approx(0.5)
    assert roll["compile_s"] == 3.0           # worst rank
    assert roll["step_time_p50_s"] == pytest.approx(0.2)
    assert roll["tokens_total"] == 150
    assert set(roll["workers"]) == {0, 1}
    assert aggregate({0: None}) is None
    assert aggregate({}) is None


# ----------------------------------------------------------- integration


def _obs_loop(config):
    import time as _t

    from ray_tpu import train as rt_train
    obs = rt_train.get_context().observability()
    obs.set_model(flops_per_token=1e3, tokens_per_step=64,
                  peak_flops=1e9)
    for i in range(4):
        with obs.phase("data_wait"):
            _t.sleep(0.001)
        with obs.phase("step_compute"):
            _t.sleep(0.005)
        rt_train.report({"step": i})


def _find_step_chain(evs):
    """chief span -> start_training task -> train_step spans, linked by
    (trace_id, parent_id)."""
    for chief in evs:
        if not (chief.get("state") == "SPAN"
                and chief.get("name") == "train_chief"):
            continue
        tid = chief.get("trace_id")
        tasks = [e for e in evs
                 if e.get("parent_id") == chief.get("span_id")
                 and e.get("trace_id") == tid
                 and "start_training" in (e.get("name") or "")]
        for t in tasks:
            steps = [e for e in evs if e.get("state") == "SPAN"
                     and e.get("name") == "train_step"
                     and e.get("trace_id") == tid
                     and e.get("parent_id") == t.get("span_id")]
            if steps:
                return chief, t, steps
    return None


@pytest.mark.timeout(120)
def test_two_node_run_trace_history_top_profile(ray_start_cluster,
                                                tmp_path, capsys):
    """Acceptance: a 2-node training run yields (a) a connected step
    trace in chrome_trace, (b) non-empty /api/metrics/history with
    derived rates, (c) `raytpu top --once` output with train MFU/goodput
    and node columns, (d) an on-demand profiler artifact that parses."""
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=2)
    cluster.add_node(num_cpus=2)
    assert cluster.wait_for_nodes(2)
    # fast scrape period: the history assertions need two ticks
    cluster.connect_driver(
        _system_config={"metrics_scrape_period_s": 1.0})

    trainer = DataParallelTrainer(
        train_loop_per_worker=_obs_loop,
        scaling_config=ScalingConfig(num_workers=2,
                                     placement_strategy="STRICT_SPREAD"),
        run_config=RunConfig(name="obs-int", storage_path=str(tmp_path)))
    result = trainer.fit()
    assert result.error is None

    # the rollup rode the report channel into Result and train.status()
    obs = result.train_obs
    assert obs and obs["n_workers"] == 2 and obs["steps"] == 4
    assert obs["mfu"] is not None and obs["goodput"] is not None
    assert obs["compile_s"] is not None
    st = train.status("obs-int")
    assert st and st["steps"] == 4
    # a 2-NODE run by construction: STRICT_SPREAD placed one rank per node

    # (a) connected chief -> worker task -> step chain, rendered by
    # chrome_trace with slices for every link
    from ray_tpu.util.tracing import chrome_trace
    deadline = time.monotonic() + 45
    chain, evs = None, []
    while time.monotonic() < deadline and chain is None:
        evs = ray_tpu.timeline()
        chain = _find_step_chain(evs)
        if chain is None:
            time.sleep(0.5)
    assert chain is not None, (
        f"no connected chain in {len(evs)} events; span names: "
        f"{sorted({e.get('name') for e in evs if e.get('state') == 'SPAN'})}")
    chief, task_ev, steps = chain
    assert len(steps) >= 3  # 4 reports - 1 compile step
    trace = chrome_trace(evs)
    slice_names = {e.get("name") for e in trace if e.get("ph") == "X"}
    for name in ("train_chief", "train_step", "step_compute", "data_wait"):
        assert name in slice_names, f"no slice for {name}"
    # flow arrows: every step span finishes a flow from its parent task
    fin_ids = {e.get("id") for e in trace if e.get("ph") == "f"}
    assert steps[0]["parent_id"] in fin_ids

    # (b) dashboard history: non-empty series + derived rates
    import requests

    from ray_tpu.dashboard import start_dashboard, stop_dashboard
    port = start_dashboard(port=0)
    try:
        base = f"http://127.0.0.1:{port}/api"
        deadline = time.monotonic() + 40
        good, with_train = None, False
        while time.monotonic() < deadline and not (good and with_train):
            hist = requests.get(f"{base}/metrics/history",
                                timeout=20).json()
            for nid, nv in (hist.get("nodes") or {}).items():
                if nv.get("n_samples", 0) >= 2 and nv.get("rates"):
                    good = (nid, nv)
                if any(k.startswith("raytpu_train_")
                       for k in nv.get("series", ())):
                    with_train = True
            if not (good and with_train):
                time.sleep(1.0)
        assert good is not None, "no node accumulated rate-able history"
        nid, nv = good
        assert any(k.startswith("raytpu_") for k in nv["series"])
        # the run's own series reached the history store via an agent
        assert with_train, "no raytpu_train_* series in any node's history"
        # /api/metrics serves the freshest sample per node from the SAME
        # store (both nodes present, neither silently dropped)
        m = requests.get(f"{base}/metrics", timeout=20).json()
        assert len(m["nodes"]) == 2, m["nodes"].keys()
    finally:
        stop_dashboard()

    # (c) raytpu top --once: train MFU/goodput next to the node columns.
    # The workers flushed their final registry synchronously at the done
    # round, but the agent-side snapshot lands async — poll briefly.
    import re

    from ray_tpu.scripts import cli
    deadline = time.monotonic() + 30
    out = ""
    while time.monotonic() < deadline:
        cli.cmd_top(types.SimpleNamespace(once=True, interval=0.6))
        out = capsys.readouterr().out
        if re.search(r"mfu=\d", out):
            break
    assert "NODE" in out and "CPU" in out and "SHM" in out, out
    assert re.search(r"mfu=\d", out) and re.search(r"goodput=\d", out), out
    # both node ids appear as rows
    for n in ray_tpu.nodes():
        assert n["NodeID"][:12] in out

    # (d) on-demand profiler capture: artifact exists and parses
    res = cli.cmd_profile(types.SimpleNamespace(node=None, duration=0.6))
    assert os.path.exists(res["path"]), res
    assert res["mode"] == "stacks"  # CPU cluster: the sampling fallback
    data = json.load(open(res["path"]))
    assert data["traceEvents"], "profile captured no events"
    assert {e["ph"] for e in data["traceEvents"]} >= {"B", "E"}
