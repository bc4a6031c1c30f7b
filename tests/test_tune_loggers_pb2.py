"""Per-trial loggers (progress.csv / result.json / tfevents) + PB2.

Reference: ``python/ray/tune/logger/`` and ``tune/schedulers/pb2.py``.
"""

import csv
import glob
import json
import os
import struct

import pytest

import ray_tpu
from ray_tpu import tune
from ray_tpu.tune import PB2, TuneConfig, Tuner
from ray_tpu.train import RunConfig


def test_per_trial_logger_files(ray_start_regular, tmp_path):
    def trainable(config):
        for i in range(3):
            tune.report({"loss": config["x"] * (3 - i),
                         "nested": {"acc": i / 3.0}})

    results = Tuner(
        trainable,
        param_space={"x": tune.grid_search([1.0, 2.0])},
        tune_config=TuneConfig(metric="loss", mode="min"),
        run_config=RunConfig(name="log", storage_path=str(tmp_path)),
    ).fit()
    assert len(results.trials) == 2

    for t in results.trials:
        # progress.csv: header + 3 rows, nested keys flattened
        with open(os.path.join(t.trial_dir, "progress.csv")) as f:
            rows = list(csv.DictReader(f))
        assert len(rows) == 3
        assert "loss" in rows[0] and "nested/acc" in rows[0]
        assert float(rows[-1]["loss"]) == pytest.approx(t.config["x"])

        # result.json: one JSON object per line
        with open(os.path.join(t.trial_dir, "result.json")) as f:
            recs = [json.loads(line) for line in f]
        assert len(recs) == 3
        assert recs[0]["loss"] == pytest.approx(t.config["x"] * 3)

        # tfevents: valid TFRecord framing with Event payloads
        evs = glob.glob(os.path.join(t.trial_dir, "events.out.tfevents.*"))
        assert len(evs) == 1
        with open(evs[0], "rb") as f:
            data = f.read()
        n, off = 0, 0
        while off < len(data):
            (length,) = struct.unpack_from("<Q", data, off)
            off += 12 + length + 4  # header + len-crc + payload + data-crc
            n += 1
        assert off == len(data)       # framing is exact
        assert n == 4                 # file_version event + 3 results


def test_tb_events_readable_by_tensorflow_format():
    """Cross-check the hand-rolled Event protobuf against a reference
    decoding of the varint/field layout."""
    from ray_tpu.tune.loggers import _event, _scalar_summary
    ev = _event(123.5, 7, summary=_scalar_summary("loss", 1.25))
    # field 1 (wall_time, double)
    assert ev[0] == (1 << 3) | 1
    assert struct.unpack_from("<d", ev, 1)[0] == 123.5
    # field 2 (step, varint)
    assert ev[9] == (2 << 3) | 0 and ev[10] == 7
    # field 5 (summary, length-delimited)
    assert ev[11] == (5 << 3) | 2


def test_pb2_min_mode_and_bounded_fallback():
    """mode="min" improvements must be recorded as POSITIVE model reward
    (TrialScheduler._score already negates; no double sign flip), and
    pre-GP exploration must stay inside hyperparam_bounds."""
    from types import SimpleNamespace

    pb2 = PB2(metric="loss", mode="min", perturbation_interval=1,
              hyperparam_bounds={"lr": (0.1, 1.0)}, seed=0)
    trial = SimpleNamespace(trial_id="t1", config={"lr": 0.9})
    pb2.on_result(trial, {"loss": 10.0, "training_iteration": 1})
    pb2.on_result(trial, {"loss": 4.0, "training_iteration": 2})  # improved
    assert len(pb2._data) == 1
    assert pb2._data[0][1] > 0  # loss fell -> positive reward delta

    # fallback explore (fewer than 4 observations): bounded + in-range
    for _ in range(50):
        new = pb2._explore_fallback({"lr": 0.9})
        assert 0.1 <= new["lr"] <= 1.0, new


def test_pb2_beats_random_on_quadratic(ray_start_regular, tmp_path):
    """PB2's GP-UCB explore should steer lr toward the optimum of a toy
    quadratic reward faster than the initial bad configs would.

    The trainable checkpoints every report: PBT's exploit clones a donor
    checkpoint (reference pb2.py/pbt.py contract), so a bottom-quantile
    trial resumes from the donor's cumulative progress with a new config."""
    from ray_tpu.train import Checkpoint
    up = str(tmp_path / "up")
    os.makedirs(up)

    def trainable(config):
        import time
        lr = config["lr"]
        start, score = 0, 0.0
        ckpt = tune.get_checkpoint()
        if ckpt is not None:
            with open(os.path.join(ckpt.path, "state.json")) as f:
                st = json.load(f)
            start, score = st["i"], st["score"]
        else:
            # all four trials are up before any reports (a loaded machine
            # starts their workers seconds apart) and then report in step.
            # PB2 holds a trial at step t against the others' latest scores,
            # so the bowl is steep enough that a weak trial (0.19 a step)
            # ranks under a strong one (0.36) that is up to half its steps
            # behind it: whatever order the reports arrive in, a weak trial
            # is in the bottom half at its fourth step.
            open(os.path.join(up, str(lr)), "w").close()
            deadline = time.monotonic() + 60
            while len(os.listdir(up)) < 4 and time.monotonic() < deadline:
                time.sleep(0.02)
        for i in range(start, 8):
            score += 1.0 - 4.0 * (lr - 0.5) ** 2  # optimum at lr=0.5
            cdir = os.path.join(tune.get_trial_dir(), f"ck_{i}")
            os.makedirs(cdir, exist_ok=True)
            with open(os.path.join(cdir, "state.json"), "w") as f:
                json.dump({"i": i + 1, "score": score}, f)
            tune.report({"score": score, "lr": lr, "training_iteration": i + 1},
                        checkpoint=Checkpoint(cdir))

    results = Tuner(
        trainable,
        param_space={"lr": tune.grid_search([0.05, 0.1, 0.9, 0.95])},
        tune_config=TuneConfig(
            metric="score", mode="max", max_concurrent_trials=4,
            scheduler=PB2(perturbation_interval=2,
                          quantile_fraction=0.5,
                          hyperparam_bounds={"lr": [0.0, 1.0]}, seed=0)),
        run_config=RunConfig(name="pb2", storage_path=str(tmp_path)),
    ).fit()
    assert any(t.restarts > 0 for t in results.trials)
    # after perturbation, some trial must have moved lr off the grid values
    final_lrs = [t.config["lr"] for t in results.trials]
    assert any(lr not in (0.05, 0.1, 0.9, 0.95) for lr in final_lrs), final_lrs


def test_resource_changing_scheduler(ray_start_regular, tmp_path):
    """After iteration 2 the allocation fn doubles the trial's CPUs: the
    controller checkpoint-restarts the trial actor with the new allocation
    and the trainable observes it via tune.get_trial_resources()
    (reference: tune/schedulers/resource_changing_scheduler.py)."""
    from ray_tpu.train import Checkpoint
    from ray_tpu.tune import ResourceChangingScheduler

    def trainable(config):
        start = 0
        ck = tune.get_checkpoint()
        if ck is not None:
            with open(os.path.join(ck.path, "it")) as f:
                start = int(f.read())
        for i in range(start, 5):
            cdir = os.path.join(tune.get_trial_dir(), f"rck_{i}")
            os.makedirs(cdir, exist_ok=True)
            with open(os.path.join(cdir, "it"), "w") as f:
                f.write(str(i + 1))
            tune.report({"score": 1.0, "training_iteration": i + 1,
                         "cpus": tune.get_trial_resources().get("CPU", 0)},
                        checkpoint=Checkpoint(cdir))

    def alloc(_state, trial, result):
        if result.get("training_iteration", 0) >= 2:
            return {"CPU": 2}
        return None

    results = Tuner(
        trainable,
        param_space={"x": tune.grid_search([1.0])},
        tune_config=TuneConfig(
            metric="score", mode="max",
            scheduler=ResourceChangingScheduler(
                resources_allocation_function=alloc)),
        run_config=RunConfig(name="rcs", storage_path=str(tmp_path)),
    ).fit()
    (t,) = results.trials
    assert t.restarts >= 1
    assert t.resources == {"CPU": 2}
    # the trainable saw the new allocation after the restart
    cpus = [m["cpus"] for m in t.metrics_history]
    assert cpus[0] == 1.0 and cpus[-1] == 2.0, cpus
