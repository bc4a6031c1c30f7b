"""Tune tests — mirrors reference ``python/ray/tune/tests`` coverage for
variant generation, the controller loop, ASHA early stopping, PBT
perturbation, checkpointed trials, and Trainer integration."""

import os

import pytest

import ray_tpu
from ray_tpu import tune
from ray_tpu.train import Checkpoint, FailureConfig, RunConfig
from ray_tpu.tune import (AsyncHyperBandScheduler, BasicVariantGenerator,
                          PopulationBasedTraining, TuneConfig, Tuner)


def test_basic_variant_grid_and_samples():
    gen = BasicVariantGenerator(
        {"lr": tune.grid_search([0.1, 0.01]),
         "wd": tune.uniform(0.0, 1.0),
         "nested": {"bs": tune.grid_search([8, 16])}},
        num_samples=2, seed=0)
    configs = []
    while True:
        c = gen.suggest(f"t{len(configs)}")
        if c is None:
            break
        configs.append(c)
    assert len(configs) == 2 * 2 * 2  # grid 2x2 × num_samples 2
    assert {c["lr"] for c in configs} == {0.1, 0.01}
    assert {c["nested"]["bs"] for c in configs} == {8, 16}
    assert all(0.0 <= c["wd"] <= 1.0 for c in configs)


def test_search_space_samplers():
    import random
    rng = random.Random(0)
    assert 1 <= tune.randint(1, 10).sample(rng) < 10
    assert tune.choice(["a", "b"]).sample(rng) in ("a", "b")
    v = tune.loguniform(1e-4, 1e-1).sample(rng)
    assert 1e-4 <= v <= 1e-1
    q = tune.quniform(0, 1, 0.25).sample(rng)
    assert q in (0.0, 0.25, 0.5, 0.75, 1.0)


def test_tuner_fifo(ray_start_regular, tmp_path):
    def trainable(config):
        for i in range(3):
            tune.report({"score": config["x"] * (i + 1)})

    results = Tuner(
        trainable,
        param_space={"x": tune.grid_search([1, 2, 3])},
        tune_config=TuneConfig(metric="score", mode="max"),
        run_config=RunConfig(name="fifo", storage_path=str(tmp_path)),
    ).fit()
    assert len(results) == 3
    best = results.get_best_result()
    assert best.metrics["score"] == 9
    df = results.get_dataframe()
    assert len(df) == 3 and "config/x" in df.columns
    # experiment state snapshot written
    assert os.path.exists(tmp_path / "fifo" / "experiment_state.json")


def test_asha_stops_bad_trials(ray_start_regular, tmp_path):
    def trainable(config):
        for i in range(8):
            tune.report({"acc": config["q"] * (i + 1)})

    # Sequential trials with the strong config first make the rung cutoffs
    # deterministic: weak trials must be stopped at a rung.
    results = Tuner(
        trainable,
        param_space={"q": tune.grid_search([2.0, 0.1, 1.0, 0.2])},
        tune_config=TuneConfig(
            metric="acc", mode="max", max_concurrent_trials=1,
            scheduler=AsyncHyperBandScheduler(max_t=8, grace_period=2,
                                              reduction_factor=2)),
        run_config=RunConfig(name="asha", storage_path=str(tmp_path)),
    ).fit()
    best = results.get_best_result()
    assert best.metrics["acc"] == 16.0  # q=2.0 ran to completion
    lens = sorted(len(r.metrics_history or []) for r in results.results)
    assert lens[0] < 8  # weak trials early-stopped
    assert lens[-1] == 8  # strong trial completed


def test_trial_checkpoint_and_restart(ray_start_regular, tmp_path):
    marker = str(tmp_path / "crashed")

    def trainable(config):
        import json, tempfile
        start = 0
        ck = tune.get_checkpoint()
        if ck:
            with open(os.path.join(ck.path, "it.json")) as f:
                start = json.load(f)["i"] + 1
        for i in range(start, 4):
            if i == 2 and not os.path.exists(marker):
                open(marker, "w").close()
                raise RuntimeError("boom")
            d = tempfile.mkdtemp()
            with open(os.path.join(d, "it.json"), "w") as f:
                json.dump({"i": i}, f)
            tune.report({"i": i}, checkpoint=Checkpoint(d))

    results = Tuner(
        trainable,
        param_space={},
        tune_config=TuneConfig(metric="i", mode="max"),
        run_config=RunConfig(name="ckpt", storage_path=str(tmp_path),
                             failure_config=FailureConfig(max_failures=2)),
    ).fit()
    best = results.get_best_result()
    assert best.metrics["i"] == 3
    assert best.checkpoint is not None


def test_pbt_perturbs(ray_start_regular, tmp_path):
    up = str(tmp_path / "up")
    os.makedirs(up)

    def trainable(config):
        import json, tempfile, time
        ck = tune.get_checkpoint()
        base = 0.0
        lr = config["lr"]
        if ck:
            with open(os.path.join(ck.path, "w.json")) as f:
                base = json.load(f)["w"]
        else:
            # both trials are up before either reports: on a loaded machine
            # the weak one, which starts first, otherwise ends its 8 rounds
            # while the strong one's worker is still starting, and PBT has
            # had nobody to hold it against (a whole run of PR 51 failed so)
            open(os.path.join(up, str(lr)), "w").close()
            deadline = time.monotonic() + 60
            while len(os.listdir(up)) < 2 and time.monotonic() < deadline:
                time.sleep(0.02)
        w = base
        for i in range(8):
            w += lr
            d = tempfile.mkdtemp()
            with open(os.path.join(d, "w.json"), "w") as f:
                json.dump({"w": w}, f)
            tune.report({"w": w}, checkpoint=Checkpoint(d))

    results = Tuner(
        trainable,
        param_space={"lr": tune.grid_search([0.01, 1.0])},
        tune_config=TuneConfig(
            metric="w", mode="max", max_concurrent_trials=2,
            scheduler=PopulationBasedTraining(
                perturbation_interval=2, quantile_fraction=0.5,
                hyperparam_mutations={"lr": [0.01, 1.0, 2.0]}, seed=0)),
        run_config=RunConfig(name="pbt", storage_path=str(tmp_path)),
    ).fit()
    # the weak trial (lr=0.01) should have been perturbed at least once
    assert any(t.restarts > 0 for t in results.trials)


def test_tuner_over_trainer(ray_start_regular, tmp_path):
    from ray_tpu.train import DataParallelTrainer, ScalingConfig
    from ray_tpu import train as rt_train

    def loop(config):
        for i in range(2):
            rt_train.report({"loss": 1.0 / config["lr"] + i})

    trainer = DataParallelTrainer(
        train_loop_per_worker=loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="inner", storage_path=str(tmp_path)))
    results = Tuner(
        trainer,
        param_space={"train_loop_config": {"lr": tune.grid_search([1.0, 2.0])}},
        tune_config=TuneConfig(metric="loss", mode="min"),
        run_config=RunConfig(name="over_trainer", storage_path=str(tmp_path)),
        resources_per_trial={"CPU": 1},
    ).fit()
    best = results.get_best_result()
    assert best.metrics["loss"] == pytest.approx(1.5)


def test_tpe_searcher_beats_random_on_toy():
    """TPE must concentrate samples near the optimum once past startup
    (seeded, offline — no cluster needed)."""
    from ray_tpu.tune import TPESearcher

    space = {"x": tune.uniform(-1.0, 1.0), "y": tune.choice([0, 1, 2])}

    def score(cfg):
        # optimum at x=0.3, y=1 (small categorical coupling: per-dimension
        # Parzen models are marginal, so a dominant cross-dim penalty would
        # make the toy deceptive — a known TPE limitation, not a bug)
        return -(cfg["x"] - 0.3) ** 2 - 0.1 * (cfg["y"] != 1)

    tpe = TPESearcher(space, metric="obj", mode="max", n_startup=8, seed=0)
    xs = []
    best = -1e9
    for i in range(40):
        cfg = tpe.suggest(f"t{i}")
        xs.append(cfg["x"])
        val = score(cfg)
        best = max(best, val)
        tpe.on_trial_complete(f"t{i}", {"obj": val})
    startup_err = sum(abs(x - 0.3) for x in xs[:8]) / 8
    late_err = sum(abs(x - 0.3) for x in xs[-10:]) / 10
    assert late_err < startup_err, (
        f"no exploitation: late {late_err:.3f} vs startup {startup_err:.3f}")
    assert best > -0.05, f"best {best} too far from optimum"
    # random search with the same budget: expected best ~= -0.0025 only with
    # luck; assert TPE used < half its samples far from the optimum
    assert sum(1 for x in xs[8:] if abs(x - 0.3) < 0.25) > 16


def test_tpe_log_and_int_domains():
    from ray_tpu.tune import TPESearcher

    space = {"lr": tune.loguniform(1e-5, 1e-1),
             "layers": tune.randint(1, 16)}
    import math

    tpe = TPESearcher(space, metric="m", mode="min", n_startup=5, seed=1)
    layer_picks = []
    for i in range(25):
        cfg = tpe.suggest(f"t{i}")
        assert 1e-5 <= cfg["lr"] <= 1e-1
        assert 1 <= cfg["layers"] < 16
        assert isinstance(cfg["layers"], int)
        layer_picks.append(cfg["layers"])
        # optimum near lr=1e-3, layers=4
        val = (math.log10(cfg["lr"]) + 3) ** 2 + (cfg["layers"] - 4) ** 2
        tpe.on_trial_complete(f"t{i}", {"m": val})
    # exploitation: late suggestions cluster nearer layers=4 than startup
    late = layer_picks[-8:]
    assert sum(abs(v - 4) for v in late) / 8 <= \
        sum(abs(v - 4) for v in layer_picks[:5]) / 5 + 0.5


def test_bayesopt_searcher_converges():
    """GP-EI must concentrate near the optimum after startup and beat the
    startup phase (seeded, offline — parity target: tune.search.bayesopt)."""
    from ray_tpu.tune import BayesOptSearcher

    space = {"x": tune.uniform(-1.0, 1.0),
             "lr": tune.loguniform(1e-4, 1.0),
             "k": tune.choice(["a", "b"])}
    import math

    def score(cfg):
        return (-(cfg["x"] - 0.25) ** 2
                - 0.3 * (math.log10(cfg["lr"]) + 2) ** 2
                - 0.1 * (cfg["k"] != "b"))

    s = BayesOptSearcher(space, metric="obj", mode="max", n_startup=8, seed=3)
    xs, best = [], -1e9
    for i in range(40):
        cfg = s.suggest(f"t{i}")
        assert -1.0 <= cfg["x"] <= 1.0 and 1e-4 <= cfg["lr"] <= 1.0
        xs.append(cfg["x"])
        val = score(cfg)
        best = max(best, val)
        s.on_trial_complete(f"t{i}", {"obj": val})
    startup_err = sum(abs(x - 0.25) for x in xs[:8]) / 8
    late_err = sum(abs(x - 0.25) for x in xs[-10:]) / 10
    assert late_err < startup_err, (
        f"no exploitation: late {late_err:.3f} vs startup {startup_err:.3f}")
    assert best > -0.08, f"best {best} too far from optimum"


def test_experiment_resume(ray_start_regular, tmp_path):
    """Kill an experiment mid-flight; Tuner.restore must finish the
    interrupted trials from their checkpoints and keep finished results."""
    from ray_tpu.tune import TuneController

    def trainable(config):
        ckpt = tune.get_checkpoint()
        start = 0
        if ckpt:
            with open(os.path.join(ckpt.path, "it.txt")) as f:
                start = int(f.read()) + 1
        for i in range(start, 4):
            d = os.path.join(tune.get_trial_dir(), f"_w{i}")
            os.makedirs(d, exist_ok=True)
            with open(os.path.join(d, "it.txt"), "w") as f:
                f.write(str(i))
            tune.report({"iter": i, "obj": config["x"] + i},
                        checkpoint=Checkpoint(d))

    exp_dir = str(tmp_path / "resume_exp")
    os.makedirs(exp_dir, exist_ok=True)
    searcher = BasicVariantGenerator({"x": tune.grid_search([10.0, 20.0])})
    searcher.metric, searcher.mode = "obj", "max"

    class StopAfterFirst(TuneController):
        """Simulates a crash: stop the event loop after one trial finishes."""
        def run(self):
            try:
                self._abort_after_one = True
                return super().run()
            except KeyboardInterrupt:
                return self.trials

        def _on_report(self, trial, metrics, ckpt):
            super()._on_report(trial, metrics, ckpt)
            done = [t for t in self.trials if t.status == "TERMINATED"]
            if done and getattr(self, "_abort_after_one", False):
                self._save_state()
                raise KeyboardInterrupt

    ctrl = StopAfterFirst(trainable, searcher, None, exp_dir,
                          metric="obj", mode="max", max_concurrent=1)
    trials = ctrl.run()
    assert any(t.status == "TERMINATED" for t in trials)
    assert os.path.exists(os.path.join(exp_dir, "experiment_state.pkl"))

    # restore and finish
    tuner = Tuner.restore(exp_dir, trainable,
                          tune_config=TuneConfig(metric="obj", mode="max"))
    results = tuner.fit()
    assert len(results.trials) == 2
    assert all(t.status == "TERMINATED" for t in results.trials)
    best = results.get_best_result()
    assert best.metrics["obj"] == pytest.approx(23.0)  # x=20 + iter 3


def test_cmaes_searcher_converges():
    """CMA-ES adapts mean/step-size toward the optimum across
    generations (seeded, offline — parity target: the CMA samplers tune
    wraps via nevergrad/optuna)."""
    from ray_tpu.tune import CMAESSearcher

    space = {"x": tune.uniform(0.0, 1.0),
             "y": tune.uniform(-2.0, 2.0),
             "k": tune.choice(["a", "b"])}

    def score(cfg):
        return (-(cfg["x"] - 0.7) ** 2 - (cfg["y"] - 0.4) ** 2
                - 0.05 * (cfg["k"] != "b"))

    s = CMAESSearcher(space, metric="obj", mode="max", seed=0)
    sigma0 = s._sigma
    best = -1e9
    for i in range(120):
        cfg = s.suggest(f"t{i}")
        assert 0.0 <= cfg["x"] <= 1.0 and -2.0 <= cfg["y"] <= 2.0
        val = score(cfg)
        best = max(best, val)
        s.on_trial_complete(f"t{i}", {"obj": val})
    assert best > -0.02, best
    # step size annealed as the distribution concentrated
    assert s._sigma < sigma0
    with pytest.raises(ValueError, match="popsize"):
        CMAESSearcher(space, metric="obj", popsize=1)
