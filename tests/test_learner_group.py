"""Cross-process LearnerGroup tests (reference:
``rllib/core/learner/learner_group.py:61`` — multi-worker DDP learners).

Two learner ACTOR processes form one jax.distributed namespace over the
virtual CPU mesh (the seam proven in tests/test_train.py's two-process
trainer test); each feeds its half of the global batch and XLA's gradient
psum crosses the process boundary.  The equality test pins the collective
math to the single-process answer; the CartPole test is the learning gate.
"""

import numpy as np
import pytest


def _ppo_rollout(rng, T, B):
    actions = rng.randint(0, 2, (T, B)).astype(np.float32)
    return {
        "obs": rng.randn(T, B, 4).astype(np.float32),
        "actions": actions,
        "logp": np.full((T, B), np.log(0.5), np.float32),
        "values": np.zeros((T, B), np.float32),
        "rewards": actions.copy(),
        "dones": np.zeros((T, B), np.float32),
        "last_values": np.zeros((B,), np.float32),
    }


@pytest.mark.timeout(120)
def test_distributed_group_matches_local_update(ray_start_regular):
    """2 learner processes x 2 devices (dp=4) == single-device learner,
    same seed, same batch: proves the cross-process psum computes the same
    gradient the local path does."""
    from ray_tpu.rllib.learner import Learner
    from ray_tpu.rllib.learner_group import DistributedLearnerGroup
    from ray_tpu.rllib.models import build_model

    spec = dict(obs_dim=4, action_dim=2, hidden=(16,), continuous=False)
    cfg = {"lr": 1e-3, "num_epochs": 1, "num_minibatches": 2}
    rng = np.random.RandomState(3)
    rollout = _ppo_rollout(rng, T=8, B=8)

    local = Learner(build_model(spec), cfg, seed=11)
    group = DistributedLearnerGroup(spec, cfg, num_learners=2, seed=11,
                                    devices_per_learner=2)
    assert group.info["num_processes"] == 2
    assert group.info["num_devices"] == 4  # 2 procs x 2 devices in the mesh

    m_local = local.update({k: v.copy() for k, v in rollout.items()})
    m_group = group.update(rollout)
    assert set(m_local) == set(m_group)

    w_local, w_group = local.get_weights(), group.get_weights()
    for k in w_local:
        np.testing.assert_allclose(w_local[k], w_group[k],
                                   rtol=2e-4, atol=2e-5)
    group.shutdown()


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_ppo_learns_cartpole_with_learner_actors(ray_start_regular):
    """The learning gate with num_learners=2: CartPole return clears 100
    (random policy ~20) with the update running in two learner actor
    processes, never in the driver."""
    from ray_tpu.rllib import PPOConfig

    algo = (PPOConfig()
            .environment("CartPole-v1")
            .env_runners(num_env_runners=2, num_envs_per_env_runner=2,
                         rollout_fragment_length=128)
            .learners(num_learners=2)
            .training(lr=1e-3, num_epochs=8, num_minibatches=4,
                      entropy_coeff=0.01, model={"hidden": (64, 64)})
            .debugging(seed=0)
            .build())
    best = 0.0
    try:
        for _ in range(30):
            result = algo.train()
            ret = result["episode_return_mean"]
            if np.isfinite(ret):
                best = max(best, ret)
            if best >= 100.0:
                break
    finally:
        algo.stop()
    assert best >= 100.0, f"best return {best} < 100 within budget"


@pytest.mark.timeout(120)
def test_impala_with_learner_actors_smoke(ray_start_regular):
    """IMPALA's async loop with a remote V-trace learner group: a couple of
    iterations run, metrics flow back, and the version-lag diagnostic is
    still tracked (the decoupling evidence)."""
    from ray_tpu.rllib import IMPALAConfig

    # IMPALA updates on ONE fragment at a time, so the fragment's env axis
    # (num_envs_per_env_runner) must divide across the 2 learner ranks.
    algo = (IMPALAConfig()
            .environment("CartPole-v1")
            .env_runners(num_env_runners=2, num_envs_per_env_runner=2,
                         rollout_fragment_length=32)
            .learners(num_learners=2)
            .training(updates_per_iter=4)
            .build())
    try:
        result = algo.train()
        assert result["training_iteration"] == 1
        assert "policy_loss" in result
        assert np.isfinite(result["mean_version_lag"])
    finally:
        algo.stop()
