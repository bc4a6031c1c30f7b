"""DQN + replay buffers (reference: rllib/algorithms/dqn tests and
rllib/utils/replay_buffers tests)."""

import numpy as np
import pytest


def test_uniform_replay_buffer():
    from ray_tpu.rllib.replay_buffer import ReplayBuffer

    buf = ReplayBuffer(capacity=100, seed=0)
    buf.add({"x": np.arange(10), "y": np.ones((10, 2))})
    assert len(buf) == 10
    s = buf.sample(4)
    assert s["x"].shape == (4,) and s["y"].shape == (4, 2)
    # ring wrap: capacity bounds the size
    for _ in range(20):
        buf.add({"x": np.arange(10), "y": np.ones((10, 2))})
    assert len(buf) == 100


def test_prioritized_replay_buffer():
    from ray_tpu.rllib.replay_buffer import PrioritizedReplayBuffer

    buf = PrioritizedReplayBuffer(capacity=64, alpha=1.0, beta=1.0, seed=0)
    buf.add({"x": np.arange(64)})
    # give one transition overwhelming priority -> it should dominate samples
    buf.update_priorities([7], [1000.0])
    counts = np.zeros(64)
    for _ in range(50):
        s = buf.sample(8)
        for i in s["_indices"]:
            counts[i] += 1
    assert counts[7] == counts.max()
    assert "_weights" in buf.sample(8)
    # importance weights: the high-priority sample gets the smallest weight
    s = buf.sample(32)
    w7 = s["_weights"][s["_indices"] == 7]
    if len(w7):
        assert w7.min() <= s["_weights"].max()


def test_sum_tree_prefix_find():
    from ray_tpu.rllib.replay_buffer import _SumTree

    t = _SumTree(8)
    for i, p in enumerate([1.0, 2.0, 3.0, 4.0]):
        t.set(i, p)
    assert t.total() == 10.0
    assert t.find(0.5) == 0
    assert t.find(1.5) == 1
    assert t.find(9.9) == 3


@pytest.mark.timeout(240)
def test_dqn_learns_cartpole(ray_start_regular):
    pytest.importorskip("gymnasium")
    from ray_tpu.rllib.dqn import DQNConfig

    algo = (DQNConfig()
            .environment("CartPole-v1")
            .env_runners(num_env_runners=1, num_envs_per_env_runner=2,
                         rollout_steps=400)
            .training(lr=1e-3, batch_size=64, train_iters=16,
                      target_update_tau=0.005, n_step=3,
                      replay=dict(capacity=50_000, learn_starts=1_000))
            .exploring(epsilon_start=1.0, epsilon_end=0.05,
                       epsilon_decay_steps=10_000)
            .debugging(seed=0)
            .build())
    try:
        best = -np.inf
        for _ in range(90):
            result = algo.train()
            best = max(best, result["episode_return_mean"])
            if best >= 60.0:
                break
        # untrained CartPole hovers near ~20 return; learning must clear it
        assert best >= 60.0, f"DQN failed to learn: best={best}"
        assert np.isfinite(result["loss"])
    finally:
        algo.stop()


def test_dqn_prioritized_smoke(ray_start_regular):
    pytest.importorskip("gymnasium")
    from ray_tpu.rllib.dqn import DQNConfig

    algo = (DQNConfig()
            .environment("CartPole-v1")
            .env_runners(rollout_steps=200)
            .training(batch_size=32, train_iters=2,
                      replay=dict(capacity=5_000, learn_starts=100,
                                  prioritized=True))
            .build())
    try:
        for _ in range(3):
            result = algo.train()
        assert result["replay_size"] > 0
        assert np.isfinite(result["loss"])
    finally:
        algo.stop()
