"""External-env protocol (reference: ``rllib/env/policy_client.py``,
``policy_server_input.py``, ``rllib/examples/serving/``): an out-of-cluster
simulator drives episodes over HTTP while the algorithm trains on the
resulting stream."""

import threading

import numpy as np
import pytest

import gymnasium as gym

from ray_tpu.rllib import PPO, PPOConfig, PolicyClient
from ray_tpu.rllib.external import PolicyServerInput
from ray_tpu.rllib.models import build_model


def _serve(model_spec, port=0, fragment_len=8, **kw):
    import jax

    model = build_model(model_spec)
    params = model.init(jax.random.PRNGKey(0))
    return PolicyServerInput(model, params, port=port,
                             fragment_len=fragment_len, **kw)


SPEC = dict(obs_dim=4, action_dim=2, hidden=(16,), continuous=False)


def test_episode_stream_and_fragments():
    """Commands append contiguous per-episode fragments; rewards attach to
    the step that earned them; truncation folds the bootstrap."""
    srv = _serve(SPEC, fragment_len=4)
    try:
        client = PolicyClient(srv.address)
        eid = client.start_episode()
        for t in range(3):
            a = client.get_action(eid, np.ones(4) * t)
            assert a in (0, 1)
            client.log_returns(eid, 1.0)
        client.end_episode(eid)
        batch = srv.next(3, timeout=10)
        assert batch["obs"].shape == (3, 1, 4)
        assert batch["rewards"].ravel().tolist() == [1.0, 1.0, 1.0]
        assert batch["dones"].ravel().tolist() == [0.0, 0.0, 1.0]
        assert batch["last_values"].tolist() == [0.0]
    finally:
        srv.stop()


def test_fragment_flush_mid_episode():
    """A long-running episode flushes fixed-size fragments without waiting
    for end_episode; the cut step carries the folded bootstrap."""
    srv = _serve(SPEC, fragment_len=4, gamma=0.5)
    try:
        client = PolicyClient(srv.address)
        eid = client.start_episode()
        for t in range(6):  # episode still open; nonzero obs so V(obs) != 0
            client.get_action(eid, np.ones(4) * (t + 1))
            client.log_returns(eid, 2.0)
        batch = srv.next(4, timeout=10)  # flushed at the 5th get_action
        assert batch["dones"].ravel().tolist() == [0.0, 0.0, 0.0, 1.0]
        r = batch["rewards"].ravel()
        assert r[:3].tolist() == [2.0, 2.0, 2.0]
        assert r[3] != 2.0  # 2.0 + gamma * V(next obs) folded in
        client.end_episode(eid)
    finally:
        srv.stop()


def test_truncated_end_folds_bootstrap():
    """A time-limit end (truncated=True + final obs) folds gamma*V into
    the last reward instead of training a fake terminal."""
    srv = _serve(SPEC, fragment_len=100, gamma=0.5)
    try:
        client = PolicyClient(srv.address)
        eid = client.start_episode()
        client.get_action(eid, np.ones(4))
        client.log_returns(eid, 1.0)
        client.end_episode(eid, np.ones(4) * 2, truncated=True)
        truncated = srv.next(1, timeout=10)

        eid = client.start_episode()
        client.get_action(eid, np.ones(4))
        client.log_returns(eid, 1.0)
        client.end_episode(eid, np.ones(4) * 2)  # true terminal
        terminal = srv.next(1, timeout=10)

        assert terminal["rewards"].ravel().tolist() == [1.0]
        assert truncated["rewards"].ravel()[0] != 1.0  # + 0.5 * V(final)
        assert truncated["dones"].ravel().tolist() == [1.0]
    finally:
        srv.stop()


def test_log_action_and_weights():
    """Client-side inference: pull weights, act locally, log the action."""
    srv = _serve(SPEC, fragment_len=100)
    try:
        client = PolicyClient(srv.address)
        weights, version = client.get_weights()
        assert version == 0 and isinstance(weights, dict)
        eid = client.start_episode()
        client.log_action(eid, np.zeros(4), 1)
        client.log_returns(eid, 0.5)
        client.end_episode(eid)
        batch = srv.next(1, timeout=10)
        assert batch["actions"].ravel().tolist() == [1.0]
        assert batch["rewards"].ravel().tolist() == [0.5]
        # unknown episode surfaces as a typed server error
        with pytest.raises(RuntimeError, match="unknown episode"):
            client.get_action("nope", np.zeros(4))
    finally:
        srv.stop()


@pytest.mark.timeout(240)
def test_external_ppo_trains(ray_start_regular):
    """End-to-end: PPO in external mode learns from a CartPole simulator
    that lives in the test process and talks HTTP only (reference:
    rllib/examples/serving/cartpole_server.py + cartpole_client.py)."""
    probe = gym.make("CartPole-v1")
    config = (PPOConfig()
              .environment(observation_space=probe.observation_space,
                           action_space=probe.action_space)
              .external(port=0)
              .env_runners(rollout_fragment_length=256)
              .training(num_epochs=2, num_minibatches=2,
                        model={"hidden": (32, 32)}))
    probe.close()
    algo = PPO(config)
    stop = threading.Event()

    def simulator():
        env = gym.make("CartPole-v1")
        client = PolicyClient(algo.policy_server.address)
        try:
            _run_episodes(env, client)
        except Exception:
            if not stop.is_set():  # only teardown races are expected
                raise
        finally:
            env.close()

    def _run_episodes(env, client):
        while not stop.is_set():
            eid = client.start_episode()
            obs, _ = env.reset()
            done = False
            term = trunc = False
            while not done and not stop.is_set():
                action = client.get_action(eid, obs)
                obs, reward, term, trunc, _ = env.step(action)
                client.log_returns(eid, reward)
                done = term or trunc
            client.end_episode(eid, obs, truncated=trunc and not term)

    sim = threading.Thread(target=simulator, daemon=True)
    sim.start()
    try:
        results = [algo.train() for _ in range(3)]
        assert results[-1]["training_iteration"] == 3
        assert results[-1]["num_env_steps_sampled"] == 3 * 256
        assert np.isfinite(results[-1]["policy_loss"])
        assert results[-1]["episode_return_mean"] > 0
    finally:
        stop.set()
        algo.stop()
        sim.join(timeout=10)
