"""RLlib breadth, offline: the sample files, BC, MARWIL, CQL.

Models the reference's algorithm test strategy: learning tests with
reward thresholds (rllib/tuned_examples/bc/cartpole_bc.py).

CQL's two learning cases are in ``tests/test_rllib_breadth_cql.py``
(``tests/rllib_breadth.py`` says why the files are apart).
"""
import numpy as np
from rllib_breadth import cluster  # noqa: F401 - the fixture


# --------------------------------------------------------------- offline
def _scripted_cartpole_episodes(n_episodes: int, seed: int = 0):
    """Expert-ish scripted policy: push toward the pole's fall
    direction (reaches ~150-200 return)."""
    import gymnasium as gym

    from ray_tpu.rllib.env.episode import SingleAgentEpisode

    env = gym.make("CartPole-v1")
    eps = []
    rng = np.random.default_rng(seed)
    for i in range(n_episodes):
        obs, _ = env.reset(seed=int(rng.integers(0, 2**31)))
        ep = SingleAgentEpisode(initial_observation=obs)
        while True:
            action = int(obs[2] + 0.5 * obs[3] > 0)
            obs, r, term, trunc, _ = env.step(action)
            ep.add_env_step(obs, action, r, terminated=term, truncated=trunc)
            if term or trunc:
                break
        eps.append(ep.finalize())
    env.close()
    return eps


def test_offline_roundtrip(tmp_path):
    from ray_tpu.rllib.offline import SampleReader, SampleWriter

    eps = _scripted_cartpole_episodes(3)
    w = SampleWriter(str(tmp_path / "samples"))
    w.write(eps)
    w.close()
    back = SampleReader(str(tmp_path / "samples"), shuffle=False).read_all()
    assert len(back) == 3
    for a, b in zip(eps, back):
        assert len(a) == len(b)
        np.testing.assert_allclose(
            np.asarray(a.observations), np.asarray(b.observations), rtol=1e-6
        )
        np.testing.assert_array_equal(a.actions, b.actions)
        assert a.is_terminated == b.is_terminated


def test_offline_data_rides_data_library(cluster, tmp_path):
    from ray_tpu.rllib.offline import OfflineData, SampleWriter

    eps = _scripted_cartpole_episodes(5)
    w = SampleWriter(str(tmp_path / "samples"))
    w.write(eps)
    w.close()
    data = OfflineData(str(tmp_path / "samples"))
    batches = list(data.iter_episode_batches(batch_size=100))
    total = sum(len(ep) for b in batches for ep in b)
    assert total == sum(len(e) for e in eps)


def test_bc_learns_from_expert_data(cluster, tmp_path):
    from ray_tpu.rllib.algorithms.marwil import BCConfig
    from ray_tpu.rllib.offline import SampleWriter

    w = SampleWriter(str(tmp_path / "expert"))
    w.write(_scripted_cartpole_episodes(40, seed=1))
    w.close()
    algo = (
        BCConfig()
        .environment("CartPole-v1")
        .offline_data(input_=str(tmp_path / "expert"))
        .training(train_batch_size=2000, lr=1e-3, minibatch_size=128,
                  num_epochs=5)
        .debugging(seed=0)
        .build()
    )
    for _ in range(30):
        algo.train()
    ev = algo.evaluate(num_episodes=10)
    algo.stop()
    # Random CartPole is ~20; the scripted expert is ~150+. Cloning
    # should comfortably clear 80.
    assert ev["episode_return_mean"] >= 80.0, f"BC failed: {ev}"


def test_marwil_learns_from_mixed_data(cluster, tmp_path):
    """MARWIL's advantage weighting upweights the good trajectories in
    a mixed expert+random dataset."""
    import gymnasium as gym

    from ray_tpu.rllib.algorithms.marwil import MARWILConfig
    from ray_tpu.rllib.env.episode import SingleAgentEpisode
    from ray_tpu.rllib.offline import SampleWriter

    # Random-policy episodes (bad data).
    env = gym.make("CartPole-v1")
    rng = np.random.default_rng(7)
    bad = []
    for _ in range(40):
        obs, _ = env.reset(seed=int(rng.integers(0, 2**31)))
        ep = SingleAgentEpisode(initial_observation=obs)
        while True:
            a = int(rng.integers(0, 2))
            obs, r, term, trunc, _ = env.step(a)
            ep.add_env_step(obs, a, r, terminated=term, truncated=trunc)
            if term or trunc:
                break
        bad.append(ep.finalize())
    env.close()
    w = SampleWriter(str(tmp_path / "mixed"))
    w.write(_scripted_cartpole_episodes(20, seed=2))
    w.write(bad)
    w.close()
    algo = (
        MARWILConfig()
        .environment("CartPole-v1")
        .offline_data(input_=str(tmp_path / "mixed"))
        .training(train_batch_size=2000, lr=1e-3, beta=1.0)
        .debugging(seed=0)
        .build()
    )
    for _ in range(30):
        algo.train()
    ev = algo.evaluate(num_episodes=10)
    algo.stop()
    assert ev["episode_return_mean"] >= 60.0, f"MARWIL failed: {ev}"
