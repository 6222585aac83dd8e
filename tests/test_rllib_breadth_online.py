"""RLlib breadth, online: SAC, APPO, multi-agent.

Models the reference's algorithm test strategy: learning tests with
reward thresholds (rllib/tuned_examples/sac/pendulum_sac.py,
appo/cartpole_appo.py) and multi-agent CartPole
(tuned_examples/ppo/multi_agent_cartpole_ppo.py).
"""
import numpy as np

from rllib_breadth import cluster  # noqa: F401 - the fixture


# ------------------------------------------------------------------- SAC
def test_sac_module_sample_action_logp():
    """Squashed-Gaussian logp matches a numeric change-of-variables
    check and actions respect the env bounds."""
    import gymnasium as gym
    import jax

    from ray_tpu.rllib.algorithms.sac import SACModule

    env = gym.make("Pendulum-v1")
    mod = SACModule(
        env.observation_space, env.action_space, {"fcnet_hiddens": (8,)}
    )
    params = mod.init_params(jax.random.PRNGKey(0))
    obs = np.random.default_rng(0).standard_normal((16, 3)).astype(np.float32)
    a, logp = mod.sample_action(params, obs, jax.random.PRNGKey(1))
    a, logp = np.asarray(a), np.asarray(logp)
    assert a.shape == (16, 1) and logp.shape == (16,)
    assert (a >= env.action_space.low - 1e-5).all()
    assert (a <= env.action_space.high + 1e-5).all()
    assert np.isfinite(logp).all()


def test_sac_pendulum_learns(cluster):
    from ray_tpu.rllib.algorithms.sac import SACConfig

    algo = (
        SACConfig()
        .environment("Pendulum-v1")
        .env_runners(num_env_runners=0, num_envs_per_env_runner=4)
        .training(
            train_batch_size=256,
            num_steps_sampled_before_learning_starts=1500,
            sample_timesteps_per_iteration=1500,
            updates_per_iteration=350,
            lr=1e-3,
        )
        .debugging(seed=0)
        .build()
    )
    # Random policy on Pendulum averages about -1200; a learning SAC
    # clears -900 within a few thousand env steps.
    best = -1e9
    for _ in range(12):
        r = algo.train()
        if np.isfinite(r["episode_return_mean"]):
            best = max(best, r["episode_return_mean"])
        if best > -900.0:
            break
    algo.stop()
    assert best > -900.0, f"SAC failed to learn Pendulum: best={best}"


# ------------------------------------------------------------------ APPO
def test_appo_loss_clips_ratio():
    """The clipped surrogate must bound the policy update for ratios
    outside [1-clip, 1+clip] (vs IMPALA's unclipped PG)."""
    import gymnasium as gym
    import jax

    from ray_tpu.rllib.algorithms.appo import APPOConfig, APPOLearner
    from ray_tpu.rllib.core.rl_module import DiscretePolicyModule

    cfg = APPOConfig().environment("CartPole-v1")
    spec = cfg.module_spec(
        gym.spaces.Box(-1, 1, (4,), np.float32), gym.spaces.Discrete(2)
    )
    learner = APPOLearner(module_spec=spec, config=cfg.learner_config())
    learner.build()
    T = cfg.rollout_fragment_length
    rng = np.random.default_rng(0)
    batch = {
        "obs": rng.standard_normal((8, T, 4)).astype(np.float32),
        "actions": rng.integers(0, 2, (8, T)).astype(np.int64),
        "rewards": np.ones((8, T), np.float32),
        "terminateds": np.zeros((8, T), np.float32),
        # Behavior policy wildly off → big ratios → clip engages.
        "action_logp": np.full((8, T), -8.0, np.float32),
        "bootstrap_obs": rng.standard_normal((8, 4)).astype(np.float32),
        "mask": np.ones((8, T), np.float32),
    }
    loss, metrics = learner.compute_loss(
        learner.params, {k: np.asarray(v) for k, v in batch.items()},
        jax.random.PRNGKey(0),
    )
    assert np.isfinite(float(loss))
    assert float(metrics["mean_rho"]) > 1.0  # off-policy regime


def test_appo_cartpole_learns(cluster):
    from ray_tpu.rllib.algorithms.appo import APPOConfig

    algo = (
        APPOConfig()
        .environment("CartPole-v1")
        .env_runners(num_env_runners=2, num_envs_per_env_runner=4,
                     rollout_fragment_length=50)
        .training(train_batch_size=500, lr=5e-4, use_kl_loss=True)
        .debugging(seed=0)
        .build()
    )
    # Same learning envelope as the IMPALA pipeline test (the shared
    # async machinery): 150 iterations, best-of threshold.
    best = 0.0
    for _ in range(150):
        r = algo.train()
        if "episode_return_mean" in r and np.isfinite(
            r["episode_return_mean"]
        ):
            best = max(best, r["episode_return_mean"])
        if best >= 50.0:
            break
    algo.stop()
    assert best >= 50.0, f"APPO failed to learn CartPole: best={best}"


# ------------------------------------------------------------ multi-agent
def test_multi_agent_env_wrapper():
    from ray_tpu.rllib import make_multi_agent

    env = make_multi_agent("CartPole-v1", num_agents=3)({})
    assert len(env.possible_agents) == 3
    obs, _ = env.reset(seed=0)
    assert set(obs) == set(env.possible_agents)
    actions = {aid: 0 for aid in obs}
    obs, rew, term, trunc, _ = env.step(actions)
    assert set(rew) == set(env.possible_agents)
    assert "__all__" in term
    env.close()


def _map_agent_to_policy(agent_id: str) -> str:
    return {"agent_0": "p0", "agent_1": "p1"}[agent_id]


def test_multi_agent_ppo_two_policies_learn(cluster):
    from ray_tpu.rllib import make_multi_agent
    from ray_tpu.rllib.algorithms.ppo import PPOConfig

    algo = (
        PPOConfig()
        .environment(make_multi_agent("CartPole-v1", num_agents=2))
        .multi_agent(
            policies={"p0": None, "p1": None},
            policy_mapping_fn=_map_agent_to_policy,
        )
        .env_runners(num_env_runners=0)
        .training(train_batch_size=2000, minibatch_size=128, num_epochs=8,
                  lr=5e-4)
        .debugging(seed=0)
        .build()
    )
    best = 0.0
    last_modules = {}
    for _ in range(25):
        r = algo.train()
        last_modules = r["env_runners"].get(
            "module_episode_return_mean", last_modules
        )
        if np.isfinite(r["episode_return_mean"]):
            best = max(best, r["episode_return_mean"])
        if best >= 60.0 and len(last_modules) == 2:
            break
    algo.stop()
    assert best >= 60.0, f"multi-agent PPO failed: best={best}"
    assert set(last_modules) == {"p0", "p1"}, last_modules


def test_multi_agent_shared_policy(cluster):
    from ray_tpu.rllib import make_multi_agent
    from ray_tpu.rllib.algorithms.ppo import PPOConfig

    algo = (
        PPOConfig()
        .environment(make_multi_agent("CartPole-v1", num_agents=2))
        .multi_agent(policies={"shared": None})
        .env_runners(num_env_runners=0)
        .training(train_batch_size=1000, minibatch_size=128, num_epochs=6)
        .debugging(seed=0)
        .build()
    )
    r = {}
    for _ in range(5):
        r = algo.train()
    algo.stop()
    assert any(k.startswith("shared/") for k in r["learners"]), r
