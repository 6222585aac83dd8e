"""RLlib breadth, offline: CQL learns a point mass from logged episodes, and its
conservative regularizer lowers out-of-distribution Q
(``tests/test_rllib_breadth_offline.py`` has BC and MARWIL;
``tests/rllib_breadth.py`` says why the files are apart).
"""
import numpy as np
from rllib_breadth import cluster  # noqa: F401 - the fixture


# ------------------------------------------------------------------- CQL

class _PointMassEnv:
    """Stable 2-D point mass: x' = clip(x + 0.2 a), r = -|x|^2.

    Duck-typed gymnasium env (metadata/render_mode/spec for the vector
    wrapper).

    Closed-loop STABLE under an approximate controller, so offline
    learning is testable without Pendulum's compounding covariate
    shift (pure BC there needs D4RL-scale data; the reference's CQL
    learning bars live in tuned_examples on D4RL for the same
    reason)."""

    metadata = {"render_modes": []}
    render_mode = None
    spec = None

    def __init__(self, *args, **kwargs):
        import gymnasium as gym

        self.observation_space = gym.spaces.Box(-2.0, 2.0, (2,), np.float32)
        self.action_space = gym.spaces.Box(-1.0, 1.0, (2,), np.float32)
        self._x = None
        self._t = 0
        self._rng = np.random.default_rng(0)

    def reset(self, *, seed=None, options=None):
        if seed is not None:
            self._rng = np.random.default_rng(seed)
        self._x = self._rng.uniform(-1.5, 1.5, 2).astype(np.float32)
        self._t = 0
        return self._x.copy(), {}

    def step(self, action):
        a = np.clip(np.asarray(action, np.float32), -1.0, 1.0)
        self._x = np.clip(self._x + 0.2 * a, -2.0, 2.0)
        self._t += 1
        r = -float(np.sum(self._x ** 2))
        return self._x.copy(), r, False, self._t >= 50, {}

    def close(self):
        pass


def _pointmass_episodes(n_episodes: int, seed: int = 0, noise: float = 0.3):
    """Behavior: proportional pull to the origin + exploration noise."""
    from ray_tpu.rllib.env.episode import SingleAgentEpisode

    env = _PointMassEnv()
    rng = np.random.default_rng(seed)
    eps = []
    for i in range(n_episodes):
        obs, _ = env.reset(seed=int(rng.integers(0, 2**31)))
        ep = SingleAgentEpisode(initial_observation=obs)
        while True:
            a = np.clip(
                -1.5 * obs + noise * rng.standard_normal(2), -1.0, 1.0
            ).astype(np.float32)
            obs, r, term, trunc, _ = env.step(a)
            ep.add_env_step(obs, a, r, terminated=term, truncated=trunc)
            if term or trunc:
                break
        eps.append(ep.finalize())
    return eps


def test_cql_learns_pointmass_offline(cluster, tmp_path):
    """CQL trains PURELY from a recorded dataset (zero env interaction
    during training); its evaluated policy must crush the random
    baseline and approach the behavior policy."""
    from ray_tpu.rllib.algorithms.cql import CQLConfig
    from ray_tpu.rllib.offline import SampleWriter

    eps = _pointmass_episodes(60, seed=2)
    behavior = float(np.mean([np.sum(e.rewards) for e in eps]))
    # Random baseline on the same env.
    rand_eps = _pointmass_episodes(20, seed=3, noise=10.0)
    random_ret = float(np.mean([np.sum(e.rewards) for e in rand_eps]))
    w = SampleWriter(str(tmp_path / "pm"))
    w.write(eps)
    w.close()

    algo = (
        CQLConfig()
        .environment(_PointMassEnv)
        .env_runners(num_env_runners=0, num_envs_per_env_runner=2)
        .offline_data(input_=str(tmp_path / "pm"))
        .training(
            train_batch_size=256,
            updates_per_iteration=400,
            lr=1e-3,
            bc_iters=400,
            cql_n_actions=4,
            min_q_weight=2.0,
        )
        .debugging(seed=0)
        .build()
    )
    metrics = {}
    for _ in range(4):
        metrics = algo.train()["learners"]
    ev = algo.evaluate(num_episodes=10)
    algo.stop()
    got = ev["episode_return_mean"]
    # Conservatism sanity: Q stays near the feasible return scale.
    assert metrics["qf_mean"] < 50.0, metrics
    # Halfway-to-behavior clears the bar with a wide margin.
    bar = random_ret + 0.5 * (behavior - random_ret)
    assert got > bar, (
        f"CQL offline policy too weak: {got} "
        f"(behavior {behavior}, random {random_ret})"
    )


def test_cql_conservative_regularizer_lowers_ood_q(cluster, tmp_path):
    """The CQL-specific property: after training, Q on out-of-
    distribution (random) actions sits clearly BELOW Q on dataset
    actions — and the gap is wider than a plain SAC critic trained on
    the same batches (no conservative term)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.rllib.algorithms.cql import CQLConfig
    from ray_tpu.rllib.offline import SampleWriter

    eps = _pointmass_episodes(40, seed=5)
    w = SampleWriter(str(tmp_path / "pm2"))
    w.write(eps)
    w.close()

    def gap(min_q_weight):
        algo = (
            CQLConfig()
            .environment(_PointMassEnv)
            .env_runners(num_env_runners=0)
            .offline_data(input_=str(tmp_path / "pm2"))
            .training(
                train_batch_size=256,
                updates_per_iteration=300,
                lr=1e-3,
                bc_iters=0,
                cql_n_actions=4,
                min_q_weight=min_q_weight,
            )
            .debugging(seed=0)
            .build()
        )
        for _ in range(3):
            algo.train()
        learner = algo.learner_group._local
        batch = algo.replay.sample(512)
        obs = jnp.asarray(batch["obs"])
        acts = jnp.asarray(batch["actions"])
        rng = np.random.default_rng(0)
        rand = jnp.asarray(
            rng.uniform(-1, 1, acts.shape).astype(np.float32)
        )
        q_data, _ = learner.module.q_values(learner.params, obs, acts)
        q_rand, _ = learner.module.q_values(learner.params, obs, rand)
        algo.stop()
        return float(jnp.mean(q_rand) - jnp.mean(q_data))

    cql_gap = gap(5.0)
    plain_gap = gap(0.0)
    # Conservative training pushes OOD Q below data Q...
    assert cql_gap < 0.0, cql_gap
    # ...and by a clearly wider margin than the unregularized critic.
    assert cql_gap < plain_gap - 0.5, (cql_gap, plain_gap)
