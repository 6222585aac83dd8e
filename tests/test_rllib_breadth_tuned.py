"""RLlib breadth: the tuned_examples runner and DreamerV3, which learns
from one of its files."""
import os

import numpy as np

from rllib_breadth import cluster  # noqa: F401 - the fixture


# ------------------------------------------------- tuned_examples runner

def test_tuned_examples_registry_and_ppo_regression(cluster):
    """The declarative pass/fail pattern (reference: tuned_examples/):
    run the fastest config end-to-end, assert the bar is genuinely
    enforced (an impossible bar fails)."""
    from ray_tpu.rllib import tuned_examples as tx

    paths = tx.list_examples()
    names = {os.path.basename(p) for p in paths}
    assert {"cartpole_ppo.yaml", "cartpole_dqn.yaml",
            "pendulum_sac.yaml", "cartpole_dreamerv3.yaml"} <= names

    res = tx.run_regression(
        os.path.join(tx.EXAMPLES_DIR, "cartpole_ppo.yaml")
    )
    assert res.passed, (res.best, res.iterations)
    assert res.best["episode_return_mean"] >= 80.0
    assert len(res.history) == res.iterations

    # The bar is real: an unreachable stop within 1 iteration fails.
    import tempfile

    import yaml

    with open(os.path.join(tx.EXAMPLES_DIR, "cartpole_ppo.yaml")) as f:
        spec = yaml.safe_load(f)
    spec["stop"] = {"episode_return_mean": 1e9}
    spec["max_iterations"] = 1
    with tempfile.NamedTemporaryFile(
        "w", suffix=".yaml", delete=False
    ) as f:
        yaml.safe_dump(spec, f)
        impossible = f.name
    res2 = tx.run_regression(impossible)
    assert not res2.passed and res2.iterations == 1
    os.unlink(impossible)


# -------------------------------------------------------------- DreamerV3

def test_twohot_symlog_roundtrip():
    """Twohot encode/decode is (approximately) the identity through
    the symlog bins, and encodings are proper distributions."""
    import jax.numpy as jnp

    from ray_tpu.rllib.algorithms.dreamerv3 import _TwoHot

    th = _TwoHot(41)
    xs = jnp.asarray([-50.0, -3.2, -1.0, 0.0, 0.7, 2.5, 99.0])
    enc = th.encode(xs)
    np.testing.assert_allclose(np.asarray(enc.sum(-1)), 1.0, atol=1e-5)
    dec = np.asarray(th.decode(jnp.log(enc + 1e-8)))
    # Exact inside the bin range; clipped at the symlog edges.
    for x, d in zip(np.asarray(xs), dec):
        lo, hi = -np.expm1(20.0), np.expm1(20.0)
        assert abs(d - np.clip(x, lo, hi)) < 0.05 * max(1.0, abs(x)), (x, d)


def test_dreamerv3_cartpole_learns_in_imagination(cluster):
    """World-model RL end-to-end via the TUNED EXAMPLE (single source
    of truth for the hyperparameters): the return climbs well clear of
    random (~20) within a few thousand env steps — learning happens IN
    the model, ~32 replayed steps per env step."""
    from ray_tpu.rllib import tuned_examples as tx

    res = tx.run_regression(
        os.path.join(tx.EXAMPLES_DIR, "cartpole_dreamerv3.yaml")
    )
    assert res.passed, (res.best, res.iterations)
    assert res.best["episode_return_mean"] >= 55.0
