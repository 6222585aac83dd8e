"""RLlib breadth: the tuned_examples runner and DreamerV3, which learns
from one of its files.

DreamerV3's cases are in ``tests/test_rllib_breadth_dreamerv3.py``
(``tests/rllib_breadth.py`` says why the files are apart).
"""
import os

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
