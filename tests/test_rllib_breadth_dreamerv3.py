"""RLlib breadth: DreamerV3's two-hot symlog round trip, and DreamerV3 learns
CartPole in imagination, the suite's longest case (``tests/rllib_breadth.py``
says why the files are apart).
"""
import os

import numpy as np
from rllib_breadth import cluster  # noqa: F401 - the fixture


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
