"""The remat policy's own cases, which trace no layer: the other policies are
what they were, and every name the package tags is one the policy keeps
(``tests/test_remat_residuals.py`` has what it keeps of each layer).
"""
import ast
import pathlib

import jax
import jax.extend

import ray_tpu
from ray_tpu.models.llama import REPLAY_KEEPS, remat_policy

from remat_cases import NOTHING, _cfg, _interpret_mode  # noqa: F401 - fixtures


def test_the_other_policies_are_what_they_were():
    """``"dots"`` as ever; and without the barrier no replay is executed, so
    the names would cost memory and delete nothing (``remat_policy``)."""
    for barrier in (False, True):
        assert remat_policy(_cfg(remat_policy="dots", remat_prevent_cse=barrier)) is (
            jax.checkpoint_policies.checkpoint_dots)
    assert remat_policy(_cfg()) is NOTHING
    assert remat_policy(_cfg(remat_prevent_cse=True)) is not NOTHING


def _tagged_names():
    """The literal names ``checkpoint_name`` is called with anywhere in the
    package."""
    names = []
    for path in pathlib.Path(ray_tpu.__file__).parent.rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call)
                    and getattr(node.func, "id", getattr(node.func, "attr", None))
                    == "checkpoint_name"):
                assert isinstance(node.args[1], ast.Constant), (path, node.lineno)
                names.append(node.args[1].value)
    return names


def test_every_name_is_tagged_and_every_tag_is_kept():
    tagged = _tagged_names()
    assert len(tagged) == len(set(tagged)), tagged  # a name has one site
    assert set(tagged) == set(REPLAY_KEEPS)
    assert len(REPLAY_KEEPS) == len(set(REPLAY_KEEPS))
