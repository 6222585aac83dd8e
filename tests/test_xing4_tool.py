"""Xing4's architecture through the program's models, on the CPU:
``wrong_xing4.py`` as a script at the rehearsal size
(``tests/test_xing4_model.py`` has the model against its reference and says
what the reference is; ``tests/xing4_cases.py`` what the files share).
"""
import importlib

import pytest

from benchmarks.tools import wrong_xing4

from xing4_cases import interpret  # noqa: F401 - fixtures


def test_the_tool_that_reads_the_modules_head_walks_on_the_cpu(tmp_path, monkeypatch):
    """``wrong_xing4.py`` as a script at the rehearsal size: the module's
    logits against the reference's ``mtp_logits``, and the first loss against
    the reference's ``loss``."""
    import json
    import sys

    monkeypatch.setattr(sys, "argv", [
        "wrong_xing4.py", "--seeds", "4000000001", "--rehearse", "--out", str(tmp_path)])
    importlib.reload(wrong_xing4).main()
    (line,) = (tmp_path / f"{wrong_xing4.CELL}.mtp.jsonl").read_text().splitlines()
    line = json.loads(line)
    assert line["seed"] == 4000000001 and line["positions"] == 64
    assert line["mtp_logits"]["rel_err_median"] < 0.05
    assert line["loss_rel_err"] < 5e-3
    assert line["reference_loss"] == pytest.approx(
        line["reference_main"] + 0.3 * line["reference_mtp"])
