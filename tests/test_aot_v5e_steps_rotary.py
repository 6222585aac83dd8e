"""The rotary kernel's calls in three cells' train steps at their real sizes,
lowered ahead of time for a v5e chip, with no chip (``tests/aot_v5e.py`` has
how; ``tests/test_kernels_aot_v5e.py`` the flash kernels).
"""
import pytest

from aot_v5e import (  # noqa: F401 - fixtures
    ROTARY_STEPS, _lowered_step, _turns, topo, v5e,
)


@pytest.mark.parametrize("name", [
    "laguna-xs2-33b-a3b-l8.longctx-16k", "mistral-7b-l4.short2k",
    "sarvam-105b-l5.pretrain-4k"])
def test_a_step_turns_q_and_k_by_the_kernel_where_a_head_is_128_lanes(v5e, name):
    from benchmarks.lib import cells, checks

    cell, text = _lowered_step(v5e, name)
    assert _turns(text) == ROTARY_STEPS[name]
    # and lost none of the kernels its configuration states
    stated = cells.stated_kernels(cell)
    assert checks.holds_stated_kernels(checks.count_pallas_kernels(text, stated), stated)
