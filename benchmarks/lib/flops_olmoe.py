"""Required FLOPs per token of OLMoE's decoder, from the source's own keys:
``flops.moe_decoder``'s count (6 x the matmul parameters a token passes
through: its top-k experts and the router, not the experts it was not sent
to; the untied head; no embedding gather; + causal attention) with the
experts under OLMoE's key ``num_experts``; ``intermediate_size`` is one
expert's width. QK-norm has no matmul and counts for nothing, and neither do
the padding rows of a tile-aligned dispatch."""
from __future__ import annotations

from .flops import moe_decoder


def olmoe_decoder(cfg: dict, seq: int) -> float:
    return moe_decoder({**cfg, "num_local_experts": cfg["num_experts"]}, seq)
