"""Required FLOPs per token of LFM2-MoE's decoder as one pipeline stage holds
it, and what one call of a gated-convolution kernel needs, from the source's
own keys.

6 x the matmul parameters a token passes through: each kept ``conv`` layer's
input projection [hidden, 3 hidden] and output projection; each kept
``full_attention`` layer's q and o at ``num_attention_heads``, k and v at
``num_key_value_heads``; the dense SwiGLU of ``intermediate_size`` in a layer
the source counts below ``num_dense_layers``, and in every other the router at
``num_experts`` and the ``num_experts_per_tok`` experts of
``moe_intermediate_size`` a token is sent to (every expert is here, so every
pair is met); the tied head over the held vocabulary, once; no embedding
gather. Plus the causal attention of the attention layers. The filter's taps,
the two gates, the norms and the rotation are no matmuls and count for
nothing, and neither do the rows that pad a tile-aligned dispatch."""
from __future__ import annotations

GATED_CONV_KERNELS = ("_gated_conv_fwd_kernel", "_gated_conv_bwd_kernel")


def layer_kinds(cfg: dict) -> list:
    """[(mixer, ffn)] of the layers kept: the source's ``first_layer`` on."""
    first = cfg.get("first_layer", 0)
    mixers = {"conv": "shortconv", "full_attention": "attn"}
    return [
        (mixers[cfg["layer_types"][i]], "mlp" if i < cfg["num_dense_layers"] else "moe")
        for i in range(first, first + cfg["num_hidden_layers"])
    ]


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def shortconv_matmul_params(cfg: dict) -> int:
    return 4 * cfg["hidden_size"] ** 2


def attention_matmul_params(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * head_dim(cfg) * (
        cfg["num_attention_heads"] + cfg["num_key_value_heads"])


def expert_layer_matmul_params(cfg: dict) -> int:
    """The router and the experts a token is sent to."""
    h = cfg["hidden_size"]
    return h * cfg["num_experts"] + (
        cfg["num_experts_per_tok"] * 3 * h * cfg["moe_intermediate_size"])


def macs_per_token(cfg: dict, seq: int) -> dict:
    """Multiply-accumulates of one token's forward pass, by part."""
    kinds = layer_kinds(cfg)
    n_attn = sum(mixer == "attn" for mixer, _ in kinds)
    n_moe = sum(ffn == "moe" for _, ffn in kinds)
    h = cfg["hidden_size"]
    return {
        "experts_and_router": n_moe * expert_layer_matmul_params(cfg),
        "shortconv": (len(kinds) - n_attn) * shortconv_matmul_params(cfg),
        "dense_swiglu": (len(kinds) - n_moe) * 3 * h * cfg["intermediate_size"],
        "attention_projections": n_attn * attention_matmul_params(cfg),
        # scores and weighted values, the causal half
        "attention_scores": n_attn * seq * cfg["num_attention_heads"] * head_dim(cfg),
        "head": h * cfg["vocab_size"],
    }


def lfm2_decoder(cfg: dict, seq: int) -> float:
    """2 FLOPs a multiply-accumulate, forward and twice that backward."""
    return 6.0 * sum(macs_per_token(cfg, seq).values())


def gated_conv_call(kernel: str, rows: int, channels: int, taps: int,
                    itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one call over ``rows`` tokens of ``channels``
    channels, whatever kernel design implements it. Forward: the gate's
    product, a multiply and an add a tap, the other gate's product; it reads
    the three thirds of the projection's output and writes y: 4 arrays of
    [rows, channels]. Backward: the forward's products again, a tap's product
    for the convolved input's cotangent and one for the filter's, the three
    gates' products; it reads the three thirds and y's cotangent and writes
    the three thirds' cotangents: 7 arrays."""
    if kernel not in GATED_CONV_KERNELS:
        raise KeyError(kernel)
    if kernel == "_gated_conv_fwd_kernel":
        return (2.0 * taps + 2) * rows * channels, float(4 * rows * channels * itemsize)
    return (6.0 * taps + 6) * rows * channels, float(7 * rows * channels * itemsize)
