"""Granite 4.0-H (``model_type`` ``granitemoehybrid``): Mamba-2 state-space
mixers with one softmax-attention layer to every nine of them, in a dense
decoder under Granite's multipliers, the head tied to the embedding.

The model is llama.py's pre-norm decoder body told its three numbers
(``embed_scale`` = ``embedding_multiplier``; ``residual_scale`` =
``residual_multiplier``, on each sublayer's output; ``logit_divisor`` =
``logits_scaling``, on the final norm's output), every layer over llama.py's
dense ``MLP`` at ``shared_intermediate_size`` (``num_local_experts`` 0: the
shared MLP is the whole FFN). What ``layer_types`` calls ``attention`` has
llama.py's ``Attention`` as its mixer, of a kind that turns nothing
(``position_embedding_type`` ``nope``) and scales its scores by
``attention_multiplier`` where the default is ``head_dim ** -0.5``. What it
calls ``mamba`` has ``Mamba2Mixer``:

    [z, xBC, dt] = x W_in        z [T, H P], xBC [T, H P + 2 N], dt [T, H]
    xBC = SiLU(conv(xBC) + b)    causal, depthwise, ``mamba_d_conv`` taps, a bias a channel
    [u, B, C] = split(xBC)       u [T, H, P]; B, C [T, N], one pair for every head
    dl = softplus(dt + dt_bias),  A = -exp(A_log)                       float32
    S_t = exp(dl_t A) S_{t-1} + dl_t u_t B_t^T,  y_t = S_t C_t + D u_t   a head
    mixer(x) = RMSNorm_{H P}(y * SiLU(z); w) W_out     the gate, then ONE norm over every head's channels

W_in is kept as three matrices, ``z_proj``, ``xbc_proj`` and ``dt_proj`` (z's
columns, then xBC's, then dt's of the source's one ``in_proj``): a fused and
a separate projection are one function, and the three leave in the dtypes
their readers take (z in the matmuls', xBC and dt float32, as ``conv_silu``
and the softplus read them). The convolution with its bias and SiLU is
``ops/kda.py``'s ``conv_silu``, the recurrence with the step's product and the
skip its ``chunk_ssd``; the step's softplus, and the gate with the norm over
all ``H P`` channels (which no kernel that holds a few heads a step could
take), are XLA's, under scopes of their own. No bias but the filter's. What
the source's ``config.json`` leaves open is listed in the benchmark's
configuration file under ``assumed``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.kda import chunk_ssd, conv_silu
from ..util import tracing
from .kimi_linear import NormWeight, _a_log_init, _conv_init, _dense, _dt_bias_init
from .llama import AttentionKind, LlamaConfig, LlamaForCausalLM

MAMBA, ATTENTION = "mamba", "attention"


@dataclass(frozen=True)
class GraniteHybridConfig(LlamaConfig):
    # Each layer's (mixer, ffn): "mamba" or "attn", and "mlp".
    layer_kinds: Tuple[Tuple[str, str], ...] = ()
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_conv_bias: bool = True
    # The soft-max's scale (``attention_multiplier``); None: head_dim ** -0.5.
    attention_scale: Optional[float] = None
    tie_embeddings: bool = True
    rms_eps: float = 1e-5
    remat_policy: str = "nothing"
    # Each mamba layer's replay keeps 64 MiB of chunk states at 8k tokens.
    remat_prevent_cse: bool = True

    @property
    def layers(self):
        return self.layer_kinds

    def attention(self, name: Optional[str]) -> AttentionKind:
        return AttentionKind(self.num_heads, None, scale=self.attention_scale)

    @property
    def mamba_channels(self) -> Tuple[int, int]:
        """(every head's value channels, the convolved ones: with B and C)."""
        inner = self.mamba_n_heads * self.mamba_d_head
        return inner, inner + 2 * self.mamba_d_state

    def num_params(self) -> int:
        h, hd = self.hidden_size, self.head_dim_
        inner, convolved = self.mamba_channels
        H = self.mamba_n_heads
        mixer = {
            # z, xBC and dt; the filter and its bias; A_log, D, dt_bias; the
            # norm; the output projection
            tracing.MAMBA: h * (inner + convolved + H)
            + (self.mamba_d_conv + self.mamba_conv_bias) * convolved + 3 * H
            + inner + inner * h,
            # q and o; k and v
            tracing.ATTN: 2 * h * self.num_heads * hd + 2 * h * self.num_kv_heads * hd,
        }
        total = self.vocab_size * h * (1 if self.tie_embeddings else 2) + h
        return total + sum(
            mixer[m] + 3 * h * self.intermediate_size + 2 * h
            for m, _ in self.layer_kinds
        )


def granite_hybrid_config(
    *, layer_types, num_layers: int, embedding_multiplier: float,
    residual_multiplier: float, logits_scaling: float,
    attention_multiplier: float, shared_intermediate_size: int,
    mamba_n_heads: int, mamba_d_head: int, mamba_d_state: int,
    mamba_d_conv: int, mamba_conv_bias: bool = True, mamba_expand: int = 2,
    mamba_n_groups: int = 1, mamba_proj_bias: bool = False,
    num_local_experts: int = 0, position_embedding_type: str = "nope",
    **fields,
) -> GraniteHybridConfig:
    """The program's config from the source's keys: ``layer_types`` (each
    layer ``mamba`` or ``attention``, read up to ``num_layers``), the four
    multipliers, the ``mamba_*`` keys and the shared MLP's width. The model is
    built as published (one B/C group, no projection bias, no routed expert,
    no rotation, ``mamba_expand`` hidden = heads x head): a key that says
    otherwise is refused."""
    if mamba_n_groups != 1 or mamba_proj_bias or num_local_experts:
        raise ValueError("Mamba2Mixer has one B/C group and no projection "
                         "bias, and the FFN no routed expert")
    if position_embedding_type != "nope":
        raise ValueError("the attention layers turn nothing (nope)")
    if mamba_expand * fields["hidden_size"] != mamba_n_heads * mamba_d_head:
        raise ValueError("mamba_expand x hidden_size is mamba_n_heads x mamba_d_head")
    kinds = {MAMBA: tracing.MAMBA, ATTENTION: tracing.ATTN}
    unknown = set(layer_types[:num_layers]) - set(kinds)
    if unknown or len(layer_types) < num_layers:
        raise ValueError(f"layer_types names {sorted(unknown)} or is short of {num_layers} layers")
    return GraniteHybridConfig(
        num_layers=num_layers,
        layer_kinds=tuple((kinds[t], tracing.MLP) for t in layer_types[:num_layers]),
        embed_scale=float(embedding_multiplier),
        residual_scale=float(residual_multiplier),
        logit_divisor=float(logits_scaling),
        attention_scale=float(attention_multiplier),
        intermediate_size=shared_intermediate_size,
        mamba_n_heads=mamba_n_heads, mamba_d_head=mamba_d_head,
        mamba_d_state=mamba_d_state, mamba_d_conv=mamba_d_conv,
        mamba_conv_bias=mamba_conv_bias, **fields,
    )


def _conv_bias_init(taps: int):
    """torch's Conv1d default for the bias of a depthwise filter of ``taps``
    taps: uniform(-1/sqrt(taps), 1/sqrt(taps)), the filter's own bound."""
    def init(key, shape, dtype):
        return jax.random.uniform(key, shape, dtype, -taps ** -0.5, taps ** -0.5)
    return init


class Mamba2Mixer(nn.Module):
    """The Mamba-2 state-space mixer of a layer. One device's: the recurrence
    is not sharded over the sequence."""
    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        H, P, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
        inner, convolved = cfg.mamba_channels
        B, T, _ = x.shape
        f32 = jnp.float32
        z = _dense(cfg, inner, "z_proj")(x)
        xbc = _dense(cfg, convolved, "xbc_proj", dtype=f32)(x)
        # The step's map comes out in float32, as KDA's decay does: exp(A_log)
        # is up to 16 and dl A adds up over a chunk before it is exponentiated.
        dt = _dense(cfg, H, "dt_proj", dtype=f32)(x)
        with tracing.scope(tracing.KDA_CONV):
            bias = (self.param("conv_bias", _conv_bias_init(cfg.mamba_d_conv),
                               (convolved,), cfg.param_dtype)
                    if cfg.mamba_conv_bias else None)
            xbc = conv_silu(xbc, self.param(
                "conv", _conv_init, (cfg.mamba_d_conv, convolved), cfg.param_dtype,
            ), cfg.dtype, bias)
        with tracing.scope(tracing.MAMBA_STEP):
            dl = jax.nn.softplus(dt + self.param("dt_bias", _dt_bias_init, (H,), f32))
        y = chunk_ssd(
            xbc[..., :inner].reshape(B, T, H, P), dl,
            self.param("A_log", _a_log_init, (H,), f32),
            xbc[..., inner:inner + N], xbc[..., inner + N:],
            self.param("D", nn.initializers.ones, (H,), f32),
        ).reshape(B, T, inner)
        with tracing.scope(tracing.MAMBA_NORM):
            gated = y.astype(f32) * jax.nn.silu(z.astype(f32))
            normed = gated * jax.lax.rsqrt(
                jnp.mean(gated * gated, axis=-1, keepdims=True) + cfg.rms_eps)
            y = (normed * NormWeight(cfg.param_dtype, name="norm")(inner)).astype(cfg.dtype)
        return _dense(cfg, cfg.hidden_size, "out_proj")(y)


class GraniteHybridForCausalLM(LlamaForCausalLM):
    """The decoder body of llama.py with ``Mamba2Mixer`` or ``Attention`` as a
    layer's mixer (``GraniteHybridConfig.layers``) over the dense ``MLP``."""

    blocks = {**LlamaForCausalLM.blocks, tracing.MAMBA: Mamba2Mixer}
