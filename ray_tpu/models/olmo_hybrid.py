"""Olmo-Hybrid (``model_type`` ``olmo_hybrid``): Gated DeltaNet linear
attention layers with one full softmax-attention layer to every three of
them, in a dense decoder whose norms follow their sublayers.

The model is llama.py's decoder body told that its norms come after the
sublayers (``norm_after``, the OLMo 2/3 order: h = x + RMSNorm(mixer(x)), out
= h + RMSNorm(mlp(h)), both sublayers reading the raw stream), every layer
over llama.py's dense ``MLP``. The layers the source's ``layer_types`` calls
``full_attention`` have llama.py's ``Attention`` as their mixer, with
``qk_norm`` (an RMSNorm over the whole q and the whole k projection, before
the head split, as OLMoE has it) and, where ``rope_parameters.rope_theta`` is
null, no rotation (``AttentionKind.freqs`` None: position reaches these layers
through the linear layers' state alone). The ``linear_attention`` layers have
``GDNMixer``, fla's ``GatedDeltaNet`` as far as it is known: q, k and v
projections, each through a causal depthwise convolution of
``linear_conv_kernel_dim`` taps and SiLU; q and k L2-normalised a head, q
scaled by dk^-1/2; key heads of ``linear_key_head_dim`` and value heads of
``linear_value_head_dim`` channels, which differ; one log-decay a head and
token, g = -exp(A_log) softplus(W_a x + dt_bias); a write strength beta = 2
sigmoid(W_b x) in (0, 2) under ``linear_allow_neg_eigval``, else sigmoid(W_b
x); the recurrence S_t = (I - beta k k^T) e^g S_{t-1} + beta k v^T, o_t =
S_t^T q_t; o through an RMSNorm over a head's channels (one weight shared by
the heads) times SiLU(W_g x); the output projection. No bias anywhere. The
three normalisations over a head's channels and the output gate are
``ops/kda.py``'s ``chunk_gdn``, on the blocks its kernels hold. What the
source's ``config.json`` leaves open is listed in the benchmark's
configuration file under ``assumed``.

q's and k's projections are one matrix here, ``qk_proj`` [hidden, 2 H dk],
q's columns first: a fused and a separate projection are one function, and 2
x 30 x 96 channels are 45 vregs of lanes where 30 x 96 are 22.5, so the one
convolution pass over q and k tiles (``ops/kda.py`` ``conv_silu``) where two
would fall to XLA's passes. v's 30 x 192 channels are 45 vregs alone.

Layouts. The projections come out of their matmuls [B, T, channels]. q's and
k's convolution pass writes [B, 2 H, T, dk], a head at a time (``conv_silu``'s
``heads``: a block of 384 lanes is four key heads), which is what
``chunk_gdn``'s kernels read, a head of 96 lanes (or a grid step's two) being
no whole number of vregs; q and k stay the one array [B, 2, H, T, dk], of
which a scan block holds both, and their cotangents come back the same way.
v, the gate and o stay [B, T, H dv] from the convolution and ``g_proj``
through the scan to ``o_proj``: a grid step's two value heads are 384 lanes,
three vregs side by side, and the kernels take them apart in VMEM. So no
slice, transposed copy or reshape copy stands between the Pallas calls or
between them and the matmuls, in either direction; only the decay and beta,
one float a head and token, are laid out for the kernels by XLA.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.kda import chunk_gdn, conv_silu
from ..util import tracing
from .kimi_linear import (
    NormWeight, _a_log_init, _conv_init, _dense, _dt_bias_init,
)
from .llama import AttentionKind, LlamaConfig, LlamaForCausalLM, rope_frequencies

LINEAR, FULL = "linear_attention", "full_attention"


@dataclass(frozen=True)
class OlmoHybridConfig(LlamaConfig):
    # Each layer's (mixer, ffn): "gdn" or "attn", and "mlp".
    layer_kinds: Tuple[Tuple[str, str], ...] = ()
    linear_num_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    # The source's key: beta = 2 sigmoid(W_b x), in (0, 2), where it is
    # sigmoid(W_b x), in (0, 1).
    linear_allow_neg_eigval: bool = True
    # None: the full-attention layers turn nothing (rope_theta null).
    rope_theta: Optional[float] = None
    qk_norm: bool = True
    norm_after: bool = True
    rms_eps: float = 1e-6
    remat_policy: str = "nothing"
    # Each linear layer's replay keeps 360 MiB of chunk states at 8k tokens.
    remat_prevent_cse: bool = True

    @property
    def layers(self):
        return self.layer_kinds

    def attention(self, name: Optional[str]) -> AttentionKind:
        freqs = None
        if self.rope_theta is not None:
            freqs = rope_frequencies(self.head_dim_, self.rope_theta)
        return AttentionKind(self.num_heads, freqs)

    def num_params(self) -> int:
        h, hd = self.hidden_size, self.head_dim_
        H, dk, dv = (self.linear_num_heads, self.linear_key_head_dim,
                     self.linear_value_head_dim)
        mixer = {
            # q, k, v, the gate, o; a, b; the filters of q, k, v; A_log,
            # dt_bias, o_norm
            tracing.GDN: h * H * (2 * dk + 3 * dv) + 2 * h * H
            + self.linear_conv_kernel_dim * H * (2 * dk + dv) + 2 * H + dv,
            # q and o; k and v; the norms of q and k
            tracing.ATTN: 2 * h * self.num_heads * hd + 2 * h * self.num_kv_heads * hd
            + (self.num_heads + self.num_kv_heads) * hd * self.qk_norm,
        }
        total = self.vocab_size * h * (1 if self.tie_embeddings else 2) + h
        return total + sum(
            mixer[m] + 3 * h * self.intermediate_size + 2 * h
            for m, _ in self.layer_kinds
        )


def olmo_hybrid_config(
    *, layer_types, num_layers: int, linear_num_key_heads: int,
    linear_num_value_heads: int, linear_key_head_dim: int,
    linear_value_head_dim: int, linear_conv_kernel_dim: int,
    linear_allow_neg_eigval: bool, rope_parameters: Optional[dict] = None,
    **fields,
) -> OlmoHybridConfig:
    """The program's config from the source's keys: ``layer_types`` (each
    layer ``linear_attention`` or ``full_attention``, read up to
    ``num_layers``), the five ``linear_*`` keys and ``rope_parameters``, whose
    ``rope_theta`` null builds full-attention layers that turn nothing and a
    number the rotating kind."""
    if linear_num_value_heads != linear_num_key_heads:
        raise ValueError("GDNMixer's v has q's and k's head count")
    kinds = {LINEAR: tracing.GDN, FULL: tracing.ATTN}
    unknown = set(layer_types[:num_layers]) - set(kinds)
    if unknown or len(layer_types) < num_layers:
        raise ValueError(f"layer_types names {sorted(unknown)} or is short of {num_layers} layers")
    return OlmoHybridConfig(
        num_layers=num_layers,
        layer_kinds=tuple((kinds[t], tracing.MLP) for t in layer_types[:num_layers]),
        linear_num_heads=linear_num_key_heads,
        linear_key_head_dim=linear_key_head_dim,
        linear_value_head_dim=linear_value_head_dim,
        linear_conv_kernel_dim=linear_conv_kernel_dim,
        linear_allow_neg_eigval=linear_allow_neg_eigval,
        rope_theta=(rope_parameters or {}).get("rope_theta"), **fields,
    )


class GDNMixer(nn.Module):
    """The Gated DeltaNet mixer of a layer. One device's: the recurrence is
    not sharded over the sequence."""
    cfg: OlmoHybridConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        H, dk, dv = (cfg.linear_num_heads, cfg.linear_key_head_dim,
                     cfg.linear_value_head_dim)
        B, T, _ = x.shape
        f32 = jnp.float32
        proj = {"qk": _dense(cfg, 2 * H * dk, "qk_proj", dtype=f32)(x),
                "v": _dense(cfg, H * dv, "v_proj", dtype=f32)(x)}
        # As KDAMixer's: float32 passes of ``conv_silu``, q and k left raw for
        # the scan's kernels to normalise, v rounded as its pass stores it.
        # q's and k's pass writes a head at a time, [B, 2 H, T, dk], q's heads
        # then k's: what the scan's kernels read. v's stays [B, T, H dv].
        with tracing.scope(tracing.KDA_CONV):
            qk, v = (
                conv_silu(y, self.param(
                    f"{n}_conv", _conv_init,
                    (cfg.linear_conv_kernel_dim, y.shape[-1]), cfg.param_dtype,
                ), cfg.dtype if n == "v" else f32, heads=None if n == "v" else dk)
                for n, y in proj.items()
            )
        # The decay's map comes out in float32, as KDA's does: exp(A_log) is
        # up to 16 and the log-decay adds up over a chunk.
        a = _dense(cfg, H, "a_proj", dtype=f32)(x)
        b = _dense(cfg, H, "b_proj", dtype=f32)(x)
        with tracing.scope(tracing.KDA_GATE):
            rate = jnp.exp(self.param("A_log", _a_log_init, (H,), f32))
            g = -rate * jax.nn.softplus(a + self.param("dt_bias", _dt_bias_init, (H,), f32))
            beta = jax.nn.sigmoid(b)
            if cfg.linear_allow_neg_eigval:
                beta = 2.0 * beta
        gate = _dense(cfg, H * dv, "g_proj")(x)
        with tracing.scope(tracing.KDA_SCAN):
            o = chunk_gdn(
                qk.reshape(B, 2, H, T, dk), v.reshape(B, T, H, dv), g, beta,
                gate.reshape(B, T, H, dv),
                NormWeight(cfg.param_dtype, name="o_norm")(dv),
                scale=dk ** -0.5, rms_eps=cfg.rms_eps,
            )
        return _dense(cfg, cfg.hidden_size, "o_proj")(o.reshape(B, T, H * dv))


class OlmoHybridForCausalLM(LlamaForCausalLM):
    """The decoder body of llama.py with ``GDNMixer`` or ``Attention`` as a
    layer's mixer (``OlmoHybridConfig.layers``) over the dense ``MLP``."""

    blocks = {**LlamaForCausalLM.blocks, tracing.GDN: GDNMixer}
