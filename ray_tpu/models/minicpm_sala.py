"""MiniCPM-SALA (``model_type`` ``minicpm_sala``): block-sparse top-k softmax
attention layers (``minicpm4``: InfLLM-V2) among decay-only linear-attention
layers (``lightning-attn``: Lightning Attention-2), one to three, in a dense
decoder under MiniCPM's muP scaling.

The model is llama.py's pre-norm decoder body told its three muP numbers
(``embed_scale`` = ``scale_emb``; ``residual_scale`` = ``scale_depth`` /
sqrt(published layers), on each sublayer's output; ``logit_divisor`` =
``hidden_size`` / ``dim_model_base``, on the final norm's output), every
layer over llama.py's dense ``MLP``. What ``mixer_types`` calls ``minicpm4``
has ``SparseAttention`` as its mixer, ``lightning-attn`` ``LightningMixer``.

``LightningMixer``: q, k, v projections at ``lightning_nh`` heads of
``lightning_head_dim`` (``lightning_nkv`` equal: no grouping); q and k through
an RMSNorm over a head's channels (one weight [d] each) and then the plain
rotary table over the whole head; the recurrence S_t = exp(-s) S_{t-1} + k_t
v_t^T, o_t = d^-1/2 S_t^T q_t with the slope s a constant of head and layer
(``lightning_slopes``); o through an RMSNorm over a head's channels (one
weight [d]) times sigmoid(W_g x), W_g full rank; the output projection. The
recurrence, o's norm and the gate are ``ops/kda.py``'s ``chunk_lightning``.

``SparseAttention``: q at ``num_heads``, k and v at ``num_kv_heads`` heads of
``head_dim``; q and k through an RMSNorm over a head's channels; nothing
turns (``attn_use_rope`` false; true builds the rotating kind). Up to
``dense_len`` tokens it is causal attention whole; beyond, each row and K/V
group attends the blocks ``ops/attention.py``'s ``select_blocks`` chooses
(``SparseSelection``: compressed keys, group-summed scores, max-pool, forced
blocks, top-k; an integer set under ``stop_gradient``) through
``flash_attention(..., blocks=...)``. o times sigmoid(W_g x), W_g of q's
width; the output projection. No bias anywhere. What the source's
``config.json`` leaves open is listed in the benchmark's configuration file
under ``assumed``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Tuple

import flax.linen as nn
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops.attention import flash_attention, select_blocks
from ..ops.kda import chunk_lightning
from ..ops.rotary import rotate
from ..util import tracing
from .kimi_linear import NormWeight, _dense
from .llama import (
    LlamaConfig, LlamaForCausalLM, RMSNorm, rope_frequencies, weight_init,
)

LIGHTNING, MINICPM4 = "lightning-attn", "minicpm4"


@dataclass(frozen=True)
class SparseSelection:
    """MiniCPM4's ``sparse_config``: how a ``minicpm4`` layer chooses the key
    blocks a row attends once the sequence is longer than ``dense_len``."""
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192


@dataclass(frozen=True)
class MiniCPMSalaConfig(LlamaConfig):
    # Each layer's (mixer, ffn): "sparse" or "lightning", and "mlp".
    layer_kinds: Tuple[Tuple[str, str], ...] = ()
    # The source's ``num_hidden_layers``, whatever part of them is held: the
    # residual scale and the slopes are functions of it.
    published_layers: int = 32
    lightning_nh: int = 32
    lightning_head_dim: int = 128
    lightning_use_rope: bool = True
    attn_use_rope: bool = False
    sparse: SparseSelection = SparseSelection()
    rms_eps: float = 1e-6
    remat_policy: str = "nothing"
    remat_prevent_cse: bool = True

    @property
    def layers(self):
        return self.layer_kinds

    def lightning_slopes(self, layer: int) -> Tuple[float, ...]:
        """s_h of layer ``layer`` (counted over the published layers), as
        ``fla.layers.lightning_attn`` builds them: 2^(-8 (h + 1) / H) times
        1 - layer / (L - 1) + 1e-5. Constants, not parameters."""
        H = self.lightning_nh
        factor = 1.0 - layer / (self.published_layers - 1) + 1e-5
        return tuple(2.0 ** (-8.0 * (h + 1) / H) * factor for h in range(H))

    def num_params(self) -> int:
        h, hd = self.hidden_size, self.head_dim_
        d = self.lightning_head_dim
        mixer = {
            # q, k, v, the gate, o; the norms of q, k and o
            tracing.LIGHTNING: 5 * h * self.lightning_nh * d + 3 * d,
            # q, the gate and o; k and v; the norms of q and k
            tracing.SPARSE: 3 * h * self.num_heads * hd
            + 2 * h * self.num_kv_heads * hd + 2 * hd,
        }
        total = self.vocab_size * h * (1 if self.tie_embeddings else 2) + h
        return total + sum(
            mixer[m] + 3 * h * self.intermediate_size + 2 * h
            for m, _ in self.layer_kinds
        )


def minicpm_sala_config(
    *, mixer_types, num_layers: int, published_layers: int, scale_emb: float,
    scale_depth: float, dim_model_base: int, lightning_nh: int,
    lightning_nkv: int, lightning_head_dim: int, sparse_config: dict,
    qk_norm: bool = True, use_output_gate: bool = True,
    use_output_norm: bool = True, attn_use_output_gate: bool = True,
    **fields,
) -> MiniCPMSalaConfig:
    """The program's config from the source's keys: ``mixer_types`` (each
    layer ``minicpm4`` or ``lightning-attn``, read up to ``num_layers``), the
    three muP numbers, the ``lightning_*`` keys and MiniCPM4's
    ``sparse_config``. The mixers are built as published (QK norm, output
    norm and both output gates on): a key that says otherwise is refused."""
    if lightning_nkv != lightning_nh:
        raise ValueError("LightningMixer's k and v have q's head count")
    if not (qk_norm and use_output_gate and use_output_norm and attn_use_output_gate):
        raise ValueError("the mixers have their QK norm, output norm and gates")
    kinds = {MINICPM4: tracing.SPARSE, LIGHTNING: tracing.LIGHTNING}
    unknown = set(mixer_types[:num_layers]) - set(kinds)
    if unknown or len(mixer_types) < num_layers:
        raise ValueError(f"mixer_types names {sorted(unknown)} or is short of {num_layers} layers")
    names = set(SparseSelection.__dataclass_fields__)
    return MiniCPMSalaConfig(
        num_layers=num_layers, published_layers=published_layers,
        layer_kinds=tuple((kinds[t], tracing.MLP) for t in mixer_types[:num_layers]),
        embed_scale=float(scale_emb),
        residual_scale=scale_depth / math.sqrt(published_layers),
        logit_divisor=fields["hidden_size"] / dim_model_base,
        lightning_nh=lightning_nh, lightning_head_dim=lightning_head_dim,
        sparse=SparseSelection(**{k: v for k, v in sparse_config.items() if k in names}),
        **fields,
    )


def _head_normed(cfg, q, k):
    """q and k [B, T, heads, d] through an RMSNorm over a head's channels,
    one weight [d] each."""
    with tracing.scope(tracing.QK_NORM):
        norm = lambda name: RMSNorm(cfg.rms_eps, cfg.param_dtype, name=name)  # noqa: E731
        return norm("q_norm")(q), norm("k_norm")(k)


def _turned(cfg, q, k, positions, dim):
    """q and k [B, heads, T, d] turned by the plain table over the whole
    head."""
    with tracing.scope(tracing.ATTN_ROPE):
        freqs = rope_frequencies(dim, cfg.rope_theta)
        return rotate(q, positions, freqs), rotate(k, positions, freqs)


class LightningMixer(nn.Module):
    """The decay-only linear-attention mixer of a layer. One device's: the
    recurrence is not sharded over the sequence."""
    cfg: MiniCPMSalaConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        H, d = cfg.lightning_nh, cfg.lightning_head_dim
        B, T, _ = x.shape
        heads = lambda y: y.reshape(B, T, H, d)  # noqa: E731
        q, k, v = (heads(_dense(cfg, H * d, f"{n}_proj")(x)) for n in "qkv")
        q, k = _head_normed(cfg, q, k)
        if cfg.lightning_use_rope:
            # [B, H, T, d] is how chunk_lightning's kernels take q and k, so
            # XLA moves nothing here and back.
            turn = lambda y: y.transpose(0, 2, 1, 3)  # noqa: E731
            q, k = map(turn, _turned(cfg, turn(q), turn(k), positions, d))
        gate = heads(_dense(cfg, H * d, "g_proj")(x))
        # The layer's index is its own flax name's (llama.py: LAYER + index).
        layer = int(self.path[-2].removeprefix(tracing.LAYER))
        o = chunk_lightning(
            q, k, v, gate, NormWeight(cfg.param_dtype, name="o_norm")(d),
            jnp.asarray(cfg.lightning_slopes(layer), jnp.float32),
            scale=d ** -0.5, rms_eps=cfg.rms_eps,
        )
        return _dense(cfg, cfg.hidden_size, "o_proj")(o.reshape(B, T, H * d))


class SparseAttention(nn.Module):
    """Softmax attention over grouped K/V heads that, past ``dense_len``
    tokens, attends the key blocks chosen a row and group."""
    cfg: MiniCPMSalaConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        hd, sel = cfg.head_dim_, cfg.sparse
        T = x.shape[1]
        dense = lambda feats, name: nn.DenseGeneral(  # noqa: E731
            feats, axis=-1, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, kernel_init=weight_init(cfg), name=name,
        )
        q = dense((cfg.num_heads, hd), "q_proj")(x)
        k = dense((cfg.num_kv_heads, hd), "k_proj")(x)
        v = dense((cfg.num_kv_heads, hd), "v_proj")(x)
        q, k = _head_normed(cfg, q, k)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))  # [B, H, T, D]
        if cfg.attn_use_rope:
            q, k = _turned(cfg, q, k, positions, hd)
        if T <= sel.dense_len:
            o = flash_attention(q, k, v, causal=True)
        else:
            with tracing.scope(tracing.SPARSE_SELECT):
                # Named for the remat policy (llama.py REPLAY_KEEPS): a replay
                # that holds the set chooses nothing again.
                blocks = checkpoint_name(select_blocks(
                    q, k, block_size=sel.block_size, topk=sel.topk,
                    window=sel.window_size, init_blocks=sel.init_blocks,
                    kernel_size=sel.kernel_size, kernel_stride=sel.kernel_stride,
                ), "sparse_blocks")
            o = flash_attention(q, k, v, causal=True, blocks=blocks,
                                block_size=sel.block_size)
        o = o.transpose(0, 2, 1, 3)  # [B, T, H, D]
        with tracing.scope(tracing.ATTN_GATE):
            gate = nn.sigmoid(dense((cfg.num_heads, hd), "g_proj")(x).astype(jnp.float32))
            o = o * gate.astype(o.dtype)
        return nn.DenseGeneral(
            cfg.hidden_size, axis=(-2, -1), use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, kernel_init=weight_init(cfg),
            name="o_proj",
        )(o)


class MiniCPMSalaForCausalLM(LlamaForCausalLM):
    """The decoder body of llama.py with ``SparseAttention`` or
    ``LightningMixer`` as a layer's mixer (``MiniCPMSalaConfig.layers``) over
    the dense ``MLP``."""

    blocks = {**LlamaForCausalLM.blocks, tracing.SPARSE: SparseAttention,
              tracing.LIGHTNING: LightningMixer}
