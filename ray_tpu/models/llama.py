"""Llama-family causal LM in flax, designed for GSPMD sharding.

The reference framework ships no model code (models live in user code /
integrations); the TPU rebuild needs a flagship model family to carry
the Train/RLlib benchmarks (BASELINE.md: Llama-2-7B >=40% MFU on v5e).
Architecture follows Llama-2: RMSNorm, rotary embeddings, GQA
attention, SwiGLU MLP, untied or tied LM head.

Sharding: parameters keep flax's standard naming so
`parallel.mesh.spec_for_param` places them (kernel [in, out] ->
(fsdp, tensor); embedding [vocab, embed] -> (tensor, fsdp)).
Activations get in-graph constraints through
`parallel.with_logical_constraint`. The mesh is the ambient one
(`jax.set_mesh`), which everything here reads: a mixer calls
`ops.attention.flash_attention`, which rings where that mesh splits the
sequence (long-context sequence parallelism, net-new vs reference).

Compute in bfloat16, parameters and reductions in float32 (MXU-friendly,
HBM-light).
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops.attention import flash_attention
from ..ops.rotary import rotate
from ..parallel.mesh import logical_axis_shards, with_logical_constraint
from ..util import tracing
from .hyper_connections import (
    HyperConnection, HyperConnections, collapse_streams, expand_streams,
    write_streams,
)


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: Optional[int] = None
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    tie_embeddings: bool = False
    # OLMoE's attention: RMSNorm over the whole q and the whole k
    # projection (all heads at once), before the head split and rope. A
    # fact of the architecture, read from its modelling code.
    qk_norm: bool = False
    # The architecture's own draw (its config's initializer_range, 0.02
    # for OLMoE): every weight matrix and the embedding normal(0, this),
    # one matrix at a time. None keeps flax's draws (weight_init).
    initializer_range: Optional[float] = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # "nothing": of what XLA computes, save only a layer's input. With
    # prevent_cse=False that is all, and XLA merges the replay with its
    # forward twin (step.remat_share reads 0.00-2.57% of busy time in the
    # dense cells and the OLMoE cell, PERF.md §5). With the barrier, where
    # a replay is executed, also the values REPLAY_KEEPS names, by two
    # rules: what a Pallas forward kernel wrote for its own backward, so
    # that no replay runs a forward kernel a second time, and a matmul's
    # output that only an element-wise consumer reads (a SwiGLU's two
    # products, a sublayer's output), so that no replay runs gate_proj,
    # up_proj, o_proj or down_proj. A kept float costs its bytes from the
    # forward pass to its layer's backward and one reduce_precision pass
    # over them.
    # "kernels": a word one configuration's file still carries; the same as
    # "nothing" since PR 55.
    # "dots": save matmul outputs, recompute only elementwise — moves
    # memory, not time, where nothing is replayed.
    remat_policy: str = "nothing"
    # True puts a barrier around each layer's replay, so that XLA neither
    # merges it with its forward twin nor moves it ahead of the backward
    # pass that needs it: a replay that keeps large residuals (a scan's
    # per-chunk states) then lives for one layer's backward pass only.
    remat_prevent_cse: bool = False
    # The residual path as ``hyper_connections.py`` has it: that many streams
    # a token, [n, B, T, C] between the layers, each sublayer reading a
    # weighted sum of them and writing back through two more maps. None: the
    # one stream of x + F(x).
    hyper_connections: Optional[HyperConnections] = None
    # The OLMo 2/3 order: a layer norms what its sublayers give and not what
    # they take, h = x + RMSNorm(mixer(x)), out = h + RMSNorm(ffn(h)), the
    # sublayers reading the raw stream. False: the pre-norm of every other
    # family, h = x + mixer(RMSNorm(x)).
    norm_after: bool = False
    # MiniCPM's muP and Granite's multipliers, each 1.0 where a family has
    # none (nothing is then lowered for it): the embedding times
    # ``embed_scale``; each sublayer's
    # output times ``residual_scale`` before the residual sum; the final
    # norm's output over ``logit_divisor`` before the head, in the full-logit
    # and the chunked loss alike.
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_divisor: float = 1.0

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    def attention(self, name: Optional[str]) -> "AttentionKind":
        """What the ``Attention`` bound under the flax name ``name`` is. One
        kind here; a family whose layers differ tells them apart by the
        names its ``layers`` gives their mixers (laguna.py)."""
        return AttentionKind(
            self.num_heads, rope_frequencies(self.head_dim_, self.rope_theta)
        )

    @property
    def layers(self) -> Tuple[Tuple[str, str], ...]:
        """Each layer's (mixer, ffn), by the names under which the model
        class binds their modules (``LlamaForCausalLM.blocks``), which are
        also their flax names. A family with one kind of layer says it
        once; one with several kinds gives the tuple from its file."""
        return ((tracing.ATTN, tracing.MLP),) * self.num_layers

    def num_params(self) -> int:
        h, i, v, l = self.hidden_size, self.intermediate_size, self.vocab_size, self.num_layers
        hd = self.head_dim_
        attn = h * (self.num_heads * hd) * 2 + h * (self.num_kv_heads * hd) * 2
        mlp = 3 * h * i
        per_layer = attn + mlp + 2 * h
        if self.qk_norm:
            per_layer += (self.num_heads + self.num_kv_heads) * hd
        emb = v * h * (1 if self.tie_embeddings else 2)
        return l * per_layer + emb + h


@dataclass(frozen=True)
class AttentionKind:
    """What one layer's softmax attention is beside the widths every layer
    shares (K/V heads, head dim), as ``LlamaConfig.attention`` gives it."""
    num_heads: int
    # One frequency a pair of turning channels (``rope_frequencies`` or a
    # scaled table): the leading ``2 * len(freqs)`` channels of a head turn,
    # the whole head where that is its width. None: nothing turns (a layer
    # that learns positions from the layers beside it).
    freqs: Any
    # cos and sin times this (YaRN's ``attention_factor``).
    rope_amplitude: float = 1.0
    # Row i sees keys 0 <= i - j < window; None: every key up to its own.
    window: Optional[int] = None
    # o_h times sigmoid(x W_g)_h, one gate a head and token, before o_proj.
    gate: bool = False
    # The gate has q's width, W_g [hidden, heads, head dim]: one value a
    # channel of every head, where it is one a head.
    gate_channels: bool = False
    # What the scores are multiplied by before the soft-max (Granite's
    # ``attention_multiplier``); None: ``head_dim ** -0.5``.
    scale: Optional[float] = None
    # LFM2's QK norm: an RMSNorm over each head's channels of q and of k, one
    # weight [head_dim] for q and one for k shared by their heads, before the
    # rotation (``cfg.qk_norm`` is OLMoE's, over the whole projection).
    qk_head_norm: bool = False


CONFIGS: Dict[str, LlamaConfig] = {
    # test-size
    "llama-tiny": LlamaConfig(
        vocab_size=512, hidden_size=128, intermediate_size=352, num_layers=2,
        num_heads=4, num_kv_heads=2, max_seq_len=256,
    ),
    "llama-125m": LlamaConfig(
        vocab_size=32000, hidden_size=768, intermediate_size=2048, num_layers=12,
        num_heads=12, num_kv_heads=12, max_seq_len=2048,
    ),
    "llama-1b": LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5504, num_layers=22,
        num_heads=16, num_kv_heads=16, max_seq_len=4096,
    ),
}


# The values a layer's replay does not make again, each tagged where it is
# made (``jax.ad_checkpoint.checkpoint_name``). A value is here by one of two
# rules.
#
# Keeping it deletes a replayed kernel call: a Pallas forward kernel's outputs
# that its own backward kernels read, tagged where the custom-vjp forward rule
# returns them (ops/attention.py, ops/kda.py, models/hyper_connections.py).
# Under ``nothing_saveable`` a replay runs each forward rule whole, so the
# kernel that wrote o and lse (or o and the per-chunk states and inverses)
# runs twice a layer only to hand its backward what it had already written
# once. The ring's rule (ops/ring_attention.py) is not tagged: its cell has no
# memory to spare and replays 1% of its step.
#
# It is a matmul's output that the replay would make again only to hand it to
# an element-wise consumer, tagged in this file: a SwiGLU's two products
# (``MLP``: the dense layers and, as ``shared``, the shared experts; silu(gate)
# * up is one fused pass from the two and is not kept), and a sublayer's output
# (``DecoderLayer``), which with the kernel's o kept leaves no replay an o_proj,
# an output gate or a down_proj to run. Partial evaluation drops a kept value
# that no backward reads: an FFN's output is read by the norm after it
# (``norm_after``) or by a hyper-connection's write, and by nothing in a
# pre-norm layer, where it is not kept.
#
# What a name costs: its bytes from the forward pass to its layer's backward,
# and one pass over them, because JAX hands every float a remat keeps through
# ``reduce_precision``, a read and a write the TPU compiler does not elide
# (PERF.md §6, PR 47). Every matmul here is eight or more such passes. The
# routed experts' products, the kernels' operands (q, k, v, the rotations, the
# convolutions) and the router are not here: they are 1.25-2.7 GiB a step and
# do not fit in every replaying cell, and a choice by cell would be a knob.
REPLAY_KEEPS = ("flash_o", "flash_lse", "kda_o", "kda_states", "kda_t",
                "hc_read", "hc_maps", "hc_write", "gdn_o", "gdn_states",
                "gdn_t", "mlp_gate", "mlp_up", "mixer_out", "ffn_out",
                "lightning_o", "lightning_states", "sparse_o", "sparse_lse",
                "sparse_blocks", "ssd_y", "ssd_states", "select_o",
                "select_lse", "select_words")
# One object for every caller: JAX caches a jitted function's partial
# evaluation by the policy's identity, and a second ``_through`` (xing4.py's
# module) with a policy of its own would lower every jitted kernel entry's
# body again.
_KEEP = jax.checkpoint_policies.save_only_these_names(*REPLAY_KEEPS)


def remat_policy(cfg: LlamaConfig):
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.checkpoint_dots
    if not cfg.remat_prevent_cse:
        # Without the barrier no replay is executed (XLA merges it with its
        # forward twin), so there is nothing to delete, and the names cost
        # memory all the same: the compiled step's temporaries rose by
        # every layer's o, 4.90 -> 5.15 GiB in mistral-7b-l4.short2k and
        # 5.50 -> 5.59 in the OLMoE cell (AOT compiles for v5e, PR 47).
        return jax.checkpoint_policies.nothing_saveable
    return _KEEP


def weight_init(cfg: LlamaConfig, default=nn.initializers.lecun_normal()):
    """The initializer of a weight: normal(0, cfg.initializer_range) where
    the architecture names one, else `default` (flax's own for the layer)."""
    if cfg.initializer_range is None:
        return default
    return nn.initializers.normal(cfg.initializer_range)


def rope_frequencies(dim: int, theta: float) -> jax.Array:
    """The plain rotary table: ``dim // 2`` frequencies theta^(-2i/dim)."""
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def _rope(x: jax.Array, positions: jax.Array, freqs: jax.Array, *,
          leading: bool = False, amplitude: float = 1.0) -> jax.Array:
    """Rotary embeddings. x [B, H, T, D], positions [B, T]; ``freqs`` is
    the caller's table (``rope_frequencies``, or a scaled one), one
    frequency a pair of channels. ``2 * len(freqs)`` channels of a head
    turn, the trailing ones (the leading ones under ``leading``), channel i
    of them with channel i + len(freqs), and the others pass as they are.
    cos and sin are times ``amplitude``."""
    d = 2 * freqs.shape[0]
    whole = d == x.shape[-1]
    angles = positions[:, None, :, None].astype(jnp.float32) * freqs  # [B,1,T,d/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if amplitude != 1.0:
        cos, sin = cos * amplitude, sin * amplitude
    turned = x if whole else x[..., :d] if leading else x[..., -d:]
    x1, x2 = jnp.split(turned.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    out = out.astype(x.dtype)
    if whole:
        return out
    if leading:
        return jnp.concatenate([out, x[..., d:]], axis=-1)
    return jnp.concatenate([x[..., :-d], out], axis=-1)


class RMSNorm(nn.Module):
    eps: float = 1e-5
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), self.param_dtype)
        xf = x.astype(jnp.float32)
        norm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps)
        return (norm * scale).astype(x.dtype)


class Attention(nn.Module):
    """Softmax attention over grouped K/V heads. What the layer is beyond
    the shared widths (its head count, a norm over each head's channels of q
    and k, its rotation or none, a window, an output gate and its width) it
    learns from the config by its own flax name
    (``cfg.attention``): a Llama or Mistral layer is all one kind."""
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        kind = cfg.attention(self.name)
        hd = cfg.head_dim_
        dense = lambda feats, name: nn.DenseGeneral(  # noqa: E731
            feats, axis=-1, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, kernel_init=weight_init(cfg),
            name=name,
        )
        q = dense((kind.num_heads, hd), "q_proj")(x)
        k = dense((cfg.num_kv_heads, hd), "k_proj")(x)
        v = dense((cfg.num_kv_heads, hd), "v_proj")(x)
        if cfg.qk_norm:
            with tracing.scope(tracing.QK_NORM):
                norm = lambda t, name: RMSNorm(  # noqa: E731
                    cfg.rms_eps, cfg.param_dtype, name=name
                )(t.reshape(*t.shape[:2], -1)).reshape(t.shape)
                q, k = norm(q, "q_norm"), norm(k, "k_norm")
        if kind.qk_head_norm:
            with tracing.scope(tracing.QK_NORM):
                norm = lambda t, name: RMSNorm(  # noqa: E731
                    cfg.rms_eps, cfg.param_dtype, name=name)(t)
                q, k = norm(q, "q_norm"), norm(k, "k_norm")
        # [B, T, H, D] -> [B, H, T, D]
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        if kind.freqs is not None:
            with tracing.scope(tracing.ATTN_ROPE):
                turn = lambda t: rotate(  # noqa: E731
                    t, positions, kind.freqs, leading=True,
                    amplitude=kind.rope_amplitude,
                )
                q, k = turn(q), turn(k)
        o = flash_attention(q, k, v, causal=True, window=kind.window,
                            sm_scale=kind.scale)
        o = o.transpose(0, 2, 1, 3)  # [B, T, H, D]
        if kind.gate:
            with tracing.scope(tracing.ATTN_GATE):
                width = (kind.num_heads, hd) if kind.gate_channels else kind.num_heads
                gate = nn.sigmoid(dense(width, "g_proj")(x).astype(jnp.float32))
                if not kind.gate_channels:
                    gate = gate[..., None]
                o = o * gate.astype(o.dtype)
        out = nn.DenseGeneral(
            cfg.hidden_size, axis=(-2, -1), use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, kernel_init=weight_init(cfg),
            name="o_proj",
        )(o)
        return out


class MLP(nn.Module):
    cfg: LlamaConfig
    # The hidden width, cfg.intermediate_size where none is given (a
    # shared expert has the routed experts' width, not the dense layer's).
    width: Optional[int] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        width = self.width or cfg.intermediate_size
        dense = lambda feats, name: nn.Dense(  # noqa: E731
            feats, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, kernel_init=weight_init(cfg),
            name=name,
        )
        # Named for the remat policy (REPLAY_KEEPS): with the two products
        # kept a replay makes h from them in one pass and runs no matmul.
        gate = checkpoint_name(dense(width, "gate_proj")(x), "mlp_gate")
        up = checkpoint_name(dense(width, "up_proj")(x), "mlp_up")
        h = nn.silu(gate) * up
        h = with_logical_constraint(h, ("batch", "seq", "mlp"))
        return dense(cfg.hidden_size, "down_proj")(h)


class DecoderLayer(nn.Module):
    cfg: LlamaConfig
    # The mixer's and the FFN's flax names and modules, called as
    # module(cfg, name=name)(x, positions) and module(cfg, name=name)(x).
    mixer: Tuple[str, Any]
    ffn: Tuple[str, Any]

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        mixer_name, mixer = self.mixer
        ffn_name, ffn = self.ffn
        # The sublayers, their outputs named for the remat policy
        # (REPLAY_KEEPS): a replay that holds them ends at the kernel's o and
        # at the SwiGLU's h, before o_proj and down_proj.
        mix = lambda u: checkpoint_name(  # noqa: E731
            mixer(cfg, name=mixer_name)(u, positions), "mixer_out")
        feed = lambda u: checkpoint_name(  # noqa: E731
            ffn(cfg, name=ffn_name)(u), "ffn_out")
        norm = lambda name, y: RMSNorm(cfg.rms_eps, cfg.param_dtype, name=name)(y)  # noqa: E731
        if cfg.hyper_connections is not None:
            return _hyper_connected(cfg, x, mix, feed, norm)
        if cfg.norm_after:
            h = x + norm(tracing.POST_MIXER_NORM, mix(x))
            out = h + norm(tracing.POST_FFN_NORM, feed(h))
            return with_logical_constraint(out, ("batch", "seq", "embed"))
        if cfg.residual_scale != 1.0:
            mix, feed = _scaled(mix, cfg.residual_scale), _scaled(feed, cfg.residual_scale)
        h = x + mix(norm(tracing.INPUT_NORM, x))
        out = h + feed(norm(tracing.POST_ATTN_NORM, h))
        return with_logical_constraint(out, ("batch", "seq", "embed"))


def _scaled(sublayer, scale: float):
    """``sublayer``'s output times ``scale`` in its own dtype (muP's
    ``scale_depth / sqrt(num_hidden_layers)``)."""
    def call(u):
        y = sublayer(u)
        return y * jnp.asarray(scale, y.dtype)
    return call


def _hyper_connected(cfg: LlamaConfig, x, mix, feed, norm):
    """A layer's two sublayers ``mix`` and ``feed`` on the streams x
    [n, B, T, C], inside ``DecoderLayer.__call__``: each reads through its
    hyper-connection (``mixer_hc``, ``ffn_hc``) and writes back through it."""
    connection = lambda name: HyperConnection(  # noqa: E731
        cfg.hyper_connections, cfg.rms_eps, weight_init(cfg),
        cfg.param_dtype, name=name,
    )
    u, x, maps = connection(tracing.MIXER_HC)(x, streams=True)
    h = write_streams(x, mix(norm(tracing.INPUT_NORM, u)), *maps)
    u, h, maps = connection(tracing.FFN_HC)(h, streams=True)
    out = write_streams(h, feed(norm(tracing.POST_ATTN_NORM, u)), *maps)
    return with_logical_constraint(out, (None, "batch", "seq", "embed"))


def _embedding(cfg: LlamaConfig) -> nn.Embed:
    return nn.Embed(
        cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
        param_dtype=cfg.param_dtype, name=tracing.EMBED,
        embedding_init=weight_init(cfg, nn.linear.default_embed_init),
    )


def _lookup(cfg: LlamaConfig, emb: nn.Embed, input_ids):
    if logical_axis_shards("vocab") * logical_axis_shards("embed") > 1:
        # One-hot matmul lookup where the ambient mesh splits the
        # table (vocab=tensor, embed=fsdp): a gather forces SPMD into
        # full rematerialization (replicate-then-repartition every
        # step); a contraction over the vocab axis instead becomes
        # partial products + psum over `tensor`, rides the MXU, and
        # XLA fuses the one-hot so the [B,S,V] operand is never
        # materialized. A whole table (one chip, or a mesh of data,
        # seq and expert axes alone) is read by a gather.
        with tracing.scope(tracing.EMBED):  # outside the module's own scope
            one_hot = jax.nn.one_hot(input_ids, cfg.vocab_size, dtype=cfg.dtype)
            x = jnp.einsum(
                "bsv,ve->bse", one_hot, emb.embedding.astype(cfg.dtype)
            )
    else:
        x = emb(input_ids)
    with tracing.scope(tracing.EMBED):
        if cfg.embed_scale != 1.0:
            x = x * jnp.asarray(cfg.embed_scale, x.dtype)
        return with_logical_constraint(x, ("batch", "seq", "embed"))


def _through(model: "LlamaForCausalLM", layers, x, positions):
    """x [B, T, C] through ``layers`` (flax name, mixer, ffn) of ``model``,
    inside its ``__call__``: on hyper-connections, copied to the streams
    before them and summed after."""
    cfg = model.cfg
    ambient = jax.sharding.get_abstract_mesh()
    if model.mesh is not None and dict(ambient.shape) != dict(model.mesh.shape):
        raise ValueError(
            f"the model was built with mesh={dict(model.mesh.shape)} and is "
            f"applied under the ambient mesh {dict(ambient.shape)}: the layers "
            "read the ambient one, so apply it inside `with jax.set_mesh(mesh)`"
        )
    layer_cls = DecoderLayer
    if cfg.remat:
        layer_cls = nn.remat(
            DecoderLayer, prevent_cse=cfg.remat_prevent_cse,
            policy=remat_policy(cfg),
        )
    hc = cfg.hyper_connections
    if hc is not None:
        with tracing.scope(tracing.HC_STREAMS):
            x = expand_streams(x, hc.mult)
    for name, mixer, ffn in layers:
        x = layer_cls(
            cfg, (mixer, model.blocks[mixer]), (ffn, model.blocks[ffn]), name=name,
        )(x, positions)
    if hc is None:
        return x
    with tracing.scope(tracing.HC_STREAMS):
        return collapse_streams(x)


def _logits(cfg: LlamaConfig, emb: nn.Embed, x):
    if cfg.tie_embeddings:
        # flax names the method embed_tokens.attend: the head's name in front,
        # so that a tied head is read where an untied one is.
        with tracing.scope(tracing.LM_HEAD):
            return emb.attend(x.astype(cfg.param_dtype))
    return nn.Dense(
        cfg.vocab_size, use_bias=False, dtype=jnp.float32,
        param_dtype=cfg.param_dtype, kernel_init=weight_init(cfg),
        name=tracing.LM_HEAD,
    )(x)


def _positions(input_ids):
    return jnp.broadcast_to(
        jnp.arange(input_ids.shape[1])[None], input_ids.shape
    )


class LlamaForCausalLM(nn.Module):
    """The decoder body of every family: embedding, layers, final norm,
    head. Each layer's mixer and FFN are the configuration's to name
    (``cfg.layers``); a family binds the modules its names stand for in
    ``blocks`` (MixtralForCausalLM: the sparse layer, as ``moe``). The
    pieces of ``__call__`` are functions of this file, not methods (flax
    would put a method's name into every operation's path), so that a family
    whose ``__call__`` has more to it (xing4.py) is made of the same."""

    cfg: LlamaConfig
    # What the caller will `jax.set_mesh`: checked against the ambient mesh
    # (`_through`), and read by nothing else.
    mesh: Optional[Any] = None
    # A class attribute, not a field: no caller sets it.
    blocks = {tracing.ATTN: Attention, tracing.MLP: MLP}

    @nn.compact
    def __call__(self, input_ids, positions=None, return_hidden=False):
        """``return_hidden=True`` yields the final-norm hidden states
        instead of logits, so a chunked loss can apply the LM head
        per sequence chunk — at long T the full [B, T, V] logits
        tensor (4.2 GB in f32 at T=32k, V=32k) is the single biggest
        activation and never needs to exist."""
        cfg = self.cfg
        if positions is None:
            positions = _positions(input_ids)
        emb = _embedding(cfg)
        x = _through(
            self, [(f"{tracing.LAYER}{i}", *kinds) for i, kinds in enumerate(cfg.layers)],
            _lookup(cfg, emb, input_ids), positions,
        )
        x = RMSNorm(cfg.rms_eps, cfg.param_dtype, name=tracing.FINAL_NORM)(x)
        if cfg.logit_divisor != 1.0:
            with tracing.scope(tracing.FINAL_NORM):  # outside the module's own scope
                x = x * jnp.asarray(1.0 / cfg.logit_divisor, x.dtype)
        return x if return_hidden else _logits(cfg, emb, x)


def lm_head_weight(params) -> jax.Array:
    """[V, H] output-projection weight from a param tree (tied
    embedding table, or the dedicated lm_head kernel transposed)."""
    p = params.get("params", params)
    if tracing.LM_HEAD in p:
        with tracing.scope(tracing.LOSS):  # the transpose is the loss's
            return p[tracing.LM_HEAD]["kernel"].T
    return p[tracing.EMBED]["embedding"]


def chunked_causal_lm_loss(
    model,
    params,
    input_ids: jax.Array,
    targets: jax.Array,
    mask: Optional[jax.Array] = None,
    chunk_size: int = 2048,
) -> jax.Array:
    """Next-token cross-entropy without materializing full logits.

    The [B, T, V] logits tensor is the largest activation at long T
    (f32 T=32k, V=32k is 4.2 GB — bigger than the whole remat'd
    transformer). Scanning the LM head + softmax-xent over sequence
    chunks keeps only [B, chunk, V] alive, and the scan makes each chunk's
    gradients while its logits are (``chunked_head_loss``), so the memory
    bound holds end-to-end and no chunk is computed twice. Net-new vs the
    reference (its torch trainers materialize logits); the standard
    long-context recipe on TPU.
    """
    hidden = model.apply(params, input_ids, return_hidden=True)
    return chunked_head_loss(
        hidden, lm_head_weight(params), targets, mask, chunk_size
    )


@tracing.scope(tracing.LOSS)
def chunked_head_loss(
    hidden: jax.Array,
    head: jax.Array,
    targets: jax.Array,
    mask: Optional[jax.Array] = None,
    chunk_size: int = 2048,
) -> jax.Array:
    """``chunked_causal_lm_loss`` from the final-norm hidden states [B, T, H]
    on: the head [V, H] and the cross-entropy a chunk of the sequence at a
    time, the mean over the positions ``mask`` keeps.

    Differentiated by a rule of its own (``_chunked_nll``): reverse mode
    only, with respect to ``hidden`` and ``head``; ``jax.jvp`` of it raises."""
    b, t = targets.shape
    if mask is None:
        m_full = jnp.ones((b, t), jnp.float32)
    else:
        m_full = jnp.broadcast_to(
            mask.astype(jnp.float32), targets.shape
        )
    chunk_size = min(chunk_size, t)
    pad = (-t) % chunk_size
    if pad:
        # Pad to a whole number of chunks; padded rows carry mask 0 so
        # they never contribute (odd lengths must not collapse the
        # chunking into per-token scan steps).
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        m_full = jnp.pad(m_full, ((0, 0), (0, pad)))
        t += pad
    n_chunks = t // chunk_size
    h_c = hidden.reshape(b, n_chunks, chunk_size, -1).swapaxes(0, 1)
    t_c = targets.reshape(b, n_chunks, chunk_size).swapaxes(0, 1)
    m_c = m_full.reshape(b, n_chunks, chunk_size).swapaxes(0, 1)
    return _chunked_nll(h_c, head, t_c, m_c)


def _chunk_nll(h, head, tg, m):
    """One chunk's summed negative log-likelihood over the rows ``m`` keeps,
    and what its gradient is made of: the logits [B, C, V] float32, each
    row's maximum and the sum of its exponentials below it."""
    # f32 accumulation on the MXU regardless of param dtype — the
    # full path's lm_head computes f32 logits, and the two losses
    # must stay numerically comparable.
    with tracing.scope(tracing.LOSS_HEAD):
        logits = jnp.matmul(
            h.astype(head.dtype),
            head.T,
            preferred_element_type=jnp.float32,
        )  # [B, C, V] f32
    # jax.nn.logsumexp's own lines (the value is its, bit for bit), written
    # out so that the soft-max of the rule below divides the same
    # exponentials by the same sum as autodiff's does
    top = jnp.max(logits, axis=-1)
    top = jnp.where(jnp.isfinite(top), top, 0.0)
    sums = jnp.sum(jnp.exp(logits - top[..., None]), axis=-1)
    logz = jnp.log(sums) + top
    if logits.shape[-1] % 128:
        # A vocabulary that fills no whole number of lanes (an eighth of
        # 73,448 is 9,181): the gather takes the chunk's logits flat, and
        # [B, C, V] -> [B * C * V] is then a copy the compiler makes in a
        # loop of its own, under no name (8.5 ms a step at 16k tokens:
        # PERF.md §6, PR 54). A select and a sum ride the passes logsumexp
        # makes; the value is the same.
        gold = jnp.sum(jnp.where(_hits(logits, tg), logits, 0.0), axis=-1)
    else:
        gold = jnp.take_along_axis(logits, tg[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - gold) * m), logits, top, sums


def _hits(logits, tg):
    """[B, C, V] bool: the target's column of each row."""
    return jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2) == tg[..., None]


@jax.custom_vjp
def _chunked_nll(h_c, head, t_c, m_c):
    """The mean over the kept rows of the cross-entropy of ``h_c``
    [chunks, B, C, H] through ``head`` [V, H], a chunk at a time."""
    def body(total, inp):
        h, tg, m = inp
        return total + _chunk_nll(h, head, tg, m)[0], None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (h_c, t_c, m_c))
    return total / jnp.maximum(jnp.sum(m_c), 1.0)


def _chunked_nll_fwd(h_c, head, t_c, m_c):
    """The same loss, and each chunk's gradients made while its logits are
    alive: nothing in them needs a cotangent from upstream but the loss's own
    scalar, so no chunk's head matmul or soft-max runs again for the backward
    pass. The two products and the sum over chunks are autodiff's own of the
    forward matmul: float32 cotangent by the operand in ``head.dtype``,
    float32 out, rounded to ``head.dtype`` and summed there."""
    count = jnp.maximum(jnp.sum(m_c), 1.0)

    def body(carry, inp):
        total, d_head, rows = carry
        i, tg, m = inp
        h = rows[i]
        nll, logits, top, sums = _chunk_nll(h, head, tg, m)
        weight = m / count  # the mean's cotangent, a row
        d_logits = jnp.exp(logits - top[..., None]) * (weight / sums)[..., None] - (
            jnp.where(_hits(logits, tg), weight[..., None], 0.0))
        with tracing.scope(tracing.LOSS_HEAD):
            d_h = jax.lax.dot_general(
                d_logits, head, (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(rows.dtype)
            d_head = d_head + jax.lax.dot_general(
                d_logits, h.astype(head.dtype), (((0, 1), (0, 1)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(head.dtype)
        # The chunk's rows are spent, and their gradient takes their place: a
        # second [chunks, B, C, H] stack beside the hidden states would be
        # alive with every layer's residuals (128 MiB at 16k tokens of 4,096).
        return (total + nll, d_head, rows.at[i].set(d_h)), None

    (total, d_head, d_h), _ = jax.lax.scan(
        body,
        (jnp.zeros((), jnp.float32), jnp.zeros_like(head), h_c),
        (jnp.arange(h_c.shape[0]), t_c, m_c),
    )
    return total / count, (d_h, d_head)


def _chunked_nll_bwd(gradients, g):
    # (traced under the scope the loss was called in: JAX names these lines
    # transpose(jvp(loss))/ without a scope opened here)
    return (*((g * d).astype(d.dtype) for d in gradients), None, None)


_chunked_nll.defvjp(_chunked_nll_fwd, _chunked_nll_bwd)


@tracing.scope(tracing.LOSS)
def causal_lm_loss(logits: jax.Array, targets: jax.Array,
                   mask: Optional[jax.Array] = None) -> jax.Array:
    """Next-token cross-entropy in f32. logits [B, T, V], targets [B, T]
    (already shifted by the data pipeline)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        # Broadcast BEFORE the sums: a shared [1, T] mask must weight
        # the denominator per batch row too, or the mean is scaled by B.
        mask = jnp.broadcast_to(mask.astype(nll.dtype), nll.shape)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
