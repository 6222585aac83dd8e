"""Mixtral-style sparse-MoE causal LM with expert parallelism.

Net-new vs the reference (SURVEY.md §2.3: EP/MoE "absent — integration
delegated"; here it's first-class). TPU-first design: experts live in
one stacked tensor with logical axis "expert" → the `expert` mesh axis,
and token dispatch/combine are dense einsums against a capacity-bounded
one-hot dispatch mask (GShard-style). Under GSPMD, batch-sharded
activations meeting expert-sharded weights compile into the all-to-all
over ICI automatically — no hand-written routing collectives, static
shapes throughout (XLA-friendly: no ragged tensors).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..parallel import with_logical_constraint
from ..util import tracing
from .llama import CONFIGS as LLAMA_CONFIGS
from .llama import Attention, LlamaConfig, RMSNorm, causal_lm_loss  # noqa: F401


@dataclass(frozen=True)
class MixtralConfig(LlamaConfig):
    num_experts: int = 8
    num_experts_per_tok: int = 2  # top-k routing
    # Sparse models are small enough to save matmul outputs in remat:
    # full recompute would cap MFU at 0.75 of peak for no memory win.
    remat_policy: str = "dots"
    # Per-expert token capacity = capacity_factor * T * k / E
    # (capacity dispatch only).
    capacity_factor: float = 1.25
    router_aux_loss_coef: float = 0.02
    # "auto" (default): measured selection between the backends below,
    # cached per process and shape — resolve_moe_dispatch().
    # "capacity": capacity-bounded static buffers with an [E, B, C, D]
    # expert axis — mesh-shards for expert parallelism (dispatch rides
    # an all-to-all over ICI) and lowers to plain batched matmuls, at
    # the cost of capacity_factor padding FLOPs (25% at 1.25).
    # "gmm": tile-aligned group-sorted dispatch through the pallas
    # grouped matmul (ops/gmm.py) — <=E*block_m rows of padding (~6%)
    # and zero drops; single-device per expert shard (the EP path
    # stays capacity). "ragged": exact-group lax.ragged_dot — the
    # semantic oracle; measured slower than both on current backends.
    moe_dispatch: str = "auto"

    def num_params(self) -> int:
        """Llama count minus its dense MLP, plus E stacked experts and
        the router (LlamaConfig.num_params would undercount the FFN by
        ~E x)."""
        h, i, l = self.hidden_size, self.intermediate_size, self.num_layers
        dense_mlp = 3 * h * i
        moe_mlp = self.num_experts * 3 * h * i + h * self.num_experts
        return super().num_params() + l * (moe_mlp - dense_mlp)

    def active_params_per_token(self) -> int:
        """FLOPs-relevant parameter count: only top-k experts run per
        token (what an MFU estimate should use)."""
        h, i, l = self.hidden_size, self.intermediate_size, self.num_layers
        dense_mlp = 3 * h * i
        active_mlp = self.num_experts_per_tok * 3 * h * i + h * self.num_experts
        return super().num_params() + l * (active_mlp - dense_mlp)


# moe_dispatch="auto" resolutions, keyed by _shape_key: warmed by
# resolve_moe_dispatch() (outside jit), read at trace time.
_RESOLVED: dict = {}


def _shape_key(cfg: "MixtralConfig") -> str:
    return (
        f"E{cfg.num_experts}-K{cfg.num_experts_per_tok}-"
        f"D{cfg.hidden_size}-F{cfg.intermediate_size}"
    )


def resolve_moe_dispatch(
    cfg: "MixtralConfig",
    tokens: int = 4096,
    mesh=None,
    steps: int = 10,
) -> str:
    """Measure-and-pick the MoE dispatch backend for this device.

    The judge of record is a timed probe of the dispatch+FFN core
    (fwd+bwd) at this config's shapes on the live backend — not a
    config flag: ragged_dot vs capacity vs the pallas gmm rank
    differently across TPU generations and compiler versions.
    Resolutions are kept per process, keyed by shape. A backend that
    fails to compile or run raises: a verdict reached by dropping the
    failed candidate would hide a broken kernel behind the other path.
    Under an expert-sharded mesh the capacity path is returned without
    probing (its [E, B, C, D] layout is what rides the EP all-to-all;
    the gmm layout is per-shard).
    """
    import os
    import time

    if cfg.moe_dispatch != "auto":
        return cfg.moe_dispatch
    env = os.environ.get("RAY_TPU_MOE_DISPATCH")
    if env:
        _RESOLVED[_shape_key(cfg)] = env
        return env
    if mesh is not None and mesh.shape.get("expert", 1) > 1:
        _RESOLVED[_shape_key(cfg)] = "capacity"
        return "capacity"
    skey = _shape_key(cfg)
    if skey in _RESOLVED:
        return _RESOLVED[skey]

    import numpy as np
    from dataclasses import replace as _replace

    probe_cfg = _replace(
        cfg,
        vocab_size=256,
        num_layers=1,
        num_heads=4,
        num_kv_heads=4,
        remat=False,
    )
    rng = np.random.RandomState(0)
    x = jnp.asarray(
        rng.randn(1, tokens, cfg.hidden_size), probe_cfg.dtype
    )

    def _time_backend(name: str) -> float:
        layer = MoELayer(_replace(probe_cfg, moe_dispatch=name))
        params = jax.jit(layer.init)(jax.random.PRNGKey(0), x[:, :256])

        @jax.jit
        def step(p, x):
            def loss(p):
                return (layer.apply(p, x) ** 2).sum()

            return jax.grad(loss)(p)

        g = step(params, x)
        jax.tree_util.tree_map(lambda a: a.block_until_ready(), g)
        t0 = time.perf_counter()
        for _ in range(steps):
            g = step(params, x)
        jax.tree_util.tree_map(lambda a: a.block_until_ready(), g)
        return time.perf_counter() - t0

    times = {name: _time_backend(name) for name in ("capacity", "gmm")}
    winner = min(times, key=times.get)
    _RESOLVED[skey] = winner
    return winner


CONFIGS = {
    "mixtral-tiny": MixtralConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, num_experts=4, num_experts_per_tok=2,
        max_seq_len=256,
    ),
    "mixtral-small": MixtralConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=3584,
        num_layers=8, num_heads=16, num_kv_heads=8, num_experts=8,
        num_experts_per_tok=2, max_seq_len=4096,
    ),
}


class MoELayer(nn.Module):
    """Top-k router with two dispatch backends (cfg.moe_dispatch).

    "capacity" (default): gather/scatter into capacity-bounded static
    buffers with an explicit [E, B, C, D] expert axis — under GSPMD the
    expert dim mesh-shards and dispatch rides an all-to-all over ICI,
    and the expert FFN lowers to batched matmuls that fill the MXU.
    Still far cheaper than the GShard dense one-hot einsum, whose
    [B,T,E,C] mask costs O(B*T^2*D) MXU FLOPs at long T.

    "ragged" (opt-in): (token, k) pairs argsorted by expert feed
    `lax.ragged_dot` with exact group sizes — zero capacity padding and
    zero drops. Measured slower than capacity on current TPU backends
    (ragged_dot lowers to a masked loop), so it serves as the semantic
    oracle and the path for backends where it wins.

    Gradients flow through the gathers/ragged dots and gate weights."""

    cfg: MixtralConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dispatch = cfg.moe_dispatch
        if dispatch == "auto":
            # Trace-time: use the process cache warmed by
            # resolve_moe_dispatch() (bench/trainer call it before jit);
            # capacity is the safe fallback everywhere.
            dispatch = _RESOLVED.get(_shape_key(cfg), "capacity")
        if dispatch not in ("ragged", "capacity", "gmm"):
            raise ValueError(
                f"moe_dispatch must be 'auto', 'ragged', 'capacity' or "
                f"'gmm', got {cfg.moe_dispatch!r}"
            )
        B, T, D = x.shape
        E, K = cfg.num_experts, cfg.num_experts_per_tok
        C = max(1, int(cfg.capacity_factor * T * K / E))

        # The four scopes below are the layer's names in a profile
        # (util/tracing.py); every dispatch branch uses the same four.
        with tracing.scope(tracing.MOE_ROUTER):
            router = nn.Dense(
                E, use_bias=False, dtype=jnp.float32,
                param_dtype=cfg.param_dtype, name="router",
            )
            logits = router(x.astype(jnp.float32))  # [B, T, E] — fp32 routing
            probs = jax.nn.softmax(logits, axis=-1)

            # Top-k gates, renormalized over the chosen experts.
            gate_vals, gate_idx = jax.lax.top_k(probs, K)  # [B, T, K]
            gate_vals = gate_vals / jnp.maximum(
                gate_vals.sum(-1, keepdims=True), 1e-9
            )

            # Aux load-balance loss (Switch Transformer eq. 4): mean gate
            # fraction x mean dispatch fraction per expert.
            onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # [B,T,K,E]
            expert_mask = onehot.sum(2)  # [B, T, E] (0/1 per expert)
            frac_tokens = expert_mask.mean(axis=(0, 1))
            frac_probs = probs.mean(axis=(0, 1))
            aux = E * jnp.sum(frac_tokens * frac_probs)
            self.sow("intermediates", "router_aux_loss", aux)

        def pvar(name, shape):
            return self.param(
                name, nn.initializers.lecun_normal(), shape, cfg.param_dtype
            )

        w_gate = pvar("w_gate", (E, D, cfg.intermediate_size))
        w_up = pvar("w_up", (E, D, cfg.intermediate_size))
        w_down = pvar("w_down", (E, cfg.intermediate_size, D))

        if dispatch == "gmm":
            # Tile-aligned group-sorted dispatch through the pallas
            # grouped matmul: every block_m row-tile belongs to one
            # expert, so the FFN runs as dense MXU tiles with ~6%
            # padding instead of capacity's 25% — and zero drops.
            from ..ops.gmm import aligned_group_layout, gmm

            N = B * T * K
            with tracing.scope(tracing.MOE_DISPATCH):
                x2 = x.astype(cfg.dtype).reshape(B * T, D)
                e_flat = gate_idx.reshape(N)
                order, dst, tile_group, m_pad = aligned_group_layout(
                    e_flat, E, block_m=128
                )
                tok_of_pair = jnp.arange(N, dtype=jnp.int32) // K
                tok_sorted = tok_of_pair[order]
                # Row GATHER into the aligned layout (row scatters
                # serialize on TPU; gathers vectorize — same trick as the
                # capacity path). inv maps aligned slot -> sorted-pair
                # index, with padding slots reading a zero row.
                inv = (
                    jnp.full((m_pad,), N, jnp.int32)
                    .at[dst]
                    .set(jnp.arange(N, dtype=jnp.int32), unique_indices=True)
                )
                src_tok = jnp.concatenate(
                    [tok_sorted, jnp.full((1,), B * T, jnp.int32)]
                )[inv]
                x_pad = jnp.concatenate(
                    [x2, jnp.zeros((1, D), x2.dtype)], axis=0
                )
                lhs = x_pad[src_tok]  # [m_pad, D]
            with tracing.scope(tracing.MOE_EXPERTS):
                h = gmm(lhs, w_gate.astype(cfg.dtype), tile_group)
                u = gmm(lhs, w_up.astype(cfg.dtype), tile_group)
                act = nn.silu(h) * u
                eo = gmm(act, w_down.astype(cfg.dtype), tile_group)
            with tracing.scope(tracing.MOE_COMBINE):
                gates_sorted = gate_vals.astype(cfg.dtype).reshape(N)[order]
                pair_out = eo[dst] * gates_sorted[:, None]
                out2 = (
                    jnp.zeros((B * T, D), cfg.dtype)
                    .at[tok_sorted]
                    .add(pair_out)
                )
                out = out2.reshape(B, T, D)
                return with_logical_constraint(out, ("batch", "seq", "embed"))

        if dispatch == "ragged":
            # Exact-group dispatch: argsort the (token, k) pairs by
            # expert and run each group through its expert with
            # lax.ragged_dot — FLOPs are exactly the active tokens'.
            N = B * T * K
            with tracing.scope(tracing.MOE_DISPATCH):
                x2 = x.astype(cfg.dtype).reshape(B * T, D)
                e_flat = gate_idx.reshape(N)
                order = jnp.argsort(e_flat)
                tok_of_pair = jnp.arange(N, dtype=jnp.int32) // K
                tok_sorted = tok_of_pair[order]
                xs = x2[tok_sorted]  # [N, D] grouped by expert
                group_sizes = jnp.bincount(e_flat, length=E).astype(jnp.int32)
            with tracing.scope(tracing.MOE_EXPERTS):
                h = jax.lax.ragged_dot(xs, w_gate.astype(cfg.dtype), group_sizes)
                u = jax.lax.ragged_dot(xs, w_up.astype(cfg.dtype), group_sizes)
                act = nn.silu(h) * u
                eo = jax.lax.ragged_dot(
                    act, w_down.astype(cfg.dtype), group_sizes
                )
            with tracing.scope(tracing.MOE_COMBINE):
                gates_sorted = gate_vals.astype(cfg.dtype).reshape(N)[order]
                out2 = (
                    jnp.zeros((B * T, D), cfg.dtype)
                    .at[tok_sorted]
                    .add(eo * gates_sorted[:, None])
                )
                out = out2.reshape(B, T, D)
                return with_logical_constraint(out, ("batch", "seq", "embed"))

        NK = T * K

        def route_one(xrow, idx_row, pos_row):
            """One batch row: the first C arrivals per expert own its
            buffer slots; drops past capacity land in per-pair dump
            slots (kept unique so XLA needs no collision handling).

            TPU shape of the dispatch: scatter only the int32 slot->token
            inverse map (cheap scalar scatter), then fill the buffer with
            a row GATHER — row scatters serialize on TPU, row gathers
            vectorize. Pair order stays token-major, so combine is a
            reshape-sum, not a scatter."""
            e_flat = idx_row.reshape(NK)  # expert of each (token, k) pair
            pos = jnp.take_along_axis(
                pos_row, idx_row, axis=1
            ).reshape(NK).astype(jnp.int32)  # position within expert
            keep = pos < C
            slot = jnp.where(
                keep, e_flat * C + pos, E * C + jnp.arange(NK, dtype=jnp.int32)
            )
            tok_ids = jnp.arange(NK, dtype=jnp.int32) // K
            inv = (
                jnp.full((E * C + NK,), T, jnp.int32)
                .at[slot]
                .set(tok_ids, unique_indices=True)
            )
            x_pad = jnp.concatenate(
                [xrow, jnp.zeros((1, D), xrow.dtype)], axis=0
            )
            buf = x_pad[inv[: E * C]]  # [E*C, D] row gather
            return buf, jnp.minimum(slot, E * C)

        with tracing.scope(tracing.MOE_DISPATCH):
            # Arrival-order position of each token within its expert's
            # buffer: cumsum over T of the small [B,T,E] mask (E is tiny) —
            # no sort, no [B,T,E,C] one-hot.
            position = (
                jnp.cumsum(expert_mask, axis=1) - expert_mask
            )  # [B, T, E] tokens before me per expert
            buf, slot = jax.vmap(route_one)(
                x.astype(cfg.dtype), gate_idx, position.astype(jnp.float32)
            )
            # [B, E*C, D] -> [E, B, C, D]; under GSPMD the expert axis is
            # mesh-sharded (all-to-all over ICI).
            expert_in = buf.reshape(B, E, C, D).transpose(1, 0, 2, 3)
            expert_in = with_logical_constraint(
                expert_in, ("expert", "batch", None, "embed")
            )

        # Stacked expert FFN (SwiGLU like the dense path). E-major
        # weights (created above); parallel.mesh.spec_for_param shards
        # them P("expert", "fsdp"/"tensor", ...) by name.
        with tracing.scope(tracing.MOE_EXPERTS):
            h = jnp.einsum(
                "ebcd,edf->ebcf", expert_in, w_gate.astype(cfg.dtype)
            )
            u = jnp.einsum("ebcd,edf->ebcf", expert_in, w_up.astype(cfg.dtype))
            act = nn.silu(h) * u
            expert_out = jnp.einsum(
                "ebcf,efd->ebcd", act, w_down.astype(cfg.dtype)
            )

        def combine_one(eo_row, slot_row, gate_row):
            eo_row = jnp.concatenate(
                [eo_row, jnp.zeros((1, D), eo_row.dtype)], axis=0
            )
            pair_out = eo_row[slot_row] * gate_row[:, None]
            return pair_out.reshape(T, K, D).sum(1)

        # Combine back to token order, weighted by gates: gather each
        # pair's expert output (dropped pairs read the zero dump row),
        # scale, and reduce the K pairs of every token — pair order is
        # token-major, so the reduction is a reshape-sum, no scatter.
        with tracing.scope(tracing.MOE_COMBINE):
            expert_out = expert_out.transpose(1, 0, 2, 3).reshape(B, E * C, D)
            out = jax.vmap(combine_one)(
                expert_out, slot, gate_vals.astype(cfg.dtype).reshape(B, NK)
            )
            return with_logical_constraint(out, ("batch", "seq", "embed"))


class MoEDecoderLayer(nn.Module):
    cfg: MixtralConfig
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        h = x + Attention(cfg, mesh=self.mesh, name="attn")(
            RMSNorm(cfg.rms_eps, cfg.param_dtype, name="input_norm")(x),
            positions,
        )
        out = h + MoELayer(cfg, name="moe")(
            RMSNorm(cfg.rms_eps, cfg.param_dtype, name="post_attn_norm")(h)
        )
        return with_logical_constraint(out, ("batch", "seq", "embed"))


class MixtralForCausalLM(nn.Module):
    cfg: MixtralConfig
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, input_ids, positions=None):
        cfg = self.cfg
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(input_ids.shape[1])[None], input_ids.shape
            )
        emb = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="embed_tokens",
        )
        x = emb(input_ids)
        x = with_logical_constraint(x, ("batch", "seq", "embed"))
        from .llama import remat_policy

        layer_cls = MoEDecoderLayer
        if cfg.remat:
            layer_cls = nn.remat(
                MoEDecoderLayer, prevent_cse=False,
                policy=remat_policy(cfg),
            )
        for i in range(cfg.num_layers):
            x = layer_cls(cfg, mesh=self.mesh, name=f"layers_{i}")(x, positions)
        x = RMSNorm(cfg.rms_eps, cfg.param_dtype, name="final_norm")(x)
        logits = emb.attend(x.astype(cfg.param_dtype))
        return logits


def moe_lm_loss(model: MixtralForCausalLM, params, input_ids, targets,
                mask=None):
    """Causal LM loss + router aux loss (call instead of apply+loss so
    the sown aux terms are collected)."""
    logits, state = model.apply(
        params, input_ids, mutable=["intermediates"]
    )
    loss = causal_lm_loss(logits, targets, mask)
    aux_terms = jax.tree_util.tree_leaves(
        state.get("intermediates", {})
    )
    if aux_terms:
        loss = loss + model.cfg.router_aux_loss_coef * (
            sum(aux_terms) / len(aux_terms)
        )
    return loss
