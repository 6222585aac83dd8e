"""Flash attention for TPU (pallas) with a portable reference path.

The reference framework has no attention kernels at all (it delegates
compute to torch); this is net-new capability required by the TPU
north-star (BASELINE.md long-context targets). Design follows the
standard blockwise-softmax scheme: iterate kv blocks innermost,
carrying a running (max, sum, acc) triple in VMEM so the full [Tq, Tk]
score matrix never materializes in HBM.

Forward and backward are pallas kernels on a TPU backend (MXU matmuls
in f32 accumulation; the backward recomputes probabilities from the
saved log-sum-exp). On any other backend `flash_attention` is
`attention_reference`; which one ran is visible in the lowered program
(`tpu_custom_call`), and chip_smoke.py asserts it. Where the ambient mesh
splits the sequence it is the ring of `ring_attention.py` over the same
blocks (`_block_fwd`, `_block_bwd`).
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..parallel.mesh import ambient_axes, ambient_spec, logical_axis_shards

NEG_INF = -1e30


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Pallas interpreter mode: lets the TPU kernels (incl. the causal
    block-skip control flow) run bit-accurately on CPU for tests.
    Refused on a TPU backend, where it would quietly stand in for the
    compiled kernels."""
    on = os.environ.get("RAY_TPU_PALLAS_INTERPRET") == "1"
    if on and _on_tpu():
        raise RuntimeError(
            "RAY_TPU_PALLAS_INTERPRET=1 on a TPU backend: interpret mode "
            "is for the CPU tests; unset it to run the compiled kernels"
        )
    return on


def attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Plain XLA attention; also the numerics oracle for kernel tests.

    Shapes: q [B, H, Tq, D]; k [B, Hkv, Tk, D]; v [B, Hkv, Tk, Dv] with
    H % Hkv == 0 (GQA). Dv may differ from D (latent attention: q/k heads
    of 192, v heads of 128); the default scale is D ** -0.5.
    """
    b, h, tq, d = q.shape
    hkv = k.shape[1]
    if h != hkv:
        k = jnp.repeat(k, h // hkv, axis=1)
        v = jnp.repeat(v, h // hkv, axis=1)
    scale = sm_scale if sm_scale is not None else 1.0 / d**0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if causal:
        tk = k.shape[2]
        qpos = jnp.arange(tq)[:, None] + (tk - tq)  # align ends (kv cache)
        kpos = jnp.arange(tk)[None, :]
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


# ----------------------------------------------------------------- pallas fwd


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, sm_scale: float, causal: bool, block_q: int, block_k: int,
                seq_k: int, seq_q: int):
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[0]  # [block_q, d]
        k = k_ref[0]  # [block_k, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * sm_scale

        kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < seq_k  # padded keys
        if causal:
            # Ends aligned (kv-cache semantics, matching
            # attention_reference): query row i attends keys up to
            # i + (seq_k - seq_q).
            qpos = iq * block_q + (seq_k - seq_q) + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0
            )
            mask = jnp.logical_and(mask, qpos >= kpos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_scr[:] = l_scr[:] * correction + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * correction + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = m_new

    if causal:
        # Blocks entirely above the diagonal are fully masked: skip
        # their MXU work (a skipped block is exactly a p=0 update —
        # m/l/acc unchanged). Halves attention compute at long T.
        pl.when(
            (iq + 1) * block_q + (seq_k - seq_q) > ik * block_k
        )(_compute)
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l_safe)


def _flash_fwd_pallas(q, k, v, *, causal, sm_scale, block_q, block_k):
    bh, tq, d = q.shape
    tk, d_v = k.shape[1], v.shape[2]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    tq_p = (tq + block_q - 1) // block_q * block_q
    tk_p = (tk + block_k - 1) // block_k * block_k
    if tq_p != tq:
        q = jnp.pad(q, ((0, 0), (0, tq_p - tq), (0, 0)))
    if tk_p != tk:
        k = jnp.pad(k, ((0, 0), (0, tk_p - tk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, tk_p - tk), (0, 0)))
    grid = (bh, tq_p // block_q, tk_p // block_k)
    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        seq_k=tk,
        seq_q=tq,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        interpret=_interpret(),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d_v), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d_v), lambda b, i, j: (b, i, 0)),
            # lse kept 3-D (bh, tq, 1) so the trailing dims satisfy TPU
            # tiling (block_q % 8, last dim == full dim).
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq_p, d_v), q.dtype),
            jax.ShapeDtypeStruct((bh, tq_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d_v), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * bh * tq_p * tk_p * (d + d_v),
            bytes_accessed=(q.size + k.size + v.size + bh * tq_p * d_v) * 2,
            transcendentals=bh * tq_p * tk_p,
        ),
    )(q, k, v)
    return o[:, :tq], lse[:, :tq, 0]


# ----------------------------------------------------------------- pallas bwd
# FlashAttention-2 style backward: probabilities recomputed per block
# from the saved log-sum-exp, two kernels so each output accumulates in
# VMEM over its contraction dimension (dk/dv over q blocks, dq over kv
# blocks) and the [Tq, Tk] score matrix never hits HBM.


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, sm_scale: float, causal: bool, block_q: int,
                    block_k: int, seq_k: int, seq_q: int):
    ik, jq = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(jq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute():
        q = q_ref[0]  # [block_q, d]
        k = k_ref[0]  # [block_k, d]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]  # [block_q, 1]
        delta = delta_ref[0]  # [block_q, 1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < seq_k
        if causal:
            qpos = jq * block_q + (seq_k - seq_q) + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0
            )
            mask = jnp.logical_and(mask, qpos >= kpos)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)  # [block_q, block_k]

        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * sm_scale
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # q blocks entirely above this k block's diagonal contribute
        # p=0 — skip their MXU work.
        pl.when(
            (jq + 1) * block_q + (seq_k - seq_q) > ik * block_k
        )(_compute)
    else:
        _compute()

    @pl.when(jq == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr,
                   *, sm_scale: float, causal: bool, block_q: int,
                   block_k: int, seq_k: int, seq_q: int):
    iq, jk = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(jk == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        kpos = jk * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < seq_k
        if causal:
            qpos = iq * block_q + (seq_k - seq_q) + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0
            )
            mask = jnp.logical_and(mask, qpos >= kpos)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * sm_scale
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(
            (iq + 1) * block_q + (seq_k - seq_q) > jk * block_k
        )(_compute)
    else:
        _compute()

    @pl.when(jk == nk - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, do, *, causal, sm_scale,
                      block_q, block_k):
    bh, tq, d = q.shape
    tk, d_v = k.shape[1], v.shape[2]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    tq_p = (tq + block_q - 1) // block_q * block_q
    tk_p = (tk + block_k - 1) // block_k * block_k
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )  # [bh, tq]
    if tq_p != tq:
        pad = ((0, 0), (0, tq_p - tq), (0, 0))
        q = jnp.pad(q, pad)
        do = jnp.pad(do, pad)
        lse = jnp.pad(lse, ((0, 0), (0, tq_p - tq)))
        delta = jnp.pad(delta, ((0, 0), (0, tq_p - tq)))
    if tk_p != tk:
        pad = ((0, 0), (0, tk_p - tk), (0, 0))
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    lse3 = lse[..., None]
    delta3 = delta[..., None]

    # q and k (and their gradients) have d lanes; v, do and dv have d_v.
    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, j, 0))
    do_spec = pl.BlockSpec((1, block_q, d_v), lambda b, i, j: (b, j, 0))
    kv_spec_i = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0))
    v_spec_i = pl.BlockSpec((1, block_k, d_v), lambda b, i, j: (b, i, 0))
    row_spec = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, seq_k=tk, seq_q=tq,
        ),
        interpret=_interpret(),
        grid=(bh, tk_p // block_k, tq_p // block_q),
        in_specs=[q_spec, kv_spec_i, v_spec_i, do_spec, row_spec, row_spec],
        out_specs=[kv_spec_i, v_spec_i],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tk_p, d), k.dtype),
            jax.ShapeDtypeStruct((bh, tk_p, d_v), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d_v), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=5 * bh * tq_p * tk_p * (d + d_v) // 2,
            bytes_accessed=(q.size + k.size + v.size + do.size) * 2,
            transcendentals=bh * tq_p * tk_p,
        ),
    )(q, k, v, do, lse3, delta3)

    q_spec2 = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    do_spec2 = pl.BlockSpec((1, block_q, d_v), lambda b, i, j: (b, i, 0))
    kv_spec_j = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0))
    v_spec_j = pl.BlockSpec((1, block_k, d_v), lambda b, i, j: (b, j, 0))
    row_spec2 = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, seq_k=tk, seq_q=tq,
        ),
        interpret=_interpret(),
        grid=(bh, tq_p // block_q, tk_p // block_k),
        in_specs=[q_spec2, kv_spec_j, v_spec_j, do_spec2, row_spec2, row_spec2],
        out_specs=q_spec2,
        out_shape=jax.ShapeDtypeStruct((bh, tq_p, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=5 * bh * tq_p * tk_p * (d + d_v) // 2,
            bytes_accessed=(q.size + k.size + v.size + do.size) * 2,
            transcendentals=bh * tq_p * tk_p,
        ),
    )(q, k, v, do, lse3, delta3)
    return dq[:, :tq], dk[:, :tk], dv[:, :tk]


# ------------------------------------------------- one block, kernels or XLA


def _kernels_fit(tq: int, tk: int, d: int, d_v: int) -> bool:
    """Where the Pallas kernels run: on the TPU, or under the interpreter,
    at shapes their tiling takes (>= 8 x 128 blocks). Below them (unit
    tests, short prompts, a short shard of a ring) the XLA mathematics."""
    return (
        (_on_tpu() or _interpret())
        and tq >= 128 and tk >= 128 and d % 8 == 0 and d_v % 8 == 0
    )


def _scores(q, k, causal: bool, scale: float):
    """Scaled scores [bh, tq, tk] in float32, the causal mask with the
    ends aligned like the kernels' and attention_reference's (the plain
    lower triangle for a ring's square block)."""
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
    ) * scale
    if causal:
        tq, tk = s.shape[-2:]
        qpos = jnp.arange(tq)[:, None] + (tk - tq)
        s = jnp.where(qpos >= jnp.arange(tk)[None, :], s, NEG_INF)
    return s


def _block_fwd(q, k, v, causal, scale, block_q, block_k):
    """One block of attention on [bh, t, d] operands -> (o, lse [bh, tq])."""
    if _kernels_fit(q.shape[1], k.shape[1], q.shape[2], v.shape[2]):
        return _flash_fwd_pallas(
            q, k, v, causal=causal, sm_scale=scale,
            block_q=block_q, block_k=block_k,
        )
    s = _scores(q, k, causal, scale)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = jax.lax.dot_general(
        (p / l_safe), v.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)
    return o, (m + jnp.log(l_safe))[..., 0]


def _block_bwd(q, k, v, o, lse, do, causal, scale, block_q, block_k):
    """(dq, dk, dv) of one block given the (o, lse) of the whole row, which
    for a ring is the merged one: probabilities are recomputed from it,
    p = exp(s - lse). In XLA the memory high-water is the [tq, tk] block
    per batch*head slice."""
    if _kernels_fit(q.shape[1], k.shape[1], q.shape[2], v.shape[2]):
        return _flash_bwd_pallas(
            q, k, v, o, lse, do, causal=causal, sm_scale=scale,
            block_q=block_q, block_k=block_k,
        )
    p = jnp.exp(_scores(q, k, causal, scale) - lse[..., :, None])
    do_f = do.astype(jnp.float32)
    dv = jax.lax.dot_general(
        p, do_f, (((1,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )
    delta = jnp.sum(do_f * o.astype(jnp.float32), axis=-1, keepdims=True)
    dp = jax.lax.dot_general(
        do_f, v.astype(jnp.float32), (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta) * scale
    dq = jax.lax.dot_general(
        ds, k.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    dk = jax.lax.dot_general(
        ds, q.astype(jnp.float32), (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ------------------------------------------------------------------ custom vjp


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, sm_scale, block_q, block_k):
    return _block_fwd(q, k, v, causal, sm_scale, block_q, block_k)[0]


def _flash_fwd_rule(q, k, v, causal, sm_scale, block_q, block_k):
    o, lse = _block_fwd(q, k, v, causal, sm_scale, block_q, block_k)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, sm_scale, block_q, block_k, res, do):
    return _block_bwd(*res, do, causal, sm_scale, block_q, block_k)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    # 1024x1024 measured fastest across d=64/128, T=2048..16384 on v5e
    # (22-27% over 512x512): fewer grid steps amortize the per-block
    # softmax bookkeeping, and VMEM still holds q/k/v/acc comfortably.
    block_q: int = 1024,
    block_k: int = 1024,
) -> jax.Array:
    """Blockwise (flash) attention, the one entry point of the mixers.

    q [B, H, Tq, D]; k [B, Hkv, Tk, D]; v [B, Hkv, Tk, Dv], GQA via
    H % Hkv == 0. Dv may differ from D; ``sm_scale`` is the caller's,
    D ** -0.5 where none is given. Which road it takes follows from what
    it can observe: a ring over the ambient mesh (``jax.set_mesh``) where
    that splits the sequence, else the Pallas kernels where they fit
    (``_kernels_fit``), else the XLA reference.
    """
    b, h, tq, d = q.shape
    hkv, tk, d_v = k.shape[1], k.shape[2], v.shape[-1]
    if causal and tq > tk:
        # End-aligned (kv-cache) causal semantics put the first
        # tq - tk query rows before every key; their softmax is over an
        # empty set. A kv cache always satisfies tk >= tq.
        raise ValueError(
            f"causal attention requires Tq <= Tk (got Tq={tq}, Tk={tk}): "
            "query rows are aligned to the END of the key sequence"
        )
    if h != hkv:
        k = jnp.repeat(k, h // hkv, axis=1)
        v = jnp.repeat(v, h // hkv, axis=1)
    scale = sm_scale if sm_scale is not None else 1.0 / d**0.5
    if logical_axis_shards("seq") > 1:
        from .ring_attention import ring_attention  # it imports this module

        axes = ambient_axes("seq")
        if d_v != d:
            raise ValueError(
                f"the ring's blocks are [.., {d}] throughout: v {v.shape} "
                f"has another head dim than q {q.shape}, and mesh axis "
                f"{axes} splits the sequence"
            )
        spec = ambient_spec(("batch", "heads", "seq", None))
        # Blocks of 512 where one device's kernels run 1,024: ROADMAP A13.
        return jax.shard_map(
            lambda *qkv: ring_attention(*qkv, axes, causal, scale, 512),
            in_specs=(spec, spec, spec), out_specs=spec, check_vma=False,
        )(q, k, v)
    if not _kernels_fit(tq, tk, d, d_v):
        return attention_reference(q, k, v, causal=causal, sm_scale=scale)
    o = _flash(
        q.reshape(b * h, tq, d), k.reshape(b * h, tk, d),
        v.reshape(b * h, tk, d_v), causal, scale, block_q, block_k,
    )
    return o.reshape(b, h, tq, d_v)
