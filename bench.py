"""Headline benchmark: train-step throughput / MFU on one TPU chip.

Measures the end-to-end jitted training step (fwd + bwd + adamw update,
remat on, bf16 compute, donated buffers) of the Llama-1B config at
batch 2 x seq 2048 and reports tokens/sec/chip and model FLOPs
utilization against the chip's bf16 peak; also runs a Mixtral-style
sparse-MoE config (top-2 of 8 experts) and reports its MFU over
*active* FLOPs.

Batch is 2 because the 1B model's bf16 params + adamw moments + grads
leave room for exactly two 2048-token activations sets on a 16 GiB
chip even with buffer donation and full remat (b4 fits but is slower;
b1 under-utilizes the MXU).

BASELINE.md north star: Llama finetune >=40% MFU. vs_baseline is
MFU / 0.40 (>1.0 beats the target).

One process that touches JAX once. It runs on a TPU backend or not at
all: no chip, an unknown device kind, or a failure in any phase is a
non-zero exit with no result line. On success it prints exactly one
JSON line that names the device; the MoE numbers ride in "extra".
"""
from __future__ import annotations

import json
import os
import sys
import time

# bf16 peak FLOP/s by jax device_kind. Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16 per chip). A kind that is
# not listed is an error, never a default.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def flops_per_token(n_params: float, cfg, seq_len: int) -> float:
    """6N matmul flops/token + attention score flops
    (12 * L * T * hidden per token, fwd+bwd)."""
    return 6.0 * n_params + 12.0 * cfg.num_layers * seq_len * cfg.hidden_size


def bench_model(model, cfg, n_params, batch, seq, steps, peak_flops,
                chunked_loss: bool = False):
    import jax
    import numpy as np
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.llama import causal_lm_loss, chunked_causal_lm_loss
    from ray_tpu.train import make_train_step

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    targets = jnp.roll(ids, -1, axis=1)

    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids[:1, :8])
    tx = optax.adamw(3e-4, b1=0.9, b2=0.95, mu_dtype=jnp.bfloat16)
    opt_state = tx.init(params)

    def loss_fn(p, ids, targets):
        if chunked_loss:
            # Long context: the [B, T, V] logits tensor would be the
            # biggest activation (4.2 GB f32 at 32k/32k); chunk the
            # head + softmax-xent over the sequence.
            return chunked_causal_lm_loss(model, p, ids, targets)
        return causal_lm_loss(model.apply(p, ids), targets)

    train_step = make_train_step(loss_fn, tx)

    # Warm up / compile; the scalar fetch waits for the device.
    params, opt_state, loss = train_step(params, opt_state, ids, targets)
    _ = float(loss)

    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = train_step(params, opt_state, ids, targets)
    final_loss = float(loss)
    dt = time.perf_counter() - t0

    tokens = batch * seq * steps
    tok_per_s = tokens / dt
    mfu = tok_per_s * flops_per_token(n_params, cfg, seq) / peak_flops
    return tok_per_s, mfu, final_loss


def main() -> int:
    batch = int(os.environ.get("BENCH_BATCH", "2"))
    seq = int(os.environ.get("BENCH_SEQ", "2048"))
    model_name = os.environ.get("BENCH_MODEL", "llama-1b")
    steps = int(os.environ.get("BENCH_STEPS", "10"))
    run_moe = os.environ.get("BENCH_MOE", "1") != "0"

    from ray_tpu._private.accelerators.tpu import place_compile_cache

    place_compile_cache(os.environ)  # before jax reads its flags

    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu":
        print(
            f"bench.py: no TPU chip: JAX's default backend is {platform!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); the "
            "benchmark measures the chip and does not fall back",
            file=sys.stderr,
        )
        return 1
    if kind not in PEAK_BF16_FLOPS:
        print(
            f"bench.py: no peak FLOP/s on record for device kind {kind!r} "
            f"(known: {sorted(PEAK_BF16_FLOPS)})",
            file=sys.stderr,
        )
        return 1
    peak_flops = PEAK_BF16_FLOPS[kind]

    from dataclasses import replace

    from ray_tpu.models import CONFIGS
    from ray_tpu.models.llama import LlamaForCausalLM

    cfg = replace(CONFIGS[model_name], param_dtype=jnp.bfloat16)
    tok_per_s, mfu, final_loss = bench_model(
        LlamaForCausalLM(cfg), cfg, cfg.num_params(), batch, seq, steps,
        peak_flops,
    )

    extra = {}
    result = {
        "metric": f"{model_name} train step tokens/s/chip (b{batch} s{seq}, "
        f"loss {final_loss:.3f}, MFU {mfu:.3f})",
        "value": round(tok_per_s, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.40, 4),
        "device": {
            "platform": platform, "kind": kind, "count": len(devices),
        },
        "extra": extra,
    }
    if os.environ.get("BENCH_LONGCTX", "1") != "0":
        # Long-context sweep: same model at batch 1, 4x/8x/16x the
        # sequence — the regime the pallas flash fwd+bwd kernels exist
        # for (the score matrix at s8192 would be 256 MiB/head/layer in
        # f32 if materialized; blockwise fwd+bwd never leaves VMEM).
        # A point that does not fit the chip fails the run: list only
        # sequences that fit in BENCH_LONGCTX_SEQS.
        lc_seqs = [
            int(s)
            for s in os.environ.get(
                "BENCH_LONGCTX_SEQS", "8192,16384,32768"
            ).split(",")
        ]
        points = []
        for lc_seq in lc_seqs:
            lc_tok, lc_mfu, lc_loss = bench_model(
                LlamaForCausalLM(cfg), cfg, cfg.num_params(), 1, lc_seq,
                max(5, steps // 2), peak_flops, chunked_loss=True,
            )
            points.append(
                {
                    "seq": lc_seq,
                    "tokens_per_s": round(lc_tok, 1),
                    "mfu": round(lc_mfu, 3),
                    "loss": round(lc_loss, 3),
                }
            )
        extra["longctx"] = points
    if run_moe:
        _bench_moe(batch, seq, steps, peak_flops, extra)

    print(json.dumps(result), flush=True)
    return 0


def _bench_moe(batch, seq, steps, peak_flops, extra) -> None:
    import jax.numpy as jnp

    from dataclasses import replace

    from ray_tpu.models.mixtral import CONFIGS as MOE_CONFIGS
    from ray_tpu.models.mixtral import MixtralForCausalLM, resolve_moe_dispatch

    moe_cfg = replace(MOE_CONFIGS["mixtral-small"], param_dtype=jnp.bfloat16)
    # Measured backend selection (capacity vs pallas gmm) on the live
    # chip; the probe IS the heuristic, and a backend that fails raises.
    moe_dispatch = resolve_moe_dispatch(moe_cfg, tokens=batch * seq)
    moe_cfg = replace(moe_cfg, moe_dispatch=moe_dispatch)
    # MFU over *active* FLOPs: a top-k sparse model only computes k of
    # E experts per token.
    moe_tok, moe_mfu, moe_loss = bench_model(
        MixtralForCausalLM(moe_cfg),
        moe_cfg,
        moe_cfg.active_params_per_token(),
        batch,
        seq,
        steps,
        peak_flops,
    )
    extra.update(
        moe_model="mixtral-small (8 experts, top-2)",
        moe_dispatch=moe_dispatch,
        moe_tokens_per_s=round(moe_tok, 1),
        moe_mfu_active=round(moe_mfu, 3),
        moe_loss=round(moe_loss, 3),
    )


if __name__ == "__main__":
    sys.exit(main())
