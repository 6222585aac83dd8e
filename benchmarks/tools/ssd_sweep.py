"""The Mamba-2 scan kernels of ``ray_tpu/ops/kda.py`` (``chunk_ssd``) alone on
the chip: how far they lie from the token-by-token recurrence, and what a
forward and a backward call take at the Granite cell's shape under each design
that was weighed. Not a run of the benchmark and no metric; PERF.md §6 (PR 58)
quotes its lines.

    python3 benchmarks/tools/ssd_sweep.py [--rehearse]

The designs differ in three module constants and one kernel line, set here by
assignment as a test would (the program has no option for them): the heads a
grid step takes (``_SSD_GROUP``), the rows of a chunk (``SSD_CHUNK``), and
whether C B^T is made once a chunk or at every grid step (``every_step``: the
forward and backward kernels wrapped so that each step makes it again, which
is what a grid with the heads outermost would do)."""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def recurrence(u, dt, a_log, Bm, Cm, D):
    """The reference's token-by-token scan, float32."""
    import jax
    import jax.numpy as jnp

    A = -jnp.exp(a_log)

    def one(u, dt, Bm, Cm):
        def token(S, x):
            u, dt, b, c = x
            S = jnp.exp(dt * A)[:, None, None] * S + (dt[:, None] * u)[:, :, None] * b
            return S, jnp.einsum("hpn,n->hp", S, c) + D[:, None] * u

        zero = jnp.zeros((u.shape[1], u.shape[2], Bm.shape[1]), jnp.float32)
        return jax.lax.scan(token, zero, (u, dt, Bm, Cm))[1]

    return jax.vmap(one)(u, dt, Bm, Cm)


def operands(seed, batch, t, heads, p, n, dtype):
    import jax
    import jax.numpy as jnp
    import numpy as np

    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    log = jax.random.uniform(keys[1], (batch, t, heads), minval=np.log(1e-3), maxval=np.log(0.1))
    return (jax.random.normal(keys[0], (batch, t, heads, p)).astype(dtype), jnp.exp(log),
            jnp.log(jax.random.uniform(keys[2], (heads,), minval=1.0, maxval=16.0)),
            jax.random.normal(keys[3], (batch, t, n)).astype(dtype),
            jax.random.normal(keys[4], (batch, t, n)).astype(dtype),
            jnp.ones((heads,), jnp.float32))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    from ray_tpu.ops import kda

    t_check, t_timed, heads, p, n = (512, 512, 8, 16, 16) if args.rehearse else (2048, 8192, 64, 64, 128)
    device = jax.devices()[0].device_kind
    out_dir = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "chiprun_out", "readings")
    os.makedirs(out_dir, exist_ok=True)

    def say(line):
        text = json.dumps({"device": device, **line})
        print(text, flush=True)
        with open(os.path.join(out_dir, "ssd_sweep.jsonl"), "a") as f:
            f.write(text + "\n")

    def loss(fn, w):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w)

    # How far the kernels lie from the recurrence, in the matmuls' bfloat16
    # and in float32 (whose products the MXU makes in passes of bfloat16).
    for dtype in (jnp.bfloat16, jnp.float32):
        ops = operands(1, 1, t_check, heads, p, n, dtype)
        w = jax.random.normal(jax.random.PRNGKey(2), ops[0].shape)
        f32 = tuple(x.astype(jnp.float32) for x in ops)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(jax.value_and_grad(loss(recurrence, w), argnums=range(6)))(*f32)
        got = jax.jit(jax.value_and_grad(loss(kda.chunk_ssd, w), argnums=range(6)))(*ops)
        rel = lambda a, b: float(jnp.linalg.norm(a.astype(jnp.float32) - b) / jnp.linalg.norm(b))  # noqa: E731
        say({"check": jnp.dtype(dtype).name, "road": kda.ssd_road(p, n),
             "value": rel(got[0], want[0]),
             **{name: rel(a, b) for name, a, b in zip(
                 ("du", "ddt", "da_log", "dB", "dC", "dD"), got[1], want[1])}})

    plain = (kda._ssd_fwd_kernel, kda._ssd_bwd_kernel)

    def every_step(kernel, scratch):
        def wrapped(*refs):
            refs[scratch][...] = kda._shared_scores(refs[4], refs[5])
            return kernel(*refs)

        wrapped.__name__ = kernel.__name__
        return wrapped

    designs = [(8, 256, False), (8, 256, True), (16, 256, False), (4, 256, False),
               (2, 256, False), (8, 128, False), (16, 128, False)]
    if args.rehearse:
        designs = designs[:2]
    ops = operands(3, 1, t_timed, heads, p, n, jnp.bfloat16)
    for group, chunk, again in designs:
        kda._SSD_GROUP, kda.SSD_CHUNK = group, chunk
        kda._ssd_fwd_kernel, kda._ssd_bwd_kernel = (
            (every_step(plain[0], -2), every_step(plain[1], -3)) if again else plain)
        line = {"group": group, "chunk": chunk, "scores_every_step": again}
        try:
            forward = jax.jit(lambda *a: jax.vjp(kda.chunk_ssd, *a)[0])
            both = jax.jit(lambda *a: (lambda y, vjp: vjp(y))(*jax.vjp(kda.chunk_ssd, *a)))
            for name, fn in (("forward_ms", forward), ("forward_backward_ms", both)):
                jax.block_until_ready(fn(*ops))
                start = time.perf_counter()
                for _ in range(5):
                    out = fn(*ops)
                jax.block_until_ready(out)
                line[name] = 1e3 * (time.perf_counter() - start) / 5
        except Exception as e:  # noqa: BLE001 - a design the compiler refuses is a line
            line["error"] = repr(e)[:300]
        say(line)


if __name__ == "__main__":
    main()
