"""Every distinct attention call of the benchmark's cells, each pass under each
mask, traces to the text it had (digests of ``jax.make_jaxpr`` with the
kernels on), and the flash kernels and the indexer are the only functions
named ``*_kernel``.
"""
import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

from flash_cases import _interpret_mode  # noqa: F401 - fixtures


# ------------------------------------- one tile update a pass (PR 56)
# The three masks are data and each pass's tile update is written once
# (``ops/attention.py`` ``_Mask``), a rearrangement at trace time: the kernels
# that reach Mosaic are read off ``jax.make_jaxpr`` with the kernels on, which
# prints a ``pallas_call`` whole (kernel jaxpr, grid, block shapes, cost
# estimate, compiler parameters) and no source location, and held to digests.

# Every distinct attention call of the benchmark's fifteen cells, bfloat16:
# (batch, heads, K/V heads, tokens, q/k head dim, v head dim, the mask's
# keywords), from benchmarks/configs/*.json and benchmarks/traffic/*.json.
# The Mixtral cell's calls are the ring's blocks (seq=2: 2,048 rows a device
# at blocks of 512), the diagonal one and a rotated one.
ATTENTION_CALLS = {
    "short2k": (4, 32, 8, 2048, 128, 128, {}),
    "long16k": (1, 32, 8, 16384, 128, 128, {}),
    "sft512": (2, 32, 8, 512, 128, 128, {}),
    "dropless-4k": (2, 16, 16, 4096, 128, 128, {}),
    "kimi-mla-16k": (1, 32, 32, 16384, 192, 128, {}),
    "sarvam-mla-4k": (1, 64, 64, 4096, 192, 128, {}),
    "xing4-mla-4k": (1, 32, 32, 4096, 192, 128, {}),
    "laguna-full-16k": (1, 48, 8, 16384, 128, 128, {}),
    "laguna-swa-16k": (1, 64, 8, 16384, 128, 128, {"window": 512}),
    "solar-gqa-4k": (1, 64, 8, 4096, 128, 128, {}),
    "olmo-hybrid-8k": (1, 30, 30, 8192, 128, 128, {}),
    "minicpm-sparse-16k": (1, 32, 2, 16384, 128, 128, {"block_size": 64}),
    "granite-gqa-8k": (1, 32, 8, 8192, 64, 64, {"sm_scale": 0.015625}),
    "dots3-select-8k": (1, 32, 32, 8192, 192, 128, {"keys": True}),
    "dots3-swa-8k": (1, 16, 16, 8192, 256, 128, {"window": 513, "sm_scale": 0.0625}),
    "ring-diagonal": (1, 32, 32, 2048, 128, 128, {"ring": True}),
    "ring-rotated": (1, 32, 32, 2048, 128, 128, {"ring": False}),
}
PARTS = ("forward", "dkv", "dq", "around")


def _pallas_calls(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _pallas_calls(inner, found)
    return found


def _pass_text(eqn) -> str:
    """A ``pallas_call`` as Mosaic gets it: the kernel's name, the equation
    as printed, and the index maps, which the print leaves out."""
    maps = [str(m.index_map_jaxpr) for m in eqn.params["grid_mapping"].block_mappings]
    return "\n".join([eqn.params["jaxpr"].debug_info.func_name, str(eqn), *maps])


def call_texts(name: str) -> dict:
    """{part: text} of one of ``ATTENTION_CALLS`` and its vjp, the kernels
    on: the three ``pallas_call``s, and the whole program they stand in."""
    from ray_tpu.ops import attention

    b, h, hkv, t, d, d_v, kind = ATTENTION_CALLS[name]
    bf16 = jnp.bfloat16
    q = jax.ShapeDtypeStruct((b, h, t, d), bf16)
    k = jax.ShapeDtypeStruct((b, hkv, t, d), bf16)
    v = jax.ShapeDtypeStruct((b, hkv, t, d_v), bf16)
    if "ring" in kind:
        static = (kind["ring"], d ** -0.5, 512, 512)
        flat = lambda x: jax.ShapeDtypeStruct((b * h, *x.shape[2:]), x.dtype)  # noqa: E731
        program = lambda q, k, v, o, lse, do: (  # noqa: E731
            attention._block_fwd(q, k, v, *static),
            attention._block_bwd(q, k, v, o, lse, do, *static))
        lse = jax.ShapeDtypeStruct((b * h, t), jnp.float32)
        operands = (flat(q), flat(k), flat(v), flat(v), lse, flat(v))
    else:
        operands = (q, k, v)
        if "block_size" in kind:
            operands += (jax.ShapeDtypeStruct(
                (b, hkv, t, t // kind["block_size"]), jnp.bool_),)

        if "keys" in kind:  # the words of a bit a (row, key), 4,096 keys a lane group
            operands += (jax.ShapeDtypeStruct((b, t, -(-t // 4096) * 128), jnp.int32),)

        def program(q, k, v, *blocks):
            kw = {**kind, "blocks": blocks[0]} if blocks else kind
            if "keys" in kind:
                kw = {"keys": blocks[0]}
            o, pull = jax.vjp(
                lambda q, k, v: attention.flash_attention(q, k, v, **kw), q, k, v)
            return o, pull(o)

    closed = jax.make_jaxpr(program)(*operands)
    passes = [_pass_text(e) for e in _pallas_calls(closed.jaxpr, [])]
    assert len(passes) == 3
    return dict(zip(PARTS, (*passes, str(closed))))


@functools.lru_cache(maxsize=None)
def call_digests(name: str) -> tuple:
    """``PARTS``' digests of ``call_texts(name)``, traced once a process."""
    texts = call_texts(name)
    return tuple(hashlib.sha1(texts[p].encode()).hexdigest()[:16] for p in PARTS)


# (forward, dkv, dq, around) of each call, read by this same code
# (``python tests/test_flash_kernel_texts.py``, below). Under the causal mask
# they are the parent's of PR 56 (commit 431a236), so the same kernels reach
# Mosaic in every cell, but for dK/dV where a tile is under 1,024 x 1,024
# (sft512's one tile a head, the ring's blocks of 512: "dkv" and "around" of
# three calls), which takes dv's matmul after ds and not between p and ds
# since that PR: 0.114 -> 0.098 ms a call in sft512 on the chip. Under a band
# and a bitmap all four are that PR's, because its one p-then-ds spelling
# moves operations within a tile (the loads' order, no unused cast of p in
# dQ, the bitmap's dK/dV taking dv between p and ds as a 1,024 x 1,024 causal
# tile does and its forward masking after its scores as the other two do) and
# scalar index arithmetic within a grid step (a band's first and last block
# after the start, q's head before them in an index map); the tile's
# operations, operands and dtypes are the parent's. PERF.md §6, PR 56, has
# every such kernel's time on the chip at parent and change. The parent's:
# "sft512" dkv 3d6fd97e2bc8c9ff; "ring-diagonal" dkv 4f84761892e15d89;
# "ring-rotated" dkv 781c75c10589e26d; "laguna-swa-16k" f89705366afe0181
# 4bd2eb04ed5b00b4 0d8b9d3beffcc15b; "minicpm-sparse-16k" 81344f60b5e253a2
# 5a40af618042c5e9 759a1dc40dd8217d.
HELD = {
    "short2k": ("7e89ebf660146c1c", "d9c74d916b482cc2", "26798595d2a631c7",
                "dfff6c8d546f36e1"),
    "long16k": ("0083213af1794731", "329a26fb8cd80fee", "9be872e9643ae75d",
                "857f4a8aef76ea18"),
    "sft512": ("5bf8fd410547e411", "d32510f5e5eafc7b", "e7f88ed0f767e92f",
               "a10ddd4a4562da52"),
    "dropless-4k": ("9dff1f6a5d6fb5b0", "cf88ede11c123854", "a21db0e7dc13fd2e",
                    "040c0eb700b3f27b"),
    "kimi-mla-16k": ("7e09138d50e04924", "b4b333f6f75bdd72", "7ac66b596cff2c09",
                     "1e3b60ffe0a38eb6"),
    "sarvam-mla-4k": ("0f16695e26876199", "235f161f64d4a501", "2c5a29bf3cede115",
                      "426bb0732debe315"),
    "xing4-mla-4k": ("e1c48079cb41330b", "6adb2823c5d48477", "e9e5a90384247ba2",
                     "80946b43b7b679ff"),
    "laguna-full-16k": ("0cbefd8386f89019", "cfdd4f1e5e1a6088", "e1a5b2eec063bec4",
                        "ce64dd1333a97d31"),
    "laguna-swa-16k": ("a04b7a31403d3946", "9551379a590f8512", "b0e144c361557c25",
                       "bb5eda5b0017e587"),
    "solar-gqa-4k": ("c60c4c82f61dad58", "9e919fcf08fb9710", "3193d5e4aaadb4cb",
                     "bdec25000f235a24"),
    "olmo-hybrid-8k": ("ede36ee9d4024a5d", "8eb89112fb64a173", "c231e5b6fb3fd9cf",
                       "f155d0926136fb3a"),
    "minicpm-sparse-16k": ("1aa754956f4ca60b", "23a3f86171426f7b", "60b4a1f96b09a277",
                           "7778ac37a0af3a71"),
    # the Granite cell's one layer (PR 58): 32 heads of 64 over 8 K/V heads
    # at the config's own scale of 1/64, read as the tree of that PR reads it
    "granite-gqa-8k": ("4a31791c23ec669b", "99237a0c02ae0147", "78c0c034c0c14aff",
                       "7864cd6c7190f85e"),
    "dots3-select-8k": ("cf79b0ef7a88f639", "aceea787df6a5f78", "f80ec23b4a6573e0",
                        "0171b2e9e61c7612"),
    "dots3-swa-8k": ("03c9b8787627fbc9", "a8b906f7db52b89d", "508a5388aecc9093",
                     "7b5168c3fe37ce27"),
    "ring-diagonal": ("4f37ad660cf9ba13", "deddd842bc479ee4", "07e7512d9d037b65",
                      "39c4053850660d16"),
    "ring-rotated": ("5bdfd8d4696da1c0", "444f8125842bae66", "b7924c31d3e2cda8",
                     "8a0f3d81ea4f62c1"),
}


@pytest.fixture
def kernels_on(monkeypatch):
    """As on the chip: the probe stood in for (benchmarks/rehearse.py does
    the same), the interpreter off."""
    from ray_tpu.ops import attention

    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET")
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)


@pytest.mark.parametrize("part", range(len(PARTS)), ids=PARTS)
@pytest.mark.parametrize("name", sorted(ATTENTION_CALLS))
def test_each_pass_under_each_mask_traces_to_the_text_it_had(kernels_on, name, part):
    assert call_digests(name)[part] == HELD[name][part]


def test_the_twelve_flash_kernels_and_the_indexer_are_the_only_functions_named_so():
    """A trace names a call by the first ``*_kernel`` identifier in its Mosaic
    module (benchmarks/lib/trace.py ``kernel_name``) and the FLOP tables price
    it by that name: a helper of ``ops/attention.py`` named so would rename a
    traced call, and a kernel renamed would go unpriced."""
    import inspect

    from benchmarks.lib.flops import FLASH_MATMULS
    from benchmarks.lib.flops_dots3 import INDEX_KERNEL, SELECT_KERNELS
    from benchmarks.lib.flops_laguna import WINDOW_KERNELS
    from benchmarks.lib.flops_minicpm_sala import SPARSE_KERNELS
    from ray_tpu.ops import attention

    priced = {*FLASH_MATMULS, *WINDOW_KERNELS, *SPARSE_KERNELS, *SELECT_KERNELS,
              INDEX_KERNEL}
    assert len(priced) == 13
    source = inspect.getsource(attention)
    defined = set(re.findall(r"def (\w+_kernel)\b", source))  # nested ones too
    assert defined == priced
    assert {n for n in vars(attention) if n.endswith("_kernel")} == priced


if __name__ == "__main__":
    # ``HELD`` as the tree on the path reads it (a parent's, to hold a change
    # to; or a change's own where an operation moves on purpose):
    # JAX_PLATFORMS=cpu PYTHONPATH=<tree> python tests/test_flash_kernel_texts.py
    from ray_tpu.ops import attention

    attention._on_tpu = lambda: True
    for call in ATTENTION_CALLS:
        print(f'    "{call}": {call_digests(call)},')
