"""dots3-note-prev's architecture through the program's models, on the CPU: the
four head ranks' shares of a full layer add up to the uncut reference
(``tests/test_dots3_shares.py`` has the 32 expert ranks';
``tests/test_dots3_model.py`` the model against its reference;
``tests/dots3_cases.py`` what the files share).
"""
import dataclasses

import jax
import numpy as np

from benchmarks.reference import dots3_note_decoder as reference
from ray_tpu.models.mla import Indexer, LatentKind

from dots3_cases import SEQ, interpret, one_mixer  # noqa: F401 - fixtures


# ------------------------------------------------------- the shares add up


def test_the_head_ranks_shares_add_up_to_the_uncut_full_layer():
    """Four ranks of two heads each of a full layer: what each gives of
    o_proj's sum, from the same latents and the same selection (every rank
    computes those alike), adds up to the reference's layer at all 8 heads."""
    kind = LatentKind(
        8, 32, 16, 16, 16, 1e4, mla_rope=True, q_lora_rank=24, rescale=True,
        gate=True, indexer=Indexer(2, 16, 40))
    _, params, x, positions = one_mixer(kind, seq=SEQ)
    p = params["params"]
    config = {
        "hidden_size": 64, "rms_norm_eps": 1e-5, "q_lora_rank": 24,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 16,
        "v_head_dim": 16, "rope_theta": 1e4, "attention_gate_type": "headwise",
        "apply_mla_qkv_lora_rescale": True, "index_n_heads": 2,
        "index_head_dim": 16, "index_topk": 40, "index_norm_eps": 1e-6}
    with jax.default_matmul_precision("highest"):
        uncut = reference.latent_attention(p, x[0], "full_attention", config)
    total = 0.0
    for rank in range(4):
        held = slice(2 * rank, 2 * rank + 2)
        mine = {"params": {
            **p,
            "q_b_proj": {"kernel": p["q_b_proj"]["kernel"][:, held]},
            "kv_b_proj": {"kernel": p["kv_b_proj"]["kernel"][:, held]},
            "g_proj": {"kernel": p["g_proj"]["kernel"][:, held]},
            "o_proj": {"kernel": p["o_proj"]["kernel"][held]},
        }}
        share = one_mixer(dataclasses.replace(
            kind, heads_held=(2 * rank, 2 * rank + 2)))[0]
        out = share.apply(mine, x, positions)[0]
        with jax.default_matmul_precision("highest"):
            want = reference.latent_attention(
                mine["params"], x[0], "full_attention", config)
        np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
        total = total + out
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=2e-5)
