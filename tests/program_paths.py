"""What the tests of the program's in-graph scopes share
(``tests/test_program_paths_*.py``, a file a family of models, and
``tests/test_program_spans.py``): a tiny model's train step compiled on the
CPU and the ``op_name`` of every instruction in it, the benchmark readers'
rule for an instruction's pass, the paths that may hold no name of the program
(``EXEMPT``), and the bodies of the cases every family's compiled step passes,
which each family's file runs over its own fixtures (``FAMILIES``). A new
model brings a file of its own with its fixture and its ``FAMILIES``; it adds
its names to ``SHOWN_BY`` and ``LOSS_KINDS`` here and edits no sibling's file.
"""
import os
import re

import jax
import jax.numpy as jnp
import optax
import pytest

from ray_tpu.train import make_train_step
from ray_tpu.util import tracing


MOE_SCOPES = (tracing.MOE_ROUTER, tracing.MOE_DISPATCH, tracing.MOE_EXPERTS,
              tracing.MOE_COMBINE)


def paths_of(compiled) -> list:
    """The ``op_name`` of every instruction that has a path (parameters and
    the bodies of reductions carry a bare name)."""
    names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    return [n for n in names if n.startswith("jit(")]


def pass_of(path: str):
    """The benchmark readers' rule (benchmarks/lib/program_trace.py)."""
    classes = [
        "rematted_computation" in path,
        "transpose(" in path and "rematted_computation" not in path,
        "jvp(" in path and "transpose(" not in path,
        "/" + tracing.OPTIMIZER + "/" in path,
    ]
    if sum(classes) != 1:
        return None
    return ("replay", "backward", "forward", "optimizer")[classes.index(True)]


def compiled_step(model, loss_fn, ids):
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)
    tx = optax.adamw(1e-3)
    step = make_train_step(loss_fn, tx)
    return step.lower(params, tx.init(params), ids, ids).compile()


@pytest.fixture(scope="module")
def sarvam_paths():
    """Paths of a tiny sarvam_mla model's compiled train step: a dense and
    an expert layer, each under latent attention with its 8-wide parts
    rotated under YaRN and a per-head QK norm."""
    from ray_tpu.models.llama import chunked_causal_lm_loss
    from ray_tpu.models.sarvam_mla import SarvamMLAForCausalLM, sarvam_mla_config

    os.environ["RAY_TPU_PALLAS_INTERPRET"] = "1"  # read when gmm is traced
    try:
        cfg = sarvam_mla_config(
            num_layers=2, num_experts_held=2, vocab_size=128, hidden_size=32,
            intermediate_size=64, moe_intermediate_size=16, num_heads=2,
            num_experts=8, num_experts_per_tok=2, num_shared_experts=1,
            routed_scaling_factor=2.5, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16,
            rope_scaling={"type": "deepseek_yarn", "factor": 40,
                          "original_max_position_embeddings": 4096,
                          "mscale": 1, "mscale_all_dim": 1},
        )
        model = SarvamMLAForCausalLM(cfg)
        ids = jnp.zeros((1, 64), jnp.int32)
        return paths_of(compiled_step(
            model,
            lambda p, i, t: chunked_causal_lm_loss(model, p, i, t, chunk_size=32),
            ids,
        ))
    finally:
        del os.environ["RAY_TPU_PALLAS_INTERPRET"]


# Paths that may hold no name of the program, and why.
EXEMPT = (
    # _positions' arange, inside the model's __call__ and outside every part:
    # integer positions shared by every layer, no device time of their own
    (r"^jit\(train_step\)/jvp\(\w+ForCausalLM\)/iota$", "positions"),
    # JAX's own, at a layer's checkpoint boundary: the transposed remat2
    # equation rounds the residual stream's summed cotangent to the stream's
    # dtype outside the layer's name, which flax opens inside the checkpoint.
    # No line of the program emits it; the benchmark's step.unnamed_share
    # reads what it costs on the chip (PERF.md 7)
    (r"^jit\(train_step\)/transpose\(jvp\((\w+ForCausalLM|mtp)\)\)/(jvp\(\w+\)/|mtp/)*remat2$",
     "remat boundary"),
)


def paths_in(request, family):
    """A family's compiled step, by fixture (and dispatch branch): each file of
    ``tests/test_program_paths_*.py`` lists its own as ``FAMILIES``."""
    fixture, _, branch = family.partition(":")
    paths = request.getfixturevalue(fixture)
    return paths[branch] if branch else paths


def every_instruction_path_names_a_part_of_the_program(paths):
    """The scope tree is closed over the step: the reader of the benchmark's
    step table (benchmarks/lib/step_table.py, by tracing's lists alone) finds
    a part for every instruction but the exempt, and a pass for each."""
    from benchmarks.lib import step_table

    nameless = [
        p for p in paths
        if not step_table.part_of(p)[0]
        and not any(re.search(pattern, p) for pattern, _ in EXEMPT)
    ]
    assert not nameless, sorted(set(nameless))[:40]


# Where each name of ``tracing.SCOPES``, ``MIXERS`` and ``BODY`` is held to appear:
# the family (fixture and dispatch branch) whose compiled step shows it, the
# first of those that do. Every name is listed once
# (``tests/test_program_spans.py`` ``test_names_emitted_are_exactly_the_list``)
# and each family's file holds its own step to its own names.
SHOWN_BY = {
    "llama_paths": (
        "optimizer", "rotary", "loss", "attn", "embed_tokens", "layers_",
        "input_norm", "post_attn_norm", "mlp", "final_norm", "lm_head",
    ),
    "qk_norm_paths": ("qk_norm",),
    "tied_paths": (),
    "moe_paths:capacity": ("router", "dispatch", "experts", "combine", "moe"),
    "moe_paths:gmm": ("layout",),
    "moe_paths:ragged": (),
    "kimi_paths": ("shared", "conv", "gate", "scan", "latent", "head", "kda", "mla"),
    "sarvam_paths": ("rope",),
    "xing4_paths": (
        "q_latent", "hc", "pre", "sinkhorn", "post", "streams", "mtp",
        "mixer_hc", "ffn_hc", "mtp_hidden_norm", "mtp_embed_norm", "mtp_proj",
        "mtp_layer", "mtp_norm",
    ),
    "laguna_paths": ("out_gate", "swa"),
    "solar_paths": (),
    "olmo_paths": ("gdn", "post_mixer_norm", "post_ffn_norm"),
    "sala_paths": ("select", "lightning", "sparse"),
    "granite_paths": ("step", "norm", "mamba"),
    "lfm2_paths": ("conv_in", "gated_conv", "conv_out", "shortconv"),
    "dots3_paths": ("indexer", "swa_mla"),
}


def a_step_shows_the_names_it_is_listed_for(paths, family):
    """A scope directly under a transform is in its brackets; a mixer's and the
    body's names are flax's, and a layer's ends in its index."""
    for name in SHOWN_BY[family]:
        if name in tracing.SCOPES:
            assert any(f"/{name}/" in p or f"({name})/" in p for p in paths), name
        if name in tracing.MIXERS + tracing.BODY:
            assert any(f"/{name}/" in p or f"/{name}0/" in p for p in paths), name


LOSS_KINDS = {
    "llama_paths": "full", "qk_norm_paths": "full", "tied_paths": "full",
    "moe_paths:capacity": "full",
    "moe_paths:gmm": "full", "moe_paths:ragged": "full", "kimi_paths": "chunked",
    "sarvam_paths": "chunked", "laguna_paths": "chunked", "solar_paths": "chunked",
    "olmo_paths": "chunked", "sala_paths": "chunked", "granite_paths": "chunked",
    "lfm2_paths": "chunked", "dots3_paths": "chunked",
    "xing4_paths": "mtp",
}


def the_loss_and_the_chunked_head_carry_their_scopes(paths, family):
    """What the benchmark's model.head_loss_share selects by. A loss function
    is called outside every flax module, directly under the transform, so
    JAX renders its scope in the brackets: jvp(loss), transpose(jvp(loss)).
    The full-logit loss multiplies nothing (its matmul is the module
    lm_head, or the tied table's attend); the chunked one multiplies under
    ``head`` alone, three times a chunk and all of them forward: its rule
    (models/llama.py ``_chunked_nll``) makes a chunk's gradients in the
    forward scan, replays nothing and leaves the backward pass the cotangent's
    two scalings, under ``loss``; the MTP loss opens ``loss`` inside ``mtp``,
    so its second pass of the head reads jvp(mtp)/loss/ and "(mtp)" still
    finds it."""
    kind = LOSS_KINDS[family]
    top = [p for p in paths if f"({tracing.LOSS})" in p]
    # (a cotangent of 1.0 folds the chunked rule's scalings away, and with a
    # batch of one the reshapes back to [B, T, H] too)
    assert {pass_of(p) for p in top} - {"backward"} == {"forward"}
    assert kind != "full" or "backward" in {pass_of(p) for p in top}
    assert all(p.startswith((f"jit(train_step)/jvp({tracing.LOSS})/",
                             f"jit(train_step)/transpose(jvp({tracing.LOSS}))/"))
               for p in top)
    assert any(p.endswith("/reduce_max") for p in top)  # the logsumexp
    assert not [p for p in top if "ForCausalLM" in p or "/layers_" in p]
    matmuls = [p for p in top if p.endswith("/dot_general")]
    if kind == "full":
        assert not matmuls and not [p for p in paths if f"/{tracing.LOSS_HEAD}/" in p]
        # the module lm_head, or the tied table's attend under the head's name
        head = [p for p in paths if p.endswith("/dot_general")
                and f"/{tracing.LM_HEAD}/" in p]
        assert {pass_of(p) for p in head} >= {"forward", "backward"}
        assert not [p for p in paths if ".attend/" in p and f"/{tracing.LM_HEAD}/" not in p]
        assert (family == "tied_paths") == any(
            f"/{tracing.LM_HEAD}/{tracing.EMBED}.attend/dot_general" in p for p in paths)
        return
    the_heads = f"jit(train_step)/jvp({tracing.LOSS})/while/body/closed_call/head/dot_general"
    assert set(matmuls) == {the_heads} and pass_of(the_heads) == "forward"
    assert not [p for p in paths if "rematted_computation" in p and tracing.LOSS in p]
    # nothing but the matmuls, the casts around them and the sum into the
    # head's gradient (the product's own output fusion) is the head's
    assert {p.rpartition("/")[2] for p in paths if f"/{tracing.LOSS_HEAD}/" in p} <= {
        "dot_general", "convert_element_type", "transpose", "add"}
    # what is left for the backward pass is the loss's, and no loop
    assert not [p for p in top if pass_of(p) == "backward"
                and ("/while" in p or f"/{tracing.LOSS_HEAD}/" in p)]
    second = [p for p in paths if f"({tracing.MTP})" in p]
    if kind != "mtp":
        assert not second
        return
    # (the mask of the positions that have a target and the targets' roll are
    # the module's and outside the loss function)
    assert all(f"({tracing.MTP})/{tracing.LOSS}/{tracing.LOSS_HEAD}/" in p or
               f"/{tracing.LOSS}/while/" in p
               for p in second if p.endswith("/dot_general"))
    assert not [p for p in second if f"({tracing.LOSS})" in p]
    again = [p for p in second if p.endswith(f"/{tracing.LOSS_HEAD}/dot_general")]
    assert {pass_of(p) for p in again} == {"forward"}
    # its cotangent is mtp_weight, not 1.0: the rule's two scalings stay
    assert f"jit(train_step)/transpose(jvp({tracing.MTP}))/{tracing.LOSS}/mul" in second
    # the sum of the two terms is the loss's, outside the module's scope
    assert f"jit(train_step)/jvp({tracing.LOSS})/mul" in top
