"""Program spans on the profiler's clock: the one place that names them.

Three things and a list. ``span(name)`` is a host span: a
``jax.profiler.TraceAnnotation`` when ``jax`` is already in ``sys.modules``
and nothing otherwise. It never imports jax, so the head, a raylet and a
driver that opens no backend pay a dictionary lookup and nothing else; in
the process that holds the chip a span costs under a microsecond while no
profiler session is open and is recorded, on the device's clock and under
the thread it ran on, while one is (``jax.profiler.start_trace``).
``scope(name)`` is ``jax.named_scope``: trace-time metadata that reaches
every device operation's ``op_name``, at no run-time cost. ``SCOPES``,
``MIXERS`` and ``BODY`` together name every part of a train step: an
operation whose path holds none of them is one the program gave no name
(``tests/test_program_spans.py`` lists the few that are exempt, the
benchmark's ``step.unnamed_share`` reads their time).

A new scope shows in a trace only from a fresh compile: JAX's persistent
cache key leaves operation metadata out
(``jax_compilation_cache_include_metadata_in_key`` is False), so a warm
cache (``place_compile_cache``: ``JAX_COMPILATION_CACHE_DIR``, else the
checkout's ``.jax_cache``) serves the executable compiled before the edit,
old names and all. Empty it, or point that variable at a fresh directory,
before a traced run that is to show a name added since.

There is no switch: a profiler session is "on". A span goes around work,
never around a blocking wait, except the one that is named as a wait.
The control plane's own always-on record is the flight recorder
(``_private/events.py``, ``ray_tpu timeline``), on the host's wall clock.
In the process that holds a train session (``train/session.py``) a span
also leaves one event in that recorder's ``train`` category when it ends,
profiler session or none: the thread, the name and both ends on the
monotonic clock. ``ray_tpu.worker.exec`` leaves none: the ``task``
category's ``EXEC_SPAN`` already holds its interval and thread.

``watch_compiles()`` is the third: JAX's own account of every trace,
lowering, backend compile and persistent-cache read of the process
(``jax.monitoring``), as events of the same layout in the same category
while a session is held, so that set-up is on the record the turns are on.
Their attrs are a dict, ``{"m_start": ..., "fun_name": ...}``: it is built
when something compiles, never in a turn of the loop. Beside them
``count_entry`` tallies what JAX does not report: how often each kernel's
inlined jitted entry (``ops/attention.py`` ``kernel_entry``) was called and
how often a call traced its body; ``entry_counts`` reads the tally, and a
session says its share as its record ends (``COMPILE_ENTRIES``).
"""
from __future__ import annotations

import contextlib
import sys
import threading
import time

from .._private import events as _events

# Host spans, in the process that holds the chip. Every name starts with
# ``ray_tpu.`` (the benchmark's own spans start with ``bench.``).
TRAIN_REPORT = "ray_tpu.train.report"  # TrainSession.report, the loop's thread
TRAIN_NEXT_RESULT = "ray_tpu.train.next_result"  # TrainWorker.next_result, whole call
TRAIN_RESULT_WAIT = "ray_tpu.train.result_wait"  # the blocking queue.get in it: a wait
WORKER_EXEC = "ray_tpu.worker.exec"  # one task or actor method, arguments to value
WORKER_REPLY = "ray_tpu.worker.reply"  # results packed, reply and task_done handed over
WORKER_RECV = "ray_tpu.worker.recv"  # one received frame dispatched, not the socket wait
HOST_SPANS = (TRAIN_REPORT, TRAIN_NEXT_RESULT, TRAIN_RESULT_WAIT,
              WORKER_EXEC, WORKER_REPLY, WORKER_RECV)
# Set-up's spans, on the thread that compiled or placed. The compile ones are
# stamped by watch_compiles()'s listeners as JAX reports each duration (its
# end is the report, its start the end less the duration); a jit called while
# another is traced sends an interval inside its caller's, so a reader takes
# unions, never sums.
COMPILE_TRACE = "ray_tpu.compile.trace"  # a function traced to a jaxpr; fun_name is the function's
COMPILE_LOWER = "ray_tpu.compile.lower"  # a jaxpr lowered to its module, the Mosaic bodies inside it; fun_name jit(<function>)
COMPILE_BACKEND = "ray_tpu.compile.backend"  # an executable made: the backend's compile, or the persistent cache's read on a hit; fun_name jit(<function>)
COMPILE_CACHE_READ = "ray_tpu.compile.cache_read"  # inside backend, on a hit: the read and the executable's load
COMPILE_CACHE_HIT = "ray_tpu.compile.cache_hit"  # inside backend, of no length: the persistent cache held the executable
COMPILE_CACHE_MISS = "ray_tpu.compile.cache_miss"  # inside backend, of no length: it did not, and the compiled one was written there
COMPILE_SHORT = "ray_tpu.compile.short"  # of no length, once, as the session's record ends: {stage: [count, seconds]} of the durations under COMPILE_FLOOR_S, which leave no event of their own
COMPILE_ENTRIES = "ray_tpu.compile.entries"  # of no length, once, as the session's record ends: {entry: [calls, traces]} of the kernels' inlined jitted entries (ops/attention.py kernel_entry) that the session called: a trace is the entry's body run in Python, a call that made none took the jaxpr the entry held
SHARD_PARAMS = "ray_tpu.parallel.shard_params"  # parallel/mesh.py shard_params: the host placing the leaves (device_put returns before a copy ends)
SETUP_SPANS = (COMPILE_TRACE, COMPILE_LOWER, COMPILE_BACKEND,
               COMPILE_CACHE_READ, COMPILE_CACHE_HIT, COMPILE_CACHE_MISS,
               COMPILE_SHORT, COMPILE_ENTRIES, SHARD_PARAMS)

# In-graph scopes. Forward and backward are already told apart by JAX's
# ``jvp(`` / ``transpose(`` and a remat replay by ``rematted_computation``.
OPTIMIZER = "optimizer"  # tx.update and apply_updates in make_train_step
MOE_ROUTER = "router"  # logits, softmax, top-k, renormalisation, aux loss
MOE_DISPATCH = "dispatch"  # positions, slot map, gather into the expert buffer
MOE_EXPERTS = "experts"  # the three expert matmuls and the activation
MOE_COMBINE = "combine"  # gather back, gate scaling, the reduction over k
MOE_LAYOUT = "layout"  # inside dispatch, "gmm" only: sort, tile layout, inverse map
QK_NORM = "qk_norm"  # RMSNorm of q and k: the whole projections in attn (cfg.qk_norm), or a head's channels there under one weight [head_dim] for q and one for k (AttentionKind.qk_head_norm: models/lfm2.py), inside sparse and inside lightning; a head's channels inside mla (cfg.qk_head_norm) on the XLA road, and on the kernel road (ops/rotary.py latent_road) the weights' casts alone, or the latent kernels where the layer norms and turns nothing
MOE_SHARED = "shared"  # inside moe: the shared expert every token passes
# The mixers' flax names, which reach op_name as the attention's "attn" does.
KDA = "kda"  # the gated delta-rule mixer (models/kimi_linear.py KDAMixer; Solar-Open2's KDA layers too)
MLA = "mla"  # the latent-attention mixer (models/mla.py MLAMixer); in a model whose layers differ, the full layers' (models/dots3.py's beside swa_mla): there also indexer, select and out_gate
SWA_MLA = "swa_mla"  # the same module as a sliding layer's mixer (models/dots3.py): latents and heads of the sliding kind's own widths, its own theta, flash_attention under a window, out_gate
GDN = "gdn"  # the scalar-decay gated delta-rule mixer (models/olmo_hybrid.py GDNMixer); conv, gate and scan inside it as inside kda, scan holding ops/kda.py chunk_gdn and the decay's and beta's layout for its kernels (q and k come heads first from conv; v, the gate and o go through as they lie)
LIGHTNING = "lightning"  # the decay-only linear-attention mixer (models/minicpm_sala.py LightningMixer): projections, qk_norm and rotary scopes, ops/kda.py chunk_lightning (its kernels hold o's norm and the output gate) and the transpositions around it
SPARSE = "sparse"  # the block-sparse top-k softmax mixer (models/minicpm_sala.py SparseAttention): projections, qk_norm, select, ops/attention.py sparse_attention's kernels, out_gate
SPARSE_SELECT = "select"  # inside sparse, where T > dense_len: ops/attention.py select_blocks (compressed keys, the scores of every head against them, their soft-max, the sum over a group's heads, the max-pool to blocks, the forced blocks, top-k, the packed bitmap); inside mla (a kind with an indexer): ops/attention.py index_keys (_index_kernel: every index head's scores of a row block against the keys up to it, their weighted sum, each row's topk-th largest by bisection, the packed words; the pads around it); a reader tells the two apart by the mixer above. Nothing of either is differentiated
INDEXER = "indexer"  # inside mla (a kind with an indexer): the index queries' projection from the q latent, the index key's projection and LayerNorm, the heads' weights, the rotation of both's leading channels; forward alone
MAMBA = "mamba"  # the Mamba-2 state-space mixer (models/granite_hybrid.py Mamba2Mixer): the three input projections, conv, step, ops/kda.py chunk_ssd's kernels (they hold the step's product with u and the skip) and the slices around them, norm, the output projection
SHORTCONV = "shortconv"  # the gated short-convolution mixer (models/lfm2.py ShortConvMixer): the whole mixer of an LFM2 conv layer, its three scopes below and nothing else
SHORTCONV_IN = "conv_in"  # inside shortconv: the one input projection to the gates B and C and the convolved x~, [hidden, 3 hidden]
SHORTCONV_GATED = "gated_conv"  # inside shortconv: ops/kda.py gated_conv, C * conv(B * x~) with no activation: its two Pallas kernels (_gated_conv_fwd_kernel, _gated_conv_bwd_kernel) where a third tiles, XLA's lines elsewhere, and the filter's casts
SHORTCONV_OUT = "conv_out"  # inside shortconv: the output projection
MAMBA_STEP = "step"  # inside mamba: the step's softplus with its bias
MAMBA_NORM = "norm"  # inside mamba: y times SiLU(z), then the one RMSNorm over every head's channels
KDA_CONV = "conv"  # inside kda (models/kimi_linear.py KDAMixer) and inside gdn (models/olmo_hybrid.py GDNMixer): the short convolutions of q, k, v and their SiLU; inside mamba (models/granite_hybrid.py Mamba2Mixer): the one convolution of x, B and C with its bias and its SiLU. Not opened inside shortconv, whose convolution has gates and no SiLU and a scope of its own (SHORTCONV_GATED)
KDA_GATE = "gate"  # inside kda: the log-decay g and the write strength beta, with its doubling where the config writes in (0, 2)
KDA_SCAN = "scan"  # inside kda: ops/kda.py chunk_kda (its kernels hold q's, k's and o's norms and the output gate), v's rounding, beta's transpose
# (No "out_norm": o's per-head RMSNorm and output gate left XLA for the scan's
# kernels, and a scope that no operation carries is not in this list.)
MLA_LATENT = "latent"  # inside mla: down-projection, norm, up-projection of K/V
MLA_ROPE = "rope"  # inside mla (cfg.mla_rope): the frequency table and, on the XLA road, the rotation of q's and k's pe parts with the slices and concatenations around it; on the kernel road the four latent kernels (the per-head norm, the rotation, k's assembly, v's slice, and their passes back), the tables' fusion and the sums of the weights' partial gradients
MLA_Q_LATENT = "q_latent"  # inside mla (cfg.q_lora_rank): q's down-projection, its norm, the up-projection to the heads
ATTN = "attn"  # the softmax-attention mixer (models/llama.py Attention); in a model whose layers differ, the full-attention layers' (Laguna's beside swa, Solar-Open2's beside kda)
SWA = "swa"  # the same module as a sliding-window layer's mixer (models/laguna.py): its own head count and rotation, flash_attention under a window
ATTN_ROPE = "rotary"  # inside attn, swa and lightning: the angles, cos and sin, the rotation of q and of k (a part of each head where the layer's kind says so) by ops/rotary.py rotate: its tables and _rotary_kernel where a head is whole vregs of lanes, _rope elsewhere; not opened by a kind that turns nothing
ATTN_GATE = "out_gate"  # inside attn and swa (a kind with a gate), inside sparse, and inside mla and swa_mla (a kind with a gate): the gate's projection (one value a head, or of q's width), its sigmoid, the product with each head's output
HC = "hc"  # a hyper-connection (models/hyper_connections.py), twice a layer: the three maps of the streams, the read before the sublayer, the write after it
HC_PRE = "pre"  # inside hc: the streams' rms, x~ Phi, the three logits, H_pre and H_post, the read u = sum H_pre[i] X[i]
HC_SINKHORN = "sinkhorn"  # inside hc: exp, the iterations of rows and columns, and their backward
HC_POST = "post"  # inside hc: the write X'[i] = sum H_res[i, j] X[j] + H_post[i] y
HC_STREAMS = "streams"  # outside hc and every layer (models/llama.py _through): the copy of the layers' input to the n streams and their sum after the last layer; no hyper-connection's, so no /hc/ reader takes it
MTP = "mtp"  # the multi-token-prediction module (flax name, models/xing4.py) and, in the loss, its pass of the shared head
LOSS = "loss"  # the loss functions of models/, called outside every flax module: the head's weight taken from the tree, padding, reshapes, the scan over chunks, the soft-max arithmetic, the mean, an auxiliary or second term added. Opened directly under a transform it renders in its brackets, jvp(loss), as mtp does
LOSS_HEAD = "head"  # inside loss: a chunk's float32 matmul with the head, alone (the full-logit path's is the flax module lm_head)
SCOPES = (OPTIMIZER, MOE_ROUTER, MOE_DISPATCH, MOE_EXPERTS, MOE_COMBINE,
          MOE_LAYOUT, QK_NORM, MOE_SHARED, KDA_CONV, KDA_GATE, KDA_SCAN,
          MLA_LATENT, MLA_ROPE, MLA_Q_LATENT, ATTN_ROPE, ATTN_GATE, HC, HC_PRE,
          HC_SINKHORN, HC_POST, HC_STREAMS, MTP, LOSS, LOSS_HEAD, SPARSE_SELECT,
          MAMBA_STEP, MAMBA_NORM, SHORTCONV_IN, SHORTCONV_GATED, SHORTCONV_OUT,
          INDEXER)
# Flax module names, bound in the model classes' ``blocks``.
MIXERS = (KDA, MLA, ATTN, SWA, GDN, LIGHTNING, SPARSE, MAMBA, SHORTCONV,
          SWA_MLA)
# The decoder body's flax names (models/llama.py, xing4.py), a layer's and
# above: parameter trees and checkpoints hold them, so none is ever renamed.
EMBED = "embed_tokens"  # the embedding table's flax name; models/llama.py _lookup opens it as a scope around what it does outside the module (the one-hot product where a mesh splits the table, the constraint on the result)
LAYER = "layers_"  # a decoder layer is LAYER + its index
INPUT_NORM = "input_norm"  # a layer's RMSNorm before its mixer
POST_ATTN_NORM = "post_attn_norm"  # a layer's RMSNorm before its FFN
POST_MIXER_NORM = "post_mixer_norm"  # where cfg.norm_after: a layer's RMSNorm of its mixer's output, before the residual sum
POST_FFN_NORM = "post_ffn_norm"  # where cfg.norm_after: a layer's RMSNorm of its FFN's output, before the residual sum
MLP = "mlp"  # the dense SwiGLU FFN
MOE = "moe"  # the expert layer (models/mixtral.py MoELayer)
MIXER_HC = "mixer_hc"  # a hyper-connected layer's connection around its mixer
FFN_HC = "ffn_hc"  # and around its FFN
FINAL_NORM = "final_norm"
LM_HEAD = "lm_head"  # the head's float32 matmul in the full-logit path: an untied head's flax name, and a scope around a tied table's attend (embed_tokens.attend)
MTP_HIDDEN_NORM = "mtp_hidden_norm"  # inside mtp: the norm of the body's last hidden states
MTP_EMBED_NORM = "mtp_embed_norm"  # inside mtp: the norm of the next tokens' embeddings
MTP_PROJ = "mtp_proj"  # inside mtp: the projection of the two joined
MTP_LAYER = "mtp_layer"  # inside mtp: the module's decoder layer
MTP_NORM = "mtp_norm"  # inside mtp: the norm before the shared head
BODY = (EMBED, LAYER, INPUT_NORM, POST_ATTN_NORM, MLP, MOE, MIXER_HC, FFN_HC,
        FINAL_NORM, LM_HEAD, MTP_HIDDEN_NORM, MTP_EMBED_NORM, MTP_PROJ,
        MTP_LAYER, MTP_NORM, POST_MIXER_NORM, POST_FFN_NORM)

_OFF = contextlib.nullcontext()
# The flight recorder, while this process holds a train session, and the
# session's tally of compile durations too short for an event of their own.
_recorder = None
_short = {}


def record_spans_into(recorder, short=None) -> None:
    """``train/session.py`` hands over the process's flight recorder, and the
    dict it tallies short compile durations in (``COMPILE_SHORT``), when a
    session starts and None when it ends."""
    global _recorder, _short
    _recorder, _short = recorder, {} if short is None else short


class _RecordedSpan:
    """A span that also lands in the flight recorder: one tuple and one
    append when it ends, nothing built before that."""

    __slots__ = ("name", "annotation", "recorder", "m_start")

    def __init__(self, name, annotation, recorder):
        self.name, self.annotation, self.recorder = name, annotation, recorder

    def __enter__(self):
        if self.annotation is not None:
            self.annotation.__enter__()
        self.m_start = time.monotonic()

    def __exit__(self, *exc):
        self.recorder.record_at(
            time.time(), time.monotonic(), _events.TRAIN,
            str(threading.get_ident()), self.name, self.m_start,
        )
        if self.annotation is not None:
            self.annotation.__exit__(*exc)


def span(name: str):
    """A host span around the ``with`` body; nothing where jax is not loaded
    and no train session is held."""
    # getattr: another thread may be half way through importing jax.
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    annotation = None if profiler is None else profiler.TraceAnnotation(name)
    recorder = _recorder
    if recorder is None or not recorder.enabled or name == WORKER_EXEC:
        return _OFF if annotation is None else annotation
    return _RecordedSpan(name, annotation, recorder)


#: A compile duration under this leaves no event: a model's step traces
#: thousands of ``jnp`` functions in well under a millisecond each, and the
#: recorder's ring holds 8,192 events between two flushes, none of which
#: comes before set-up's report. They are counted (``COMPILE_SHORT``).
COMPILE_FLOOR_S = 1e-3
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": COMPILE_TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": COMPILE_LOWER,
    "/jax/core/compile/backend_compile_duration": COMPILE_BACKEND,
    "/jax/compilation_cache/cache_retrieval_time_sec": COMPILE_CACHE_READ,
}
_POINTS = {
    "/jax/compilation_cache/cache_hits": COMPILE_CACHE_HIT,
    "/jax/compilation_cache/cache_misses": COMPILE_CACHE_MISS,
}
_watch_lock = threading.Lock()
_watching = False


def _record_compile(name, duration, fun_name=None):
    recorder = _recorder
    if recorder is None or not recorder.enabled:
        return
    if 0.0 < duration < COMPILE_FLOOR_S:
        tally = _short.setdefault(name.rpartition(".")[2], [0, 0.0])
        tally[0] += 1
        tally[1] += duration
        return
    m = time.monotonic()
    attrs = {"m_start": m - duration}
    if fun_name is not None:
        attrs["fun_name"] = fun_name
    recorder.record_at(
        time.time(), m, _events.TRAIN, str(threading.get_ident()), name, attrs
    )


# entry -> [calls, traces] of the process, session or none: a list update a
# call and one a trace, while something is traced only (a compiled step calls
# no entry).
_entries = {}


def count_entry(name: str, traced: bool) -> None:
    """``ops/attention.py`` ``kernel_entry``: an entry was called (``traced``
    False), or its body ran in Python (True), which is a trace."""
    _entries.setdefault(name, [0, 0])[traced] += 1


def entry_counts(since=None) -> dict:
    """{entry: [calls, traces]} of the process so far, or of what came after
    an earlier reading ``since``, the entries that were called alone."""
    since = since or {}
    counts = {}
    for name, (calls, traces) in list(_entries.items()):
        before = since.get(name, (0, 0))
        if calls > before[0]:
            counts[name] = [calls - before[0], traces - before[1]]
    return counts


def _on_duration(event, duration, fun_name=None, **_):
    if event in _DURATIONS:
        _record_compile(_DURATIONS[event], duration, fun_name)


def _on_point(event, **_):
    if event in _POINTS:
        _record_compile(_POINTS[event], 0.0)  # a span of no length


def watch_compiles() -> None:
    """Registers, once a process, the two ``jax.monitoring`` listeners that
    turn JAX's compile durations and cache events into ``SETUP_SPANS``
    events. It imports jax: ``ray_tpu.parallel`` calls it as it is imported
    and ``train.make_train_step`` as it is called, and a train session as
    it starts where jax is already loaded. A loop that compiles before it
    touches any of these is seen from that touch on. The two stay for the
    life of the process and send nowhere while no session is held
    (``record_spans_into``): sessions that follow one another in a process
    stack nothing, and there is nothing to take off."""
    global _watching
    with _watch_lock:
        if _watching:
            return
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_point)
        _watching = True


def scope(name: str):
    """``jax.named_scope(name)``: called from code that is being traced."""
    import jax

    return jax.named_scope(name)
