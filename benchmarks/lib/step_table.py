"""A traced step by the names the program gave it: part, scope and pass.

``ray_tpu/util/tracing.py`` names every part of a train step: the decoder
body's flax modules (``BODY``), the mixers (``MIXERS``) and the in-graph
scopes (``SCOPES``). ``part_of(path)`` reads an operation's metadata path by
those names and no others; ``table(run)`` sums a traced window's self times
by them, and ``note(run)`` says the sums once a run (``--keep`` holds the
notes), with every Pallas kernel's calls and times under them:

    part / scope        forward  replay  backward  update  no pass  total  % busy

in milliseconds a step. The rows of the first level add up to the device's
busy time; a row under 0.05% of it stays in its parent's total and is not
printed. ``python -m benchmarks.lib.step_table <kept dir>`` prints the same
from a run kept with ``run.py --trace 1 --keep DIR``.

An event the compiled text gives no path (XLA's own copies, converts,
bitcasts, a fusion named for none of its instructions; also one labelled
with its own instruction name, ``convert.20``) takes the path of
its fusion's body where the body's instructions have one, else that of the
instruction that consumes its result in ``step.hlo.txt`` (through consumers
that have none either, four deep), else, where only the step's result takes
it, that of the instruction it reads. What none of them reaches, and an event
whose path holds no name of the program, is ``no name``:
``step.unnamed_share``.

A program from before PR 50 has no ``BODY``, ``LOSS`` or ``LOSS_HEAD`` in
its ``tracing``; it named the same modules by literals, which ``_named``
repeats so that the table reads a parent commit too (its loss is nameless:
the "before" of ``step.unnamed_share``). In a program that has the list
nothing reads the literals.
"""
from __future__ import annotations

import glob
import os
import re
import statistics
import sys
from collections import Counter, defaultdict

from ray_tpu.util import tracing as names  # imports no jax

from . import program_trace, trace as tracing


def _named(constant: str, before):
    """The program's constant, or what a program from before PR 50 wrote
    as a literal in its place."""
    return getattr(names, constant, before)


EMBED, LAYER = _named("EMBED", "embed_tokens"), _named("LAYER", "layers_")
MLP, LM_HEAD = _named("MLP", "mlp"), _named("LM_HEAD", "lm_head")
MIXER_HC, FFN_HC = _named("MIXER_HC", "mixer_hc"), _named("FFN_HC", "ffn_hc")
FINAL_NORM = _named("FINAL_NORM", "final_norm")
LOSS, LOSS_HEAD = _named("LOSS", "loss"), _named("LOSS_HEAD", "head")
BODY = _named("BODY", (
    EMBED, LAYER, "input_norm", "post_attn_norm", MLP, "moe", MIXER_HC, FFN_HC,
    FINAL_NORM, LM_HEAD, "mtp_hidden_norm", "mtp_embed_norm", "mtp_proj",
    "mtp_layer", "mtp_norm"))
KNOWN = frozenset(BODY + names.MIXERS + names.SCOPES + (LOSS, LOSS_HEAD))
HEAD_AND_LOSS = "head and loss"
NO_NAME = "no name"
# (part, the names that put a path there), first match first: the MTP
# module holds a layer and a pass of the loss, the loss holds a head. The
# module's flax names begin with its scope's, and a parameter's path
# (``params['params']['mtp_layer']...``) holds them without the scope.
PARTS = (
    (names.MTP, (names.MTP, *(n for n in BODY if n.startswith(names.MTP + "_")))),
    (names.OPTIMIZER, (names.OPTIMIZER,)),
    (HEAD_AND_LOSS, (LM_HEAD, LOSS)),
    ("layers", (LAYER, getattr(names, "HC_STREAMS", None))),
    (EMBED, (EMBED,)),
    (FINAL_NORM, (FINAL_NORM,)),
)
# A hyper-connection is one row whichever sublayer it is around: its flax
# name holds pre and sinkhorn, the write (post) is opened outside it.
SAME = {MIXER_HC: names.HC, FFN_HC: names.HC}
PASSES = ("forward", "replay", "backward", "optimizer", "")
COLUMNS = ("forward", "replay", "backward", "update", "no pass")
FOLD_UNDER = 0.0005  # of busy time
NEIGHBOURS_DEEP = 4


def names_of(path: str) -> list:
    """The program's names a path holds, outermost first. A segment is a
    name where it is one exactly (a module's, also where flax adds a method,
    ``embed_tokens.attend``), where it is a layer's (``layers_3``), or where
    a transform's brackets hold one (``transpose(jvp(loss))``); the
    last segment is the primitive's and is never one. XLA names what it
    does to an argument of the step for the argument
    (``params['params']['lm_head']['kernel']``: a copy to another layout):
    there the keys of the tree are the segments."""
    held = []
    segments = path.partition(" kernel_name=")[0].split("/")[:-1]
    if not segments and "[" in path:
        segments = re.findall(r"\[\\?'(\w+)\\?'\]", path)
    for segment in segments:
        # flax says a method other than __call__ after the module's name
        inner = segment.rpartition("(")[2].rstrip(")").partition(".")[0]
        if inner in KNOWN:
            held.append(inner)
        elif inner.startswith(LAYER) and inner[len(LAYER):].isdigit():
            held.append(LAYER)
    return held


def part_of(path: str) -> tuple:
    """(part, scope, pass) of a metadata path. The part is one of ``PARTS``
    or, for a path that holds none of their names, the first name it does
    hold, or "". The scope is the chain of names inside the part, innermost
    last (``attn/rotary``, ``moe/dispatch/layout``, ``hc/pre``,
    ``loss/head``). The pass is ``program_trace.pass_of_path``'s."""
    held = [SAME.get(n, n) for n in names_of(path)]
    pass_ = program_trace.pass_of_path(path)
    if not held:
        return "", "", pass_
    part = next((p for p, marks in PARTS if any(n in held for n in marks)), held[0])
    # The part's own name is not said again, nor any name twice (the
    # router's Dense is "router" inside the scope "router"; a jitted
    # function called under a scope can repeat the stack it was traced in).
    chain = [n for n in held if n not in (part, LAYER)]
    return part, "/".join(dict.fromkeys(chain)), pass_


def holds_a_name(path: str) -> bool:
    return bool(names_of(path))


def is_a_path(op_name: str) -> bool:
    """XLA labels some of its own instructions with their own name
    (``convert.20``): no path of the program, as an empty one."""
    return "/" in op_name or "[" in op_name


# ------------------------------------------------------------- attribution


def operands_of(hlo_text: str) -> dict:
    """instruction -> the instructions its line names after the ``=``
    (its operands, and the computations it calls), from the compiled text."""
    out = {}
    for line in hlo_text.splitlines():
        head = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line)
        if head:
            rest = line[head.end():].partition(", metadata=")[0]
            out[head.group(1)] = re.findall(r"%([\w.\-]+)", rest)
    return out


def consumers_of(operands: dict) -> dict:
    """instruction -> the instructions that take its result."""
    out = defaultdict(list)
    for name, taken in operands.items():
        for operand in taken:
            out[operand].append(name)
    return out


def body_path(body: list) -> str:
    """The path a fusion without one takes from its body's instructions:
    one of the (part, scope, pass) most of those that hold a name are of."""
    named = [p for p in body if holds_a_name(p)]
    if not named:
        return ""
    key = Counter(part_of(p) for p in named).most_common(1)[0][0]
    return next(p for p in named if part_of(p) == key)


def attributed_paths(events, bodies: dict, hlo_path: str) -> dict:
    """event name -> the path it is read by, for the events the compiled
    text gives none: its fusion's body's; else that of the instructions
    that take its result, through takers that have none either; else, for
    what only the step's result takes (a new parameter's copy to the layout
    it leaves in), that of the instructions it reads. Names that none of
    the three reaches are left out."""
    bare = {e.name for e in events if not is_a_path(e.path)}
    found = {name: path for name in bare
             if (path := body_path(bodies.get(name, ())))}
    left = bare - set(found)
    if not left or not os.path.exists(hlo_path):
        return found
    with open(hlo_path) as f:
        text = f.read()
    paths = tracing.op_paths(text)
    operands = operands_of(text)
    for beside in (consumers_of(operands), operands):
        frontier = {name: [name] for name in left}  # event -> instructions reached
        for _ in range(NEIGHBOURS_DEEP):
            for name, reached in list(frontier.items()):
                nxt = [n for i in reached for n in beside.get(i, ())]
                path = body_path([paths.get(n) or body_path(bodies.get(n, ()))
                                  for n in nxt])
                if path:
                    found[name] = path
                if path or not nxt:
                    del frontier[name]
                else:
                    frontier[name] = nxt
        left -= set(found)
    return found


# ------------------------------------------------------------------- table


def hlo_path_of(run: dict) -> str:
    return run.get("hlo_path") or os.path.join(run.get("out_dir", ""), "step.hlo.txt")


def table(run: dict):
    """The traced window's self times by the program's names, or None
    without a device trace. ``{"busy_s", "steps", "rows": {"part/scope":
    {pass: seconds}}, "unnamed": {"with a path" | opcode: seconds},
    "kernels": {kernel: [seconds a call]}}``; the run record keeps it."""
    if "step_table" not in run:
        run["step_table"] = _table(run)
    return run["step_table"]


def _table(run: dict):
    found = program_trace.of(run)
    if found is None:
        return None
    program, trace, device, window = found
    events = [e for e in trace.devices[device]
              if e.end > window[0] and e.start < window[1]]
    busy = tracing.busy_seconds(events, window)
    if not busy:
        return None
    read_by = attributed_paths(events, program.bodies, hlo_path_of(run))
    rows = defaultdict(lambda: defaultdict(float))
    unnamed, kernels = defaultdict(float), defaultdict(list)
    for e, t in tracing.self_times(events):
        path = e.path if is_a_path(e.path) else read_by.get(e.name, "")
        part, scope, pass_ = part_of(path)
        if not part:
            part = NO_NAME
            scope = "with a path" if is_a_path(e.path) else e.name.split(".")[0]
            unnamed[scope] += t
        rows["/".join(filter(None, (part, scope)))][pass_] += t
        kernel = tracing.kernel_of(e)
        if kernel:
            kernels[kernel].append(e.dur)
    steps = sum(1 for e in trace.host if e.name == "bench.step")
    return {"busy_s": busy, "steps": max(steps, 1),
            "rows": {k: dict(v) for k, v in rows.items()},
            "unnamed": dict(unnamed), "kernels": dict(kernels)}


def unnamed_seconds(found: dict) -> tuple:
    """(of events that have a path, of events that have none)."""
    with_path = found["unnamed"].get("with a path", 0.0)
    return with_path, sum(found["unnamed"].values()) - with_path


def render(found: dict) -> str:
    busy, steps = found["busy_s"], found["steps"]
    ms = 1e3 / steps
    total = defaultdict(lambda: defaultdict(float))  # a row and the rows under it
    for key, by_pass in found["rows"].items():
        parts = key.split("/")
        for depth in range(1, len(parts) + 1):
            for pass_, t in by_pass.items():
                total["/".join(parts[:depth])][pass_] += t
    lines = [
        f"step table: ms a step over {steps} traced steps, busy "
        f"{ms * busy:.3f}; self time by part and scope, a row holds the rows under it",
        f"{'part / scope':<34}" + "".join(f"{c:>10}" for c in COLUMNS)
        + f"{'total':>10}{'% busy':>8}",
    ]

    def say(key, name, depth):
        row = total[key]
        whole = sum(row.values())
        lines.append(
            f"{'  ' * depth + name:<34}"
            + "".join(f"{ms * row.get(p, 0.0):>10.3f}" for p in PASSES)
            + f"{ms * whole:>10.3f}{100 * whole / busy:>8.2f}"
        )
        under = [k for k in total if k.startswith(key + "/")
                 and k.count("/") == key.count("/") + 1]
        for k in sorted(under, key=lambda k: -sum(total[k].values())):
            if sum(total[k].values()) >= FOLD_UNDER * busy:
                say(k, k.rpartition("/")[2], depth + 1)

    first = [k for k in total if "/" not in k]
    for key in sorted(first, key=lambda k: (k == NO_NAME, -sum(total[k].values()))):
        say(key, key, 0)
    whole = sum(sum(total[k].values()) for k in first)
    with_path, without = unnamed_seconds(found)
    lines.append(
        f"rows sum to {ms * whole:.3f} ms, {100 * whole / busy:.2f}% of busy; no "
        f"name: {ms * with_path:.3f} ms of events that have a path, "
        f"{ms * without:.3f} of events that have none, nor a fusion body, a "
        "consumer or an operand that has"
    )
    lines.append("Pallas kernels: calls a step, median ms a call (least - most)")
    for kernel, durs in sorted(found["kernels"].items(), key=lambda kv: -sum(kv[1])):
        lines.append(
            f"  {kernel:<32}{len(durs) / steps:>8.2f}{1e3 * statistics.median(durs):>10.3f}"
            f" ({1e3 * min(durs):.3f} - {1e3 * max(durs):.3f})"
        )
    return "\n".join(lines)


def note(run: dict):
    """``table(run)``, and the table said once among the run's notes."""
    found = table(run)
    if found is not None:
        program_trace.note_once(run, "step_table", render(found))
    return found


def kept_run(directory: str) -> dict:
    """A run record, as the readers take it, from what ``--keep`` left."""
    xplane = glob.glob(os.path.join(directory, "*.xplane.pb"))[0]
    hlo_path = os.path.join(directory, "step.hlo.txt")
    with open(hlo_path) as f:
        text = f.read()
    return {"trace_data": tracing.load_xplane(xplane, text),
            "program_trace": program_trace.load_xplane(xplane, text).to_json(),
            "hlo_path": hlo_path, "notes": []}


if __name__ == "__main__":
    print(render(table(kept_run(sys.argv[1]))))
