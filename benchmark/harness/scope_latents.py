"""Device time by the program's scopes for a decoder whose latent
attention layers are of two kinds (``dots3_note``: full layers over the
rows an indexer picks, window layers over a ring): ``scope_table``'s
reduction with this block's own table of scopes, for a decode step AND
for a prefill piece.

``table(ctx, program)``: milliseconds by scope, by KIND of attention
layer (everything under ``attn/latent`` / ``attn/latent_window``,
whatever finer scope it lies in) and by Pallas kernel, over the
operations that began inside the WHOLE executions of ``program`` in the
capture (``scope_table.whole_executions``), divided by ``per`` (the
steps of a chunk for ``_decode_chunk``, 1 for ``_prefill_piece``: an
engine whose attention chooses its rows runs one piece a call), read
once a run and kept in ``ctx``; logged for people.  ``None`` when the
capture holds no such execution or none of its operations lies under
``MARKER`` (a program without latent window layers: the parent commit).
"""

from __future__ import annotations

import collections

from benchmark.harness import (scope_share, scope_table, scopes,
                               trace as trace_lib)

#: First match on an operation's path wins: a window layer's write of
#: its ring before the full layers' write, the gate, then
#: ``scope_share``'s own (the selection's stages, the latents, the
#: experts, the dense layer, the head).
SCOPES = ("kv_pool/write/window", "attn/gate") + scope_share.SCOPES
KINDS = ("attn/latent_window", "attn/latent")
MARKER = "attn/latent_window"
#: The absorbed decode kernel over a ring, by the name its call carries
#: (``pallas_kernels.paged_latent_attention(window=)``).
WINDOW_KERNEL = "paged_latent_window"
DECODE, PIECE = "_decode_chunk", "_prefill_piece"


def scope_of(op_name: str):
    path = "/" + (op_name or "") + "/"
    return next((s for s in SCOPES if "/" + s + "/" in path), None)


def kind_of(op_name: str):
    """The kind of attention layer an operation lies in, or None."""
    path = "/" + (op_name or "") + "/"
    return next((k for k in KINDS if "/" + k + "/" in path), None)


def kernel_of(name: str):
    """Which Pallas kernel an operation's event is, by its name."""
    if "tpu_custom_call" not in name:
        return None
    head = name.lstrip("%").split(" ", 1)[0]
    if head.startswith(WINDOW_KERNEL):
        return WINDOW_KERNEL
    return scope_share.kernel_of(name)


def by_scope(ops, executions):
    """``(seconds by scope, seconds by kind, seconds by kernel, calls by
    kernel)`` over the operations that began inside one of
    ``executions``, loops and conditionals left out; a kernel's seconds
    are counted under its scope and its kind too.  ``None`` without
    ``MARKER``."""
    spans = sorted((ev.start, ev.start + ev.dur) for ev in executions)
    agg, kinds, kernels, calls = (collections.Counter() for _ in range(4))
    i = 0
    for op in sorted(ops, key=lambda op: op.start):
        while i < len(spans) and spans[i][1] <= op.start:
            i += 1
        if i == len(spans) or op.start < spans[i][0]:
            continue
        if trace_lib.CONTAINER_RE.match(op.name):
            continue
        kernel = kernel_of(op.name)
        if kernel:
            kernels[kernel] += op.dur
            calls[kernel] += 1
        kind = kind_of(op.op_name)
        if kind:
            kinds[kind] += op.dur
        agg[scope_of(op.op_name) or kernel or scopes.PLUMBING] += op.dur
    if not kinds[MARKER]:
        return None
    return dict(agg), dict(kinds), dict(kernels), dict(calls)


def table(ctx: dict, program: str):
    """``{"ms": by scope, "kind_ms": by kind of layer, "kernel_ms": by
    kernel, "kernel_calls": by kernel, "n": executions x per,
    "program_ms": mean}`` of ``program``'s whole executions in this
    run's capture, each a step (``_decode_chunk``) or a piece
    (``_prefill_piece``); or ``None``."""
    key = "_latents_table" + program
    if key in ctx:
        return ctx[key]
    out = None
    tracer = ctx.get("tracer")
    if tracer is not None:
        ops, programs = scopes.load(tracer.directory)
        runs = scope_table.whole_executions(ctx, programs, program)
        got = by_scope(ops, runs) if runs else None
        if got is not None:
            per = (ctx["result"]["counters"]["chunk"]
                   if program == DECODE else 1)
            n = len(runs) * per
            seconds, kinds, kernels, calls = got
            out = {
                "ms": {k: 1e3 * v / n for k, v in sorted(
                    seconds.items(), key=lambda kv: -kv[1])},
                "kind_ms": {k: 1e3 * v / n for k, v in kinds.items()},
                "kernel_ms": {k: 1e3 * v / n for k, v in kernels.items()},
                "kernel_calls": {k: v / n for k, v in calls.items()},
                "n": n,
                "program_ms": 1e3 * sum(ev.dur for ev in runs) / n}
            ctx["log"](phase="ms_by_scope.notes", program=program,
                       executions=len(runs), **out)
    ctx[key] = out
    return out
