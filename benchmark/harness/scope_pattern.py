"""Device time by the program's scopes for a decoder whose attention
layers differ by a pattern (``laguna``: full and sliding-window layers,
a per-head gate, a share of the experts): ``scope_table``'s reduction
with this block's own table of scopes, for a decode step AND for a
prefill piece.

``table(ctx, program)``: milliseconds by scope and by kernel over the
operations that began inside the WHOLE executions of ``program`` in the
capture (``scope_table.whole_executions``), divided by ``per`` (the
steps of a chunk for ``_decode_chunk``, 1 for ``_prefill_piece``), read
once a run and kept in ``ctx``; logged for people.  ``None`` when the
capture holds no such execution or none of its operations lies under
``MARKER`` (a program without window layers: the parent commit).
"""

from __future__ import annotations

import collections

from benchmark.harness import scope_table, scopes, trace as trace_lib

#: First match on an operation's path wins: a window layer's write of
#: its ring before the full layers' write, the gate, then the layer's
#: kind (which holds its projections, its rotation and its kernel),
#: then ``scope_table``'s own (the experts, the dense layer, the head).
SCOPES = ("kv_pool/write/window", "kv_pool/write", "attn/gate",
          "attn/window", "attn/full") + tuple(
    s for s in scope_table.SCOPES if s != "kv_pool/write")
MARKER = "attn/window"
#: The paged attention kernel's events: the ``tpu_custom_call``s the
#: calling method names; by the kind of layer whose scope they lie in.
PAGED_KERNEL = "_paged_decode_step"
DECODE, PIECE = "_decode_chunk", "_prefill_piece"


def scope_of(op_name: str):
    path = "/" + (op_name or "") + "/"
    return next((s for s in SCOPES if "/" + s + "/" in path), None)


def kernel_of(name: str, scope):
    """Which Pallas kernel an operation's event is: the paged attention
    kernel by its layer's kind, or one of ``scope_table``'s."""
    if "tpu_custom_call" not in name:
        return None
    head = name.lstrip("%").split(" ", 1)[0]
    if PAGED_KERNEL in head and scope in ("attn/window", "attn/full"):
        return "paged_attn/" + scope.split("/")[1]
    return scope_table.kernel_of(name)


def by_scope(ops, executions):
    """``(seconds by scope, seconds by kernel, calls by kernel)`` over
    the operations that began inside one of ``executions``, loops and
    conditionals left out; a kernel's seconds are counted under its
    scope too.  ``None`` without ``MARKER``."""
    spans = sorted((ev.start, ev.start + ev.dur) for ev in executions)
    agg, kernels, calls = (collections.Counter() for _ in range(3))
    marked = False
    i = 0
    for op in sorted(ops, key=lambda op: op.start):
        while i < len(spans) and spans[i][1] <= op.start:
            i += 1
        if i == len(spans) or op.start < spans[i][0]:
            continue
        if trace_lib.CONTAINER_RE.match(op.name):
            continue
        scope = scope_of(op.op_name)
        marked = marked or scope == MARKER
        kernel = kernel_of(op.name, scope)
        if kernel:
            kernels[kernel] += op.dur
            calls[kernel] += 1
        agg[scope or scopes.PLUMBING] += op.dur
    return (dict(agg), dict(kernels), dict(calls)) if marked else None


def table(ctx: dict, program: str):
    """``{"ms": by scope, "kernel_ms": by kernel, "kernel_calls": by
    kernel, "n": executions x per, "program_ms": mean}`` of
    ``program``'s whole executions in this run's capture, each a step
    (``_decode_chunk``) or a piece (``_prefill_piece``); or ``None``."""
    key = "_pattern_table" + program
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
            seconds, kernels, calls = got
            out = {
                "ms": {k: 1e3 * v / n for k, v in sorted(
                    seconds.items(), key=lambda kv: -kv[1])},
                "kernel_ms": {k: 1e3 * v / n for k, v in kernels.items()},
                "kernel_calls": {k: v / n for k, v in calls.items()},
                "n": n,
                "program_ms": 1e3 * sum(ev.dur for ev in runs) / n}
            ctx["log"](phase="ms_by_scope.mixed", program=program,
                       executions=len(runs), **out)
    ctx[key] = out
    return out
