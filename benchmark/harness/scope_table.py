"""Device time of a decode step by the program's scopes, for programs
whose scopes ``harness/scopes.py``'s fixed table does not name (the
latent-attention, routed-expert block).

``decode_table(ctx)``: milliseconds a decode step by scope over the
operations that began inside a WHOLE execution of ``_decode_chunk``
(one the capture's edges did not cut: this cell's capture is short,
``trace_s`` 1, set when the reduction's attribution of idle gaps was
quadratic in the capture and a step here ~900 small operations; it is
one sweep since PR 28, and ``trace_s`` stays 1 until a ``benchmark`` PR
that may move readings raises it), read once a run and kept in
``ctx``.  The Pallas kernels are classes of
their own, found by the names their calls carry
(``paged_latent_attention``, megablox's ``gmm``); what lies under none
of ``SCOPES`` and is no kernel is ``scopes.PLUMBING``.  ``None`` when
the capture has no such execution or no operation under ``MARKER``
(a program without these scopes).
"""

from __future__ import annotations

import collections

from benchmark.harness import scopes, spans, trace as trace_lib

#: First match on an operation's path wins: the block's own regions
#: before the shared modules inside them (a norm inside ``attn/q_latent``
#: is the query's making; the shared expert's ``mlp`` is ``moe/shared``).
SCOPES = ("kv_pool/write", "moe/router", "moe/sort", "moe/experts",
          "moe/shared", "moe/combine", "attn/q_latent", "attn/kv_latent",
          "attn/absorb", "attn/out", "mlp", "head", "sample", "embed",
          "norm")
MARKER = "moe/experts"
LATENT_KERNEL = "paged_latent_attention"
GMM_KERNEL = "gmm"
PROGRAM = "_decode_chunk"


def scope_of(op_name: str):
    path = "/" + (op_name or "") + "/"
    return next((s for s in SCOPES if "/" + s + "/" in path), None)


def kernel_of(name: str):
    """Which Pallas kernel an operation's event is, by its name."""
    if "tpu_custom_call" not in name:
        return None
    head = name.lstrip("%").split(" ", 1)[0]
    if head.startswith(LATENT_KERNEL):
        return LATENT_KERNEL
    if head.split(".")[0] == GMM_KERNEL:
        return GMM_KERNEL
    return None


def by_scope(ops, executions):
    """``(seconds by scope, seconds by kernel, calls by kernel)`` over
    the operations that began inside one of ``executions``; loops and
    conditionals left out.  A kernel's seconds are counted under its
    scope too (``gmm`` under ``moe/experts``); the latent kernel, under
    no scope, is its own row.  ``None`` without ``MARKER``."""
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
        kernel = kernel_of(op.name)
        if kernel:
            kernels[kernel] += op.dur
            calls[kernel] += 1
        if kernel == LATENT_KERNEL:
            scope = LATENT_KERNEL
        agg[scope or scopes.PLUMBING] += op.dur
    return (dict(agg), dict(kernels), dict(calls)) if marked else None


def whole_executions(ctx: dict, programs, needle: str = PROGRAM) -> list:
    """The executions of a program that lie wholly inside the capture:
    not the one running when it began nor the one it ended in."""
    lo, hi = ctx["trace_window"]
    edge = 1e-6
    return [ev for ev in programs if needle in ev.name
            and ev.start > lo + edge and ev.start + ev.dur < hi - edge]


def decode_table(ctx: dict):
    """``{"ms": by scope a step, "kernel_ms": by kernel a step,
    "kernel_calls": by kernel a step, "steps": n}`` of this run's
    capture, or ``None``."""
    if "_decode_table" in ctx:
        return ctx["_decode_table"]
    table = None
    tracer = ctx.get("tracer")
    if tracer is not None:
        ops, programs = scopes.load(tracer.directory)
        runs = whole_executions(ctx, programs)
        got = by_scope(ops, runs) if runs else None
        pieces = whole_executions(ctx, programs, "_prefill_piece")
        if got is not None and pieces:
            # For people: where a prefill piece's time goes.
            seconds = by_scope(ops, pieces)[0]
            ctx["log"](phase="prefill_ms_per_piece_by_scope.ctx",
                       executions=len(pieces),
                       piece_ms=1e3 * sum(ev.dur for ev in pieces)
                       / len(pieces),
                       by_scope_ms={k: 1e3 * v / len(pieces)
                                    for k, v in sorted(
                                        seconds.items(),
                                        key=lambda kv: -kv[1])})
        if got is not None:
            steps = len(runs) * ctx["result"]["counters"]["chunk"]
            seconds, kernels, calls = got
            table = {
                "ms": {k: 1e3 * v / steps for k, v in sorted(
                    seconds.items(), key=lambda kv: -kv[1])},
                "kernel_ms": {k: 1e3 * v / steps
                              for k, v in kernels.items()},
                "kernel_calls": {k: v / steps for k, v in calls.items()},
                "steps": steps}
            ctx["log"](phase="decode_ms_per_step_by_scope.ctx",
                       executions=len(runs), by_scope_ms=table["ms"],
                       sum_ms=sum(table["ms"].values()),
                       kernel_ms=table["kernel_ms"],
                       kernel_calls=table["kernel_calls"],
                       program_ms=1e3 * sum(ev.dur for ev in runs) / steps)
    ctx["_decode_table"] = table
    return table


def step_attr_mean(ctx: dict, attr: str, captured: bool = False):
    """Mean of an ``engine/step`` attr over the window's steps that
    carry it and not as zero (a step that dispatched no chunk counts 0
    blocks); ``None`` where none does.  ``captured``: only the steps
    the profiler's capture overlapped, for a reader that sets a count
    beside device times of that capture (the lanes fill and empty over
    a window, so its mean is another step's count)."""
    got = spans.window_steps(ctx)
    if not got:
        return None
    values = [s.attrs[attr] for s in got[0]
              if s.attrs.get(attr) and (s.traced or not captured)]
    return sum(values) / len(values) if values else None
