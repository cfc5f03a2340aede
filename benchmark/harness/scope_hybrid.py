"""Device time by the program's scopes for a decoder with delta-rule
linear-attention layers beside latent-attention ones
(``bailing_hybrid``): ``scope_table``'s reduction with this block's own
table of scopes, for a decode step AND for a prefill call.

``table(ctx, program)``: milliseconds by scope, by KIND of attention
layer (everything under ``attn/linear`` / ``attn/latent``, whatever
finer scope it lies in) and by kernel, over the operations that began
inside the WHOLE executions of ``program`` in the capture
(``scope_table.whole_executions``), divided by ``per`` (the steps of a
chunk for ``_decode_chunk``; for ``_prefill_piece`` the PIECES its
calls ran, from the ``prefill/dispatch`` spans that launched them: a
call of four pieces counts four, so the numbers are per 1024 tokens
whatever the schedule), read once a run and kept in ``ctx``; logged for
people.  ``None`` when the capture holds no such execution or none of
its operations lies under ``MARKER`` (a program without linear layers:
the parent commit).
"""

from __future__ import annotations

import collections

from benchmark.harness import (scope_table, scopes, step_stages,
                               trace as trace_lib)

#: First match on an operation's path wins: the state's and the rows'
#: writes, the gate, the linear layer's stages, then ``scope_table``'s
#: own (the latent layer's stages, the experts, the dense layer, the
#: head), then what is left of a layer's kind (its projections).
SCOPES = ("state_pool/write", "kv_pool/write", "attn/gate",
          "attn/linear/conv", "attn/linear/gates", "attn/linear/scan",
          "attn/linear/step") + tuple(
    s for s in scope_table.SCOPES if s != "kv_pool/write") + (
    "attn/linear", "attn/latent")
KINDS = ("attn/linear", "attn/latent")
MARKER = "attn/linear"
STEP_KERNEL = "delta_state_step"
SCAN = "attn/linear/scan"
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
    if head.startswith(STEP_KERNEL):
        return STEP_KERNEL
    return scope_table.kernel_of(name)


def by_scope(ops, executions):
    """``(seconds by scope, seconds by kind, seconds by kernel, calls by
    kernel)`` over the operations that began inside one of
    ``executions``, loops and conditionals left out; a kernel's seconds
    are counted under its scope too (the latent kernel, which
    ``scope_table`` files under no scope, under its name).  ``None``
    without ``MARKER``."""
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
        scope = scope_of(op.op_name)
        if kernel == scope_table.LATENT_KERNEL:
            scope = kernel
        agg[scope or scopes.PLUMBING] += op.dur
    if not kinds[MARKER]:
        return None
    return dict(agg), dict(kinds), dict(kernels), dict(calls)


def piece_calls(ctx: dict):
    """``[(attrs of the prefill/dispatch span, execution)]``: every
    WHOLE execution of ``_prefill_piece`` in the capture joined to the
    span that launched it (``step_stages``'s join); ``None`` where the
    program records no such span or the join cannot be made."""
    if "_hybrid_piece_calls" in ctx:
        return ctx["_hybrid_piece_calls"]
    pairs = None
    twins = step_stages.ring_twins(ctx, "prefill/dispatch")
    if twins:
        modules = sorted(ctx["trace"].devices[0].modules,
                         key=lambda ev: ev.start)
        whole = scope_table.whole_executions(ctx, modules, PIECE)
        level = step_stages.level_point(
            step_stages.program_spans(ctx),
            [ev for ev in modules if DECODE in ev.name])
        pairs = step_stages.join_in_order(twins, whole, level) or None
    ctx["_hybrid_piece_calls"] = pairs
    return pairs


def table(ctx: dict, program: str):
    """``{"ms": by scope, "kind_ms": by kind of layer, "kernel_ms": by
    kernel, "kernel_calls": by kernel, "n": what the sums were divided
    by, "program_ms": mean}`` of ``program``'s whole executions in this
    run's capture, each number a decode STEP's (``_decode_chunk``) or a
    prefill PIECE's (``_prefill_piece``; with ``tokens``, the real rows
    of the joined calls, which their linear layers scanned, and
    ``calls``); or ``None``."""
    key = "_hybrid_table" + program
    if key in ctx:
        return ctx[key]
    out = None
    tracer = ctx.get("tracer")
    if tracer is not None:
        ops, programs = scopes.load(tracer.directory)
        extra = {}
        if program == DECODE:
            runs = scope_table.whole_executions(ctx, programs, program)
            n = len(runs) * ctx["result"]["counters"]["chunk"]
        else:
            pairs = piece_calls(ctx) or []
            runs = [ex for _, ex in pairs]
            n = sum(a.get("pieces", 1) for a, _ in pairs)
            extra = {"calls": len(pairs), "tokens": sum(
                a.get("tokens", 0) for a, _ in pairs)}
        got = by_scope(ops, runs) if runs and n else None
        if got is not None:
            seconds, kinds, kernels, calls = got
            out = dict(
                ms={k: 1e3 * v / n for k, v in sorted(
                    seconds.items(), key=lambda kv: -kv[1])},
                kind_ms={k: 1e3 * v / n for k, v in kinds.items()},
                kernel_ms={k: 1e3 * v / n for k, v in kernels.items()},
                kernel_calls={k: v / n for k, v in calls.items()},
                n=n, program_ms=1e3 * sum(ev.dur for ev in runs) / n,
                **extra)
            ctx["log"](phase="ms_by_scope.hybrid", program=program,
                       executions=len(runs), **out)
    ctx[key] = out
    return out
