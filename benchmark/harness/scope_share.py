"""Device time by the program's scopes for the latent-attention block
with a learned selection and a share of the experts (``deepseek_v32``):
``scope_table``'s reduction with this block's own table of scopes, for
a decode step AND for a prefill piece.

``table(ctx, program)``: milliseconds by scope and by Pallas kernel
over the operations that began inside the WHOLE executions of
``program`` in the capture (``scope_table.whole_executions``), divided
by ``per`` (the steps of a chunk for ``_decode_chunk``, 1 for
``_prefill_piece``), read once a run and kept in ``ctx``; logged for
people.  ``None`` when the capture holds no such execution or none of
its operations lies under ``MARKER`` (a program without the selection:
the parent commit).
"""

from __future__ import annotations

import collections

from benchmark.harness import scope_table, scopes, trace as trace_lib

#: First match on an operation's path wins: the selection's stages
#: before the attention scopes they call into (``attn/sparse`` holds the
#: up-projection of the rows it walks), ``moe/route_groups`` before the
#: router it lies in, then ``scope_table``'s own.
SCOPES = ("index_pool/write", "attn/index_q", "attn/index_k",
          "attn/index_score", "attn/select", "attn/sparse",
          "moe/route_groups") + scope_table.SCOPES
MARKER = "attn/select"
INDEX_KERNEL = "paged_index_scores"
KERNELS = (INDEX_KERNEL, scope_table.LATENT_KERNEL)
DECODE, PIECE = "_decode_chunk", "_prefill_piece"
#: The three stages of the selection, as the ``sparse_*`` metrics sum
#: the scopes (of a piece; ``sparse_*_step_ms`` of a decode step).
STAGES = {
    "index": ("attn/index_q", "attn/index_k", "attn/index_score",
              "index_pool/write"),
    "select": ("attn/select",),
    "attn": ("attn/sparse",),
}


def scope_of(op_name: str):
    path = "/" + (op_name or "") + "/"
    return next((s for s in SCOPES if "/" + s + "/" in path), None)


def kernel_of(name: str):
    """Which Pallas kernel an operation's event is, by its name."""
    if "tpu_custom_call" not in name:
        return None
    head = name.lstrip("%").split(" ", 1)[0]
    return next((k for k in KERNELS if head.startswith(k)),
                scope_table.kernel_of(name))


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
        kernel = kernel_of(op.name)
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
    key = "_share_table" + program
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
            ctx["log"](phase="ms_by_scope.longctx", program=program,
                       executions=len(runs), **out)
    ctx[key] = out
    return out


def stage_ms(ctx: dict, stage: str, program: str = PIECE):
    """Device ms a prefill piece (or, for ``DECODE``, a decode step)
    spends in one stage of the selection (``STAGES``), or ``None``."""
    got = table(ctx, program)
    if not got:
        return None
    return sum(got["ms"].get(s, 0.0) for s in STAGES[stage])
