"""Device time by the program's scopes for a decoder with full and
window layers of two row shapes and a sink (``mimo_v2``): the scopes are
``scope_pattern``'s (``attn/full``, ``attn/window``, both pools'
writes, the experts), so a decode step is read by ``scope_pattern.table``
as it stands.  What is here is a prefill PIECE's table per 1,024 tokens
whatever the schedule: ``scope_pattern.by_scope`` over the WHOLE
executions of ``_prefill_piece`` that ``scope_hybrid.piece_calls``
joins to the ``prefill/dispatch`` spans that launched them, divided by
the pieces those calls ran (a call of four pieces counts four).

``None`` where the capture holds no such execution, the program records
no such span, or none of the operations lies under
``scope_pattern.MARKER`` (a program without window layers).
"""

from __future__ import annotations

from benchmark.harness import scope_hybrid, scope_pattern, scopes


def piece_table(ctx: dict):
    """``{"ms": by scope, "n": pieces, "calls": calls, "tokens": real
    rows, "program_ms": mean}`` a prefill piece of this run's capture;
    or ``None``."""
    key = "_sink_piece_table"
    if key in ctx:
        return ctx[key]
    out = None
    tracer = ctx.get("tracer")
    pairs = scope_hybrid.piece_calls(ctx) if tracer is not None else None
    if pairs:
        ops, _ = scopes.load(tracer.directory)
        runs = [ex for _, ex in pairs]
        n = sum(a.get("pieces", 1) for a, _ in pairs)
        got = scope_pattern.by_scope(ops, runs) if n else None
        if got is not None:
            seconds = got[0]
            out = {
                "ms": {k: 1e3 * v / n for k, v in sorted(
                    seconds.items(), key=lambda kv: -kv[1])},
                "n": n, "calls": len(pairs),
                "tokens": sum(a.get("tokens", 0) for a, _ in pairs),
                "program_ms": 1e3 * sum(ev.dur for ev in runs) / n}
            ctx["log"](phase="ms_by_scope.agent",
                       program=scope_pattern.PIECE, **out)
    ctx[key] = out
    return out
