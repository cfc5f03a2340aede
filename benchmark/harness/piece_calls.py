"""How many prefill pieces one call of the piece program ran.

The engine runs the consecutive pieces of one prompt that ride one step
as ONE ``_prefill_piece`` call of several pieces, so that the call
reads every weight once and not once a piece.  ``engine/step`` counts
both: ``pieces`` (of ``prefill_chunk`` tokens, as before) and
``piece_calls`` (programs launched).  Their ratio over the window says
how often the mechanism engages under the cell's prompts and budget.

A program that does not count ``piece_calls`` (a parent commit that
predates it) gives ``None``, never an error.
"""

from __future__ import annotations

from benchmark.harness import spans, stats


def read(ctx: dict, phase: str):
    """Sum of ``pieces`` over sum of ``piece_calls`` of the window's
    ``engine/step`` spans (the ring: every step of the window, capture
    or none); pieces and calls a step that ran any go to the log."""
    got = spans.window_steps(ctx)
    if not got:
        return None
    steps, dropped = got
    ran = [s.attrs for s in steps if s.attrs.get("piece_calls")]
    calls = sum(a["piece_calls"] for a in ran)
    if not calls:
        return None
    pieces = sum(a.get("pieces", 0) for a in ran)
    ctx["log"](phase=phase, steps=len(steps), with_pieces=len(ran),
               ring_dropped=dropped, pieces=pieces, piece_calls=calls,
               pieces_a_step=stats.summarize([a.get("pieces", 0)
                                              for a in ran]),
               calls_a_step=stats.summarize([a["piece_calls"]
                                             for a in ran]),
               budget=ctx.get("traffic", {}).get("engine", {}).get(
                   "prefill_budget"))
    return pieces / calls
