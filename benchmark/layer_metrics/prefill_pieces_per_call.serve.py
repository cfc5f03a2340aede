"""Prefill pieces one call of the piece program runs, on average over
the window: the sum of ``engine/step``'s ``pieces`` over the sum of its
``piece_calls``.  1 where every piece is a program of its own; up to
``prefill_budget / prefill_chunk`` where the pieces of a step belong to
one prompt and run as one call, which reads every held expert's kernel
once and not once a piece.  Pieces and calls a step go to the log.
Read from the program's ring of spans.  Layer: engine programs.  Moves
``serve_tokens_per_s``."""

from benchmark.harness import piece_calls


def read(ctx):
    return piece_calls.read(ctx, "prefill_pieces_per_call.serve")
