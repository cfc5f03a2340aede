"""Prefill pieces one call of the piece program runs, on average over
the window, in the open-loop cell: the sum of ``engine/step``'s
``pieces`` over the sum of its ``piece_calls``.  A decode chunk waits
behind the prefill calls of its step, up to four pieces of 1024 tokens
in one to four programs.  Read from the program's ring of spans.
Layer: engine programs.  Moves ``gap_p90_ms``."""

from benchmark.harness import piece_calls


def read(ctx):
    return piece_calls.read(ctx, "prefill_pieces_per_call.longprompt")
