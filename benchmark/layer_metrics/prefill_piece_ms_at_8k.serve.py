"""Device time of one prefill piece whose attention walks 8,192 cache
rows: every WHOLE execution of ``_prefill_piece`` in the capture joined
to the ``prefill/dispatch`` span that launched it, and the least-squares
line of device ms on the span's ``rows`` read at 8,192 (the mean, and
the log says so, with fewer than three pairs or one ``rows`` value).  A
piece is a fixed part (projections, the experts of its 1,024 tokens)
and a part that grows with the rows walked; a capture's pieces lie
where the schedule put them, so their mean compares unlike pieces and
the line compares like with like.  The pairs, intercept, slope and the
residuals' spread go to the log.  Layer: engine programs.  Moves
``serve_tokens_per_s``."""

from benchmark.harness import step_stages


def read(ctx):
    return step_stages.read_piece_at(ctx, "prefill_piece_at_8k.serve",
                                     rows=8192)
