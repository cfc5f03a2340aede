"""Device time of one prefill piece: the mean WHOLE execution of
``_prefill_piece`` in the traced window (1024 tokens of one prompt of
512-16k rows: a full layer walks the tiles its lane holds, a window
layer at most three; every held expert's kernel read once).  Its table
by scope goes to the log.  Layer: engine programs.  Moves
``serve_tokens_per_s``."""

from benchmark.harness import scope_pattern, scope_table


def read(ctx):
    runs = scope_table.whole_executions(
        ctx, ctx["trace"].devices[0].modules, scope_pattern.PIECE)
    if not runs:
        return None
    scope_pattern.table(ctx, scope_pattern.PIECE)      # for the log
    return 1e3 * sum(ev.dur for ev in runs) / len(runs)
