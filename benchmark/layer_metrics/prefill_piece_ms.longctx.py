"""Device time of one prefill piece under the learned selection: the
mean WHOLE execution of ``_prefill_piece`` in the traced window (1024
tokens of one prompt at 4k-24k rows: index scores and the choice over
the rows the lane holds, attention restricted to the chosen rows, the
16 experts held here).  Its table by scope goes to the log.  Layer:
engine programs.  Moves ``serve_tokens_per_s``."""

from benchmark.harness import scope_table


def read(ctx):
    runs = scope_table.whole_executions(
        ctx, ctx["trace"].devices[0].modules, "_prefill_piece")
    if not runs:
        return None
    return 1e3 * sum(ev.dur for ev in runs) / len(runs)
