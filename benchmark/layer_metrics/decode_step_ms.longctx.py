"""Device time of one decode step under the learned selection: the mean
WHOLE execution of ``_decode_chunk`` in the traced window, divided by
the steps in a chunk (every step scores a lane's index keys, chooses
2048 rows and attends over those; 16 of 256 experts are here).  Layer:
engine programs.  Moves ``serve_tokens_per_s``."""

from benchmark.harness import scope_table


def read(ctx):
    runs = scope_table.whole_executions(ctx, ctx["trace"].devices[0].modules)
    if not runs:
        return None
    return (1e3 * sum(ev.dur for ev in runs) / len(runs)
            / ctx["result"]["counters"]["chunk"])
