"""Device time a decode step spends in its sliding-WINDOW attention
layers: the operations under the program's ``attn/window`` scope
(projections of 72 query heads, rotation, the paged kernel over the
blocks of a lane's ring that its window reaches, the out projection;
the ring's write and the gate are rows of their own) inside the
executions of ``_decode_chunk``.  Layer: engine programs.  Moves
``serve_tokens_per_s``."""

from benchmark.harness import scope_pattern


def read(ctx):
    table = scope_pattern.table(ctx, scope_pattern.DECODE)
    return table and table["ms"].get("attn/window")
