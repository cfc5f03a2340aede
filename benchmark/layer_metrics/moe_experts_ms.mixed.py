"""Device time a decode step spends in the routed experts held here:
the operations under the program's ``moe/experts`` scope (three grouped
matmuls over 64 of 256 experts and the gating product between them,
every expert layer) inside the executions of ``_decode_chunk``.  Layer:
engine programs.  Moves ``serve_tokens_per_s``."""

from benchmark.harness import scope_pattern


def read(ctx):
    table = scope_pattern.table(ctx, scope_pattern.DECODE)
    return table and table["ms"].get("moe/experts")
