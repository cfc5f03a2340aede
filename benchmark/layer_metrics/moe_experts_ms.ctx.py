"""Device time a decode step spends in the routed experts: the
operations under the program's ``moe/experts`` scope (three grouped
matmuls and the gating product between them, every expert layer)
inside the executions of ``_decode_chunk``.  The table by scope goes to
the log.  Layer: engine programs.  Moves ``serve_tokens_per_s``."""

from benchmark.harness import scope_table


def read(ctx):
    table = scope_table.decode_table(ctx)
    return table and table["ms"].get("moe/experts")
