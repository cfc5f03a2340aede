"""Device time a decode step spends in its FULL attention layers: the
operations under the program's ``attn/full`` scope (projections,
rotation of half of each head under YaRN, the paged kernel over what
the lanes hold, the out projection; the pool's write and the gate are
rows of their own) inside the executions of ``_decode_chunk``.  Layer:
engine programs.  Moves ``serve_tokens_per_s``."""

from benchmark.harness import scope_pattern


def read(ctx):
    table = scope_pattern.table(ctx, scope_pattern.DECODE)
    return table and table["ms"].get("attn/full")
