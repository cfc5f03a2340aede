"""Device time a decode step spends in its LATENT attention layer: every
operation under the program's ``attn/latent`` scope (the query
projection, the row's making and its write, the absorbed kernel over
what the lanes hold, the gate, the out projection of one layer) inside
the executions of ``_decode_chunk``.  Layer: engine programs.  Moves
``serve_tokens_per_s``."""

from benchmark.harness import scope_hybrid


def read(ctx):
    table = scope_hybrid.table(ctx, scope_hybrid.DECODE)
    return table and table["kind_ms"].get("attn/latent")
