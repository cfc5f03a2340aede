"""Device time a decode step spends in its FULL latent attention layers:
every operation under the program's ``attn/latent`` scope (the two
latents, the indexer's queries, keys and scores, the choice and the
gather of the chosen rows, the absorbed kernel over them, the gate, the
out projection; three layers) inside the executions of
``_decode_chunk``.  Layer: engine programs.  Moves
``serve_tokens_per_s``."""

from benchmark.harness import scope_latents


def read(ctx):
    table = scope_latents.table(ctx, scope_latents.DECODE)
    return table and table["kind_ms"].get("attn/latent")
