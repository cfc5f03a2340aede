"""Device time a decode step spends in its WINDOW latent attention
layers: every operation under the program's ``attn/latent_window`` scope
(the two latents, the ring's write, the absorbed kernel over the blocks
the window reaches, the gate, the out projection; six layers) inside the
executions of ``_decode_chunk``.  Layer: engine programs.  Moves
``serve_tokens_per_s``."""

from benchmark.harness import scope_latents


def read(ctx):
    table = scope_latents.table(ctx, scope_latents.DECODE)
    return table and table["kind_ms"].get("attn/latent_window")
