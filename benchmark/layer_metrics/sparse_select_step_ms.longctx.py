"""Device ms a decode step spends choosing: ``lax.top_k`` over a lane's scores and the gather of the chosen latent rows into a pool of their own (scope attn/select),
all layers, over the whole executions of ``_decode_chunk`` in the
capture, a step (``harness/scope_share.py``).  Layer: engine programs.
Moves ``serve_tokens_per_s``."""

from benchmark.harness import scope_share


def read(ctx):
    return scope_share.stage_ms(ctx, "select", scope_share.DECODE)
