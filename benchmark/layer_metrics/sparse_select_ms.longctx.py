"""Device ms a prefill piece spends in the choice of the 2048 best rows a query (scope attn/select),
all layers, over the whole executions of ``_prefill_piece`` in the
capture (``harness/scope_share.py``).  Layer: engine programs.  Moves
``serve_tokens_per_s``."""

from benchmark.harness import scope_share


def read(ctx):
    return scope_share.stage_ms(ctx, "select")
