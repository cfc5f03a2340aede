"""Device ms a prefill piece spends in the indexer: its queries, keys, their write into the index cache and the scores of every row the lane holds (scopes attn/index_q, attn/index_k, index_pool/write, attn/index_score),
all layers, over the whole executions of ``_prefill_piece`` in the
capture (``harness/scope_share.py``).  Layer: engine programs.  Moves
``serve_tokens_per_s``."""

from benchmark.harness import scope_share


def read(ctx):
    return scope_share.stage_ms(ctx, "index")
