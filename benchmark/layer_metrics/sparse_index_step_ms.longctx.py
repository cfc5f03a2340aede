"""Device ms a decode step spends in the indexer: its query, its key and the key's write into the index pool, and the scores of every row the lanes hold through their tables, the ``paged_index_scores`` kernel (scopes attn/index_q, attn/index_k, index_pool/write, attn/index_score),
all layers, over the whole executions of ``_decode_chunk`` in the
capture, a step (``harness/scope_share.py``).  Layer: engine programs.
Moves ``serve_tokens_per_s``."""

from benchmark.harness import scope_share


def read(ctx):
    return scope_share.stage_ms(ctx, "index", scope_share.DECODE)
