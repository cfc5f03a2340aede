"""Device time one prefill piece (1024 prompt tokens) spends in the
chunked scans of its six linear layers: the operations under the
program's ``attn/linear/scan`` scope inside the whole executions of
``_prefill_piece``, over the pieces their calls ran.  Layer: engine
programs.  Moves ``serve_tokens_per_s``."""

from benchmark.harness import scope_hybrid


def read(ctx):
    table = scope_hybrid.table(ctx, scope_hybrid.PIECE)
    return table and table["ms"].get(scope_hybrid.SCAN)
