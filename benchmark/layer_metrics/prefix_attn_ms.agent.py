"""Device time a prefill piece (1,024 prompt tokens) spends in its
attention layers of both kinds: the operations under ``attn/full``
(projections, rotation, ``prefix_attention``'s walk of the tiles the
prompt holds so far with keys of 192 beside values of 128, the out
projection) and ``attn/window`` (the same over the tiles a window of
128 and the piece's own rows reach, from a sink) inside the joined
executions of ``_prefill_piece``, over the pieces they ran; each kind's
part goes to the log.  Layer: engine programs.  Moves
``serve_tokens_per_s``."""

from benchmark.harness import scope_sink


def read(ctx):
    table = scope_sink.piece_table(ctx)
    if not table:
        return None
    full = table["ms"].get("attn/full", 0.0)
    window = table["ms"].get("attn/window", 0.0)
    ctx["log"](phase="prefix_attn_ms.agent", full_ms=full,
               window_ms=window, pieces=table["n"])
    return full + window
