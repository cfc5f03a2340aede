"""Device time of one prefill piece of the latent-attention,
routed-expert program: the mean WHOLE execution of ``_prefill_piece``
in the traced window (a piece is ``prefill_chunk`` tokens of one
prompt: every expert of every layer read once, attention over the whole
cache under a mask).  Pieces share the engine's steps with the decode
chunks, and here take about half of the device's time, so a piece's
time sets how many lanes decode.  Its table by scope goes to the log
with ``decode_plumbing_ms.ctx``'s.  Layer: engine programs.  Moves
``serve_tokens_per_s``."""

from benchmark.harness import scope_table


def read(ctx):
    runs = scope_table.whole_executions(
        ctx, ctx["trace"].devices[0].modules, "_prefill_piece")
    if not runs:
        return None
    return 1e3 * sum(ev.dur for ev in runs) / len(runs)
