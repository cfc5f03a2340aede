"""Device time of one prefill piece with latent layers of two kinds: the
mean WHOLE execution of ``_prefill_piece`` in the traced window (1024
tokens of one prompt at 1k-14k rows: the full layers' index scores,
choice and one kernel over the rows held, the window layers' XLA walk
over the two or three tiles their window reaches, the 16 experts held
here).  Its table by scope goes to the log.  Layer: engine programs.
Moves ``serve_tokens_per_s``."""

from benchmark.harness import scope_latents, scope_table


def read(ctx):
    runs = scope_table.whole_executions(
        ctx, ctx["trace"].devices[0].modules, "_prefill_piece")
    if not runs:
        return None
    scope_latents.table(ctx, scope_latents.PIECE)      # for people
    return 1e3 * sum(ev.dur for ev in runs) / len(runs)
