"""Device time of one decode step of the latent-attention, routed-expert
program: the mean WHOLE execution of ``_decode_chunk`` in the traced
window (one the capture's edges did not cut; this cell's capture is
short, ``harness/scope_table.py`` says why), divided by the steps in a
chunk.  Its steps read ~53 of 64 experts a layer and the lanes' latent
rows.  Layer: engine programs.  Moves ``serve_tokens_per_s``."""

from benchmark.harness import scope_table


def read(ctx):
    runs = scope_table.whole_executions(ctx, ctx["trace"].devices[0].modules)
    if not runs:
        return None
    return (1e3 * sum(ev.dur for ev in runs) / len(runs)
            / ctx["result"]["counters"]["chunk"])
