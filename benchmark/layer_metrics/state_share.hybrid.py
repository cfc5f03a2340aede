"""The recurrent state's share of what a decode step's caches hold, in
percent, over the window's steps: ``engine/step``'s ``state_bytes``
(live lanes x a lane's state and tail x linear layers) over itself plus
its ``kv_bytes`` (the latent rows the step's walk reaches: ``kv_blocks``
x the block's rows x a stored row, 640 values in bf16).  What a model of
full attention would hold as rows that this one holds as state, whatever
the context.  Layer: engine host loop.  Moves ``serve_tokens_per_s``."""

from benchmark.harness import spans


def read(ctx):
    got = spans.window_steps(ctx)
    if not got:
        return None
    steps = [s.attrs for s in got[0] if s.attrs.get("state_bytes")]
    state = sum(a["state_bytes"] for a in steps)
    if not state:
        return None
    rows = sum(a.get("kv_bytes", 0) for a in steps)
    ctx["log"](phase="state_share.hybrid", steps=len(steps),
               state_bytes=state, row_bytes=rows)
    return 100.0 * state / (state + rows)
