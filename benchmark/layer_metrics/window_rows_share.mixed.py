"""Blocks a window layer's walk reads over blocks its lanes hold, in
percent, over the decode steps of the capture: ``engine/step``'s
``kv_window_blocks`` over its ``kv_blocks``, both by the kernel's own
walk rule (``paged_blocks_walked``, with and without the window's first
block) over the lengths of the step's lanes.  What the bounded cache
leaves of a window layer's read: at 5.5k rows a lane about a tenth.
Layer: engine host loop.  Moves ``serve_tokens_per_s``."""

from benchmark.harness import spans


def read(ctx):
    got = spans.window_steps(ctx)
    if not got:
        return None
    steps = [s.attrs for s in got[0]
             if s.traced and s.attrs.get("kv_window_blocks")
             and s.attrs.get("kv_blocks")]
    held = sum(a["kv_blocks"] for a in steps)
    if not held:
        return None
    read_ = sum(a["kv_window_blocks"] for a in steps)
    ctx["log"](phase="window_rows_share.mixed", steps=len(steps),
               kv_blocks=held, kv_window_blocks=read_)
    return 100.0 * read_ / held
