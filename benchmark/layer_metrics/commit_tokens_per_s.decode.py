"""Output tokens per second taken between commits: from the end of the
first commit wholly inside the window to the end of the last, every
token received in between (``loadgen.committed_rate``).  It holds a
whole number of chunk periods and leaves the window's edges out, so it
is steadier than ``serve_tokens_per_s`` and blind to a stall at either
edge.  Layer: engine host loop.  Moves ``serve_tokens_per_s``."""


def read(ctx):
    return ctx["result"]["counters"].get("committed_tokens_per_s")
