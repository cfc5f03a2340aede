"""Lanes busy in a decode chunk: the mean of ``lanes`` (active at the
dispatch, as the engine counts them) over the ``engine/step`` spans of
the window that dispatched a chunk, weighted by the step's duration.
Read from the program's ring of spans.  Layer: engine host loop.  Moves
``serve_tokens_per_s``."""

from benchmark.harness import spans


def read(ctx):
    got = spans.window_steps(ctx)
    if not got:
        return None
    steps, dropped = got
    ran = [s for s in steps if s.attrs.get("lanes")]
    weight = sum(s.dur for s in ran)
    if weight <= 0:
        return None
    ctx["log"](phase="decode_lanes_mean", steps=len(steps),
               dispatched=len(ran), ring_dropped=dropped,
               slots=ctx["result"]["counters"].get("slots"),
               positions_mean=sum(s.attrs.get("positions", 0) * s.dur
                                  for s in ran) / weight,
               committed=sum(s.attrs.get("committed", 0) for s in steps))
    return sum(s.attrs["lanes"] * s.dur for s in ran) / weight
