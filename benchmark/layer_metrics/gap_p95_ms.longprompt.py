"""The 95th percentile of the gap between consecutive tokens of one
request, client side, over every request of the window: the statistic
``gap_p95_ms`` reports end to end in the decode cell, as the runner
takes it.  Here seven gaps in eight are zero, so it is about the 60th
percentile of the commit periods, which lie on levels ~60 ms apart by
how many prefill pieces a period carried; by how the seed's order lays
pieces on chunks it falls on one level or the next (~527 or ~580 ms), a
spread of 9% that no bound can hold (PERF.md section 2).  So it is read
per layer, as the tail that sees prefill beside decode, and
``gap_p90_ms`` is the cell's end-to-end metric.  Layer: engine host
loop.  Moves ``gap_p90_ms``."""


def read(ctx):
    return ctx["result"]["end_to_end"].get("gap_p95_ms")
