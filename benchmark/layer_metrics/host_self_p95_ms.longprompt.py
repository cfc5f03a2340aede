"""The 95th percentile of an engine step's host self time over the
whole window (``engine/step`` less its ``*/wait`` children): the steps
that stage, run and finalize prefills beside a decode chunk.  Read from
the program's ring of spans.  Layer: engine host loop.  Moves
``gap_p90_ms``."""

from benchmark.harness import spans, stats


def read(ctx):
    self_ms = spans.self_times_ms(ctx, "host_self_p95_ms")
    return self_ms and stats.percentile(self_ms, 95.0)
