"""Host work an engine step carries: the median self time of the
program's ``engine/step`` spans over the whole window, a step's
duration less its ``*/wait`` children (the reads that block on the
device).  With the device never idle the host is hidden, and this says
how short a chunk may get before the host loop sets the pace.  Read
from the program's ring of spans.  Layer: engine host loop.  Moves
``serve_tokens_per_s``."""

from benchmark.harness import spans, stats


def read(ctx):
    self_ms = spans.self_times_ms(ctx, "host_self_ms")
    return self_ms and stats.median(self_ms)
