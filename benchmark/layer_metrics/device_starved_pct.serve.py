"""Share of the engine's time in which it knew the device's queue empty
while it had work: the sum of ``engine/step``'s ``starved_ms`` over the
sum of the steps' durations and ``away_ms`` (the caller's passes
between them), over the window's steps clear of the capture.  The
engine counts it on its own clock: from a poll that found the newest
program's output ready to the next enqueue, so a lower bound of the
device's idle with work pending, over the whole window and with no
capture running.  Drains a step, the sums, and the same over the
capture's steps beside the capture's own idle in their extent go to the
log.  Read from the program's ring of spans.  Layer: engine host loop.
Moves ``serve_tokens_per_s``."""

from benchmark.harness import step_stages


def read(ctx):
    return step_stages.read_device_starved(ctx, "device_starved.serve")
