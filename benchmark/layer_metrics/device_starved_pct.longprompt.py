"""Share of the engine's time in which it knew the device's queue empty
while it had work, under arrivals at a fixed rate: the sum of
``engine/step``'s ``starved_ms`` over the sum of the steps' durations
and ``away_ms`` (``device_starved_pct.serve``'s reader).  Idle with no
request in the engine is not in it: the engine charges an empty queue
only while a lane decodes, a task is staged or a request is queued.
Read from the program's ring of spans.  Layer: engine host loop.  Moves
``gap_p90_ms``."""

from benchmark.harness import step_stages


def read(ctx):
    return step_stages.read_device_starved(ctx, "device_starved.longprompt")
