"""What the engine's caller holds it for between two steps: the median
of ``engine/step``'s ``away_ms`` (from the previous step's exit to this
one's entry) over the window's steps clear of the capture.  The caller
is ``server/driver.py``'s loop: submissions taken in, commits handed to
the requests' streams, retirements.  The 75th percentile and the sum's
share of the steps' time go to the log.  Read from the program's ring
of spans.  Layer: gateway / driver.  Moves ``serve_tokens_per_s``."""

from benchmark.harness import step_stages


def read(ctx):
    return step_stages.read_driver_away(ctx, "driver_away.serve")
