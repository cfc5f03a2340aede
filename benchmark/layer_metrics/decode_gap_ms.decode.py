"""Median idle gap on the device between consecutive executions of the
``_decode_chunk`` program (time in which nothing ran: the host loop's
harvest, refill and staging).  Layer: engine host loop.  Moves
``serve_tokens_per_s``."""

from benchmark.harness import stats, trace


def read(ctx):
    gaps = trace.gaps_between(ctx["trace"].devices[0], "_decode_chunk")
    if not gaps:
        return None
    return 1e3 * stats.median(gaps)
