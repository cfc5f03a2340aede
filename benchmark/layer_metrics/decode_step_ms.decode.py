"""Device time of one decode step: the mean execution of the
``_decode_chunk`` program in the traced window, divided by the steps in
a chunk.  Layer: engine programs.  Moves ``serve_tokens_per_s``."""

from benchmark.harness import trace


def read(ctx):
    chunk_s = trace.mean_execution_seconds(ctx["trace"].devices[0],
                                           "_decode_chunk")
    if chunk_s is None:
        return None
    return 1e3 * chunk_s / ctx["result"]["counters"]["chunk"]
