"""Prompt tokens prefilled per second of device time of the
``_prefill_piece`` program: the real prompt tokens a piece carried on
average over the whole load (the engine's count of pieces, the clients'
prompt lengths) over the mean device time of the program's executions
in the traced window.  Layer: engine programs.  Moves ``gap_p90_ms``."""

from benchmark.harness import trace


def read(ctx):
    piece_s = trace.mean_execution_seconds(ctx["trace"].devices[0],
                                           "_prefill_piece")
    counters = ctx["result"]["counters"]
    pieces = counters.get("prefill_pieces")
    if piece_s is None or not pieces:
        return None
    return counters["prefill_prompt_tokens"] / pieces / piece_s
