"""Device time of one prefill piece (1,024 prompt tokens): the WHOLE
executions of ``_prefill_piece`` in the traced window, each joined to
the ``prefill/dispatch`` span that launched it, their time over the
PIECES they ran (a call of four pieces counts four), so the number is
per 1,024 tokens whatever the schedule.  Its table by scope goes to the
log.  Layer: engine programs.  Moves ``serve_tokens_per_s``."""

from benchmark.harness import scope_sink


def read(ctx):
    table = scope_sink.piece_table(ctx)
    return table and table["program_ms"]
