"""Host time of an engine step that no span names: the median over the
window's steps clear of the capture of a step's duration less the union
of ALL spans its thread recorded inside it.  Its log is the stage
table: ms a step by span, the self time of each (what no span nested in
it covers: mean, median, 75th percentile), and the slowest step's.
Read from the program's ring of spans.  Layer: engine host loop.  Moves
``serve_tokens_per_s``."""

from benchmark.harness import step_stages


def read(ctx):
    return step_stages.read_step_unnamed(ctx, "step_stages.serve")
