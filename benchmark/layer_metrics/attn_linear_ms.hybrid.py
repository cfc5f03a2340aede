"""Device time a decode step spends in its LINEAR attention layers: every
operation under the program's ``attn/linear`` scope (projections, the
short convolution, the gates, the state kernel ``delta_state_step``, the
state's write, the output norm, gate and projection of six layers)
inside the executions of ``_decode_chunk``.  Layer: engine programs.
Moves ``serve_tokens_per_s``."""

from benchmark.harness import scope_hybrid


def read(ctx):
    table = scope_hybrid.table(ctx, scope_hybrid.DECODE)
    return table and table["kind_ms"].get("attn/linear")
