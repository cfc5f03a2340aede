"""Device time a decode step spends under none of the program's scopes
and in no kernel (``harness/scope_pattern.py``; the per-head gate has
its own scope, ``attn/gate``, and is no part of this): by exclusion the
slices, updates, reshapes and copies that carry two kinds of pool and
the step's state between the named regions.  Layer: engine programs.
Moves ``serve_tokens_per_s``."""

from benchmark.harness import scope_pattern, scopes


def read(ctx):
    table = scope_pattern.table(ctx, scope_pattern.DECODE)
    return table and table["ms"].get(scopes.PLUMBING, 0.0)
