"""Device time a decode step spends under none of the program's scopes
and in no kernel (``harness/scope_hybrid.py``): by exclusion the slices,
updates, reshapes and copies that carry two kinds of cache and the
step's state between the named regions.  Layer: engine programs.  Moves
``serve_tokens_per_s``."""

from benchmark.harness import scope_hybrid, scopes


def read(ctx):
    table = scope_hybrid.table(ctx, scope_hybrid.DECODE)
    return table and table["ms"].get(scopes.PLUMBING, 0.0)
