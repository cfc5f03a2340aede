"""Device time a decode step spends under none of the program's scopes
and in no kernel (``harness/scope_table.py``): by exclusion the slices,
updates, reshapes and copies that carry the latent pools and the step's
state between the named regions.  The second reading of
``decode_plumbing_ms.decode``'s quantity, at another row size and with
the layers unrolled.  Layer: engine programs.  Moves
``serve_tokens_per_s``."""

from benchmark.harness import scope_table, scopes


def read(ctx):
    table = scope_table.decode_table(ctx)
    return table and table["ms"].get(scopes.PLUMBING, 0.0)
