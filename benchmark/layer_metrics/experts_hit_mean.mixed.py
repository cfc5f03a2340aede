"""Distinct experts, of the 64 held here, that a decode step reads in
an expert layer: the mean of ``engine/step``'s ``experts_hit`` over the
window's steps; ``experts_held``, ``routed_here`` (the share of a
step's (token, choice) pairs that fell here: 1/4 under uniform choice)
and ``expert_load_cv`` go to the log.  Layer: engine host loop.  Moves
``serve_tokens_per_s``."""

from benchmark.harness import scope_table


def read(ctx):
    hit = scope_table.step_attr_mean(ctx, "experts_hit")
    if hit is None or scope_table.step_attr_mean(
            ctx, "kv_window_blocks") is None:
        return None
    ctx["log"](phase="experts_hit_mean.mixed", experts_hit=hit,
               **{k: scope_table.step_attr_mean(ctx, k)
                  for k in ("experts_held", "routed_here",
                            "expert_load_cv", "kv_blocks",
                            "kv_window_blocks", "lanes")})
    return hit
