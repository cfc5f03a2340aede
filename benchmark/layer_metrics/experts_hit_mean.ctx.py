"""Distinct experts a decode step reads in an expert layer: the mean of
``engine/step``'s ``experts_hit`` (counted on the device from the
router's choices, every lane, returned with the chunk's tokens) over
the window's steps.  It sets the bytes a step moves: 64 x (1 - (63/64)
^ 128) = 55.5 of 64 for 128 uniform choices.  Layer: engine host loop.
Moves ``serve_tokens_per_s``."""

from benchmark.harness import scope_table


def read(ctx):
    hit = scope_table.step_attr_mean(ctx, "experts_hit")
    if hit is not None:
        ctx["log"](phase="experts_hit_mean", experts_hit=hit,
                   expert_load_cv=scope_table.step_attr_mean(
                       ctx, "expert_load_cv"),
                   kv_blocks=scope_table.step_attr_mean(ctx, "kv_blocks"),
                   lanes=scope_table.step_attr_mean(ctx, "lanes"),
                   # what the rooflines take: the capture's own steps
                   captured={k: scope_table.step_attr_mean(ctx, k, True)
                             for k in ("experts_hit", "kv_blocks",
                                       "lanes")})
    return hit
