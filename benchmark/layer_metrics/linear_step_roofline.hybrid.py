"""The state kernel's share of its roofline: the least time the chip
could take for one call (``costs_hybrid.state_step_call``: every lane's
state of 32 x 128 x 128 float32 read once and written once, the step's
rows beside it; memory-bound) over the mean device time of a
``tpu_custom_call`` named ``delta_state_step``.  Layer: kernels /
program roofline.  Moves ``serve_tokens_per_s``."""

from benchmark.harness import costs, costs_hybrid, scope_hybrid


def read(ctx):
    table = scope_hybrid.table(ctx, scope_hybrid.DECODE)
    if not table or ctx["peaks"] is None:
        return None
    ms = table["kernel_ms"].get(scope_hybrid.STEP_KERNEL)
    calls = table["kernel_calls"].get(scope_hybrid.STEP_KERNEL)
    if not ms or not calls:
        return None
    flops, nbytes = costs_hybrid.state_step_call(
        ctx["config"], ctx["result"]["counters"]["slots"])
    least, _ = costs.roofline_seconds(flops, nbytes, ctx["peaks"])
    return costs.share_pct(least, 1e-3 * ms / calls,
                           "linear_step_roofline.hybrid")
