"""The paged index-score kernel's share of its roofline: the least time
the chip could take for one call (``costs_share.index_scores_call``
over the index keys the lanes hold, ``engine/step``'s ``rows_scored``,
each key of 256 bytes read once; memory-bound) over the kernel's mean
device time a call (the ``tpu_custom_call`` events named
``paged_index_scores`` inside the executions of ``_decode_chunk``).
Layer: kernels / program roofline.  Moves ``serve_tokens_per_s``."""

from benchmark.harness import costs, costs_share, scope_share, scope_table


def read(ctx):
    table = scope_share.table(ctx, scope_share.DECODE)
    rows = scope_table.step_attr_mean(ctx, "rows_scored", captured=True)
    if not table or not rows or ctx["peaks"] is None:
        return None
    ms = table["kernel_ms"].get(scope_share.INDEX_KERNEL)
    calls = table["kernel_calls"].get(scope_share.INDEX_KERNEL)
    if not ms or not calls:
        return None
    flops, nbytes = costs_share.index_scores_call(
        ctx["config"], rows, ctx["result"]["counters"]["slots"])
    least, _ = costs.roofline_seconds(flops, nbytes, ctx["peaks"])
    return costs.share_pct(least, 1e-3 * ms / calls,
                           "paged_index_scores_roofline.longctx")
