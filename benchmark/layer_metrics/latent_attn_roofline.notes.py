"""The absorbed decode kernel's share of its roofline in the FULL
layers, where it walks the rows the indexer chose and not the rows the
lanes hold: the least time the chip could take for one call
(``costs_moe.latent_attention_call`` over ``engine/step``'s
``rows_selected`` of the steps the capture overlapped, each row of
1,152 bytes read once; memory-bound) over the kernel's mean device time
a call (the ``tpu_custom_call``s named ``paged_latent_attention``; the
window layers' calls are named ``paged_latent_window`` and have
``latent_window_roofline.notes``).  ``latent_attn_roofline.ctx`` counts
the blocks held (``kv_blocks``), which this kernel never reads here.
Layer: kernels / program roofline.  Moves ``serve_tokens_per_s``."""

from benchmark.harness import costs, costs_moe, scope_latents, scope_table


def read(ctx):
    table = scope_latents.table(ctx, scope_latents.DECODE)
    rows = scope_table.step_attr_mean(ctx, "rows_selected", captured=True)
    if not table or not rows or ctx["peaks"] is None:
        return None
    ms = table["kernel_ms"].get(scope_table.LATENT_KERNEL)
    calls = table["kernel_calls"].get(scope_table.LATENT_KERNEL)
    if not ms or not calls:
        return None
    counters = ctx["result"]["counters"]
    block = counters["kv_block_size"]
    flops, nbytes = costs_moe.latent_attention_call(
        ctx["config"], rows / block, block, counters["slots"])
    least, _ = costs.roofline_seconds(flops, nbytes, ctx["peaks"])
    return costs.share_pct(least, 1e-3 * ms / calls,
                           "latent_attn_roofline.notes")
