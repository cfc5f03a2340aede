"""The latent-attention kernel's share of its roofline: the least time
the chip could take for one call (``costs_moe.latent_attention_call``
over the blocks the lanes walk, ``engine/step``'s ``kv_blocks`` by the
kernel's own rule, each row of 1,152 bytes read once; memory-bound)
over the kernel's mean device time a call.  Layer: kernels / program
roofline.  Moves ``serve_tokens_per_s``."""

from benchmark.harness import costs, costs_moe, scope_table


def read(ctx):
    table = scope_table.decode_table(ctx)
    blocks = scope_table.step_attr_mean(ctx, "kv_blocks", captured=True)
    if not table or not blocks or ctx["peaks"] is None:
        return None
    ms = table["kernel_ms"].get(scope_table.LATENT_KERNEL)
    calls = table["kernel_calls"].get(scope_table.LATENT_KERNEL)
    if not ms or not calls:
        return None
    counters = ctx["result"]["counters"]
    flops, nbytes = costs_moe.latent_attention_call(
        ctx["config"], blocks, counters["kv_block_size"], counters["slots"])
    least, _ = costs.roofline_seconds(flops, nbytes, ctx["peaks"])
    return costs.share_pct(least, 1e-3 * ms / calls,
                           "latent_attn_roofline")
