"""The windowed absorbed decode kernel's share of its roofline: the
least time the chip could take for a step's calls
(``costs_latents.latent_window_step``: the blocks the window layers'
walks reach, ``engine/step``'s ``kv_window_blocks`` of the steps the
capture overlapped, at 2,176 B a row, each read once, in six layers;
memory-bound) over the device time a step of the kernel's events (the
``tpu_custom_call``s named ``paged_latent_window``).  Layer: kernels /
program roofline.  Moves ``serve_tokens_per_s``."""

from benchmark.harness import costs, costs_latents, scope_latents, scope_table


def read(ctx):
    table = scope_latents.table(ctx, scope_latents.DECODE)
    blocks = scope_table.step_attr_mean(ctx, "kv_window_blocks",
                                        captured=True)
    if not table or not blocks or ctx["peaks"] is None:
        return None
    ms = table["kernel_ms"].get(scope_latents.WINDOW_KERNEL)
    if not ms:
        return None
    counters = ctx["result"]["counters"]
    flops, nbytes = costs_latents.latent_window_step(
        ctx["config"], blocks, counters["kv_block_size"], counters["slots"])
    least, _ = costs.roofline_seconds(flops, nbytes, ctx["peaks"])
    return costs.share_pct(least, 1e-3 * ms, "latent_window_roofline.notes")
