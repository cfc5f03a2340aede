"""The paged attention kernel's share of its roofline over two kinds of
cache with two row shapes: the least time the chip could take for a
step's calls (``costs_sink.paged_attention_step``: the blocks the full
layers' walks reach, ``engine/step``'s ``kv_blocks``, at 2,560 B a
row, and those the window layers' reach, ``kv_window_blocks``, at
5,120 B, of the steps the capture overlapped, each read once; 40,960
operations a row; memory-bound) over the device time a step of the
kernel's events of both kinds (the ``tpu_custom_call``s named by
``attention._paged_decode_step`` under ``attn/full`` and
``attn/window``).  Layer: kernels / program roofline.  Moves
``serve_tokens_per_s``."""

from benchmark.harness import costs, costs_sink, scope_pattern, scope_table


def read(ctx):
    table = scope_pattern.table(ctx, scope_pattern.DECODE)
    full = scope_table.step_attr_mean(ctx, "kv_blocks", captured=True)
    window = scope_table.step_attr_mean(ctx, "kv_window_blocks",
                                        captured=True)
    if not table or full is None or window is None or ctx["peaks"] is None:
        return None
    kernel_ms = sum(table["kernel_ms"].get("paged_attn/" + kind, 0.0)
                    for kind in ("full", "window"))
    if not kernel_ms:
        return None
    counters = ctx["result"]["counters"]
    flops, nbytes = costs_sink.paged_attention_step(
        ctx["config"], full, window, counters["kv_block_size"],
        counters["slots"])
    least, _ = costs.roofline_seconds(flops, nbytes, ctx["peaks"])
    return costs.share_pct(least, 1e-3 * kernel_ms,
                           "paged_attn_roofline.agent")
