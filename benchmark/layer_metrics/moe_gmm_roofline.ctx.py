"""The grouped matmuls' share of their roofline in a decode step: the
least time the chip could take for a step's routed SwiGLUs
(``costs_moe.gmm_layer_call``: the weights of the experts hit, as the
program counts them into ``engine/step``'s ``experts_hit``, read once,
plus the rows in and out; memory-bound) over the device time of the
``gmm`` kernels' events a step.  Layer: kernels / program roofline.
Moves ``serve_tokens_per_s``."""

from benchmark.harness import costs, costs_moe, scope_table


def read(ctx):
    table = scope_table.decode_table(ctx)
    hit = scope_table.step_attr_mean(ctx, "experts_hit", captured=True)
    if (not table or hit is None or ctx["peaks"] is None
            or not table["kernel_ms"].get(scope_table.GMM_KERNEL)):
        return None
    cfg = ctx["config"]
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    rows = ctx["result"]["counters"]["slots"] * cfg["num_experts_per_tok"]
    flops, nbytes = costs_moe.gmm_layer_call(cfg, hit, rows)
    least, _ = costs.roofline_seconds(layers * flops, layers * nbytes,
                                      ctx["peaks"])
    return costs.share_pct(
        least, 1e-3 * table["kernel_ms"][scope_table.GMM_KERNEL],
        "moe_gmm_roofline")
