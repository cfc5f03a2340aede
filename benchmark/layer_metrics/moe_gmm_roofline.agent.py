"""The grouped matmuls' share of their roofline in a decode step, over
the 16 experts held here: the least time the chip could take
(``costs_moe.gmm_layer_call``: the kernels of the held experts a step
hits, ``engine/step``'s ``experts_hit``, read once, plus the rows that
fell on them, the step's pairs x ``routed_here``, in and out;
memory-bound) in every layer whose ``moe_layer_freq`` is 1, over the
device time of the ``gmm`` kernels' events a step.  Layer: kernels /
program roofline.  Moves ``serve_tokens_per_s``."""

from benchmark.harness import costs, costs_moe, scope_pattern, scope_table


def read(ctx):
    table = scope_pattern.table(ctx, scope_pattern.DECODE)
    hit = scope_table.step_attr_mean(ctx, "experts_hit", captured=True)
    here = scope_table.step_attr_mean(ctx, "routed_here", captured=True)
    if (not table or hit is None or here is None or ctx["peaks"] is None
            or not table["kernel_ms"].get(scope_table.GMM_KERNEL)):
        return None
    cfg = ctx["config"]
    layers = sum(cfg["moe_layer_freq"][:cfg["num_hidden_layers"]])
    pairs = ctx["result"]["counters"]["slots"] * cfg["num_experts_per_tok"]
    flops, nbytes = costs_moe.gmm_layer_call(cfg, hit, here * pairs)
    least, _ = costs.roofline_seconds(layers * flops, layers * nbytes,
                                      ctx["peaks"])
    return costs.share_pct(
        least, 1e-3 * table["kernel_ms"][scope_table.GMM_KERNEL],
        "moe_gmm_roofline.agent")
