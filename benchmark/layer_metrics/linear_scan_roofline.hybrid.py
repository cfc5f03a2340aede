"""The chunked scan's share of the roofline of THE RECURRENCE ITSELF:
the least time the chip could take for the real rows the captured
prefill calls scanned (``costs_hybrid.scan_call``: 8 x 128 x 128
operations a token and head, q, k, v and the decay in and o out, the
state once a call, in each of the linear layers; the rows are
``tokens`` of the ``prefill/dispatch`` spans, a call's padding being
the identity) over the device time under ``attn/linear/scan`` inside
those calls.  The count is the
mathematics', not the chunked form's, so a change of chunk size or a
kernel moves the time alone.  Layer: kernels / program roofline.  Moves
``serve_tokens_per_s``."""

from benchmark.harness import costs, costs_hybrid, scope_hybrid


def read(ctx):
    table = scope_hybrid.table(ctx, scope_hybrid.PIECE)
    if not table or ctx["peaks"] is None or not table.get("tokens"):
        return None
    ms = table["ms"].get(scope_hybrid.SCAN)
    if not ms:
        return None
    cfg = ctx["config"]
    flops, nbytes = costs_hybrid.scan_call(cfg, table["tokens"],
                                           table["calls"])
    layers = costs_hybrid.linear_layers(cfg)
    least, _ = costs.roofline_seconds(layers * flops, layers * nbytes,
                                      ctx["peaks"])
    return costs.share_pct(least, 1e-3 * ms * table["n"],
                           "linear_scan_roofline.hybrid")
