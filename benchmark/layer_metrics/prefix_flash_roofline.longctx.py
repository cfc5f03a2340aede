"""The prefill pieces' latent-attention kernel's share of the matrix
unit's peak: over the executions of ``_prefill_piece`` that the capture
joined to their ``prefill/dispatch`` spans, the least time the MXU
could take for what the calls' attention requires
(``costs_flash_latent.call_flops``: the (query, key) pairs the queries
SEE, ``min(p + 1, index_topk)`` a query at p, 2 x 128 x (192 + 128)
operations a pair, and each row they can see up-projected once a
layer; the call's position at the least its span's ``rows``, ``pieces``
and ``tokens`` allow; compute-bound) over the device time of the
``tpu_custom_call``s named ``prefix_flash_latent`` inside those
executions.  It reads low by construction: a masked dense walk computes
every row held where a query sees 2,048.  Each part's least, the
kernel's calls a piece and what the capture's ``prefill/piece`` spans
say of ``flash_layers`` go to the log.  ``None`` where the capture
holds no such call (a program whose pieces walk in XLA: the parent
commit).  Layer: kernels / program roofline.  Moves
``serve_tokens_per_s``."""

from benchmark.harness import (costs, costs_flash_latent, scope_hybrid,
                               scopes, step_stages)

NAME = "prefix_flash_roofline.longctx"
KERNEL = "prefix_flash_latent"


def kernel_seconds(ops, executions) -> tuple:
    """``(seconds, calls)`` of the kernel's events that began inside
    one of ``executions``."""
    spans = [(ev.start, ev.start + ev.dur) for ev in executions]
    mine = [op.dur for op in ops
            if "tpu_custom_call" in op.name
            and op.name.lstrip("%").startswith(KERNEL)
            and any(lo <= op.start < hi for lo, hi in spans)]
    return sum(mine), len(mine)


def read(ctx):
    tracer = ctx.get("tracer")
    chunk = ((ctx.get("traffic") or {}).get("engine") or {}).get(
        "prefill_chunk")
    pairs = scope_hybrid.piece_calls(ctx) if tracer is not None else None
    if not pairs or not chunk or ctx["peaks"] is None:
        return None
    ops, _ = scopes.load(tracer.directory)
    seconds, calls = kernel_seconds(ops, [ex for _, ex in pairs])
    if not calls:
        return None
    flops = {}
    for attrs, _ in pairs:
        n = attrs.get("pieces", 1)
        for part, f in costs_flash_latent.call_flops(
                ctx["config"], attrs["rows"], n * chunk,
                attrs.get("tokens", n * chunk)).items():
            flops[part] = flops.get(part, 0.0) + f
    peak = ctx["peaks"]["bf16_flops_per_s"]
    pieces = sum(a.get("pieces", 1) for a, _ in pairs)
    said = step_stages.ring_twins(ctx, "prefill/piece") or ()
    ctx["log"](phase=NAME, calls_per_piece=calls / pieces, pieces=pieces,
               flash_layers=sorted({a.get("flash_layers")
                                    for _, a in said}, key=str),
               kernel_ms_per_piece=1e3 * seconds / pieces,
               least_ms_per_piece={k: 1e3 * v / peak / pieces
                                   for k, v in flops.items()})
    return costs.share_pct(sum(flops.values()) / peak, seconds, NAME)
