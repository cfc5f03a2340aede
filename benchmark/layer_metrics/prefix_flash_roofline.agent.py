"""The prefill pieces' attention kernel's share of the matrix unit's
peak: over the executions of ``_prefill_piece`` that the capture joined
to their ``prefill/dispatch`` spans, the least time the MXU could take
for the (query, key) pairs the calls' queries SEE
(``costs_flash.call_flops``: 2 x 64 x (192 + 128) operations a pair; a
full layer's pairs from the span's ``rows``, ``pieces`` and ``tokens``
at the least position they allow, a window layer's at most 128 a
query; compute-bound) over the device time of the ``tpu_custom_call``s
named ``prefix_flash_attention`` inside those executions.  Each kind's
part, the kernel's calls a piece and what the capture's
``prefill/piece`` spans say of ``flash_layers`` go to the log.  ``None``
where the
capture holds no such call (a program whose pieces walk in XLA: the
parent commit).  Layer: kernels / program roofline.  Moves
``serve_tokens_per_s``."""

from benchmark.harness import (costs, costs_flash, scope_hybrid,
                               scope_pattern, scopes, step_stages)

KERNEL = "prefix_flash_attention"


def kernel_seconds(ops, executions) -> tuple:
    """``(seconds by kind of layer, calls)`` of the kernel's events that
    began inside one of ``executions``; the kind is the scope the
    event lies in (``attn/full``, ``attn/window``)."""
    spans = [(ev.start, ev.start + ev.dur) for ev in executions]
    seconds, calls = {}, 0
    for op in ops:
        if ("tpu_custom_call" not in op.name
                or not op.name.lstrip("%").startswith(KERNEL)
                or not any(lo <= op.start < hi for lo, hi in spans)):
            continue
        kind = (scope_pattern.scope_of(op.op_name) or "attn/?").split("/")[1]
        seconds[kind] = seconds.get(kind, 0.0) + op.dur
        calls += 1
    return seconds, calls


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
        for kind, f in costs_flash.call_flops(
                ctx["config"], attrs["rows"], n * chunk,
                attrs.get("tokens", n * chunk)).items():
            flops[kind] = flops.get(kind, 0.0) + f
    peak = ctx["peaks"]["bf16_flops_per_s"]
    pieces = sum(a.get("pieces", 1) for a, _ in pairs)
    said = step_stages.ring_twins(ctx, "prefill/piece") or ()
    ctx["log"](phase="prefix_flash_roofline.agent",
               calls_per_piece=calls / pieces, pieces=pieces,
               flash_layers=sorted({a.get("flash_layers")
                                    for _, a in said}, key=str),
               kernel_ms_per_piece={k: 1e3 * v / pieces
                                    for k, v in seconds.items()},
               least_ms_per_piece={k: 1e3 * v / peak / pieces
                                   for k, v in flops.items()},
               pct={k: 100.0 * flops.get(k, 0.0) / peak / v
                    for k, v in seconds.items() if v})
    return costs.share_pct(sum(flops.values()) / peak,
                           sum(seconds.values()),
                           "prefix_flash_roofline.agent")
