"""The fused paged-attention kernel's share of its roofline: the least
time the chip could take for one call (``costs.paged_attention_call``
at the lanes' real lengths: memory-bound, keys and values read once)
over the kernel's mean device time a call.  The kernel's events are the
``tpu_custom_call`` operations named ``attention._paged_decode_step``
(the call site's name; the Pallas call itself sets none).  Layer:
kernels / program roofline.  Moves ``serve_tokens_per_s``."""

from benchmark.harness import costs, serve, trace


def read(ctx):
    evs = trace.op_events(ctx["trace"].devices[0],
                          "_paged_decode_step", "tpu_custom_call")
    tracer = ctx["tracer"]
    if not evs or tracer is None or ctx["peaks"] is None:
        return None
    tokens, lanes = serve.lane_tokens_mean(
        ctx["result"]["counters"]["records"], tracer.t0, tracer.t1)
    if lanes <= 0:
        return None
    per_lane = tokens / lanes
    flops, nbytes = costs.paged_attention_call(
        ctx["config"], [per_lane] * max(int(round(lanes)), 1))
    least, _ = costs.roofline_seconds(flops, nbytes, ctx["peaks"])
    per_call = sum(ev.dur for ev in evs) / len(evs)
    return costs.share_pct(least, per_call, "paged_attn_roofline")
