"""Share of the chip's memory bandwidth a decode step reaches: the
bytes the step has to move (weights once, keys and values at the lanes'
real lengths; ``costs.decode_step_bytes``) over the device time of a
step, over the published bandwidth.  Layer: kernels / program roofline.
Moves ``serve_tokens_per_s``."""

from benchmark.harness import costs, serve, trace


def read(ctx):
    chunk_s = trace.mean_execution_seconds(ctx["trace"].devices[0],
                                           "_decode_chunk")
    tracer = ctx["tracer"]
    if chunk_s is None or tracer is None or ctx["peaks"] is None:
        return None
    counters = ctx["result"]["counters"]
    tokens, _ = serve.lane_tokens_mean(counters["records"], tracer.t0,
                                       tracer.t1)
    need = costs.decode_step_bytes(ctx["config"], [tokens])
    return costs.share_pct(need / (chunk_s / counters["chunk"]),
                           ctx["peaks"]["hbm_bytes_per_s"],
                           "decode_hbm_pct")
