#!/usr/bin/env python3
"""The three paged decode walks ALONE on the chip, at the benchmark
cells' shapes, over a sweep of ``pallas_kernels.PAGED_FOLD_ROWS``.

    chiprun -- python3 tools/bench_paged_walks.py [--rows 128,256,512]
        [--cases agent.full,ctx.glm] [--iters 30]

One JSON line a (case, rows) to stdout and to
``chiprun_out/bench_paged_walks.jsonl``: milliseconds a call (``iters``
calls inside ONE program, so that the program's one dispatch is little
of the time: at 5 it was a third of it, PERF.md section 6), the share of
the roofline of the bytes of the blocks the lanes HOLD at 819 GB/s (the
benchmark's rule for ``paged_attn_roofline.*``), the rows a step really
holds (``_paged_fold`` caps them by a row's cost in fast memory), the
seconds to trace and lower one instance and to compile it, and the
largest difference from the ``*_reference`` over four lanes.  Lane
lengths are drawn like the cell's traffic.  PERF.md section 6 (PR 50)
holds the sweep that chose the constant.  A chip tool: it refuses to
give a time on another backend.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from tensorflow_train_distributed_tpu.ops import pallas_kernels as pk  # noqa: E402

BF16, BS, HBM_BYTES_PER_S = jnp.bfloat16, 16, 819e9
_KEYS = iter(range(1, 1 << 20))


def _normal(shape, dtype=BF16, scale=1.0):
    """Unit-variance uniform values made on the device in ``dtype``."""
    bits = jax.random.bits(jax.random.PRNGKey(next(_KEYS)), shape,
                           jnp.uint16)
    return ((bits.astype(jnp.float32) / 65535.0 - 0.5)
            * (3.4641 * scale)).astype(dtype)


def _lanes(lanes, live, lo, hi, mean, n_blk):
    """``(table, lengths)``: ``live`` of ``lanes`` lanes hold a context
    drawn around ``mean`` rows; each lane owns its table's blocks."""
    rng = np.random.default_rng(0)
    n = np.zeros(lanes, np.int64)
    n[:live] = rng.lognormal(np.log(mean), 0.6, live).clip(lo, hi)
    rng.shuffle(n)
    table = 1 + np.arange(lanes * n_blk).reshape(lanes, n_blk)
    return jnp.asarray(table, jnp.int32), jnp.asarray(n, jnp.int32)


def _held_bytes(lengths, cache_len, window, row_bytes):
    blocks = np.asarray(pk.paged_blocks_walked(
        np.asarray(lengths, np.int64), 1, BS, cache_len // BS, window))
    return int(blocks.sum()) * BS * row_bytes


def attn_case(lanes, live, heads, kvh, hd, vd, cache_len, lo, hi, mean,
              window=None, ring=None, sink=False):
    n_blk = ring if window else cache_len // BS
    table, lengths = _lanes(lanes, live, lo, hi, mean, n_blk)
    nb = 1 + lanes * n_blk
    kw = dict(cache_len=cache_len, window=window)
    inputs = (_normal((lanes, 1, heads, hd)), _normal((nb, BS, kvh * hd)),
              _normal((nb, BS, kvh * vd)), table, lengths,
              _normal((heads,), jnp.float32) if sink else None)

    def call(q, k, v, t, n, s, **how):
        return pk.paged_attention(q, k, v, t, n, sink_logits=s, **kw, **how)

    def ref(q, k, v, t, n, s):
        return pk.paged_attention_reference(q, k, v, t, n, sink_logits=s,
                                            **kw)
    return call, ref, inputs, _held_bytes(lengths, cache_len, window,
                                          kvh * (hd + vd) * 2)


def latent_case(lanes, live, heads, row, value_dim, cache_len, lo, hi, mean,
                window=None, ring=None):
    n_blk = ring if window else cache_len // BS
    table, lengths = _lanes(lanes, live, lo, hi, mean, n_blk)
    kw = dict(value_dim=value_dim, scale=256 ** -0.5, cache_len=cache_len,
              window=window)
    inputs = (_normal((lanes, 1, heads, row), scale=0.2),
              _normal((1 + lanes * n_blk, BS, row)), table, lengths)

    def call(*a, **how):
        return pk.paged_latent_attention(*a, **kw, **how)

    def ref(*a):
        return pk.paged_latent_attention_reference(*a, **kw)
    return call, ref, inputs, _held_bytes(lengths, cache_len, window,
                                          row * 2)


def index_case(lanes, live, heads, dim, cache_len, lo, hi, mean):
    n_blk = cache_len // BS
    table, lengths = _lanes(lanes, live, lo, hi, mean, n_blk)
    inputs = (_normal((lanes, 1, heads, dim)),
              _normal((lanes, 1, heads), jnp.float32),
              _normal((1 + lanes * n_blk, BS, dim)), table, lengths)

    def call(*a, **how):
        return pk.paged_index_scores(*a, cache_len=cache_len, **how)

    def ref(*a):
        return pk.paged_index_scores_reference(*a, cache_len=cache_len)
    return call, ref, inputs, _held_bytes(lengths, cache_len, None, dim * 2)


#: name -> the walk of one cell's decode step (BENCHMARK.json's cells:
#: lanes, live lanes, the layer's published widths, the cell's contexts).
CASES = {
    "agent.full": lambda: attn_case(
        32, 16, 64, 4, 192, 128, 26624, 1000, 25000, 10000),
    "agent.window": lambda: attn_case(
        32, 16, 64, 8, 192, 128, 26624, 1000, 25000, 10000, window=128,
        ring=9, sink=True),
    "decode.qwen": lambda: attn_case(
        32, 31, 28, 4, 128, 128, 4096, 40, 1700, 420),
    "mixed.full": lambda: attn_case(
        32, 25, 48, 8, 128, 128, 17408, 512, 17000, 5500),
    "mixed.window": lambda: attn_case(
        32, 25, 72, 8, 128, 128, 17408, 512, 17000, 5500, window=512,
        ring=33),
    "ctx.glm": lambda: latent_case(
        32, 28, 20, 640, 512, 8192, 1024, 8000, 3600),
    "hybrid.ling": lambda: latent_case(
        64, 62, 32, 640, 512, 20480, 256, 19000, 3000),
    "notes.full": lambda: latent_case(
        32, 16, 128, 640, 512, 2048, 1000, 2047, 2000),
    "notes.window": lambda: latent_case(
        32, 16, 64, 1152, 1024, 16384, 1000, 15000, 6500, window=513,
        ring=34),
    "longctx.index": lambda: index_case(
        16, 6, 64, 128, 32768, 4000, 24500, 12000),
}


def bench(name, rows, iters, out):
    pk.PAGED_FOLD_ROWS = rows       # the sweep's one parameter
    call, ref, inputs, held = CASES[name]()
    seen = {}

    def fold_seen(bs, n_blk, row_bytes, real=pk._paged_fold):
        seen["step_rows"] = real(bs, n_blk, row_bytes) * bs
        return seen["step_rows"] // bs

    def one(*a):                    # a new function: no cached trace
        return call(*a, use_pallas=True)

    def many(q, *rest):
        def body(_, q):
            return q + (one(q, *rest).reshape(-1)[0] * 1e-30).astype(q.dtype)
        return jax.lax.fori_loop(0, iters, body, q)

    pk._paged_fold, real = fold_seen, pk._paged_fold
    try:
        t0 = time.perf_counter()
        lowered = jax.jit(one).lower(*inputs)  # ttd-lint: disable=compilecheck -- a benchmark's one AOT instance a width, no serving site
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        loop = jax.jit(many).lower(*inputs).compile()  # ttd-lint: disable=compilecheck -- as above
    finally:
        pk._paged_fold = real
    got = compiled(*inputs)
    lanes, err = inputs[0].shape[0], 0.0
    for lane in sorted({0, 1, lanes // 2, lanes - 1}):
        # Lane by lane: a whole call's gathered rows do not fit.
        sub = [a[lane:lane + 1] if getattr(a, "ndim", 0)
               and a.shape[0] == lanes else a for a in inputs]
        want = np.asarray(ref(*sub), np.float32)
        mine = np.asarray(got[lane:lane + 1], np.float32)
        fin = np.isfinite(want)
        assert np.array_equal(np.isfinite(mine), fin), (name, rows, lane)
        err = max(err, float(np.max(np.abs(mine[fin] - want[fin]))))
    loop(*inputs).block_until_ready()
    times = []
    for _ in range(3):
        t = time.perf_counter()
        loop(*inputs).block_until_ready()
        times.append((time.perf_counter() - t) / iters)
    line = json.dumps(dict(
        case=name, rows=rows, **seen, ms=round(min(times) * 1e3, 4),
        roofline_pct=round(held / HBM_BYTES_PER_S / min(times) * 100, 2),
        held_mbytes=round(held / 1e6, 2), lower_s=round(t1 - t0, 3),
        compile_s=round(t2 - t1, 3), max_err=err))
    print(line, flush=True)
    out.write(line + "\n")
    out.flush()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", default="128,256,512",
                    help="values of PAGED_FOLD_ROWS to sweep")
    ap.add_argument("--cases", default=",".join(CASES))
    ap.add_argument("--iters", type=int, default=30)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        sys.exit("bench_paged_walks: a time comes only from the chip")
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "bench_paged_walks.jsonl"),
              "a") as out:
        for name in args.cases.split(","):
            for rows in map(int, args.rows.split(",")):
                bench(name, rows, args.iters, out)


if __name__ == "__main__":
    main()
