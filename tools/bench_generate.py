"""Decoder DECODE throughput: generated tokens/sec/chip (serving side).

Beyond the reference (a training harness with no serving loop): measures
the KV-cache autoregressive path — one jitted prefill + ``lax.scan``
decode — end-to-end through ``models.generate``.  The decode regime is
memory-bandwidth-bound (each step reads all params + the cache for one
token), so the companion number is model-bandwidth utilization (MBU):
bytes-touched/step ≈ param_bytes + cache_bytes vs the chip's HBM
bandwidth — the serving analog of training MFU.

Prints one JSON line (bench_lm.py conventions; chip lock held on TPU).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tensorflow_train_distributed_tpu.training.memory import (  # noqa: E402
    tpu_peaks,
)


def bench_generate(preset: str, batch: int, prompt_len: int,
                   max_new: int, warmup: int, iters: int,
                   temperature: float = 0.0,
                   force_hbm: bool = False,
                   sliding_window: int = 0,
                   quant: str = "",
                   kv_cache_int8: bool = False):
    import dataclasses
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflow_train_distributed_tpu.models import generate, llama

    if max_new < 2:
        # The decode-step rate is (full - one-step) / (max_new - 1); a
        # single new token IS the prefill call. Guarded here too so
        # library callers get the clean error, not ZeroDivisionError.
        raise ValueError(f"max_new must be >= 2, got {max_new}")
    if preset in llama.LLAMA_PRESETS:
        cfg = llama.LLAMA_PRESETS[preset]
        model_cls = llama.LlamaModel
    else:
        # MoE presets decode through the same generate() dispatch.
        from tensorflow_train_distributed_tpu.models import moe

        if preset not in moe.MOE_PRESETS:
            # ValueError, not SystemExit: main()'s except-Exception turns
            # it into the one-JSON-line error record consumers parse.
            raise ValueError(
                f"unknown preset {preset!r}: not in LLAMA_PRESETS or "
                f"MOE_PRESETS")
        cfg = moe.MOE_PRESETS[preset]
        model_cls = moe.MoeLmModel
        if kv_cache_int8 or sliding_window:
            raise ValueError(
                "--kv-cache/--sliding-window apply to llama-family "
                "presets only")
    if kv_cache_int8:
        cfg = dataclasses.replace(cfg, kv_cache_int8=True)
    if sliding_window:
        # A/B the rolling window-sized KV cache against the preset's full
        # attention (cache rows = window instead of prompt+new).
        cfg = dataclasses.replace(cfg, sliding_window=sliding_window)
    total_len = prompt_len + max_new
    if total_len > cfg.max_positions:
        raise SystemExit(
            f"prompt+new = {total_len} > max_positions "
            f"{cfg.max_positions}")
    dev = jax.devices()[0]
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(
        1, cfg.vocab_size, (batch, prompt_len)).astype(np.int32))
    model = model_cls(cfg)
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.key(0), prompt[:, :8]))
    n_params = sum(x.size for x in
                   jax.tree_util.tree_leaves(abstract["params"]))
    # Decode working set in the config's COMPUTE dtype (generate casts
    # params to cfg.dtype; tiny presets are f32, big ones bf16): cast
    # params + the KV cache (2 tensors × L × B × total_len × kv_heads ×
    # head_dim).
    itemsize = jnp.dtype(cfg.dtype).itemsize
    kv_heads = cfg.num_kv_heads or cfg.num_heads
    # Normalize the llama-only knobs ONCE (MoeConfig lacks the fields and
    # its branch above rejected the flags) — scattered getattrs would
    # mask attribute typos (the lora.spec_of lesson).
    cfg_window = getattr(cfg, "sliding_window", None)
    cfg_kv8 = bool(getattr(cfg, "kv_cache_int8", False))
    cache_rows = total_len
    if cfg_window and cfg_window < total_len:
        cache_rows = cfg_window  # rolling ring buffer
    kv_itemsize = 1 if cfg_kv8 else itemsize
    cache_bytes = (2 * cfg.num_layers * batch * cache_rows
                   * kv_heads * (cfg.d_model // cfg.num_heads)
                   * kv_itemsize)
    if cfg_kv8:
        # Plus the f32 per-(position, kv_head) scale buffers (2 per
        # layer: k and v) — ~6% of the bf16 cache at head_dim 64, and
        # they stream on every step just like the cache rows.
        cache_bytes += 2 * cfg.num_layers * batch * cache_rows             * kv_heads * 4
    need = n_params * (itemsize + 4) + cache_bytes  # cast copy + f32 init
    budget = (tpu_peaks(dev.device_kind)["hbm_budget_bytes"]
              if dev.platform == "tpu" else None)
    if budget is not None and need > budget and not force_hbm:
        print(json.dumps({
            "error": "decode working set exceeds HBM budget; rerun "
                     "with --force-hbm to let the compiler decide",
            "estimated_gib": round(need / 2**30, 2),
            "budget_gib": round(budget / 2**30, 2)}), flush=True)
        raise SystemExit(2)
    params = model.init(jax.random.key(0), prompt[:, :8])["params"]
    quant_scales = None
    weight_bytes = n_params * itemsize
    if quant:
        if quant != "int8":
            raise SystemExit(f"--quant supports 'int8', got {quant!r}")
        from tensorflow_train_distributed_tpu.models.quant import (
            quantize_params,
            quantized_bytes,
        )

        params, quant_scales = quantize_params(params)
        # Exact per-step weight traffic: int8 kernels at 1 B, their f32
        # scales, and everything unquantized (embeds/norms — ~20% of a
        # 125M-class decoder, NOT negligible) at the compute dtype the
        # decode loop streams them in.
        weight_bytes = quantized_bytes(quant_scales) + sum(
            x.size * (1 if x.dtype == jnp.int8 else itemsize)
            for x in jax.tree_util.tree_leaves(params)
            if hasattr(x, "dtype"))

    def run(n):
        return generate.generate(cfg, params, prompt, n,
                                 temperature=temperature,
                                 rng=jax.random.key(1),
                                 quant_scales=quant_scales)

    def timed(n):
        # Warmup fetches (np.asarray), so the compile and the first
        # result have really landed before the window opens; the timed
        # loop keeps the cheap block (no per-iteration D2H copy).
        np.asarray(run(n))  # compile + materialize
        for _ in range(warmup):
            np.asarray(run(n))
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = run(n)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters

    # Two timed variants separate prefill from the decode loop: the
    # max_new=1 call is prefill + one step, so the per-step decode time
    # is the difference divided by the extra steps — MBU then measures
    # the DECODE loop, not a prefill-diluted blend.
    dt_full = timed(max_new)
    dt_one = timed(1)
    step_s = max(dt_full - dt_one, 1e-9) / (max_new - 1)
    decode_tok_per_sec = batch / step_s
    rec = {
        "metric": f"{preset}_decode_tokens_per_sec_per_chip",
        "value": round(decode_tok_per_sec, 1),
        "unit": "tokens/sec/chip",
        "batch": batch,
        "prompt_len": prompt_len,
        "max_new_tokens": max_new,
        "time_per_call_ms": round(dt_full * 1e3, 2),
        "prefill_ms": round(dt_one * 1e3, 2),
        "ms_per_token_step": round(step_s * 1e3, 3),
        "call_tokens_per_sec": round(batch * max_new / dt_full, 1),
        "n_params": n_params,
        "backend": dev.platform,
    }
    if cfg_window:
        rec["sliding_window"] = cfg_window
        rec["kv_cache_rows"] = cache_rows
    if quant:
        rec["quant"] = quant
    if cfg_kv8:
        rec["kv_cache"] = "int8"
    bw = (tpu_peaks(dev.device_kind)["hbm_bytes_per_sec"]
          if dev.platform == "tpu" else None)
    if bw is not None:
        # Each decode step streams the cast params + the filled cache
        # once, whatever the batch (that's why batching decode is nearly
        # free until compute-bound).
        bytes_per_step = weight_bytes + cache_bytes
        rec["mbu_pct"] = round(100 * bytes_per_step / step_s / bw, 2)
        rec["device_kind"] = dev.device_kind
        if step_s < 0.5 * bytes_per_step / bw:
            # Faster than 2x the weight-streaming roofline: a timing
            # artifact, not physics.
            rec["implausible"] = True
    return rec


def _at_least_two(s: str) -> int:
    v = int(s)
    if v < 2:
        raise argparse.ArgumentTypeError(
            f"--max-new must be >= 2 (decode rate is measured against a "
            f"max_new=1 prefill call), got {v}")
    return v


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--preset", default="llama_125m")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--prompt-len", type=int, default=128)
    # >= 2: the decode-step rate comes from (full - one-step) / (n - 1).
    p.add_argument("--max-new", type=_at_least_two, default=128)
    p.add_argument("--warmup", type=int, default=1)
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--platform", default="",
                   help="force a jax platform ('cpu' for smoke runs)")
    p.add_argument("--force-hbm", action="store_true")
    p.add_argument("--sliding-window", type=int, default=0,
                   help="override the preset with sliding-window "
                        "attention: decode keeps a rolling WINDOW-row "
                        "KV cache (A/B vs full attention; 0 = preset "
                        "default)")
    p.add_argument("--kv-cache", default="", choices=["", "int8"],
                   help="'int8': quantized KV cache (linear cache only) "
                        "— halves cache HBM traffic, the large-batch "
                        "decode lever")
    p.add_argument("--quant", default="", choices=["", "int8"],
                   help="'int8': weight-only int8 serving "
                        "(models.quant) — kernels stream from HBM at "
                        "1 byte/param in the decode loop")
    args = p.parse_args(argv)
    from tensorflow_train_distributed_tpu.runtime import compile_cache

    compile_cache.place_compile_cache()
    if args.platform:
        from tensorflow_train_distributed_tpu.runtime.mesh import (
            force_platform,
        )

        force_platform(args.platform)
    try:
        rec = bench_generate(args.preset, args.batch, args.prompt_len,
                             args.max_new, args.warmup, args.iters,
                             temperature=args.temperature,
                             force_hbm=args.force_hbm,
                             sliding_window=args.sliding_window,
                             quant=args.quant,
                             kv_cache_int8=args.kv_cache == "int8")
    except Exception as e:
        print(json.dumps({
            "metric": f"{args.preset}_decode_tokens_per_sec_per_chip",
            "value": 0.0, "unit": "tokens/sec/chip",
            "error": f"{type(e).__name__}: {e}"}), flush=True)
        return 1
    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
