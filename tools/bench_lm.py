"""Decoder (Llama-family) training throughput: tokens/sec/chip + MFU.

Secondary benchmark (the driver's headline is bench.py / ResNet-50): the
flagship causal-LM path — RoPE/RMSNorm/SwiGLU, scan+remat, pallas flash
attention on TPU — measured end-to-end through the jitted Trainer step.

MFU uses the standard decoder FLOP estimate (PaLM-appendix style):
  flops/token ≈ 6·N_params + 12·L·d_model·seq·0.5   (causal attention)
fwd+bwd included in the 6·N factor; remat recompute is NOT counted (MFU is
model FLOPs, not hardware FLOPs — remat makes true utilization higher).

Prints one JSON line per benched config.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Device tables + the calibrated activation model live in the package
# (training.memory) — one source for bench tools and the planner.
from tensorflow_train_distributed_tpu.training.memory import (  # noqa: E402
    STATE_BYTES_PER_PARAM,
    decoder_activation_bytes,
    tpu_peaks,
)


def peak_tflops(device) -> float | None:
    """bf16 peak of a TPU (unknown kind raises); None off-TPU, where no
    utilization is reported."""
    if device.platform != "tpu":
        return None
    return tpu_peaks(device.device_kind)["peak_tflops"]


def param_count(tree):
    import jax

    return sum(x.size for x in jax.tree_util.tree_leaves(tree))


def hbm_budget_bytes(device) -> float | None:
    """Per-chip HBM budget of a TPU (unknown kind raises); None off-TPU,
    where the guard does not apply."""
    if device.platform != "tpu":
        return None
    return tpu_peaks(device.device_kind)["hbm_budget_bytes"]


def check_hbm_budget(n_params: int, n_layers: int, d_model: int,
                     batch: int, seq: int, remat: bool, *,
                     causal: bool, force: bool, device,
                     score_heads: int = 1,
                     ffn_size: int | None = None,
                     save_ffn_hiddens: bool = True) -> None:
    """Pre-flight HBM estimate — refuse configs that would OOM on-chip.

    Memory planning: a compile that cannot fit spends minutes of a
    budgeted chip call before it is refused, so the bench answers "does
    it fit" from shapes first.  Skipped entirely off-TPU (CPU smoke runs
    risk nothing).  The activation model (``training.memory``) is empirical,
    calibrated against observed XLA allocations on v5e; state is
    ``params × 14 B`` (bf16 compute copy + f32 master + 2×f32 adam
    moments + grads in flight).

    Raises SystemExit with a machine-readable JSON line unless ``force``.
    """
    budget = hbm_budget_bytes(device)
    if budget is None:
        return
    state = n_params * STATE_BYTES_PER_PARAM
    act = decoder_activation_bytes(n_layers, d_model, batch, seq,
                                   remat=remat, causal=causal,
                                   score_heads=score_heads,
                                   ffn_size=ffn_size,
                                   save_ffn_hiddens=save_ffn_hiddens)
    need = state + act
    # The estimate intentionally errs a little high (b16 no-remat: est 28
    # vs 26.4 GiB observed), so compare against the full budget: known-good
    # llama_125m b8 no-remat (est 14.9) passes, the two measured OOMs
    # (b16 no-remat est 28, llama_1b no-remat state alone > 17) refuse.
    if need <= budget or force:
        return
    import json as _json

    print(_json.dumps({
        "error": "pre-flight HBM estimate exceeds budget; rerun with "
                 "--force-hbm to let the compiler decide",
        "estimated_gib": round(need / 2**30, 2),
        "budget_gib": round(budget / 2**30, 2),
        "device_kind": device.device_kind,
        "state_gib": round(state / 2**30, 2),
        "activations_gib": round(act / 2**30, 2),
    }), flush=True)
    raise SystemExit(2)


def timed_step_seconds(step, state, dev_batch, warmup: int,
                       iters: int, trace_dir: str = "") -> float:
    """Shared measure loop: warmup, then a timed window; mean step s.

    The warmup FETCHES the step metrics (host transfer), so every
    warmup step has really finished before the window opens; the timed
    loop keeps the cheap block — the chained state dependency forces
    each step anyway.

    ``trace_dir``: capture an XPlane trace of the TIMED window (post-
    warmup steady state) — one measure loop serves bench and profiling
    (the step donates its state buffers, so a second loop on the same
    state would hit deleted buffers).
    """
    import jax
    import numpy as np
    import time as _time

    for _ in range(max(warmup, 1)):  # >=1: the fetch must happen
        state, m = step(state, dev_batch)
        jax.tree.map(np.asarray, m)
    jax.block_until_ready(state)
    import contextlib

    if trace_dir:
        from tensorflow_train_distributed_tpu.runtime.profiling import (
            trace,
        )

        cm = trace(trace_dir)
    else:
        cm = contextlib.nullcontext()
    with cm:
        # Timestamps INSIDE the trace window: start_trace runs before t0
        # and stop_trace (XPlane serialization, 100s of ms) after t1, so
        # profiling never inflates the reported step time.
        t0 = _time.perf_counter()
        for _ in range(iters):
            state, m = step(state, dev_batch)
        jax.block_until_ready(m)
        t1 = _time.perf_counter()
    return (t1 - t0) / iters


def bench_lm(preset: str, batch: int, seq: int, warmup: int, iters: int,
             remat=None, remat_policy=None, force_hbm: bool = False,
             sliding_window: int = 0, fused_qkv: bool = False,
             scan_layers=None, profile_dir: str = ""):
    import jax
    import numpy as np
    import optax

    from tensorflow_train_distributed_tpu.models import llama
    from tensorflow_train_distributed_tpu.parallel.sharding import shard_batch
    from tensorflow_train_distributed_tpu.runtime.mesh import (
        MeshConfig, build_mesh,
    )
    from tensorflow_train_distributed_tpu.training import (
        Policy, Trainer, TrainerConfig,
    )

    import dataclasses

    cfg = llama.LLAMA_PRESETS[preset]
    if sliding_window:
        cfg = dataclasses.replace(cfg, sliding_window=sliding_window)
    if remat is not None:
        # remat trades recompute for memory; when the model fits without
        # it (small presets, single chip) turning it off is pure speed.
        cfg = dataclasses.replace(cfg, remat=remat)
    if remat_policy is not None:
        cfg = dataclasses.replace(cfg, remat_policy=remat_policy)
    if fused_qkv:
        # MFU lever A/B (fresh init both arms -- loss values differ from
        # split-projection runs, throughput is the comparison).
        cfg = dataclasses.replace(cfg, fused_qkv=True)
    if scan_layers is not None:
        # Unrolled-vs-scanned A/B: nn.scan keeps ONE compiled layer body
        # (fast compiles, the multi-chip default), but blocks XLA fusion
        # across layer boundaries -- a plausible MFU thief at 125m scale
        # where per-layer work is small.  Unrolling trades compile time
        # for whatever cross-layer fusion buys.
        cfg = dataclasses.replace(cfg, scan_layers=scan_layers)
    if seq > cfg.max_positions:
        raise SystemExit(f"--seq {seq} > max_positions {cfg.max_positions}")
    task = llama.CausalLmTask(cfg)
    import jax.numpy as jnp

    mesh = build_mesh(MeshConfig(data=-1))
    n_chips = mesh.devices.size
    abstract = jax.eval_shape(lambda: task.init_variables(
        jax.random.key(0),
        {"tokens": jnp.zeros((1, seq), jnp.int32),
         "targets": jnp.zeros((1, seq), jnp.int32)}))
    # remat_policy="dots" saves every matmul output — including the SwiGLU
    # hiddens that dominate the no-remat footprint — so for budgeting it
    # is the no-remat estimate, not the full-remat one.  "no_ffn" is the
    # no-remat estimate MINUS those hiddens (that's its whole point).
    effective_remat = cfg.remat and cfg.remat_policy not in ("dots",
                                                             "no_ffn")
    check_hbm_budget(
        param_count(abstract["params"]), cfg.num_layers, cfg.d_model,
        batch, seq, effective_remat, causal=True, force=force_hbm,
        device=mesh.devices.flat[0], ffn_size=cfg.ffn_size,
        save_ffn_hiddens=not (cfg.remat and cfg.remat_policy == "no_ffn"))
    trainer = Trainer(
        task, optax.adamw(1e-4, b1=0.9, b2=0.95, weight_decay=0.1), mesh,
        policy=Policy.from_name("mixed_bfloat16"),
        config=TrainerConfig(log_every=1_000_000),
    )
    rng = np.random.default_rng(0)
    global_batch = batch * n_chips
    data = {
        "tokens": rng.integers(0, cfg.vocab_size,
                               (global_batch, seq)).astype(np.int32),
        "targets": rng.integers(0, cfg.vocab_size,
                                (global_batch, seq)).astype(np.int32),
    }
    state = trainer.create_state(data)
    n_params = param_count(state.params)
    step = trainer._compiled_train_step()
    dev_batch = shard_batch(mesh, data)
    # profile_dir: XPlane trace of the timed window — the decoder analog
    # of bench.py's ResNet traces (render: tools/profile_summary.py).
    dt = timed_step_seconds(step, state, dev_batch, warmup, iters,
                            trace_dir=profile_dir)
    tok_per_sec_chip = global_batch * seq / dt / n_chips
    dev0 = mesh.devices.flat[0]
    # Average attended context per token: seq/2 causal; a binding
    # sliding window caps it (honest MFU — full-attention FLOPs would
    # overstate the windowed model's utilization).
    ctx = seq * 0.5
    if cfg.sliding_window and cfg.sliding_window < seq:
        w = cfg.sliding_window
        ctx = (w * (w + 1) / 2 + (seq - w) * w) / seq
    flops_per_token = 6 * n_params + 12 * cfg.num_layers * cfg.d_model * ctx
    rec = {
        "metric": f"{preset}_train_tokens_per_sec_per_chip",
        "value": round(tok_per_sec_chip, 1),
        "unit": "tokens/sec/chip",
        "step_time_ms": round(dt * 1e3, 2),
        "batch_per_chip": batch,
        "seq_len": seq,
        "n_chips": n_chips,
        "n_params": n_params,
        "backend": dev0.platform,
    }
    if cfg.sliding_window:
        rec["sliding_window"] = cfg.sliding_window
    if cfg.fused_qkv:
        rec["fused_qkv"] = True
    rec["scan_layers"] = cfg.scan_layers
    peak = peak_tflops(dev0)
    if peak is not None:
        mfu = tok_per_sec_chip * flops_per_token / (peak * 1e12)
        rec["mfu_pct"] = round(100 * mfu, 2)
        rec["device_kind"] = dev0.device_kind
        if mfu > 0.75:
            # No real training step sustains >75% MFU: a timing
            # artifact, flagged so it is never read as throughput.
            rec["implausible"] = True
    return rec


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--preset", default="llama_125m")
    p.add_argument("--sliding-window", type=int, default=0,
                   help="override the preset with sliding-window "
                        "attention (O(seq*window) chunked path) — A/B "
                        "vs full attention; 0 = preset default")
    p.add_argument("--batch-per-chip", type=int, default=8)
    p.add_argument("--profile-dir", default="",
                   help="capture an XPlane trace of the timed steps into "
                        "this dir (render: tools/profile_summary.py)")
    p.add_argument("--seq", type=int, default=2048)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--iters", type=int, default=10)
    rm = p.add_mutually_exclusive_group()
    rm.add_argument("--remat", dest="remat", action="store_true",
                    default=None, help="force activation remat on")
    rm.add_argument("--no-remat", dest="remat", action="store_false",
                    help="disable remat (faster when memory allows)")
    sc = p.add_mutually_exclusive_group()
    sc.add_argument("--scan-layers", dest="scan_layers",
                    action="store_true", default=None)
    sc.add_argument("--no-scan-layers", dest="scan_layers",
                    action="store_false", default=None,
                    help="unroll the depth loop (A/B vs nn.scan: trades "
                         "compile time for cross-layer fusion)")
    p.add_argument("--fused-qkv", action="store_true",
                   help="fuse q/k/v into one gemm (MFU lever A/B; "
                        "param layout differs from split projections)")
    p.add_argument("--remat-policy", default=None,
                   choices=("full", "dots", "no_ffn"),
                   help="what remat saves (see LlamaConfig.remat_policy)")
    p.add_argument("--platform", default="",
                   help="force a jax platform (e.g. 'cpu' for a smoke "
                        "run)")
    p.add_argument("--force-hbm", action="store_true",
                   help="skip the pre-flight HBM estimate")
    args = p.parse_args(argv)
    from tensorflow_train_distributed_tpu.runtime import compile_cache

    compile_cache.place_compile_cache()
    if args.platform:
        from tensorflow_train_distributed_tpu.runtime.mesh import (
            force_platform,
        )

        force_platform(args.platform)
    try:
        rec = bench_lm(args.preset, args.batch_per_chip, args.seq,
                       args.warmup, args.iters, remat=args.remat,
                       remat_policy=args.remat_policy,
                       force_hbm=args.force_hbm,
                       sliding_window=args.sliding_window,
                       fused_qkv=args.fused_qkv,
                       scan_layers=args.scan_layers,
                       profile_dir=args.profile_dir)
    except Exception as e:  # machine-readable failure, bench.py lesson
        print(json.dumps({"metric": f"{args.preset}_train_tokens_per_sec"
                          "_per_chip", "value": 0.0,
                          "unit": "tokens/sec/chip",
                          "error": f"{type(e).__name__}: {e}"}))
        return 1
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
