"""BERT-base MLM pretrain throughput: samples/sec/chip + MFU.

One of the driver-designated metrics (BASELINE.md: "BERT-base MLM
samples/sec") with no published reference number — this tool establishes
the rebuild's own baseline on the live backend, end-to-end through the
jitted Trainer step (mixed bf16, adamw, masked-token-weighted loss).

MFU uses the standard encoder FLOP estimate:
  flops/token ≈ 6·N_params + 12·L·d_model·seq
(6·N covers fwd+bwd matmuls; the attention term is un-halved — BERT
attention is bidirectional, not causal).

Prints one JSON line per run (bench_lm.py conventions).
"""

import argparse
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))  # repo root (the package)
sys.path.insert(0, _HERE)                   # tools/ (bench_lm helpers)

from bench_lm import (  # noqa: E402
    check_hbm_budget,
    param_count,
    peak_tflops,
    timed_step_seconds,
)


def bench_bert(preset: str, batch: int, seq: int, warmup: int, iters: int,
               force_hbm: bool = False, remat: bool = False):
    import jax
    import numpy as np
    import optax

    from tensorflow_train_distributed_tpu.models import bert
    from tensorflow_train_distributed_tpu.parallel.sharding import shard_batch
    from tensorflow_train_distributed_tpu.runtime.mesh import (
        MeshConfig, build_mesh,
    )
    from tensorflow_train_distributed_tpu.training import (
        Policy, Trainer, TrainerConfig,
    )

    import dataclasses

    cfg = bert.BERT_PRESETS[preset]
    if remat:
        cfg = dataclasses.replace(cfg, remat=True)
    if seq > cfg.max_positions:
        raise SystemExit(f"--seq {seq} > max_positions {cfg.max_positions}")
    task = bert.make_task(cfg)
    import jax.numpy as jnp

    mesh = build_mesh(MeshConfig(data=-1))
    n_chips = mesh.devices.size
    abstract = jax.eval_shape(lambda: task.init_variables(
        jax.random.key(0),
        {"input_ids": jnp.zeros((1, seq), jnp.int32)}))
    # Bidirectional attention; BERT runs the reference einsum attention,
    # which saves per-head [B,H,S,S] for backward when remat is off —
    # score_heads makes the estimate account for that.
    check_hbm_budget(
        param_count(abstract["params"]), cfg.num_layers, cfg.hidden_size,
        batch, seq, remat=cfg.remat, causal=False, force=force_hbm,
        device=mesh.devices.flat[0], score_heads=cfg.num_heads)
    trainer = Trainer(
        task, optax.adamw(1e-4, weight_decay=0.01), mesh,
        policy=Policy.from_name("mixed_bfloat16"),
        config=TrainerConfig(log_every=1_000_000),
    )
    rng = np.random.default_rng(0)
    global_batch = batch * n_chips
    # 15% masked positions, the BERT pretrain convention.
    weights = np.zeros((global_batch, seq), np.float32)
    for row in weights:
        row[rng.choice(seq, max(1, int(0.15 * seq)), replace=False)] = 1.0
    data = {
        "input_ids": rng.integers(0, cfg.vocab_size,
                                  (global_batch, seq)).astype(np.int32),
        "labels": rng.integers(0, cfg.vocab_size,
                               (global_batch, seq)).astype(np.int32),
        "mask_weights": weights,
    }
    state = trainer.create_state(data)
    n_params = param_count(state.params)
    step = trainer._compiled_train_step()
    dev_batch = shard_batch(mesh, data)
    dt = timed_step_seconds(step, state, dev_batch, warmup, iters)
    samples_per_sec_chip = global_batch / dt / n_chips
    dev0 = mesh.devices.flat[0]
    flops_per_token = (6 * n_params
                       + 12 * cfg.num_layers * cfg.hidden_size * seq)
    rec = {
        "metric": f"{preset}_mlm_samples_per_sec_per_chip",
        "value": round(samples_per_sec_chip, 1),
        "unit": "samples/sec/chip",
        "step_time_ms": round(dt * 1e3, 2),
        "batch_per_chip": batch,
        "seq_len": seq,
        "n_chips": n_chips,
        "n_params": n_params,
        "backend": dev0.platform,
    }
    peak = peak_tflops(dev0)
    if peak is not None:
        mfu = samples_per_sec_chip * seq * flops_per_token / (peak * 1e12)
        rec["mfu_pct"] = round(100 * mfu, 2)
        rec["device_kind"] = dev0.device_kind
        if mfu > 0.75:
            # No real training step sustains >75% MFU: a timing
            # artifact, flagged so it is never read as throughput.
            rec["implausible"] = True
    return rec


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--preset", default="bert_base")
    p.add_argument("--batch-per-chip", type=int, default=32)
    p.add_argument("--seq", type=int, default=128,
                   help="pretrain phase-1 convention: seq 128")
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--platform", default="",
                   help="force a jax platform (e.g. 'cpu' for a smoke "
                        "run)")
    p.add_argument("--force-hbm", action="store_true",
                   help="skip the pre-flight HBM estimate")
    p.add_argument("--remat", action="store_true",
                   help="per-layer activation checkpointing (bigger "
                        "batch/seq at recompute cost)")
    args = p.parse_args(argv)
    from tensorflow_train_distributed_tpu.runtime import compile_cache

    compile_cache.place_compile_cache()
    if args.platform:
        from tensorflow_train_distributed_tpu.runtime.mesh import (
            force_platform,
        )

        force_platform(args.platform)
    try:
        rec = bench_bert(args.preset, args.batch_per_chip, args.seq,
                         args.warmup, args.iters,
                         force_hbm=args.force_hbm, remat=args.remat)
    except Exception as e:  # machine-readable failure, bench.py lesson
        print(json.dumps({
            "metric": f"{args.preset}_mlm_samples_per_sec_per_chip",
            "value": 0.0, "unit": "samples/sec/chip",
            "error": f"{type(e).__name__}: {e}"}), flush=True)
        return 1
    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
