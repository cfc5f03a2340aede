"""ViT image-classification train throughput: images/sec/chip + MFU.

Beyond the reference's model list (SURVEY.md §2.1 has LeNet/ResNet-50 for
vision) — the ViT family rides the shared encoder stack, so this bench
gives the transformer-vision silicon number next to ResNet's.  Runs the
jitted Trainer step end-to-end (mixed bf16, adamw, label smoothing).

MFU uses the encoder FLOP estimate over the patch sequence:
  flops/image ≈ S·(6·N_params + 12·L·hidden·S)
(S = patches (+1 for cls pooling); bidirectional attention, un-halved —
the BERT convention in tools/bench_bert.py).

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


def bench_vit(preset: str, batch: int, warmup: int, iters: int,
              force_hbm: bool = False, remat: bool = False):
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflow_train_distributed_tpu.models import vit
    from tensorflow_train_distributed_tpu.parallel.sharding import shard_batch
    from tensorflow_train_distributed_tpu.runtime.mesh import (
        MeshConfig, build_mesh,
    )
    from tensorflow_train_distributed_tpu.training import (
        Policy, Trainer, TrainerConfig,
    )

    cfg = vit.VIT_PRESETS[preset]
    if remat:
        cfg = dataclasses.replace(cfg, remat=True)
    task = vit.make_task(cfg)
    mesh = build_mesh(MeshConfig(data=-1))
    n_chips = mesh.devices.size
    seq = cfg.num_patches + (1 if cfg.pooling == "cls" else 0)
    abstract = jax.eval_shape(lambda: task.init_variables(
        jax.random.key(0),
        {"image": jnp.zeros((1, cfg.image_size, cfg.image_size, 3)),
         "label": jnp.zeros((1,), jnp.int32)}))
    # Encoder shapes: bidirectional einsum attention saves per-head
    # [B,H,S,S] for backward when remat is off (the BERT guard setup).
    check_hbm_budget(
        param_count(abstract["params"]), cfg.num_layers, cfg.hidden_size,
        batch, seq, remat=cfg.remat, causal=False, force=force_hbm,
        device=mesh.devices.flat[0], score_heads=cfg.num_heads)
    trainer = Trainer(
        task, optax.adamw(1e-3, weight_decay=0.05), mesh,
        policy=Policy.from_name("mixed_bfloat16"),
        config=TrainerConfig(log_every=1_000_000),
    )
    rng = np.random.default_rng(0)
    global_batch = batch * n_chips
    data = {
        "image": rng.normal(0, 1, (global_batch, cfg.image_size,
                                   cfg.image_size, 3)).astype(np.float32),
        "label": rng.integers(0, cfg.num_classes,
                              (global_batch,)).astype(np.int32),
    }
    state = trainer.create_state(data)
    n_params = param_count(state.params)
    step = trainer._compiled_train_step()
    dev_batch = shard_batch(mesh, data)
    dt = timed_step_seconds(step, state, dev_batch, warmup, iters)
    images_per_sec_chip = global_batch / dt / n_chips
    dev0 = mesh.devices.flat[0]
    flops_per_image = seq * (
        6 * n_params + 12 * cfg.num_layers * cfg.hidden_size * seq)
    rec = {
        "metric": f"{preset}_train_images_per_sec_per_chip",
        "value": round(images_per_sec_chip, 1),
        "unit": "images/sec/chip",
        "step_time_ms": round(dt * 1e3, 2),
        "batch_per_chip": batch,
        "patch_seq": seq,
        "n_chips": n_chips,
        "n_params": n_params,
        "backend": dev0.platform,
    }
    peak = peak_tflops(dev0)
    if peak is not None:
        mfu = images_per_sec_chip * flops_per_image / (peak * 1e12)
        rec["mfu_pct"] = round(100 * mfu, 2)
        rec["device_kind"] = dev0.device_kind
        if mfu > 0.75:
            # No real training step sustains >75% MFU: a timing
            # artifact, flagged so it is never read as throughput.
            rec["implausible"] = True
    return rec


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--preset", default="vit_b16")
    p.add_argument("--batch-per-chip", type=int, default=64)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--platform", default="",
                   help="force a jax platform (e.g. 'cpu' for a smoke "
                        "run)")
    p.add_argument("--force-hbm", action="store_true",
                   help="skip the pre-flight HBM estimate")
    p.add_argument("--remat", action="store_true",
                   help="per-layer activation checkpointing (bigger batch "
                        "at recompute cost)")
    args = p.parse_args(argv)
    from tensorflow_train_distributed_tpu.runtime import compile_cache

    compile_cache.place_compile_cache()
    if args.platform:
        from tensorflow_train_distributed_tpu.runtime.mesh import (
            force_platform,
        )

        force_platform(args.platform)
    try:
        rec = bench_vit(args.preset, args.batch_per_chip,
                        args.warmup, args.iters,
                        force_hbm=args.force_hbm, remat=args.remat)
    except Exception as e:  # machine-readable failure, bench.py lesson
        print(json.dumps({
            "metric": f"{args.preset}_train_images_per_sec_per_chip",
            "value": 0.0, "unit": "images/sec/chip",
            "error": f"{type(e).__name__}: {e}"}), flush=True)
        return 1
    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
