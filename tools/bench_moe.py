"""MoE decoder training throughput: tokens/sec/chip + active-param MFU.

Beyond the reference (no MoE anywhere in it — SURVEY.md §2.4): the EP
family's silicon number, end-to-end through the jitted Trainer step
(GShard dense-dispatch routing, aux losses folded in, mixed bf16,
adamw).

MFU counts ACTIVE FLOPs (the MoE convention): each token runs the dense
trunk plus ``top_k`` of ``num_experts`` expert FFNs, so
  flops/token ≈ 6·(N_dense + (top_k/E)·N_expert)
               + 12·L·d_model·(seq/2)   (causal attention)
Counting total params instead would flatter a sparse model ~E/k×.

HBM pre-flight: the calibrated decoder activation model does not cover
MoE dispatch buffers, so the guard here is state-based with an explicit
dispatch-tensor term ([G,S,E,C] dispatch+combine in f32, the dominant
routing buffer) — deliberately conservative; --force-hbm overrides.

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
    hbm_budget_bytes,
    param_count,
    peak_tflops,
    timed_step_seconds,
)
from tensorflow_train_distributed_tpu.training.memory import (  # noqa: E402
    STATE_BYTES_PER_PARAM,
)


def _split_params(abstract_params):
    """(dense_params, expert_params) — expert leaves live under an
    'experts' module (the nn.vmap stack)."""
    import jax

    dense = expert = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            abstract_params)[0]:
        keys = [getattr(p, "key", "") for p in path]
        if "experts" in keys:
            expert += leaf.size
        else:
            dense += leaf.size
    return dense, expert


def bench_moe(preset: str, batch: int, seq: int, warmup: int, iters: int,
              force_hbm: bool = False, dispatch: str = "dense"):
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflow_train_distributed_tpu.models import moe
    from tensorflow_train_distributed_tpu.parallel.sharding import shard_batch
    from tensorflow_train_distributed_tpu.runtime.mesh import (
        MeshConfig, build_mesh,
    )
    from tensorflow_train_distributed_tpu.training import (
        Policy, Trainer, TrainerConfig,
    )

    cfg = dataclasses.replace(moe.MOE_PRESETS[preset], dispatch=dispatch)
    if seq > cfg.max_positions:
        raise SystemExit(f"--seq {seq} > max_positions {cfg.max_positions}")
    task = moe.MoeLmTask(cfg)
    mesh = build_mesh(MeshConfig(data=-1))
    n_chips = mesh.devices.size
    abstract = jax.eval_shape(lambda: task.init_variables(
        jax.random.key(0),
        {"tokens": jnp.zeros((1, seq), jnp.int32),
         "targets": jnp.zeros((1, seq), jnp.int32)}))
    n_params = param_count(abstract["params"])
    n_dense, n_expert = _split_params(abstract["params"])
    dev0 = mesh.devices.flat[0]
    budget = hbm_budget_bytes(dev0)
    if budget is not None and not force_hbm:
        # State + the routing/dispatch buffers; remat keeps per-layer
        # activations transient.  Conservative on purpose (a compile that
        # cannot fit wastes minutes of a chip call).
        n_moe_layers = -(-cfg.num_layers // max(cfg.moe_every, 1))
        if dispatch == "gmm":
            # Dropless path: expert-sorted row copies + f32 gate/up
            # activations instead of [G,S,E,C] dispatch one-hots.
            m = batch * seq * cfg.top_k
            dispatch_bytes = (m * (4 * cfg.d_model + 8 * cfg.ffn_size)
                              * n_moe_layers)
        else:
            capacity = max(1, int(cfg.capacity_factor * cfg.top_k * seq
                                  / cfg.num_experts))
            dispatch_bytes = (2 * batch * seq * cfg.num_experts * capacity
                              * 4 * n_moe_layers)
        act = 30 * cfg.num_layers * batch * seq * cfg.d_model * 2
        need = n_params * STATE_BYTES_PER_PARAM + dispatch_bytes + act
        if need > budget:
            print(json.dumps({
                "error": "pre-flight HBM estimate exceeds budget — rerun "
                         "with --force-hbm to gamble",
                "estimated_gib": round(need / 2**30, 2),
                "budget_gib": round(budget / 2**30, 2)}), flush=True)
            raise SystemExit(2)
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
    step = trainer._compiled_train_step()
    dev_batch = shard_batch(mesh, data)
    dt = timed_step_seconds(step, state, dev_batch, warmup, iters)
    tok_per_sec_chip = global_batch * seq / dt / n_chips
    active = n_dense + n_expert * cfg.top_k / cfg.num_experts
    flops_per_token = (6 * active
                       + 12 * cfg.num_layers * cfg.d_model * seq * 0.5)
    name = preset if dispatch == "dense" else f"{preset}_{dispatch}"
    rec = {
        "metric": f"{name}_train_tokens_per_sec_per_chip",
        "value": round(tok_per_sec_chip, 1),
        "unit": "tokens/sec/chip",
        "step_time_ms": round(dt * 1e3, 2),
        "batch_per_chip": batch,
        "seq_len": seq,
        "n_chips": n_chips,
        "n_params": n_params,
        "n_active_params": int(active),
        "num_experts": cfg.num_experts,
        "top_k": cfg.top_k,
        "dispatch": dispatch,
        "backend": dev0.platform,
    }
    peak = peak_tflops(dev0)
    if peak is not None:
        mfu = tok_per_sec_chip * flops_per_token / (peak * 1e12)
        rec["mfu_pct"] = round(100 * mfu, 2)
        rec["device_kind"] = dev0.device_kind
        if mfu > 0.75:
            rec["implausible"] = True
    return rec


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--preset", default="moe_370m")
    p.add_argument("--batch-per-chip", type=int, default=8)
    p.add_argument("--seq", type=int, default=1024)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--platform", default="",
                   help="force a jax platform ('cpu' for smoke runs)")
    p.add_argument("--dispatch", default="dense", choices=["dense", "gmm"],
                   help="expert compute: GShard dense-dispatch einsums or "
                        "megablox grouped-matmul dropless routing")
    p.add_argument("--force-hbm", action="store_true")
    args = p.parse_args(argv)
    from tensorflow_train_distributed_tpu.runtime import compile_cache

    compile_cache.place_compile_cache()
    if args.platform:
        from tensorflow_train_distributed_tpu.runtime.mesh import (
            force_platform,
        )

        force_platform(args.platform)
    if args.platform == "cpu":
        # CPU smoke: the megablox gmm lowers only for TPU, so this run
        # asks for interpret mode (the program never picks it itself).
        from tensorflow_train_distributed_tpu.models import moe

        moe.GMM_INTERPRET = True
    try:
        rec = bench_moe(args.preset, args.batch_per_chip, args.seq,
                        args.warmup, args.iters,
                        force_hbm=args.force_hbm,
                        dispatch=args.dispatch)
    except Exception as e:  # machine-readable failure, bench.py lesson
        name = (args.preset if args.dispatch == "dense"
                else f"{args.preset}_{args.dispatch}")
        print(json.dumps({
            "metric": f"{name}_train_tokens_per_sec_per_chip",
            "value": 0.0, "unit": "tokens/sec/chip",
            "dispatch": args.dispatch,
            "error": f"{type(e).__name__}: {e}"}), flush=True)
        return 1
    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
