"""Quantized gradient collectives A/B harness (ROADMAP item 4a).

One run, four legs over the SAME fixed-seed batch stream on one mesh:

- ``none``  — today's single-program GSPMD step (implicit f32 gradient
  allreduce), the baseline.
- ``f32``   — the explicit three-program pipeline (per-shard grads →
  sync → apply) with the exact f32 psum sync: isolates the pipeline
  restructuring from the quantization.
- ``int8``  — the EQuARX pipeline: int8+scales on the wire with the
  error-feedback residual carried in the train state.
- ``int8`` + ``TTD_NO_GRAD_QUANT=1`` — the kill switch, which must be
  BITWISE-equal to ``none`` (same params after N steps).

Reported per quant leg: fixed-seed loss curve (parity vs the baseline),
median wall/step, analytic gradient wire bytes
(``collectives.grad_sync_wire_bytes``), and the comm fraction measured
from the flight recorder's ``train/grad_comm`` / ``train/grad_fwdbwd``
/ ``train/optimizer_apply`` sub-spans (each a blocking dispatch — real
device time).  A restore-compat check round-trips a pre-quant
checkpoint into the residual-carrying train state.

``--overlap`` switches to the bucketed-overlap A/B (ROADMAP item 3):
three int8 legs over the same fixed-seed stream — ``seq`` (the
sequential three-program pipeline, ``grad_overlap=0``), ``ovl`` (the
bucketed overlap step, K buckets dispatched in-flight), and ``ovl`` +
``TTD_NO_GRAD_OVERLAP=1`` (the kill switch, which must be BITWISE-equal
to ``seq``).  Reported: median of per-step wall-ratio pairs, per-leg
blocking comm-fraction (the overlap step's ``train/grad_comm`` spans
meter dispatch only; its device wait is the ``train/step_barrier``
span), and loss parity ovl-vs-seq.  Record goes to
``profiles/bench/grad_overlap_ab.jsonl``.

Appends one JSON record to ``profiles/bench/grad_quant_ab.jsonl`` (or
the overlap sink above) and prints a compact headline as the last
stdout line (driver emit contract).

Usage::

    python tools/bench_grad_quant.py --platform cpu --cpu-devices 8
    python tools/bench_grad_quant.py --steps 50 --batch 64   # on TPU
    python tools/bench_grad_quant.py --overlap --platform cpu \
        --cpu-devices 8
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OUT_DEFAULT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "profiles", "bench", "grad_quant_ab.jsonl")
OUT_OVERLAP = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "profiles", "bench", "grad_overlap_ab.jsonl")

LOSS_PARITY_TOL = 0.1       # |loss_int8 - loss_none| bound, per step
#: ovl-vs-seq: both legs are int8 with error feedback; they differ only
#: in Q8 block placement (leaf-aligned vs concat-spanning), so parity
#: is held an order of magnitude tighter than int8-vs-exact.
OVERLAP_PARITY_TOL = 1e-3


def _make_task(vocab: int, d_model: int, layers: int, seq: int):
    import jax.numpy as jnp

    from tensorflow_train_distributed_tpu.models.llama import (
        CausalLmTask, LlamaConfig,
    )

    return CausalLmTask(LlamaConfig(
        vocab_size=vocab, d_model=d_model, num_layers=layers,
        num_heads=4, num_kv_heads=None, ffn_size=2 * d_model,
        max_positions=seq, dtype=jnp.float32, scan_layers=False))


def _batches(steps: int, batch: int, seq: int, vocab: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    out = []
    for _ in range(steps):
        toks = rng.integers(0, vocab, (batch, seq)).astype(np.int32)
        out.append({"tokens": toks,
                    "targets": np.roll(toks, -1, axis=1)})
    return out


def _span_totals(evs) -> dict:
    totals: dict = {}
    for name, ph, _t0, dur, _tid, _attrs in evs:
        if ph == "X" and name.startswith("train/"):
            totals[name] = totals.get(name, 0.0) + dur
    return totals


def run_leg(grad_quant: str, task, mesh, batches, seed: int,
            kill_switch: bool = False, grad_overlap=None,
            kill_env: str = "TTD_NO_GRAD_QUANT") -> dict:
    import jax
    import numpy as np
    import optax

    from tensorflow_train_distributed_tpu.parallel import collectives
    from tensorflow_train_distributed_tpu.parallel.sharding import (
        shard_batch,
    )
    from tensorflow_train_distributed_tpu.runtime import events
    from tensorflow_train_distributed_tpu.training import (
        Trainer, TrainerConfig,
    )

    cfg_kw = {} if grad_overlap is None else {"grad_overlap": grad_overlap}
    prior = os.environ.get(kill_env)
    if kill_switch:
        os.environ[kill_env] = "1"
    try:
        trainer = Trainer(
            task, optax.adamw(3e-3), mesh,
            config=TrainerConfig(seed=seed, log_every=10 ** 9,
                                 grad_quant=grad_quant, **cfg_kw))
    finally:
        if kill_switch:
            if prior is None:
                os.environ.pop(kill_env, None)
            else:
                os.environ[kill_env] = prior
    state = trainer.create_state(batches[0])
    step = trainer._compiled_train_step()
    rec = events.get_recorder()
    losses, walls = [], []
    for i, b in enumerate(batches):
        dev = shard_batch(mesh, b)
        t0 = time.perf_counter()
        state, m = step(state, dev)
        losses.append(float(m["loss"]))      # device fetch = step barrier
        walls.append(time.perf_counter() - t0)
        if i == 0:
            # Step 0 compiles all three programs INSIDE their spans;
            # drop it from the span totals, consistent with walls[1:].
            rec.clear()
    totals = _span_totals(rec.events())
    leg = {
        "grad_quant": trainer.grad_quant,
        "grad_overlap": trainer.grad_overlap,
        "kill_switch": kill_switch,
        "loss_first": round(losses[0], 6),
        "loss_last": round(losses[-1], 6),
        "losses": [round(x, 6) for x in losses],
        "wall_per_step_ms": round(
            statistics.median(walls[1:] or walls) * 1e3, 3),
        "walls_ms": [round(w * 1e3, 3) for w in walls],
        "wire_bytes_per_step": collectives.grad_sync_wire_bytes(
            state.params, mesh.shape["data"],
            "f32" if trainer.grad_quant == "none" else trainer.grad_quant),
    }
    if "grad_buckets" in m:
        leg["grad_buckets"] = int(m["grad_buckets"])
        leg["bucket_wire_mb"] = round(float(m["grad_comm_mb"]), 6)
    comm = totals.get("train/grad_comm")
    if comm is not None:
        # The barrier term is zero on the sequential pipeline (every
        # dispatch blocks inline) and the realized device wait on the
        # overlap step — so comm_fraction is BLOCKING comm share on
        # both: full device sync time sequentially, dispatch-only time
        # under overlap.
        span_sum = sum(totals.get(k, 0.0) for k in (
            "train/grad_fwdbwd", "train/grad_comm",
            "train/optimizer_apply", "train/step_barrier"))
        leg["grad_comm_ms_total"] = round(comm * 1e3, 3)
        leg["comm_fraction"] = round(comm / span_sum, 4) if span_sum else 0.0
    final_params = jax.tree.map(np.asarray, jax.device_get(state.params))
    return leg, final_params, trainer


def _bitwise_equal(a, b) -> bool:
    import jax
    import numpy as np

    leaves_a = jax.tree.leaves(a)
    leaves_b = jax.tree.leaves(b)
    return (len(leaves_a) == len(leaves_b)
            and all(np.array_equal(x, y)
                    for x, y in zip(leaves_a, leaves_b)))


def _restore_compat_check(task, mesh, batch) -> bool:
    """A checkpoint saved WITHOUT residual leaves (pre-quant trainer)
    must restore into the residual-carrying template with residuals
    zero-initialized."""
    import jax
    import numpy as np
    import optax

    from tensorflow_train_distributed_tpu.training import (
        Trainer, TrainerConfig,
    )
    from tensorflow_train_distributed_tpu.training.checkpoint import (
        CheckpointManager,
    )

    with tempfile.TemporaryDirectory() as d:
        old = Trainer(task, optax.adamw(3e-3), mesh,
                      config=TrainerConfig(log_every=10 ** 9))
        state = old.create_state(batch)
        mgr = CheckpointManager(os.path.join(d, "ckpt"))
        mgr.save(0, state, force=True)
        mgr.wait_until_finished()
        new = Trainer(task, optax.adamw(3e-3), mesh,
                      config=TrainerConfig(log_every=10 ** 9,
                                           grad_quant="int8"))
        template = new.create_state(batch)
        restored = mgr.restore(template)
        mgr.close()
        if restored is None or restored.grad_residual is None:
            return False
        zeros = all(not np.asarray(r).any()
                    for r in jax.tree.leaves(restored.grad_residual))
        params_eq = _bitwise_equal(
            jax.device_get(restored.params), jax.device_get(state.params))
        return zeros and params_eq


def run_overlap_ab(args, mesh, task, batches) -> int:
    """The bucketed-overlap A/B: sequential int8 vs K-bucket overlap
    vs the ``TTD_NO_GRAD_OVERLAP`` kill switch, same fixed-seed
    stream.  Headline value is the median of per-step wall-ratio PAIRS
    (seq_i / ovl_i — pairing before the median cancels the stream's
    per-step size/content variance)."""
    import jax

    legs = {}
    params = {}
    legs["seq"], params["seq"], _ = run_leg(
        "int8", task, mesh, batches, args.seed, grad_overlap=0)
    legs["ovl"], params["ovl"], ovl_trainer = run_leg(
        "int8", task, mesh, batches, args.seed,
        grad_overlap=args.grad_overlap)
    leg_ks, params["ks"], ks_trainer = run_leg(
        "int8", task, mesh, batches, args.seed, kill_switch=True,
        grad_overlap=args.grad_overlap, kill_env="TTD_NO_GRAD_OVERLAP")

    # Warmup step 0 (compiles) excluded from pairing, like wall medians.
    pairs = [(a, b) for a, b in zip(legs["seq"]["walls_ms"][1:],
                                    legs["ovl"]["walls_ms"][1:]) if b > 0]
    ratios = [a / b for a, b in pairs]
    diffs = [abs(a - b) for a, b in zip(legs["ovl"]["losses"],
                                        legs["seq"]["losses"])]
    cf_seq = legs["seq"].get("comm_fraction")
    cf_ovl = legs["ovl"].get("comm_fraction")
    record = {
        "metric": "grad_overlap_ab",
        "value": round(statistics.median(ratios), 4) if ratios else 0.0,
        "unit": "x wall-clock, sequential/overlap int8 "
                "(median of per-step pairs)",
        "backend": jax.default_backend(),
        "devices": int(mesh.devices.size),
        "config": {"steps": args.steps, "batch": args.batch,
                   "seq": args.seq, "vocab": args.vocab,
                   "d_model": args.d_model, "layers": args.layers,
                   "seed": args.seed, "optimizer": "adamw(3e-3)",
                   "grad_overlap": args.grad_overlap},
        "legs": legs,
        "blocking_comm_fraction": {
            "seq": cf_seq, "ovl": cf_ovl,
            "reduced": (cf_seq is not None and cf_ovl is not None
                        and cf_ovl < cf_seq),
        },
        "loss_parity": {
            "max_abs_diff_ovl_vs_seq": round(max(diffs), 6),
            "tol": OVERLAP_PARITY_TOL,
            "within_tol": max(diffs) <= OVERLAP_PARITY_TOL,
            "ovl_loss_decreased":
                legs["ovl"]["loss_last"] < legs["ovl"]["loss_first"],
        },
        "killswitch": {
            "resolved_grad_overlap": ks_trainer.grad_overlap,
            "bitwise_equal_to_seq": _bitwise_equal(params["ks"],
                                                   params["seq"]),
            "wall_per_step_ms": leg_ks["wall_per_step_ms"],
        },
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    if jax.default_backend() == "cpu":
        record["cpu_note"] = (
            "virtual CPU mesh: all devices share one host's cores, so "
            "overlapping comm with compute cannot create wall-clock "
            "headroom (there is no independent fabric to hide work on) "
            "— the blocking comm-fraction drop is the acceptance "
            "metric here; the wall ratio realizes on TPU (not measured)")
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(record) + "\n")
    full = json.dumps(record)
    if len(full) <= 4096:
        print(full, flush=True)
    headline = {k: record[k] for k in
                ("metric", "value", "unit", "backend", "devices",
                 "blocking_comm_fraction", "measured_at")}
    headline["grad_buckets"] = legs["ovl"].get("grad_buckets")
    headline["loss_parity_ok"] = record["loss_parity"]["within_tol"]
    headline["killswitch_bitwise"] = (
        record["killswitch"]["bitwise_equal_to_seq"])
    print(json.dumps(headline), flush=True)
    ok = (record["loss_parity"]["within_tol"]
          and record["killswitch"]["bitwise_equal_to_seq"]
          and record["blocking_comm_fraction"]["reduced"])
    return 0 if ok else 1


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default=None,
                   help="JSONL record sink ('' disables; default "
                        "grad_quant_ab.jsonl, or grad_overlap_ab.jsonl "
                        "with --overlap)")
    p.add_argument("--platform", default=None, choices=["cpu", "tpu"])
    p.add_argument("--cpu-devices", type=int, default=None)
    p.add_argument("--overlap", action="store_true",
                   help="run the bucketed-overlap A/B (seq int8 vs "
                        "overlap int8 vs kill switch) instead of the "
                        "quant A/B")
    p.add_argument("--grad-overlap", type=int, default=4,
                   help="bucket count K for the overlap leg")
    args = p.parse_args(argv)
    if args.out is None:
        args.out = OUT_OVERLAP if args.overlap else OUT_DEFAULT

    from tensorflow_train_distributed_tpu.runtime import compile_cache

    compile_cache.place_compile_cache()
    if args.platform or args.cpu_devices:
        from tensorflow_train_distributed_tpu.runtime.mesh import (
            force_platform,
        )

        force_platform(args.platform, args.cpu_devices)

    import jax

    from tensorflow_train_distributed_tpu.runtime.mesh import (
        MeshConfig, build_mesh,
    )

    if len(jax.devices()) < 2:
        print(json.dumps({
            "metric": "grad_quant_ab", "value": 0.0, "error":
            "needs >= 2 devices (pass --platform cpu --cpu-devices 8 "
            "for the virtual mesh)"}))
        return 1
    mesh = build_mesh(MeshConfig(data=-1))
    task = _make_task(args.vocab, args.d_model, args.layers, args.seq)
    batches = _batches(args.steps, args.batch, args.seq, args.vocab,
                       args.seed)

    if args.overlap:
        return run_overlap_ab(args, mesh, task, batches)

    legs = {}
    params = {}
    # grad_overlap=0 pins the quant A/B to the sequential pipeline the
    # record has always measured; the overlap step has its own A/B.
    leg_none, params["none"], _ = run_leg("none", task, mesh, batches,
                                          args.seed)
    legs["none"] = leg_none
    for gq in ("f32", "int8"):
        legs[gq], params[gq], _ = run_leg(gq, task, mesh, batches,
                                          args.seed, grad_overlap=0)
    leg_ks, params["ks"], ks_trainer = run_leg(
        "int8", task, mesh, batches, args.seed, kill_switch=True,
        grad_overlap=0)

    diffs = [abs(a - b) for a, b in zip(legs["int8"]["losses"],
                                        legs["none"]["losses"])]
    wire_f32 = legs["none"]["wire_bytes_per_step"]
    wire_int8 = legs["int8"]["wire_bytes_per_step"]
    record = {
        "metric": "grad_quant_ab",
        "value": round(wire_f32 / max(wire_int8, 1), 3),
        "unit": "x less gradient wire bytes (int8 vs f32)",
        "backend": jax.default_backend(),
        "devices": int(mesh.devices.size),
        "config": {"steps": args.steps, "batch": args.batch,
                   "seq": args.seq, "vocab": args.vocab,
                   "d_model": args.d_model, "layers": args.layers,
                   "seed": args.seed, "optimizer": "adamw(3e-3)"},
        "legs": legs,
        "killswitch": {
            "resolved_grad_quant": ks_trainer.grad_quant,
            "bitwise_equal_to_none": _bitwise_equal(params["ks"],
                                                    params["none"]),
            "wall_per_step_ms": leg_ks["wall_per_step_ms"],
        },
        "loss_parity": {
            "max_abs_diff_int8_vs_none": round(max(diffs), 6),
            "tol": LOSS_PARITY_TOL,
            "within_tol": max(diffs) <= LOSS_PARITY_TOL,
            "int8_loss_decreased":
                legs["int8"]["loss_last"] < legs["int8"]["loss_first"],
        },
        "comm_fraction": {
            gq: legs[gq].get("comm_fraction") for gq in ("f32", "int8")},
        # The invariant lever: gradient bytes on the wire per step,
        # int8 leg as a fraction of the f32 leg's.
        "comm_bytes_fraction": round(wire_int8 / max(wire_f32, 1), 4),
        "restore_compat_ok": _restore_compat_check(task, mesh,
                                                   batches[0]),
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    if jax.default_backend() == "cpu":
        record["cpu_note"] = (
            "virtual CPU mesh: all devices share one host's cores, so "
            "the quantize ALU work is Nx serialized and the span-time "
            "comm fraction is compute-bound — the same verdict "
            "bench_allreduce documents for the host ring's q8 leg; the "
            "wire-bytes fraction above is the invariant lever, "
            "realized where per-rank fabric bandwidth is below quant "
            "throughput (DCN/ICI-bound regimes; TPU leg not measured)")
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(record) + "\n")
    full = json.dumps(record)
    if len(full) <= 4096:
        print(full, flush=True)
    headline = {k: record[k] for k in
                ("metric", "value", "unit", "backend", "devices",
                 "comm_fraction", "measured_at")}
    headline["loss_parity_ok"] = record["loss_parity"]["within_tol"]
    headline["killswitch_bitwise"] = (
        record["killswitch"]["bitwise_equal_to_none"])
    headline["restore_compat_ok"] = record["restore_compat_ok"]
    print(json.dumps(headline), flush=True)
    ok = (record["loss_parity"]["within_tol"]
          and record["killswitch"]["bitwise_equal_to_none"]
          and record["restore_compat_ok"])
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
