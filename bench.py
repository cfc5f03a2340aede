"""Headline benchmark: ResNet-50 training throughput, images/sec/chip.

Needs a TPU.  No chip, a ``device_kind`` that ``training.memory`` has no
peaks for, or any failed config or family is a non-zero exit — there is
no CPU fallback and no echo of an older result: a number under a device
metric's name comes from the device or not at all.  The failure is still
machine-readable: the LAST stdout line is always a compact (<~500 byte)
headline JSON {"metric", "value", "unit", "vs_baseline", ...} sized for a
tail-window capture (round 4's lesson: one fat last line parsed as null),
preceded by the full per-config record on a line of its own.

One process per chip.  Benched families (``--families``): ``lm``
(llama_125m decoder, tools/bench_lm), ``bert`` (bert_base MLM,
tools/bench_bert), ``vit`` (tools/bench_vit) and opt-in ``gen``
(tools/bench_generate: KV-cache decode throughput + MBU) run as
sequential children — a fresh HBM heap per family — BEFORE this process
touches JAX, each with ``--platform tpu`` so that it fails at start-up
without a chip instead of measuring the host; ``input``
(tools/bench_input) is pure host.  Then this process takes the chip
itself for ``resnet`` (both ``resnet50`` and ``resnet50_s2d``, the
MXU-friendly space-to-depth stem — the headline is the faster one).
``--profile-dir`` captures a jax.profiler trace per ResNet config (off by
default; traces are large and belong under an ignored directory).

Baseline: the reference publishes no numbers (BASELINE.json "published":
{}), so ``vs_baseline`` is computed against TARGET_IMG_PER_SEC_PER_CHIP —
v5e peak ≈ 197 bf16 TFLOP/s; ResNet-50 fwd+bwd ≈ 3 × 4.1 ≈ 12.3
GFLOP/image → ~16k img/s roofline; a well-tuned conv pipeline sustaining
~17% of peak gives ~2800 img/s/chip, and target = 0.9 × 2800 ≈ 2500
(≥90%-of-MLPerf-class, BASELINE.md).  vs_baseline ≥ 1.0 meets the goal.

Measures true end-to-end step time: jitted train step (bf16 policy, label
smoothing, weight decay, SGD momentum), synthetic device-resident input
(the input pipeline is measured separately in tests).
"""

import argparse
import json
import os
import subprocess
import sys
import time

TARGET_IMG_PER_SEC_PER_CHIP = 2500.0
GFLOP_PER_IMAGE = 12.3            # ResNet-50 fwd+bwd ≈ 3 × 4.1 GFLOP
HEADLINE_METRIC = "resnet50_train_images_per_sec_per_chip"
_HEADLINE_KEYS = ("metric", "value", "unit", "vs_baseline", "backend",
                  "device_kind", "config", "mfu_pct", "measured_at")


def _headline(record: dict) -> dict:
    h = {k: record[k] for k in _HEADLINE_KEYS if k in record}
    err = record.get("error")
    if err is not None:
        err = str(err)
        h["error"] = err if len(err) <= 160 else err[:157] + "..."
    return h


def _emit(record: dict) -> None:
    """Print the full record, then — always the LAST stdout line — the
    compact headline a tail capture can parse whatever the record's
    size."""
    print(json.dumps(record), flush=True)
    print(json.dumps(_headline(record)), flush=True)


def _base_record() -> dict:
    return {
        "metric": HEADLINE_METRIC,
        "value": 0.0,
        "unit": "images/sec/chip",
        "vs_baseline": 0.0,
    }


def bench_config(preset_name: str, batch_per_chip: int, warmup: int,
                 iters: int, profile_dir=None):
    """Train-step throughput for one ResNet preset on the live backend."""
    import jax
    import numpy as np
    import optax

    from tensorflow_train_distributed_tpu.models import resnet
    from tensorflow_train_distributed_tpu.parallel.sharding import shard_batch
    from tensorflow_train_distributed_tpu.runtime.mesh import (
        MeshConfig, build_mesh,
    )
    from tensorflow_train_distributed_tpu.training import (
        Policy, Trainer, TrainerConfig,
    )
    from tensorflow_train_distributed_tpu.training.memory import tpu_peaks

    mesh = build_mesh(MeshConfig(data=-1))
    n_chips = mesh.devices.size
    # Keyed by device_kind; an unknown kind raises (no default peak).
    peak_tflops = tpu_peaks(mesh.devices.flat[0].device_kind)["peak_tflops"]
    batch_size = batch_per_chip * n_chips
    preset = resnet.RESNET_PRESETS[preset_name]
    task = resnet.make_task(preset)
    trainer = Trainer(
        task,
        optax.sgd(0.1, momentum=0.9, nesterov=True),
        mesh,
        policy=Policy.from_name("mixed_bfloat16"),
        config=TrainerConfig(log_every=1_000_000),
    )
    rng = np.random.default_rng(0)
    if preset.space_to_depth:
        # Host pipelines deliver s2d layout (datasets.SyntheticImageNet
        # space_to_depth=True); the device never sees the 3-channel tensor.
        img = rng.standard_normal((batch_size, 112, 112, 12),
                                  dtype=np.float32)
    else:
        img = rng.standard_normal((batch_size, 224, 224, 3),
                                  dtype=np.float32)
    batch = {"image": img,
             "label": rng.integers(0, 1000, batch_size).astype(np.int32)}
    state = trainer.create_state(batch)
    step = trainer._compiled_train_step()
    dev_batch = shard_batch(mesh, batch)
    for _ in range(warmup):
        state, m = step(state, dev_batch)
    jax.block_until_ready(state)
    # Plausibility guard: a timed window faster than the compute roofline
    # (all FLOPs at 100% peak) is a measurement artifact, not throughput
    # (observed once: 73k img/s ≈ 460% MFU).  Re-time once on the SAME
    # compiled step; a persistent artifact is reported but flagged so it
    # can never become the headline.
    roofline_dt = (batch_size * GFLOP_PER_IMAGE
                   / (peak_tflops * 1e3 * n_chips))
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(iters):
            state, m = step(state, dev_batch)
        jax.block_until_ready(m)
        dt = (time.perf_counter() - t0) / iters
        if dt >= roofline_dt:
            break
    if profile_dir is not None:
        # Short profiled window, separate from the timed one: traces are
        # evidence, not part of the measurement.
        try:
            with jax.profiler.trace(os.path.join(profile_dir, preset_name)):
                for _ in range(3):
                    state, m = step(state, dev_batch)
                jax.block_until_ready(m)
        except Exception as e:  # profiling must never kill the bench
            print(f"# profiler trace failed: {e}", file=sys.stderr)
    img_per_sec_per_chip = batch_size / dt / n_chips
    result = {
        "images_per_sec_per_chip": round(img_per_sec_per_chip, 1),
        "step_time_ms": round(dt * 1e3, 2),
        "batch_per_chip": batch_per_chip,
        "n_chips": n_chips,
        "mfu_pct": round(100 * img_per_sec_per_chip * GFLOP_PER_IMAGE
                         / (peak_tflops * 1e3), 2),
    }
    if dt < roofline_dt:
        result["implausible"] = True
    return result



# Non-ResNet model families folded into the emit.  Children of a parent
# that has not touched JAX yet: one process on the chip at a time, a
# fresh HBM heap per family.  ``--platform tpu`` makes a child without a
# chip fail at start-up.
_HERE = os.path.dirname(os.path.abspath(__file__))
_ON_CHIP = ["--platform", "tpu"]
FAMILY_CMDS = {
    "lm": ([sys.executable, os.path.join(_HERE, "tools", "bench_lm.py"),
            "--preset", "llama_125m", "--batch-per-chip", "8",
            "--seq", "2048", "--no-remat", "--warmup", "3",
            "--iters", "10", *_ON_CHIP], "llama_125m"),
    "bert": ([sys.executable, os.path.join(_HERE, "tools", "bench_bert.py"),
              "--preset", "bert_base", "--batch-per-chip", "32",
              "--seq", "128", "--warmup", "3", "--iters", "20",
              *_ON_CHIP], "bert_base"),
    # Opt-in (not in the default list): KV-cache decode throughput + MBU.
    "gen": ([sys.executable, os.path.join(_HERE, "tools",
                                          "bench_generate.py"),
             "--preset", "llama_125m", "--batch", "8",
             "--prompt-len", "128", "--max-new", "256", *_ON_CHIP],
            "llama_125m_decode"),
    "vit": ([sys.executable, os.path.join(_HERE, "tools", "bench_vit.py"),
             "--preset", "vit_b16", "--batch-per-chip", "64",
             "--warmup", "3", "--iters", "10", *_ON_CHIP],
            "vit_b16"),
    # Pure host (forces the CPU platform itself): JPEG decode+augment
    # pipeline throughput incl. the ship-raw-uint8 and native-libjpeg
    # modes.
    "input": ([sys.executable, os.path.join(_HERE, "tools",
                                            "bench_input.py"),
               "--records", "128", "--image-hw", "192", "--size", "160",
               "--batch", "32", "--workers", "2"],
              "host_input"),
}


def _run_family(cmd, timeout_s: float):
    """(record | None, error | None) from a family bench subprocess."""
    try:
        out = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return None, f"family bench timed out after {timeout_s:.0f}s"
    lines = [ln for ln in out.stdout.strip().splitlines()
             if ln.startswith("{")]
    if not lines:
        tail = (out.stderr or out.stdout).strip().splitlines()
        return None, ("family bench printed no JSON: "
                      + (tail[-1][-200:] if tail else
                         f"rc={out.returncode}"))
    try:
        rec = json.loads(lines[-1])
    except ValueError:
        return None, f"unparseable family JSON: {lines[-1][:200]!r}"
    if out.returncode != 0 or rec.get("error"):
        return None, rec.get("error", f"rc={out.returncode}")
    return rec, None


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--configs",
                   default="resnet50,resnet50_s2d",
                   help="comma-separated RESNET_PRESETS names to bench. "
                        "resnet50_s2d_bnsub exists but was MEASURED AND "
                        "REJECTED on silicon (-12%%: the strided stats "
                        "gather costs more than the stats reads it "
                        "saves) — not worth chip time by default")
    p.add_argument("--families", default="resnet,lm,bert,vit,input",
                   help="model families in the emit: resnet (in-process "
                        "headline) plus lm/bert/vit child benches; "
                        "'input' = host JPEG-pipeline throughput (pure "
                        "CPU); 'gen' (opt-in) adds KV-cache decode "
                        "throughput + MBU")
    p.add_argument("--batch-per-chip", type=int, default=256)
    p.add_argument("--warmup", type=int, default=5)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--family-timeout", type=float, default=900.0,
                   help="timeout per non-resnet family subprocess")
    p.add_argument("--profile-dir", default="",
                   help="jax.profiler trace output per ResNet config "
                        "('' = off)")
    args = p.parse_args(argv)

    record = _base_record()
    try:
        return _run(args, record)
    except Exception as e:
        # One JSON line on any outcome (round-1 lesson: rc=1 with no
        # JSON is zero evidence) — and a non-zero exit with it.
        _emit(dict(record, error=f"{type(e).__name__}: {e}"))
        return 1


def _run(args, record) -> int:
    families = [f for f in args.families.split(",") if f]
    failures = {}

    # 1. Family children, while this process is still off JAX.
    family_results = {}
    for fam in families:
        if fam == "resnet":
            continue
        if fam not in FAMILY_CMDS:
            failures[fam] = f"unknown family {fam!r}"
            continue
        cmd, key = FAMILY_CMDS[fam]
        rec_f, err = _run_family(cmd, args.family_timeout)
        if err:
            failures[fam] = err
        else:
            family_results[key] = rec_f

    # 2. Now take the chip in this process for ResNet.
    results = {}
    if "resnet" in families:
        import jax

        from tensorflow_train_distributed_tpu.runtime import compile_cache

        compile_cache.place_compile_cache()
        dev0 = jax.devices()[0]
        record.update(backend=dev0.platform, device_kind=dev0.device_kind)
        configs = [c for c in args.configs.split(",") if c]
        if dev0.platform != "tpu":
            failures["resnet"] = (f"needs a TPU; JAX found "
                                  f"{dev0.platform!r} (no CPU fallback)")
            configs = []
        for name in configs:
            try:
                results[name] = bench_config(
                    name, args.batch_per_chip, args.warmup, args.iters,
                    args.profile_dir or None)
            except Exception as e:  # recorded, and fails the run below
                failures[name] = f"{type(e).__name__}: {e}"

    record["configs"] = {**results, **family_results}
    plausible = {n: r for n, r in results.items()
                 if not r.get("implausible")}
    if results and not plausible:
        failures["resnet"] = ("all measurements exceeded the hardware "
                              "roofline (timing artifact; see bench_config "
                              "guard)")
    if plausible:
        best_name = max(plausible, key=lambda n:
                        plausible[n]["images_per_sec_per_chip"])
        best = results[best_name]
        record.update(
            value=best["images_per_sec_per_chip"],
            vs_baseline=round(best["images_per_sec_per_chip"]
                              / TARGET_IMG_PER_SEC_PER_CHIP, 3),
            config=best_name, mfu_pct=best["mfu_pct"])
    elif family_results and "resnet" not in families:
        # Families-only run (--families lm / bert): the first successful
        # family carries the headline; there is no ResNet target to
        # compare against, so vs_baseline stays 0.0 by convention.
        first = next(iter(family_results.values()))
        record.update(
            metric=first.get("metric", record["metric"]),
            value=first.get("value", 0.0),
            unit=first.get("unit", record["unit"]))
    record["measured_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                          time.gmtime())
    if args.profile_dir:
        record["profile_dir"] = args.profile_dir
    if failures:
        record["failed_configs"] = failures
        record["error"] = f"failed: {sorted(failures)}"
    _emit(record)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
