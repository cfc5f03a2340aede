"""The ``train_distributed`` launcher: flags → cluster → mesh → fit.

TPU-native rebuild of the reference's L6 entry point (SURVEY.md §2.1: a
``train_distributed`` CLI that parses ``--strategy`` / model selection,
builds ``TF_CONFIG``-aware cluster setup, and dispatches to a per-model
train fn).  The strategy zoo collapses into mesh presets
(``runtime.mesh.STRATEGY_PRESETS``), so the reference's launch contract
keeps working: ``--strategy=mirrored|multi_worker_mirrored|horovod|tpu``
all mean "data-parallel SPMD", ``--strategy=dtensor`` means the 2-D
data×tensor mesh, and ``TF_CONFIG`` in the environment still places this
process in the cluster (``runtime.distributed``).

Usage::

    train_distributed --config=resnet50_imagenet --steps=1000
    train_distributed --config=llama2_7b_sft --strategy=dp_tp \
        --mesh data=4,tensor=8 --precision=bfloat16 \
        --checkpoint-dir=/ckpt --checkpoint-every=500
    python -m tensorflow_train_distributed_tpu --config=mnist --steps=200
"""

from __future__ import annotations

import argparse
import dataclasses
import logging
from typing import Optional, Sequence

logger = logging.getLogger(__name__)


def build_parser() -> argparse.ArgumentParser:
    from tensorflow_train_distributed_tpu.models import registry
    from tensorflow_train_distributed_tpu.runtime.mesh import STRATEGY_PRESETS

    p = argparse.ArgumentParser(
        prog="train_distributed",
        description="TPU-native distributed training launcher",
    )
    p.add_argument("--config", required=True,
                   help=f"model config; one of {registry.available()}")
    p.add_argument("--strategy", default=None,
                   choices=sorted(STRATEGY_PRESETS) + ["ps", "parameter_server"],
                   help="mesh preset (default: the config's preset); "
                        "reference names (mirrored/multi_worker_mirrored/"
                        "horovod/tpu/dtensor) are accepted")
    p.add_argument("--mesh", default=None, metavar="AXIS=N,...",
                   help="explicit mesh axis sizes overriding the preset, "
                        "e.g. data=4,tensor=2 (one axis may be -1)")
    p.add_argument("--dcn", default=None, metavar="AXIS=N,...",
                   help="multi-slice placement: how many slices divide each "
                        "axis over DCN, e.g. data=4 (default: all slices on "
                        "the outermost data-like axis)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--global-batch-size", type=int, default=None,
                   help="global batch size (default: the config's)")
    p.add_argument("--learning-rate", type=float, default=None)
    p.add_argument("--optimizer", default="adamw",
                   choices=["sgd", "momentum", "adam", "adamw", "lamb",
                            "adafactor"])
    p.add_argument("--weight-decay", type=float, default=0.0,
                   help="decoupled weight decay (adamw/lamb)")
    p.add_argument("--grad-clip-norm", type=float, default=None,
                   help="clip gradients to this global norm before the "
                        "optimizer update (default: the config's "
                        "convention, e.g. 1.0 for BERT/Llama; 0 disables)")
    p.add_argument("--lora-rank", type=int, default=0,
                   help="LoRA fine-tuning for decoder-LM configs: freeze "
                        "the base, train rank-N adapters on "
                        "--lora-targets (0 = full fine-tuning). The "
                        "optimizer updates adapters only")
    p.add_argument("--lora-alpha", type=float, default=16.0,
                   help="LoRA scaling numerator (delta = alpha/rank·A·B)")
    p.add_argument("--lora-targets", default="query,value",
                   help="comma-separated Dense names to adapt (layers.py "
                        "names: query,key,value,out,wi_gate,wi_up,wo,"
                        "lm_head)")
    p.add_argument("--ema-decay", type=float, default=None,
                   help="track an exponential moving average of the "
                        "params in optimizer state (Polyak averaging — "
                        "the Keras ExponentialMovingAverage equivalent); "
                        "eval/--eval-only then score the EMA weights. "
                        "Typical: 0.999")
    p.add_argument("--warmup-steps", type=int, default=None,
                   help="linear LR warmup steps (default: the config's "
                        "warmup_ratio × --steps)")
    p.add_argument("--lr-schedule", default=None,
                   help="constant | warmup_cosine | warmup_linear | noam | "
                        "resnet_steps (default: the config's convention)")
    p.add_argument("--reduce-lr-factor", type=float, default=None,
                   help="enable ReduceLROnPlateau: multiply the LR by "
                        "this factor (0<f<1) when the monitored metric "
                        "plateaus (monitors val_loss when periodic eval "
                        "runs — --eval-every with --eval-steps — else "
                        "loss); requires a constant LR schedule")
    p.add_argument("--reduce-lr-patience", type=int, default=10,
                   help="plateau events before each reduction")
    p.add_argument("--reduce-lr-min", type=float, default=0.0,
                   help="LR floor for ReduceLROnPlateau")
    p.add_argument("--reduce-lr-cooldown", type=int, default=0,
                   help="events to skip after a reduction")
    p.add_argument("--precision", "--mixed-precision", dest="precision",
                   default="bfloat16",
                   help="dtype policy: float32 | bfloat16 | float16 "
                        "(Keras policy names mixed_bfloat16/mixed_float16 "
                        "also accepted)")
    p.add_argument("--steps-per-execution", type=int, default=1,
                   help="optimizer steps fused into one dispatch via an "
                        "inner scan (reference Model.fit arg of the same "
                        "name)")
    p.add_argument("--grad-accum", type=int, default=1,
                   help="microbatches per optimizer step (gradient "
                        "accumulation; reference analog: Horovod "
                        "backward_passes_per_step)")
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--log-grad-norm", action="store_true",
                   help="add a grad_norm metric (pre-clip global norm of "
                        "the averaged grads) to step logs")
    p.add_argument("--zero1", action="store_true",
                   help="ZeRO-1: shard optimizer moments over the data "
                        "axis (N× less optimizer memory on an N-way dp "
                        "mesh; numerically identical)")
    p.add_argument("--grad-quant", default="none",
                   choices=["none", "f32", "int8"],
                   help="quantized gradient collectives (EQuARX): "
                        "explicit reduce-scatter → int8-quantize → "
                        "all-gather gradient exchange with an "
                        "error-feedback residual in the train state "
                        "(~4x less gradient wire traffic); 'f32' is "
                        "the explicit-pipeline exact baseline (A/B "
                        "leg), 'none' (default) today's implicit GSPMD "
                        "allreduce.  TTD_NO_GRAD_QUANT=1 forces none. "
                        "Composes with dp×fsdp / dp×tp meshes and "
                        "--grad-accum")
    p.add_argument("--grad-overlap", type=int, default=4, metavar="K",
                   help="with --grad-quant: partition the grad tree "
                        "into K byte-balanced buckets (reverse-backward "
                        "order) and dispatch each bucket's quantized "
                        "sync + optimizer apply in-flight while later "
                        "buckets compute (comm/compute overlap); 0 or "
                        "1 restores the sequential three-program "
                        "pipeline byte-for-byte.  TTD_NO_GRAD_OVERLAP=1 "
                        "forces sequential")
    p.add_argument("--sharded-update", action="store_true",
                   help="cross-replica sharded weight update (arxiv "
                        "2004.13336): each data replica runs the "
                        "optimizer on only its gradient shard, then "
                        "params are all-gathered — zero1 extended from "
                        "the moments to the update compute (implies "
                        "--zero1's moment shardings)")
    p.add_argument("--bleu-eval", type=int, default=0, metavar="N",
                   help="after training, beam-decode N eval batches and "
                        "report corpus BLEU (seq2seq/wmt configs only)")
    p.add_argument("--beam-size", type=int, default=4,
                   help="beam width for --bleu-eval (1 = greedy); WMT "
                        "convention is 4")
    p.add_argument("--bos-id", type=int, default=1)
    p.add_argument("--eos-id", type=int, default=2)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--eval-steps", type=int, default=0,
                   help="run evaluation for N batches after training")
    p.add_argument("--eval-only", action="store_true",
                   help="restore from --checkpoint-dir and evaluate "
                        "--eval-steps batches without training "
                        "(Model.evaluate standalone)")
    p.add_argument("--eval-every", type=int, default=None,
                   help="also evaluate every N training steps (Keras "
                        "validation_freq analog); val_* metrics reach "
                        "callbacks/TensorBoard")
    p.add_argument("--data-dir", default=None,
                   help="train from an on-disk mmap corpus "
                        "(data.filesource.write_shards layout) instead of "
                        "the config's synthetic dataset")
    p.add_argument("--pack-seq", type=int, default=0, metavar="LEN",
                   help="treat --data-dir TFRecords as VARIABLE-length "
                        "tokenized documents (no feature spec needed) and "
                        "pack them into LEN-token rows with segment-masked "
                        "attention (decoder LM configs only)")
    p.add_argument("--pack-key", default="tokens",
                   help="feature name holding the document tokens under "
                        "--pack-seq")
    p.add_argument("--data-workers", type=int, default=0, metavar="N",
                   help="serve training batches from N out-of-process "
                        "workers PER HOST (the tf.data-service analog): "
                        "record read + decode/augment CPU work runs in "
                        "the workers, off the trainer's Python thread; "
                        "on a multi-host cluster each host runs its own "
                        "fleet serving its batch share (synthetic and "
                        "--data-dir sources)")
    p.add_argument("--data-transform", default=None,
                   help="named record transform for --data-dir (e.g. "
                        "u8_image_to_f32)")
    p.add_argument("--dataset-kwarg", action="append", default=[],
                   metavar="KEY=VALUE",
                   help="override a config's synthetic-dataset kwarg "
                        "(repeatable; VALUE parsed as JSON, falling back "
                        "to string) — e.g. --dataset-kwarg image_size=64 "
                        "--dataset-kwarg num_examples=2048. Incompatible "
                        "with --data-dir")
    p.add_argument("--init-from-hf", default=None, metavar="DIR",
                   help="initialize a Llama- or BERT-family config's "
                        "params from a local HuggingFace checkpoint dir "
                        "(dims validated against the config/pipeline)")
    p.add_argument("--eval-split", type=float, default=0.0,
                   help="fraction of the dataset held out as a validation "
                        "split for --eval-every/--eval-steps (Keras "
                        "validation_split analog). 0 (default) evaluates "
                        "on the training distribution itself — train-set "
                        "monitoring only")
    # Checkpointing (reference: ModelCheckpoint + BackupAndRestore).
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--save-best", action="store_true",
                   help="also keep the best-metric checkpoint under "
                        "<checkpoint-dir>/best (Keras ModelCheckpoint "
                        "save_best_only analog; monitors val_loss when "
                        "periodic eval runs, else loss)")
    p.add_argument("--checkpoint-every", type=int, default=None)
    p.add_argument("--max-to-keep", type=int, default=3)
    p.add_argument("--no-resume", action="store_true",
                   help="start fresh even if --checkpoint-dir has a "
                        "checkpoint")
    p.add_argument("--no-preemption-handler", action="store_true",
                   help="disable the SIGTERM-coordinated save-and-exit "
                        "(on by default when --checkpoint-dir is set)")
    p.add_argument("--watch-sigint", action="store_true",
                   help="treat SIGINT (Ctrl-C) like a preemption: "
                        "checkpoint, stop, exit with the preemption "
                        "code instead of a stack trace")
    # Self-healing supervision (runtime.supervisor): run training as a
    # child process, classify its exit (clean / preemption / crash),
    # relaunch with exponential backoff under a restart budget.  The
    # relaunch recovers through the normal auto-resume path, incl. the
    # crash-consistent restore fallback in training.checkpoint.
    p.add_argument("--supervise", action="store_true",
                   help="run training under the self-healing supervisor "
                        "(relaunch on crash/preemption; see MIGRATION "
                        "§fault tolerance)")
    p.add_argument("--max-restarts", type=int, default=3,
                   help="crash restart budget under --supervise "
                        "(preemption exits never consume it)")
    p.add_argument("--restart-backoff", type=float, default=1.0,
                   help="base crash-relaunch delay; doubles per "
                        "consecutive crash")
    p.add_argument("--restart-backoff-max", type=float, default=60.0,
                   help="cap on the crash-relaunch delay")
    p.add_argument("--restart-window", type=float, default=0.0,
                   help="rolling window (seconds) for the crash budget: "
                        "only crashes within it count against "
                        "--max-restarts, so a correlated burst cannot "
                        "permanently exhaust a long run's protection "
                        "(0 = lifetime accounting)")
    p.add_argument("--restart-jitter", type=float, default=0.1,
                   help="jitter the crash backoff UP by up to this "
                        "fraction of itself (decorrelates fleet-wide "
                        "relaunch stampedes; 0 disables)")
    p.add_argument("--no-elastic", action="store_true",
                   help="treat device-loss exits as plain crashes "
                        "instead of relaunching onto the surviving "
                        "devices with the checkpoint resharded "
                        "(TTD_NO_ELASTIC=1 is the env equivalent)")
    p.add_argument("--max-device-losses", type=int, default=16,
                   help="give up after this many device-loss relaunches "
                        "(they are crash-budget-free, but a mesh can "
                        "only shrink so many times — a flapping chip "
                        "must not relaunch forever)")
    p.add_argument("--no-restart-on-preemption", action="store_true",
                   help="hand the preemption exit code to the caller "
                        "instead of relaunching (external scheduler "
                        "owns the restart)")
    p.add_argument("--supervisor-journal", default=None,
                   help="JSON-lines attempt journal (default: "
                        "<checkpoint-dir>/supervisor.jsonl)")
    p.add_argument("--fault-plan", default=None, metavar="SPEC",
                   help="ARM deterministic fault injection "
                        "(runtime.faults grammar, e.g. "
                        "'step:200:kill9;ckpt:save:partial:step=40'); "
                        "also via TTD_FAULT_PLAN — chaos testing only")
    # Observability.
    p.add_argument("--tensorboard-dir", default=None)
    p.add_argument("--jsonl-log", default=None,
                   help="append per-step metrics as JSON lines to this file")
    p.add_argument("--profile-dir", default=None,
                   help="capture a jax.profiler trace into this directory "
                        "(reference: TensorBoard callback profile_batch)")
    p.add_argument("--profile-steps", default="10,20", metavar="START,STOP",
                   help="step window for --profile-dir")
    p.add_argument("--profiler-port", type=int, default=None,
                   help="start an on-demand profiler server on this port "
                        "(reference: tf.profiler.experimental.server.start; "
                        "capture from TensorBoard's Capture Profile dialog)")
    p.add_argument("--stall-timeout", type=float, default=0.0,
                   help="warn + dump thread stacks if no step completes in "
                        "this many seconds (reference: coordinator "
                        "watchdog); 0 disables")
    # Cluster placement (reference: TF_CONFIG / cluster resolvers; these
    # flags take precedence, then TTD_*/TF_CONFIG/SLURM env, see
    # runtime.distributed.resolve_cluster).
    p.add_argument("--coordinator-address", default=None)
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    p.add_argument("--platform", default=None, choices=["cpu", "tpu"],
                   help="force a jax backend (cpu useful with "
                        "--cpu-devices for local testing)")
    p.add_argument("--cpu-devices", type=int, default=None,
                   help="with --platform=cpu: number of virtual devices")
    p.add_argument("--list-configs", action="store_true",
                   help="print available configs and exit")
    return p


def _parse_mesh_overrides(spec: str) -> dict[str, int]:
    from tensorflow_train_distributed_tpu.runtime.mesh import AXES

    sizes: dict[str, int] = {}
    for part in spec.split(","):
        if not part:
            continue
        axis, _, val = part.partition("=")
        axis = axis.strip()
        if axis not in AXES:
            raise ValueError(f"Unknown mesh axis {axis!r}; axes: {AXES}")
        sizes[axis] = int(val)
    return sizes


def _resolve_schedule(args, entry):
    """(schedule_name, warmup_steps) from flags + config conventions —
    the ONE place this defaulting lives (validation and the optimizer
    builder must agree)."""
    name = args.lr_schedule or entry.get("lr_schedule", "constant")
    warmup = args.warmup_steps
    if warmup is None:
        warmup = int(entry.get("warmup_ratio", 0.0) * args.steps)
    return name, warmup


def _validate_constant_lr(args, entry):
    name, warmup = _resolve_schedule(args, entry)
    if name != "constant" or warmup:
        raise SystemExit(
            "--reduce-lr-factor needs a constant LR (no schedule/"
            f"warmup): got schedule={name!r}, warmup={warmup} — a "
            "schedule and metric-driven reduction would fight over "
            "the same knob")


def _eval_view(args, state):
    """The state eval should score: the EMA weights when --ema-decay is
    on (a read-only swapped view; training continues from ``state``)."""
    if getattr(args, "ema_decay", None) is not None:
        from tensorflow_train_distributed_tpu.training.ema import (
            swap_ema_params,
        )

        return swap_ema_params(state)
    return state


def _make_optimizer(args, entry):
    """(optimizer, lr_schedule) from flags + the config's LR convention."""
    import optax

    from tensorflow_train_distributed_tpu.training import schedules

    peak = args.learning_rate
    if peak is None:
        peak = entry["learning_rate"]
    name, warmup = _resolve_schedule(args, entry)
    lr = schedules.by_name(name, peak, args.steps, warmup_steps=warmup)
    wrap = False
    if getattr(args, "reduce_lr_factor", None) is not None:
        # ReduceLROnPlateau needs the LR to live in optimizer STATE, not
        # baked into a schedule closure: inject_hyperparams puts it
        # there, and the callback rewrites it functionally between steps.
        _validate_constant_lr(args, entry)  # run() checks early; re-check
        wrap, lr = True, peak

    def build(fn, **kw):
        if wrap:
            # kwargs only: inject_hyperparams injects keyword args.
            return optax.inject_hyperparams(fn)(learning_rate=lr, **kw)
        return fn(lr, **kw)

    if args.optimizer == "sgd":
        tx = build(optax.sgd)
    elif args.optimizer == "momentum":
        tx = build(optax.sgd, momentum=0.9, nesterov=True)
    elif args.optimizer == "adam":
        tx = build(optax.adam)
    elif args.optimizer == "lamb":
        # BERT large-batch convention (the reference's PS-pretrain config
        # scaled with LAMB); layerwise trust ratios make the global batch
        # scalable far past Adam's stability range.
        tx = build(optax.lamb, weight_decay=args.weight_decay)
    elif args.optimizer == "adafactor":
        # Memory-frugal second-moment factorization — the optimizer of
        # choice when optimizer state must not double 7B-param HBM use.
        tx = build(optax.adafactor,
                   weight_decay_rate=args.weight_decay or None)
    else:
        tx = build(optax.adamw, weight_decay=args.weight_decay)
    clip = args.grad_clip_norm
    if clip is None:
        clip = entry.get("grad_clip_norm")
    if clip is not None and clip < 0:
        raise ValueError(
            f"--grad-clip-norm must be >= 0 (0 disables), got {clip}; a "
            "negative max norm would flip every update's sign")
    if clip:  # 0/None = disabled
        # Applied to the already-unscaled, globally-averaged grads (the
        # Trainer unscales before tx), so the clip norm means the same
        # thing at any loss-scale or batch size.
        tx = optax.chain(optax.clip_by_global_norm(clip), tx)
    if getattr(args, "lora_rank", 0):
        # Adapters-only updates AND optimizer state; applied after the
        # clip chain so the global norm is over adapter grads.  (The CLI
        # rejects combining with --ema-decay — a full-params EMA defeats
        # LoRA's memory point — so the EMA wrap below never composes
        # with this in practice.)
        from tensorflow_train_distributed_tpu.models.lora import (
            freeze_base,
        )

        tx = freeze_base(tx)
    if getattr(args, "ema_decay", None) is not None:
        from tensorflow_train_distributed_tpu.training.ema import (
            wrap_with_ema,
        )

        # Range validation (incl. the 0.0 and 1.0 edges) lives in
        # ema_of_params — one source of truth.
        tx = wrap_with_ema(tx, args.ema_decay)
    # Under ReduceLROnPlateau the LR is optimizer STATE, not a schedule —
    # there is no step->lr function for the observational metric.
    return tx, (None if wrap else lr)


def _bleu_eval(args, task, state, loader) -> float:
    """Beam-decode eval batches and score corpus BLEU — the reference's
    Transformer-big target metric ([SPEC] config[3]), evaluated the WMT
    way (beam search + length penalty) rather than teacher-forced."""
    import numpy as np

    from tensorflow_train_distributed_tpu.models import transformer as tr
    from tensorflow_train_distributed_tpu.ops.metrics import (
        corpus_bleu, strip_after_eos,
    )

    if not isinstance(task, tr.Seq2SeqTask):
        raise ValueError(
            "--bleu-eval needs a seq2seq config (wmt family); "
            f"{type(task).__name__} does not decode")
    hyps, refs = [], []
    for _, batch in zip(range(args.bleu_eval), loader):
        out = np.asarray(tr.beam_translate(
            task.config, state.params, batch["inputs"],
            max_len=batch["targets_out"].shape[1],
            beam_size=args.beam_size, bos_id=args.bos_id,
            eos_id=args.eos_id))
        # Padded eval rows (sample_weight 0) are duplicates of a real
        # record — scoring them would double-count sentences.
        keep = (np.asarray(batch["sample_weight"]) > 0
                if "sample_weight" in batch
                else np.ones(len(out), bool))
        hyps += [strip_after_eos(list(r), args.eos_id)
                 for r, k in zip(out, keep) if k]
        refs += [strip_after_eos(list(r), args.eos_id)
                 for r, k in zip(np.asarray(batch["targets_out"]), keep)
                 if k]
    return corpus_bleu(hyps, refs)


@dataclasses.dataclass
class RunResult:
    """What a launch produced (returned by ``run`` for tests/embedding)."""

    state: object
    history: dict
    eval_metrics: Optional[dict]
    mesh: object
    preempted: bool = False


def _parse_profile_steps(spec: str) -> tuple[int, int]:
    try:
        start, stop = (int(p) for p in spec.split(","))
        return start, stop
    except ValueError:
        raise SystemExit(
            f"--profile-steps expects START,STOP (two integers), got "
            f"{spec!r}") from None


def _dataset_kwargs(entry: dict, args: argparse.Namespace) -> dict:
    """Registry dataset kwargs with ``--dataset-kwarg KEY=VALUE``
    overrides (VALUE parsed as JSON so ints/floats/bools arrive typed;
    non-JSON stays a string)."""
    import json

    kw = dict(entry["dataset_kwargs"])
    for item in args.dataset_kwarg:
        key, sep, raw = item.partition("=")
        if not sep or not key:
            raise SystemExit(
                f"--dataset-kwarg wants KEY=VALUE, got {item!r}")
        try:
            kw[key] = json.loads(raw)
        except ValueError:
            kw[key] = raw
    return kw


def run(args: argparse.Namespace, devices=None) -> RunResult:
    """Build the full stack from parsed flags and train.

    ``devices``: the devices the mesh is built over (default: all of
    ``jax.devices()``) — the ``build_mesh(devices=...)`` seam for a
    caller that runs one job on a subset of a host's chips."""
    import jax

    from tensorflow_train_distributed_tpu.runtime import faults

    # Chaos testing: arm the fault plan (flag wins over TTD_FAULT_PLAN)
    # before anything expensive so a typo'd spec dies immediately.
    if getattr(args, "fault_plan", None):
        faults.arm(args.fault_plan, seed=args.seed)
    elif faults.arm_from_env(seed=args.seed) is None:
        # No plan for THIS run: clear any plan a previous in-process
        # run() armed, or its stale entries would fire into this one.
        faults.disarm()

    # Flag-vs-flag errors are decidable before the expensive setup
    # (checkpoint restore, HF import, mesh build) — fail now.
    if args.eval_only and args.eval_steps <= 0:
        raise SystemExit("--eval-only needs --eval-steps N (>0)")
    if args.save_best and not args.checkpoint_dir:
        raise SystemExit("--save-best needs --checkpoint-dir")
    if args.data_workers > 0 and args.pack_seq:
        raise SystemExit(
            "--data-workers does not compose with --pack-seq yet "
            "(packing runs in-process); drop one of the flags")
    if args.data_workers > 0 and args.eval_split:
        raise SystemExit(
            "--data-workers does not compose with --eval-split: the "
            "worker fleet streams the FULL dataset, so training would "
            "consume the held-out examples (contaminated validation); "
            "drop one of the flags")
    if args.data_workers > 0:
        from tensorflow_train_distributed_tpu.models import registry as _r

        _gb = args.global_batch_size
        if _gb is None:
            _gb = _r.get_entry(args.config)["global_batch_size"]
        if _gb % args.data_workers:
            raise SystemExit(
                f"global batch {_gb} not divisible by "
                f"--data-workers={args.data_workers} (each worker serves "
                "an equal slice of every batch)")
    if args.reduce_lr_factor is not None:
        if not 0.0 < args.reduce_lr_factor < 1.0:
            raise SystemExit(
                f"--reduce-lr-factor must be in (0, 1), got "
                f"{args.reduce_lr_factor}")
        from tensorflow_train_distributed_tpu.models import registry as _reg

        _validate_constant_lr(args, _reg.get_entry(args.config))

    # Elastic relaunch (runtime.supervisor): after a device-loss exit
    # the supervisor pins the surviving device count; the relaunched
    # child shrinks its virtual CPU platform (or slices the real device
    # list below) and lets the mesh preset re-resolve on the survivors.
    import os as _os

    from tensorflow_train_distributed_tpu.runtime.supervisor import (
        ENV_ELASTIC_DEVICES,
    )

    elastic_devices = None
    _elastic_env = _os.environ.get(ENV_ELASTIC_DEVICES)
    if _elastic_env:
        try:
            elastic_devices = int(_elastic_env)
        except ValueError:
            raise SystemExit(
                f"{ENV_ELASTIC_DEVICES}={_elastic_env!r}: device count "
                "must be an integer") from None
        if elastic_devices < 1:
            raise SystemExit(
                f"{ENV_ELASTIC_DEVICES}={_elastic_env!r}: device count "
                "must be >= 1")
        if args.cpu_devices:
            args.cpu_devices = min(args.cpu_devices, elastic_devices)
            logger.warning(
                "elastic relaunch: virtual CPU platform shrunk to %d "
                "device(s) (%s)", args.cpu_devices, ENV_ELASTIC_DEVICES)

    from tensorflow_train_distributed_tpu.runtime import compile_cache

    compile_cache.place_compile_cache()
    if args.platform or args.cpu_devices:
        from tensorflow_train_distributed_tpu.runtime.mesh import (
            force_platform,
        )

        force_platform(args.platform, args.cpu_devices)

    from tensorflow_train_distributed_tpu.data.datasets import get_dataset
    from tensorflow_train_distributed_tpu.data.pipeline import (
        DataConfig, HostDataLoader,
    )
    from tensorflow_train_distributed_tpu.models import registry
    from tensorflow_train_distributed_tpu.runtime.distributed import (
        initialize_distributed, resolve_cluster,
    )
    from tensorflow_train_distributed_tpu.runtime.mesh import (
        MeshConfig, build_mesh, strategy_preset,
    )
    from tensorflow_train_distributed_tpu.training import (
        History, JsonlLogger, Policy, ProgressLogger, TensorBoardScalars,
        Trainer, TrainerConfig,
    )
    from tensorflow_train_distributed_tpu.training.checkpoint import (
        CheckpointManager,
    )

    # 1. Cluster: flags → env (TTD_* / TF_CONFIG / SLURM) → single-process.
    cluster = resolve_cluster(
        coordinator_address=args.coordinator_address,
        num_processes=args.num_processes,
        process_id=args.process_id,
    )
    initialize_distributed(cluster)

    # 2. Mesh from strategy preset (+ explicit axis overrides).
    entry = registry.get_entry(args.config)
    strategy = args.strategy or entry["strategy"]
    devices = list(jax.devices() if devices is None else devices)
    if elastic_devices is not None and elastic_devices < len(devices):
        # Real-backend elastic relaunch: the dead chips may still be
        # enumerable for a while — pin the mesh to the surviving count.
        # KNOWN APPROXIMATION: the sidecar carries a COUNT, not device
        # ids, so the prefix slice can pick a still-enumerable dead
        # chip (and drop a healthy one) when the runtime keeps listing
        # it.  That relaunch exits 113 again and the supervisor's
        # max_device_losses cap bounds the loop; identifying survivors
        # by id/health-probe is the multi-host elasticity seam
        # (ROADMAP) — the virtual-CPU path shrinks the platform itself,
        # so the slice is exact there.
        devices = devices[:elastic_devices]
        logger.warning(
            "elastic relaunch: building the mesh over %d of %d "
            "visible device(s)", len(devices), len(jax.devices()))
    n_dev = len(devices)
    cfg = strategy_preset(strategy, n_dev)
    if args.mesh:
        overrides = _parse_mesh_overrides(args.mesh)
        sizes = cfg.axis_sizes()
        sizes.update(overrides)
        if -1 not in sizes.values() and "data" not in overrides:
            sizes["data"] = -1  # let data absorb the remaining devices
        cfg = MeshConfig(strategy=strategy, **sizes)
    if elastic_devices is not None:
        # Divisibility degrade: explicit --mesh sizes pinned for the
        # original device count shrink to the nearest valid layout on
        # the survivors instead of crash-looping the relaunch.
        from tensorflow_train_distributed_tpu.runtime.mesh import (
            degrade_to_fit,
        )

        fitted = degrade_to_fit(cfg, n_dev)
        if fitted.axis_sizes() != cfg.axis_sizes():
            logger.warning(
                "elastic relaunch: mesh %s does not fit %d device(s); "
                "degraded to %s", cfg.axis_sizes(), n_dev,
                fitted.axis_sizes())
        cfg = fitted
    dcn_axes = _parse_mesh_overrides(args.dcn) if args.dcn else None
    mesh = build_mesh(cfg, devices=devices, dcn_axes=dcn_axes)
    logger.info("mesh: %s (strategy=%s, %d devices)",
                dict(mesh.shape), strategy, n_dev)

    # 3. Data: sharded host loader over this config's dataset.  With
    # --eval-split, a held-out tail becomes the validation source (Keras
    # validation_split semantics); otherwise eval runs on the training
    # distribution (documented train-set monitoring).
    global_batch = args.global_batch_size or entry["global_batch_size"]
    if args.pack_seq and not args.data_dir:
        raise SystemExit("--pack-seq needs --data-dir (a varlen TFRecord "
                         "corpus to pack)")
    if args.dataset_kwarg and args.data_dir:
        raise SystemExit("--dataset-kwarg overrides the config's SYNTHETIC "
                         "dataset; it has no effect with --data-dir")
    # Pure service mode: the workers own ALL record I/O — building the
    # in-process source too would re-materialize/re-index the corpus in
    # the trainer for nothing.  Any in-process consumer (eval, BLEU, HF
    # sample, checkpoint-resume sample) keeps the source.
    service_only = (args.data_workers > 0 and args.eval_steps <= 0
                    and args.bleu_eval <= 0 and args.init_from_hf is None
                    and args.checkpoint_dir is None)
    if service_only:
        source = None
        dir_kind = None
        if args.data_dir:
            import pathlib

            _root = pathlib.Path(args.data_dir)
            dir_kind = ("tfrecord_dir"
                        if any(_root.glob("*.tfrecord"))
                        or any(_root.glob("*.tfrecord.gz"))
                        else "array_dir")
    elif args.data_dir:
        # Autodetect format: a dir of *.tfrecord files (the reference's
        # tf.data corpus convention) vs the native mmap part-*/ layout.
        import pathlib

        data_root = pathlib.Path(args.data_dir)
        if args.pack_seq:
            # Varlen documents → packed LM rows (decoder configs).
            from tensorflow_train_distributed_tpu.data.packing import (
                PackedLmSource,
            )
            from tensorflow_train_distributed_tpu.data.tfrecord import (
                TFRecordSource,
            )

            if args.data_transform:
                raise SystemExit(
                    "--data-transform does not apply under --pack-seq "
                    "(packing consumes raw token documents); drop one of "
                    "the two flags")
            paths = sorted([*data_root.glob("*.tfrecord"),
                            *data_root.glob("*.tfrecord.gz")])
            if not paths:
                raise SystemExit(
                    f"--pack-seq needs *.tfrecord(.gz) files under "
                    f"{data_root}")
            source = PackedLmSource.from_source(
                TFRecordSource(paths), args.pack_seq, key=args.pack_key)
            # Fail at launch: only decoder LM tasks consume packed
            # batches, and clamped out-of-vocab ids would train on
            # garbage with a finite loss (the --init-from-hf hazard).
            from tensorflow_train_distributed_tpu.models.llama import (
                CausalLmTask,
            )
            from tensorflow_train_distributed_tpu.models.moe import (
                MoeLmTask,
            )

            probe_task = entry["task_factory"]()
            if not isinstance(probe_task, (CausalLmTask, MoeLmTask)):
                raise SystemExit(
                    f"--pack-seq needs a decoder LM config (llama or moe "
                    f"family); {type(probe_task).__name__} does not "
                    "consume packed batches")
            max_id = source.max_token_id  # tracked at pack time, O(1) here
            if max_id >= probe_task.config.vocab_size:
                raise SystemExit(
                    f"packed corpus has token id {max_id} but the "
                    f"config's vocab is {probe_task.config.vocab_size}; "
                    "re-tokenize or pick a matching config "
                    "(out-of-range ids would clamp and train on garbage)")
        else:
            dir_kind = ("tfrecord_dir"
                        if any(data_root.glob("*.tfrecord"))
                        or any(data_root.glob("*.tfrecord.gz"))
                        else "array_dir")
            source = get_dataset(dir_kind, root=args.data_dir,
                                 transform=args.data_transform)
    else:
        dir_kind = None
        source = get_dataset(entry["dataset"], **_dataset_kwargs(entry, args))
    service_spec = None
    if args.data_workers > 0:
        # pack-seq already rejected at arg validation; multiprocess is
        # only known after cluster resolution, so it lands here.
        from tensorflow_train_distributed_tpu.data.service import SourceSpec

        if cluster.is_multiprocess:
            # Per-host worker fleets: every process runs its own
            # dispatcher; worker w of host h autoshard-slices as process
            # h*W+w of H*W (reference tf.data service over a cluster).
            shards = cluster.num_processes * args.data_workers
            if global_batch % shards:
                raise SystemExit(
                    f"--global-batch-size={global_batch} must divide by "
                    f"num_hosts*data_workers={shards} (each worker "
                    "serves one equal slice)")
        if args.data_dir:
            service_spec = SourceSpec(
                dir_kind, {"root": args.data_dir,
                           "transform": args.data_transform})
        else:
            service_spec = SourceSpec(entry["dataset"],
                                      _dataset_kwargs(entry, args))
    eval_source = source
    if (args.eval_steps > 0 or args.bleu_eval > 0) and not args.eval_split:
        # Keras validation_data semantics imply HELD-OUT data; without
        # --eval-split the val_* numbers measure the training
        # distribution — fine for smoke runs, misleading for model
        # selection. Say so loudly rather than silently.
        logger.warning(
            "evaluation will run on the TRAINING distribution (no "
            "--eval-split): val_* metrics are not held-out generalization "
            "numbers; pass --eval-split F (e.g. 0.1) to hold out a split")
    if args.eval_split:
        if args.eval_steps <= 0:
            raise SystemExit(
                "--eval-split without --eval-steps N (>0) would hold out "
                "data that is never evaluated; add --eval-steps (and "
                "optionally --eval-every)")
        from tensorflow_train_distributed_tpu.data.datasets import (
            train_val_split,
        )

        source, eval_source = train_val_split(
            source, args.eval_split, min_val=global_batch,
            min_train=global_batch)
    loader = None if source is None else HostDataLoader(
        source,
        DataConfig(global_batch_size=global_batch, seed=args.seed),
        process_index=cluster.process_id if cluster.is_multiprocess else None,
        process_count=cluster.num_processes if cluster.is_multiprocess else None,
    )

    def make_eval_loader():
        # Fresh single-pass loader per eval so every run sees the same
        # records in the same (seeded) order.  drop_remainder=False: the
        # final partial batch is padded and weight-masked so a finite
        # split's metrics cover every example exactly (Task sample_weight
        # contract); training keeps whole batches.
        eval_loader = HostDataLoader(
            eval_source,
            DataConfig(global_batch_size=global_batch, seed=args.seed + 1,
                       num_epochs=1, drop_remainder=False),
            process_index=(cluster.process_id
                           if cluster.is_multiprocess else None),
            process_count=(cluster.num_processes
                           if cluster.is_multiprocess else None),
        )
        if 0 < eval_loader.steps_per_epoch() < args.eval_steps:
            logger.warning(
                "--eval-steps=%d exceeds the evaluation source's %d "
                "batches/epoch; each eval averages over the smaller count",
                args.eval_steps, eval_loader.steps_per_epoch())
        return eval_loader

    # 4. Trainer: task + optimizer + policy + callbacks.
    task = entry["task_factory"]()
    if args.lora_rank:
        from tensorflow_train_distributed_tpu.models.llama import (
            CausalLmTask,
        )
        from tensorflow_train_distributed_tpu.models.lora import (
            LoraSpec, validate_targets,
        )

        if not isinstance(task, CausalLmTask):
            raise SystemExit(
                f"--lora-rank applies to decoder-LM configs; "
                f"{args.config!r} is not one")
        if args.ema_decay is not None:
            raise SystemExit(
                "--ema-decay with --lora-rank is not supported: the EMA "
                "would keep a full f32 copy of the FROZEN base (whose "
                "average never moves) — defeating LoRA's memory point at "
                "exactly the scale LoRA exists for")
        try:
            spec = LoraSpec(
                rank=args.lora_rank, alpha=args.lora_alpha,
                targets=validate_targets(args.lora_targets.split(",")))
        except ValueError as e:
            raise SystemExit(str(e))
        task = CausalLmTask(dataclasses.replace(task.config, lora=spec))
        logger.info("LoRA enabled: rank=%d alpha=%.1f targets=%s (base "
                    "frozen)", spec.rank, spec.alpha, spec.targets)
        if args.checkpoint_dir:
            # Self-describing checkpoints: alpha is not recoverable from
            # weights, and serving/merging with a retyped-wrong spec is
            # silent corruption — sample.py / export read this sidecar.
            from tensorflow_train_distributed_tpu.models.lora import (
                load_spec, save_spec,
            )

            prior = load_spec(args.checkpoint_dir)
            if prior is not None and prior != spec:
                # A resume with mistyped flags must not silently rewrite
                # the authoritative record (alpha shape-checks nothing).
                raise SystemExit(
                    f"--lora-* flags {spec} disagree with the existing "
                    f"lora_spec.json {prior} in --checkpoint-dir — fix "
                    "the flags to resume, or use a fresh dir")
            save_spec(args.checkpoint_dir, spec)
    elif args.checkpoint_dir:
        from tensorflow_train_distributed_tpu.models.lora import load_spec

        stale = load_spec(args.checkpoint_dir)
        if stale is not None:
            raise SystemExit(
                f"--checkpoint-dir carries lora_spec.json ({stale}) from "
                "a LoRA run, but this run has no --lora-rank: pass the "
                "matching --lora-* flags to resume it, or use a fresh "
                "checkpoint dir (a stale sidecar would make sample.py "
                "mis-serve the new checkpoint)")
    if args.bleu_eval > 0:
        # Fail at launch, not after a multi-hour run completes.
        from tensorflow_train_distributed_tpu.models import transformer as tr

        if not isinstance(task, tr.Seq2SeqTask):
            raise ValueError(
                "--bleu-eval needs a seq2seq config (wmt family); "
                f"{type(task).__name__} does not decode")
    policy = Policy.from_name(args.precision)
    callbacks = [History(), ProgressLogger(examples_per_step=global_batch)]
    # val_loss only reaches step events when PERIODIC eval runs during
    # fit (--eval-every); --eval-steps alone evaluates after training.
    # Shared by ReduceLROnPlateau and BestCheckpoint — the pair must
    # watch the same signal to behave coherently.
    monitor = ("val_loss"
               if args.eval_every and args.eval_steps > 0 else "loss")
    if args.reduce_lr_factor is not None:
        from tensorflow_train_distributed_tpu.training import (
            ReduceLROnPlateau,
        )

        callbacks.append(ReduceLROnPlateau(
            monitor=monitor,
            factor=args.reduce_lr_factor,
            patience=args.reduce_lr_patience,
            min_lr=args.reduce_lr_min,
            cooldown=args.reduce_lr_cooldown))
    if args.tensorboard_dir:
        callbacks.append(TensorBoardScalars(args.tensorboard_dir))
    if args.jsonl_log:
        callbacks.append(JsonlLogger(args.jsonl_log))
    if args.profile_dir:
        from tensorflow_train_distributed_tpu.runtime.profiling import (
            ProfileCallback,
        )

        start, stop = _parse_profile_steps(args.profile_steps)
        callbacks.append(ProfileCallback(
            args.profile_dir, start_step=start, stop_step=stop))
    if args.profiler_port:
        from tensorflow_train_distributed_tpu.runtime.profiling import (
            start_profiler_server,
        )

        start_profiler_server(args.profiler_port)
    if args.stall_timeout > 0:
        from tensorflow_train_distributed_tpu.training import StallWatchdog

        callbacks.append(StallWatchdog(args.stall_timeout))
    ckpt = None
    watcher = None
    if args.checkpoint_dir:
        ckpt = CheckpointManager(
            args.checkpoint_dir, max_to_keep=args.max_to_keep)
        if args.save_best:
            import os as _os

            from tensorflow_train_distributed_tpu.training.callbacks import (
                BestCheckpoint,
            )

            callbacks.append(BestCheckpoint(
                _os.path.join(args.checkpoint_dir, "best"),
                monitor=monitor))
        if not args.no_preemption_handler:
            from tensorflow_train_distributed_tpu.runtime.preemption import (
                PreemptionCheckpointCallback, PreemptionWatcher,
            )

            try:
                watcher = PreemptionWatcher(
                    watch_sigint=getattr(args, "watch_sigint", False),
                ).install()
            except RuntimeError:  # not on the main thread (embedded use)
                watcher = None
            if watcher is not None:
                callbacks.append(PreemptionCheckpointCallback(watcher))
    optimizer, lr_schedule = _make_optimizer(args, entry)
    trainer = Trainer(
        task,
        optimizer,
        mesh,
        lr_schedule=lr_schedule,
        policy=policy,
        config=TrainerConfig(
            seed=args.seed,
            steps_per_execution=args.steps_per_execution,
            grad_accum=args.grad_accum,
            log_every=args.log_every,
            checkpoint_every=args.checkpoint_every,
            log_grad_norm=args.log_grad_norm,
            zero1=args.zero1,
            grad_quant=args.grad_quant,
            grad_overlap=args.grad_overlap,
            sharded_update=args.sharded_update,
            # Mid-training eval (--eval-every) must score the SAME model
            # the final eval/export does: the EMA view when enabled.
            eval_state_view=(
                (lambda s: _eval_view(args, s))
                if args.ema_decay is not None else None),
        ),
        callbacks=callbacks,
        checkpoint_manager=ckpt,
    )

    service = None
    try:
        # 5. Resume (reference BackupAndRestore): restore latest if present.
        state = None
        if (ckpt is not None and not args.no_resume
                and ckpt.latest_step() is not None):
            sample = next(iter(loader))
            template = trainer.create_state(sample)
            # restore() may fall back past quarantined torn saves — or
            # come back empty when EVERY retained step was corrupt; the
            # relaunch then starts fresh from the init rather than
            # crash-looping (the supervisor contract).
            state = ckpt.restore(template)
            if state is None:
                logger.error(
                    "no restorable checkpoint in %s (all retained steps "
                    "quarantined); starting fresh", args.checkpoint_dir)
                state = template
            else:
                logger.info("resumed from step %d", int(state.step))
        elif args.init_from_hf:
            # SFT entry point: start from a local HF Llama checkpoint
            # (models.import_hf) instead of random init; a later resume
            # from --checkpoint-dir takes precedence over re-importing.
            from tensorflow_train_distributed_tpu.models import import_hf
            from tensorflow_train_distributed_tpu.models.bert import (
                BertConfig,
            )
            from tensorflow_train_distributed_tpu.models.llama import (
                LlamaConfig,
            )

            from tensorflow_train_distributed_tpu.models.moe import (
                MoeConfig,
            )

            task_cfg = getattr(task, "config", None)
            sample = None
            if isinstance(task_cfg, MoeConfig):
                # Sparse-MoE checkpoints: Mixtral, or Qwen2-MoE when
                # the checkpoint says so — import_moe dispatches on the
                # checkpoint's model_type (AutoConfig: local dirs AND
                # hub ids, no weights downloaded before the decision);
                # capacity_factor E/k on import makes routing exactly
                # HF's (import_hf).
                hf_cfg, hf_params = import_hf.import_moe(
                    args.init_from_hf, config=task_cfg)
            elif isinstance(task_cfg, LlamaConfig):
                # The task's config decides the param-tree layout (scan
                # vs per-layer) and validates dims vs the checkpoint.
                hf_cfg, hf_params = import_hf.import_llama(
                    args.init_from_hf, config=task_cfg)
            elif isinstance(task_cfg, BertConfig):
                # BERT import derives its own HF-compat config (bias/
                # token-type/embed-LN knobs); rebuild the task around it
                # so the model matches the imported tree — but the
                # checkpoint must still cover the data pipeline's token
                # space and sequence length (a smaller embedding table
                # would CLAMP out-of-range ids in XLA's gather and train
                # on garbage with a finite loss).
                from tensorflow_train_distributed_tpu.models.bert import (
                    BertMlmTask,
                )

                hf_cfg, hf_params = import_hf.import_bert(args.init_from_hf)
                sample = next(iter(loader))
                if hf_cfg.vocab_size < task_cfg.vocab_size:
                    raise SystemExit(
                        f"HF checkpoint vocab ({hf_cfg.vocab_size}) is "
                        f"smaller than the config's ({task_cfg.vocab_size})"
                        " — token ids would silently clamp")
                if hf_cfg.max_positions < sample["input_ids"].shape[1]:
                    raise SystemExit(
                        f"HF checkpoint max_positions "
                        f"({hf_cfg.max_positions}) < the pipeline's "
                        f"sequence length ({sample['input_ids'].shape[1]})")
                task = BertMlmTask(hf_cfg)
                trainer.task = task
            else:
                raise SystemExit(
                    f"--init-from-hf supports Llama-, Mixtral- and "
                    f"BERT-family --config; {args.config!r} is none of "
                    "these")
            if sample is None:
                sample = next(iter(loader))
            state = trainer.create_state(sample, params=hf_params)
            logger.info("initialized from HF checkpoint %s (%d layers)",
                        args.init_from_hf, hf_cfg.num_layers)

        if args.eval_only:
            if state is None:
                raise SystemExit(
                    "--eval-only needs a restorable checkpoint "
                    "(--checkpoint-dir with a saved state) or "
                    "--init-from-hf")
            eval_metrics = trainer.evaluate(
                make_eval_loader(), _eval_view(args, state),
                steps=args.eval_steps)
            logger.info("eval-only: %s", eval_metrics)
            if args.bleu_eval > 0:
                bleu = _bleu_eval(args, task, _eval_view(args, state),
                                  make_eval_loader())
                eval_metrics = dict(eval_metrics or {}, bleu=bleu)
                logger.info("BLEU (beam %d, %d batches): %.2f",
                            args.beam_size, args.bleu_eval, bleu)
            history = next(
                (c.history for c in callbacks if isinstance(c, History)),
                {})
            return RunResult(state=state, history=history,
                             eval_metrics=eval_metrics, mesh=mesh,
                             preempted=False)

        remaining = args.steps - (0 if state is None else int(state.step))
        k = args.steps_per_execution
        if remaining > 0 and remaining % k:
            # Off-cycle resume (checkpoint step not a multiple of k) or
            # steps not divisible by k: round up rather than crashloop.
            rounded = -(-remaining // k) * k
            logger.warning(
                "remaining steps %d not a multiple of "
                "steps_per_execution=%d; training %d steps",
                remaining, k, rounded)
            remaining = rounded
        if remaining > 0:
            # Mid-epoch resume: position the data stream after the restored
            # step so no examples repeat or skip (BackupAndRestore parity).
            batches = (loader.iter_from(int(state.step))
                       if loader is not None and state is not None
                       and int(state.step) > 0
                       else loader)  # None only in service mode (below)
            if service_spec is not None:
                from tensorflow_train_distributed_tpu.data.service import (
                    DataServiceDispatcher,
                )

                if state is not None and int(state.step) > 0:
                    logger.warning(
                        "--data-workers resume: the worker stream "
                        "restarts from epoch 0 (deterministic mid-epoch "
                        "positioning is an in-process loader feature); "
                        "examples may repeat relative to a single "
                        "uninterrupted run")
                dispatcher = DataServiceDispatcher(
                    service_spec,
                    DataConfig(global_batch_size=global_batch,
                               seed=args.seed),
                    num_workers=args.data_workers,
                    host_index=(cluster.process_id
                                if cluster.is_multiprocess else 0),
                    host_count=(cluster.num_processes
                                if cluster.is_multiprocess else 1),
                    ).start()
                service = dispatcher
                batches = iter(dispatcher.client())
            eval_kwargs = {}
            if args.eval_every and args.eval_steps <= 0:
                raise SystemExit(
                    "--eval-every needs --eval-steps N (>0) to size each "
                    "validation run")
            if args.eval_every and args.eval_steps > 0:
                eval_kwargs = dict(
                    eval_batches=make_eval_loader,
                    eval_every=args.eval_every,
                    eval_steps=args.eval_steps,
                )
            state = trainer.fit(
                batches, steps=remaining, state=state,
                steps_per_epoch=(None if loader is None
                                 else loader.steps_per_epoch()),
                **eval_kwargs,
            )
        else:
            logger.info("checkpoint already at/past --steps; nothing to train")

        preempted = watcher is not None and watcher.preempted
        eval_metrics = None
        if args.eval_steps > 0 and not preempted:
            # Skip eval when preempted: the grace window is for the save,
            # and the restarted job re-runs eval at its own end.
            eval_metrics = trainer.evaluate(
                make_eval_loader(), _eval_view(args, state),
                steps=args.eval_steps)
            logger.info("eval: %s", eval_metrics)
        if args.bleu_eval > 0 and not preempted:
            bleu = _bleu_eval(args, task, _eval_view(args, state),
                              make_eval_loader())
            eval_metrics = dict(eval_metrics or {}, bleu=bleu)
            logger.info("BLEU (beam %d, %d batches): %.2f",
                        args.beam_size, args.bleu_eval, bleu)
    finally:
        if service is not None:
            service.stop()
        if watcher is not None:
            watcher.uninstall()
        if ckpt is not None:
            ckpt.close()
    history = next(
        (c.history for c in callbacks if isinstance(c, History)), {})
    return RunResult(state=state, history=history,
                     eval_metrics=eval_metrics, mesh=mesh,
                     preempted=preempted)


def _handle_device_loss(args, dl) -> int:
    """Device-loss exit contract (the elastic half of fault tolerance):
    record the surviving device count in the elastic sidecar — the path
    the supervisor exported (``TTD_ELASTIC_STATE``), falling back to a
    checkpoint-dir sidecar for externally-supervised runs — and hand
    back ``DEVICE_LOSS_EXIT_CODE`` so the supervisor relaunches onto
    the survivors instead of burning the crash budget."""
    import json
    import os
    import time

    from tensorflow_train_distributed_tpu.runtime.supervisor import (
        DEVICE_LOSS_EXIT_CODE, ENV_ELASTIC_STATE,
    )

    path = os.environ.get(ENV_ELASTIC_STATE)
    if not path and args.checkpoint_dir:
        path = os.path.join(args.checkpoint_dir, "elastic.json")
    if path:
        try:
            os.makedirs(os.path.dirname(os.path.abspath(path)),
                        exist_ok=True)
            with open(path, "w") as f:
                json.dump({"survivors": dl.survivors,
                           "time": time.time(),
                           "error": str(dl)[:500]}, f)
        except OSError:
            logger.error("could not write elastic sidecar %s", path,
                         exc_info=True)
    logger.error(
        "DEVICE LOSS: %s — exiting %d (surviving devices: %s; a "
        "supervisor relaunches onto them with the checkpoint "
        "resharded)", dl, DEVICE_LOSS_EXIT_CODE,
        "unknown" if dl.survivors is None else dl.survivors)
    return DEVICE_LOSS_EXIT_CODE


def main(argv: Optional[Sequence[str]] = None) -> int:
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.list_configs:
        from tensorflow_train_distributed_tpu.models import registry

        for name in registry.available():
            e = registry.get_entry(name)
            print(f"{name}: dataset={e['dataset']} strategy={e['strategy']} "
                  f"batch={e['global_batch_size']} lr={e['learning_rate']}")
        return 0
    if args.supervise:
        # Re-exec this CLI (minus the supervisor flags) as a supervised
        # child; this process becomes the relaunch loop.
        import sys as _sys

        from tensorflow_train_distributed_tpu.runtime.supervisor import (
            supervise_cli,
        )

        return supervise_cli(
            list(argv) if argv is not None else _sys.argv[1:], args)
    from tensorflow_train_distributed_tpu.runtime.preemption import (
        PREEMPTION_EXIT_CODE,
    )

    try:
        result = run(args)
    except Exception as e:
        # Device-loss classification: an injected DeviceLost
        # (mesh:device_lost fault plan) or a real runtime error whose
        # text matches the known device-failure signatures becomes the
        # device-loss exit contract; every other error crashes as
        # before (the supervisor's crash budget applies).
        from tensorflow_train_distributed_tpu.runtime import faults as _f

        dl = _f.as_device_loss(e)
        if dl is None:
            raise
        return _handle_device_loss(args, dl)
    if result.preempted:
        # The shared exit-code contract (runtime.preemption): non-zero so
        # schedulers reschedule, and distinct so supervisors know this
        # was a coordinated save-and-stop, not a crash (it must not
        # consume the crash restart budget).  143 = SIGTERM'd by
        # convention, which is what happened semantically.
        logger.warning("exiting after preemption-coordinated checkpoint")
        return PREEMPTION_EXIT_CODE
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
