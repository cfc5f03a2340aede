"""Memory planning: exact state arithmetic + calibrated activation model.

``plan_state_memory`` (training.trainer) answers "do params + optimizer
state fit" by pure shape arithmetic.  The OOMs measured on a v5e (below)
show the *activation working set* is what actually kills large-batch
decoder training, so this module adds an empirical activation estimate and a
combined per-device plan — the make-or-break planning tool SURVEY §7
hard-part 3 calls for (the reference answers "does it fit" only by OOM
trial on real hardware).

The activation model is calibrated against observed XLA allocations on a
real v5e chip (three measured points, pinned by tests):
- llama_125m seq2048 batch8 no-remat: fits (est 14.9 GiB of 15.75);
- llama_125m seq2048 batch16 no-remat: OOM, 26.4 GiB requested (est 28);
- llama_1b batch16 no-remat: state alone exceeds the chip (est > 17).

A compile that cannot fit costs minutes of a budgeted chip call before it
is refused — planning before compiling answers "does it fit" from shapes,
with no device attached.
"""

from __future__ import annotations

from typing import Optional

# Usable HBM per chip after the runtime's reserve, by device_kind
# substring (v5e observed directly in OOM reports: 15.75 GiB of 16).
HBM_BUDGET_GIB_BY_KIND = {
    "v5 lite": 15.75,
    "v5e": 15.75,
    "v4": 31.25,
    "v5p": 94.75,
    "v6": 31.25,
}

# bf16 peak TFLOP/s by TPU generation — kept beside the HBM table so
# roofline/MFU consumers (bench tools) share one source.
PEAK_TFLOPS_BY_KIND = {
    "v5 lite": 197.0,
    "v5e": 197.0,
    "v5p": 459.0,
    "v4": 275.0,
    "v6": 918.0,
}

# HBM bandwidth GB/s by generation (public spec sheets) — the roofline
# for bandwidth-bound regimes (BN statistics, autoregressive decode).
HBM_GBPS_BY_KIND = {
    "v5 lite": 819.0,
    "v5e": 819.0,
    "v5p": 2765.0,
    "v4": 1228.0,
    "v6": 1640.0,
}

# Bytes of optimizer+param state per parameter under the mixed-bf16 adam
# recipe: bf16 compute copy + f32 master + 2×f32 moments + grads in
# flight.
STATE_BYTES_PER_PARAM = 14


def _by_kind(table: dict, device_kind: str) -> Optional[float]:
    kind = device_kind.lower()
    for sub, value in table.items():
        if sub in kind:
            return value
    return None


def hbm_budget_bytes(device_kind: str) -> Optional[float]:
    """Per-chip HBM budget for a device kind, or None when unknown."""
    gib = _by_kind(HBM_BUDGET_GIB_BY_KIND, device_kind)
    return None if gib is None else gib * 2**30


def peak_tflops(device_kind: str) -> Optional[float]:
    return _by_kind(PEAK_TFLOPS_BY_KIND, device_kind)


def hbm_bandwidth_bytes_per_sec(device_kind: str) -> Optional[float]:
    gbps = _by_kind(HBM_GBPS_BY_KIND, device_kind)
    return None if gbps is None else gbps * 1e9


def tpu_peaks(device_kind: str) -> dict:
    """The three table rows of a TPU, for anything that divides by a
    peak (MFU, MBU, roofline guards, the chip smoke).  An unknown kind
    raises: a utilization against a guessed peak is a made-up number."""
    peaks = {
        "hbm_budget_bytes": hbm_budget_bytes(device_kind),
        "peak_tflops": peak_tflops(device_kind),
        "hbm_bytes_per_sec": hbm_bandwidth_bytes_per_sec(device_kind),
    }
    if None in peaks.values():
        raise ValueError(
            f"device_kind {device_kind!r} is not in training.memory's "
            f"tables ({sorted(PEAK_TFLOPS_BY_KIND)}); add its published "
            "peaks there before measuring on it")
    return peaks


def decoder_activation_bytes(num_layers: int, d_model: int, batch: int,
                             seq: int, *, remat: bool, causal: bool = True,
                             score_heads: int = 1,
                             ffn_size: Optional[int] = None,
                             save_ffn_hiddens: bool = True) -> int:
    """Empirical activation working set of one train step, in bytes.

    ``batch``/``seq`` are PER-DEVICE extents (divide global dims by the
    mesh's batch/seq shard degrees first — ``plan_train_memory`` does).

    remat:    ~6 residual passes of bf16 [B,S,d] per layer (layer inputs
              + flash l/m/out saved across the scan).
    no-remat: adds ~24 [B,S,d] passes per layer (q/k/v/o + SwiGLU gate/up
              hiddens saved for backward) and ~6 score-sized temps per
              layer stack.  ``score_heads=1`` models the flash path (no
              materialized [S,S] per head); pass ``num_heads`` for models
              on the reference einsum attention (BERT), which saves
              per-head [B,H,S,S] logits/probs for backward.
    """
    act = num_layers * batch * seq * d_model * 2 * 6
    score_term = (6 * score_heads * batch * seq * seq * 2
                  // (2 if causal else 1))
    if not remat:
        passes = 24
        if not save_ffn_hiddens:
            # remat_policy="no_ffn": the ~3 [B,S,ffn] hidden tensors are
            # re-computed, not saved — subtract their d_model-equivalent
            # passes (3·ffn/d; the SwiGLU default ffn≈2.67d gives 8).
            ffn = ffn_size if ffn_size else int(8 * d_model / 3)
            passes -= min(passes - 4, int(round(3 * ffn / d_model)))
        act += num_layers * batch * seq * d_model * 2 * passes
        act += num_layers * score_term
    elif score_heads > 1:
        # Per-layer remat still rematerializes ONE layer's einsum-attention
        # score buffers during its backward — a transient, but it peaks
        # alongside the saved boundaries, so large-seq configs can OOM the
        # compile even though nothing seq²-sized is *saved*.
        act += score_term
    return act


def _model_dims(task):
    """Activation-model inputs (a dict of dims/flags) from a task config.

    Decoder families (llama/moe) run the flash kernel (score_heads=1,
    causal); BERT runs the reference einsum attention (per-head scores,
    bidirectional).  Raises for configs the activation model doesn't
    cover — a wrong estimate is worse than none (it green-lights a
    compile that cannot fit).
    """
    cfg = getattr(task, "config", None)
    if cfg is None:
        raise ValueError(
            f"{type(task).__name__} has no .config; pass explicit dims "
            "via decoder_activation_bytes instead")
    if hasattr(cfg, "num_experts"):
        raise ValueError(
            "the activation model is calibrated for dense decoders/"
            "encoders only — MoE adds [G,S,E,C] dispatch/combine tensors "
            "and expert buffers it has no term for, so an estimate here "
            "would green-light OOM compiles; budget MoE configs by AOT "
            "compile (Trainer.lower_train_step + memory_analysis) instead")
    num_layers = getattr(cfg, "num_layers", None)
    width = getattr(cfg, "d_model", None) or getattr(cfg, "hidden_size",
                                                     None)
    if num_layers is None or width is None:
        raise ValueError(
            f"{type(cfg).__name__} lacks num_layers/d_model dims for the "
            "activation model")
    remat = bool(getattr(cfg, "remat", False))
    # Policy-aware budgeting (mirrors bench_lm): "dots" saves the SwiGLU
    # hiddens so it budgets as no-remat; "no_ffn" is no-remat MINUS the
    # hiddens it re-computes.
    remat_policy = getattr(cfg, "remat_policy", "full")
    effective_remat = remat and remat_policy not in ("dots", "no_ffn")
    save_ffn = not (remat and remat_policy == "no_ffn")
    ffn = (getattr(cfg, "ffn_size", None)
           or getattr(cfg, "intermediate_size", None))
    bidirectional = hasattr(cfg, "intermediate_size")  # BERT-shaped
    score_heads = cfg.num_heads if bidirectional else 1
    return {"num_layers": num_layers, "width": width,
            "remat": effective_remat, "causal": not bidirectional,
            "score_heads": score_heads, "ffn_size": ffn,
            "save_ffn_hiddens": save_ffn}


def plan_train_memory(task, sample_batch, tx, mesh, *,
                      rules=None, policy=None, zero1: bool = False,
                      device_kind: Optional[str] = None) -> dict:
    """Combined per-device plan: exact state + estimated activations.

    Returns ``plan_state_memory``'s dict extended with
    ``activation_bytes_per_device``, ``step_bytes_per_device`` (state +
    activations) and, when ``device_kind`` names a known TPU generation,
    ``budget_bytes`` and ``fits`` — the pre-flight answer for "can this
    config's train step fit that chip".
    """
    import numpy as np

    from tensorflow_train_distributed_tpu.runtime.mesh import batch_axes
    from tensorflow_train_distributed_tpu.training.mixed_precision import (
        Policy,
    )
    from tensorflow_train_distributed_tpu.training.trainer import (
        DEFAULT_RULES, plan_state_memory,
    )

    rules = DEFAULT_RULES if rules is None else rules
    policy = Policy() if policy is None else policy
    plan = plan_state_memory(task, sample_batch, tx, mesh, rules=rules,
                             policy=policy, zero1=zero1)
    dims = _model_dims(task)
    tokens = next(v for k, v in sorted(sample_batch.items())
                  if np.ndim(v) >= 2)
    global_batch, seq = np.shape(tokens)[:2]
    batch_shards = 1
    for axis in batch_axes(mesh):
        batch_shards *= mesh.shape[axis]
    seq_shards = dict(mesh.shape).get("seq", 1)
    per_dev_batch = max(1, global_batch // batch_shards)
    per_dev_seq = max(1, seq // seq_shards)
    act = decoder_activation_bytes(
        dims["num_layers"], dims["width"], per_dev_batch, per_dev_seq,
        remat=dims["remat"], causal=dims["causal"],
        score_heads=dims["score_heads"], ffn_size=dims["ffn_size"],
        save_ffn_hiddens=dims["save_ffn_hiddens"])
    plan["activation_bytes_per_device"] = float(act)
    plan["step_bytes_per_device"] = plan["per_device_bytes"] + act
    if device_kind is not None:
        budget = hbm_budget_bytes(device_kind)
        if budget is not None:
            plan["budget_bytes"] = budget
            plan["fits"] = plan["step_bytes_per_device"] <= budget
    return plan
