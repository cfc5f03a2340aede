"""Runner kind ``serve_family``: ``serve``'s run for any decoder family
the program serves.

As ``harness/serve.py`` (set-up, warm-up, ramp, window, drain, check:
its functions are imported, not copied), with three differences, each
read from the configuration file and none from the cell's name:

- the program's config and its parameter shapes come from a builder
  chosen by ``program.family`` (``FAMILIES``), which cross-checks every
  published key of the file against the program's dataclass, as
  ``program.llama_config`` does for ``llama``;
- the plain reference is ``benchmark/references/<reference>.py``, the
  file's ``reference`` key (``decoder`` where it has none);
- ``--control fp8w``: the engine is given the seeded weights rounded
  to ``float8_e4m3fn``'s values and back (``to_fp8_and_back``), the
  reference keeps the bf16 ones.  That is a lower precision than the file states, for families
  whose int8 path the program refuses (``dispatch="gmm"``): the run
  must come out not correct.

``run`` itself is a copy of ``serve.run`` around those three; a
``benchmark`` PR folds the two (PERF.md §7).
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import check, loadgen, program, serve, stats, weights

# source key -> MoeConfig field, compared after building.
_MOE_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "d_model",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "intermediate_size": "dense_ffn_size",
    "moe_intermediate_size": "ffn_size",
    "n_routed_experts": "num_experts",
    "num_experts_per_tok": "top_k",
    "first_k_dense_replace": "dense_layers",
    "max_position_embeddings": "max_positions",
    "rope_theta": "rope_base",
    "rms_norm_eps": "rms_epsilon",
    "routed_scaling_factor": "routed_scaling",
    "norm_topk_prob": "norm_topk_prob",
    "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_dim",
    "qk_rope_head_dim": "qk_rope_dim",
    "v_head_dim": "v_head_dim",
    "attention_bias": "qkv_bias",
}
# What the file must say for the program's block to be the source's
# (the program has no option for anything else).
_MOE_FIXED = {
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "hidden_act": "silu", "tie_word_embeddings": False,
    "rope_scaling": None, "partial_rotary_factor": 1,
    "num_nextn_predict_layers": 0,
}


def moe_config(cfg_file: dict):
    """The program's ``MoeConfig`` for a latent-attention, sigmoid-
    routed file (``glm4_moe_lite``), every size cross-checked."""
    from tensorflow_train_distributed_tpu.models import moe

    prog = cfg_file["program"]
    cfg = dataclasses.replace(moe.MOE_PRESETS[prog["preset"]],
                              **prog.get("replace", {}))
    for key, field in _MOE_KEYS.items():
        if key not in cfg_file:
            raise KeyError(f"configuration file lacks {key!r}")
        got, want = getattr(cfg, field), cfg_file[key]
        if got != want:
            raise ValueError(
                f"configuration file says {key}={want!r} but the program "
                f"would run {field}={got!r}")
    for key, want in _MOE_FIXED.items():
        if cfg_file.get(key, KeyError) != want:
            raise ValueError(
                f"the program's block has {key}={want!r}; the "
                f"configuration file says {cfg_file.get(key)!r}")
    if cfg_file["num_key_value_heads"] != cfg_file["num_attention_heads"]:
        raise ValueError("latent attention has as many key heads as "
                         "query heads")
    if cfg.router != "sigmoid" or cfg.dispatch != "gmm":
        raise ValueError("noaux_tc is the program's sigmoid router under "
                         "dropless dispatch")
    if (cfg.shared_expert_size or 0) != (
            cfg_file["n_shared_experts"] * cfg_file["moe_intermediate_size"]):
        raise ValueError("shared expert width differs from "
                         "n_shared_experts x moe_intermediate_size")
    return cfg


def moe_param_shapes(cfg):
    from tensorflow_train_distributed_tpu.models import moe

    model = moe.MoeLmModel(cfg)
    boxed = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    return weights.plain_shapes(boxed)["params"]


#: ``program.family`` -> (config builder, parameter shapes).
FAMILIES = {"llama": (program.llama_config, program.param_shapes),
            "moe": (moe_config, moe_param_shapes)}


def reference_module(cfg_file: dict):
    name = cfg_file.get("reference", "decoder")
    if not re.fullmatch(r"[a-z0-9_]+", name):
        raise ValueError(f"bad reference name {name!r}")
    return importlib.import_module(f"benchmark.references.{name}")


def to_fp8_and_back(x):
    """``x`` rounded to the nearest ``float8_e4m3fn`` value (below the
    format's largest, 448), in ``x``'s own type.  Written out, because
    the TPU's compiler folds the plain pair of conversions away (my
    chip runs, PR 26: a control made with ``astype`` served the sound
    run's tokens bit for bit): three mantissa bits by
    ``lax.reduce_precision``, which no pass removes, and below the
    smallest normal, 2**-6, the format's even steps of 2**-9."""
    normal = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=3)
    step = 2.0 ** -9
    small = (jnp.round(x.astype(jnp.float32) / step) * step).astype(x.dtype)
    return jnp.where(jnp.abs(x) < 2.0 ** -6, small, normal)


def through_fp8(params):
    """Every floating leaf rounded through ``float8_e4m3fn`` and back
    (the ``fp8w`` control's weights), in one jitted program."""
    def round_trip(x):
        if not jnp.issubdtype(x.dtype, jnp.floating):
            return x
        return to_fp8_and_back(x)

    return jax.jit(lambda t: jax.tree.map(round_trip, t),
                   donate_argnums=0)(params)


def served_tokens(reference, params, cfg_file: dict, finished,
                  traffic: dict, seed: int, log, compiles=None) -> dict:
    """``check.served_tokens`` against ``reference`` (a module with
    ``served_gaps`` and ``PAD``): the same sample, numbers and limits."""
    spec = traffic.get("check", {})
    rows_to = check.longest(traffic["output_len"])
    pad_to = -(-(check.longest(traffic["prompt_len"]) + rows_to)
               // reference.PAD) * reference.PAD
    n0 = len(compiles.events) if compiles is not None else 0
    sample = check.pick_sample(finished, int(spec.get("sample", 4)), seed)
    if not sample:
        log(phase="check", error="no finished request to compare")
        return {"correct": False, "compared": []}
    t0 = time.monotonic()
    allg = np.concatenate([
        reference.served_gaps(params, cfg_file, r.prompt, r.tokens,
                              pad_to=pad_to, rows_to=rows_to
                              ).astype(np.float64) for r in sample])
    values = {"served_gap_max": float(allg.max()),
              "served_gap_mean": float(allg.mean())}
    out = check.compare(values, spec.get("limits", {}))
    log(phase="check", seconds=time.monotonic() - t0,
        reference=reference.__name__, requests=len(sample),
        served_tokens=int(allg.size), pad_to=pad_to,
        compiles=(len(compiles.events) - n0
                  if compiles is not None else None),
        longest=int(max(r.planned.prompt_len + len(r.tokens)
                        for r in sample)),
        not_first_choice=int((allg > 0).sum()), **out)
    return out


def run(ctx: dict) -> dict:
    cfg_file, traffic = ctx["config"], ctx["traffic"]
    seed, seconds = ctx["seed"], ctx["seconds"]
    device = ctx["devices"][0]
    log = ctx["log"]
    control = ctx.get("control", "")

    family = cfg_file["program"]["family"]
    if family not in FAMILIES:
        raise ValueError(f"unknown program family {family!r}")
    build_config, shapes_of = FAMILIES[family]
    cfg = build_config(cfg_file)
    reference = reference_module(cfg_file)
    dtype = jnp.dtype(cfg_file.get("dtype", "bfloat16"))
    shapes = shapes_of(cfg)

    def make_weights():
        with jax.default_device(device):
            return jax.block_until_ready(
                weights.make_params(shapes, seed, dtype))

    t0 = time.monotonic()
    params = make_weights()
    log(phase="weights", seconds=time.monotonic() - t0,
        bytes=sum(x.nbytes for x in jax.tree.leaves(params)))

    rounded = None
    if control == "fp8w":
        rounded = through_fp8(params)
        engine, driver = serve.build(cfg, cfg_file, traffic, rounded)
    else:
        engine, driver = serve.build(cfg, cfg_file, traffic, params,
                                     control)
    if control:
        # The control's engine holds its own lower-precision copy; the
        # bf16 weights are made again, from the seed, for the reference.
        del params
    driver.start()
    schedule = loadgen.Schedule(traffic, seed, seconds, cfg.vocab_size)
    t0 = time.monotonic()
    info = serve.warm(engine, driver,
                      serve.warm_lengths(engine, schedule._prompts),
                      cfg.vocab_size, seed)
    log(phase="warm", seconds=time.monotonic() - t0, **info,
        compiles=len(ctx["compiles"].events),
        compile_s=ctx["compiles"].total_s(),
        cache_hits=ctx["compiles"].hits,
        cache_misses=ctx["compiles"].misses,
        kv_pool_bytes=engine.kv_pool_bytes())

    load = loadgen.LoadRun(
        schedule,
        submit=lambda prompt, max_new: driver.submit(
            prompt, max_new, stream=True),
        abandon=driver.abandon, seconds=seconds,
        drain_s=float(traffic.get("drain_s", 0.0)),
        annotate=ctx["annotate"])
    pieces_before = engine.prefill_stats["installments"]
    t_open = load.start()
    ramp = t_open - time.monotonic()
    if ramp > 0:
        time.sleep(ramp)
    ctx["window_opened"](t_open)          # setup ends here
    tracer = ctx["tracer"]
    if tracer is not None:
        tracer.start()
        time.sleep(min(float(traffic.get("trace_s", 4.0)), seconds))
        tracer.stop()
    load.wait_window()
    t_close = t_open + seconds
    in_window = ctx["compiles"].between(t_open, t_close)
    load.finish()
    if not driver.join(timeout=120):
        raise RuntimeError("engine driver did not drain")
    if driver.failure() is not None:
        raise RuntimeError(f"engine driver failed: {driver.failure()!r}")

    wm = loadgen.window_metrics(load.records, t_open, seconds,
                                schedule.loop)
    gap = stats.summarize(wm["gaps_ms"])
    ttft = stats.summarize(wm["ttft_ms"])
    waits = [(r.handle.slot_granted_at - r.handle.t_submit) * 1e3
             for r in load.records
             if r.handle is not None
             and r.handle.slot_granted_at is not None
             and t_open <= r.sent_at < t_close]
    log(phase="window", loop=schedule.loop, seconds=seconds,
        requests_offered=len(load.records),
        max_in_flight=load.max_in_flight, tokens=wm["tokens"],
        lanes_at_open=wm["lanes_at_open"],
        tokens_per_s=wm["tokens"] / seconds,
        committed=None if wm["committed"] is None else dict(zip(
            ("tokens_per_s", "tokens", "span_s", "commits"),
            wm["committed"])),
        gap_ms=gap, ttft_ms=ttft, queue_wait_ms=stats.summarize(waits),
        generator_late_ms=stats.summarize(wm["late_ms"])
        if wm["late_ms"] else None,
        ttft_halves_ms=wm["ttft_halves_ms"],
        commits=wm["timeline"],
        engine_stats={"prefill": dict(engine.prefill_stats),
                      "kv": dict(engine.kv_stats),
                      "overlap": dict(engine.overlap_stats)},
        compiles_in_window=in_window,
        refused=sum(r.status == "refused" for r in load.records),
        abandoned=sum(r.abandoned for r in load.records),
        errors=sorted({r.error for r in load.records
                       if r.status == "error" and not r.abandoned})[:3])

    e2e = {"serve_tokens_per_s": wm["tokens"] / seconds}
    for name, summary in (("gap", gap), ("ttft", ttft)):
        if summary["n"]:
            e2e.update({f"{name}_{k}_ms": summary[k]
                        for k in ("mean", "p50", "p75", "p90", "p95",
                                  "p99")})

    counters = {
        "records": load.records, "t_open": t_open, "seconds": seconds,
        "gaps_ms": wm["gaps_ms"],
        "chunk": engine.chunk, "slots": engine.slots,
        "kv_block_size": engine.kv_block_size,
        "compiles_in_window": in_window,
        "committed_tokens_per_s": (wm["committed"][0]
                                   if wm["committed"] else None),
        "prefill_pieces": (engine.prefill_stats["installments"]
                           - pieces_before),
        "prefill_prompt_tokens": sum(
            r.planned.prompt_len for r in load.records
            if r.first_token_at is not None),
    }

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in ctx["devices"])
    # Free the program's state before the reference runs, so the peak
    # above stays the program's and the reference has the room.
    finished = wm["finished"]
    del engine, driver, load
    gc.collect()

    if control:
        if rounded is not None:
            # The engine outlives its last name here (its jitted
            # methods take it as a static argument and their caches
            # keep it), and at 10 GB a copy there is no room for its
            # weights beside the reference's: free them by hand.
            for leaf in jax.tree.leaves(rounded):
                leaf.delete()
        params = make_weights()
    verdict = served_tokens(reference, params, cfg_file, finished, traffic,
                            seed, log, ctx["compiles"])
    return {"end_to_end": e2e, "attempted": wm["attempted"],
            "failed": wm["failed"], "counters": counters,
            "memory_peak_bytes": int(peak),
            "correct": bool(verdict["correct"] and in_window == 0
                            and wm["attempted"] > 0),
            "checks": verdict}
