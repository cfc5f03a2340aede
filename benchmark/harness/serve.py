"""Runner kind ``serve``: the program's ``ServingEngine`` behind its
``EngineDriver`` (the pair the gateway's handlers talk to), loaded by
``loadgen`` as the traffic file says.

Set-up (counted in ``setup_s``): configuration -> seeded weights on the
device -> engine and driver -> one warm request per prefill shape this
traffic uses -> the ramp, load offered before the window opens so the
window starts in steady state.  Then the window, the drain, and after
the engine is freed the comparison with the plain reference.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import check, loadgen, program, stats, weights


def engine_kwargs(cfg_file: dict, traffic: dict) -> dict:
    """The configuration's engine settings with the traffic file's own
    on top (a deployment sets these flags for the traffic it serves)."""
    kw = dict(cfg_file.get("engine", {}))
    kw.update(traffic.get("engine", {}))
    return kw


def build(cfg, cfg_file: dict, traffic: dict, params, control: str = ""):
    """(engine, driver) as ``tools/serve_http.py`` builds them, from
    the benchmark's own weights.  ``control="int8"`` switches on the
    program's weight-only int8 path (the lower-precision control)."""
    from tensorflow_train_distributed_tpu.server.driver import EngineDriver
    from tensorflow_train_distributed_tpu.serving import ServingEngine

    kw = engine_kwargs(cfg_file, traffic)
    max_queue = kw.pop("max_queue", 64)
    quant_scales = None
    if control == "int8":
        from tensorflow_train_distributed_tpu.models.quant import (
            quantize_params,
        )

        # Jitted, so each leaf's float32 detour is fused away: run
        # eagerly the program's quantizer needs two float32 copies of
        # the largest leaf beside the bf16 tree, which one chip lacks.
        params, quant_scales = jax.jit(quantize_params,
                                       donate_argnums=0)(params)
    elif control:
        raise ValueError(f"unknown control {control!r}")
    engine = ServingEngine(cfg, params, quant_scales=quant_scales, **kw)
    return engine, EngineDriver(engine, max_queue=max_queue)


def warm_lengths(engine, schedule_pool_lengths) -> list:
    """One prompt length per distinct prefill piece shape the traffic's
    prompt lengths map to (the longest length of each shape)."""
    by_piece = {}
    for n in schedule_pool_lengths:
        piece, _ = engine._pieces_for(int(n))
        by_piece[piece] = max(by_piece.get(piece, 0), int(n))
    return sorted(by_piece.values())


def warm(engine, driver, lengths, vocab: int, seed: int) -> dict:
    """Compile (or load) every program the window will use, by driving
    the engine through the situations the window brings:

    1. one request per prefill shape, all at once, to completion;
    2. the same shapes again with staggered lengths, so lanes retire
       one by one while others decode, a request that joins while they
       do (a freed lane is reset and refilled under a running batch),
       and one that is abandoned mid-stream (the cancel path).

    The small eager programs of the host loop (carry splices, lane
    resets, fresh prefill caches) compile on first use just like the
    big ones; a warm-up of the big ones alone leaves those to the
    window."""
    rng = np.random.default_rng([int(seed), 3])
    chunk = engine.chunk

    def prompt(n):
        return rng.integers(loadgen.FIRST_TOKEN_ID, vocab, n).tolist()

    def finish(handles, wants):
        out = [h.result(timeout=1500) for h in handles]
        bad = [(len(o) - len(h.prompt), w)
               for o, h, w in zip(out, handles, wants)
               if len(o) - len(h.prompt) != w]
        if bad:
            raise RuntimeError(f"warm requests returned/wanted {bad}")

    first = 2 * chunk + 1
    finish([driver.submit(prompt(n), first) for n in lengths],
           [first] * len(lengths))
    wants = [chunk * (2 + i) + 1 for i in range(len(lengths))]
    wave = [driver.submit(prompt(n), w) for n, w in zip(lengths, wants)]
    cut = driver.submit(prompt(lengths[0]), chunk * (8 + len(lengths)),
                        stream=True)
    wave[0].result(timeout=1500)
    late = driver.submit(prompt(lengths[-1]), first)
    for _ in cut.iter_tokens():
        driver.abandon(cut)          # after its first tokens
        break
    finish(wave + [late], wants + [first])
    try:
        for _ in cut.iter_tokens():
            pass
    except Exception:  # noqa: BLE001 — the abandoned stream ends in its
        pass           # deadline error; that is the path being warmed
    return {"warm_requests": 2 * len(lengths) + 2,
            "warm_lengths": list(lengths)}


def lane_tokens_mean(records, lo: float, hi: float, samples: int = 64):
    """Mean over [lo, hi) of the cached positions summed over the
    requests decoding at that moment (prompt + tokens so far), and the
    mean number of such requests: what a decode step has to read."""
    spans = []
    for r in records:
        times = r.token_times()
        if not times:
            continue
        spans.append((times[0], r.ended_at or times[-1],
                      r.planned.prompt_len, np.asarray(times)))
    tot, lanes = [], []
    for t in np.linspace(lo, hi, samples, endpoint=False):
        live = [(p, ts) for a, b, p, ts in spans if a <= t < b]
        lanes.append(len(live))
        tot.append(sum(p + int(np.searchsorted(ts, t, "right"))
                       for p, ts in live))
    return float(np.mean(tot)), float(np.mean(lanes))


def run(ctx: dict) -> dict:
    cfg_file, traffic = ctx["config"], ctx["traffic"]
    seed, seconds = ctx["seed"], ctx["seconds"]
    device = ctx["devices"][0]
    log = ctx["log"]

    cfg = program.llama_config(cfg_file)
    dtype = jnp.dtype(cfg_file.get("dtype", "bfloat16"))
    shapes = program.param_shapes(cfg)

    def make_weights():
        with jax.default_device(device):
            return jax.block_until_ready(
                weights.make_params(shapes, seed, dtype))

    t0 = time.monotonic()
    params = make_weights()
    log(phase="weights", seconds=time.monotonic() - t0,
        bytes=sum(x.nbytes for x in jax.tree.leaves(params)))

    engine, driver = build(cfg, cfg_file, traffic, params,
                           ctx.get("control", ""))
    if ctx.get("control"):
        # The control's engine holds its own lower-precision copy; the
        # bf16 weights are made again, from the seed, for the reference.
        del params
    driver.start()
    schedule = loadgen.Schedule(traffic, seed, seconds, cfg.vocab_size)
    t0 = time.monotonic()
    info = warm(engine, driver,
                warm_lengths(engine, schedule._prompts), cfg.vocab_size,
                seed)
    log(phase="warm", seconds=time.monotonic() - t0, **info,
        compiles=len(ctx["compiles"].events),
        compile_s=ctx["compiles"].total_s(),
        cache_hits=ctx["compiles"].hits,
        cache_misses=ctx["compiles"].misses,
        kv_pool_bytes=engine.kv_pool_bytes())

    annotate = ctx["annotate"]
    load = loadgen.LoadRun(
        schedule,
        submit=lambda prompt, max_new: driver.submit(
            prompt, max_new, stream=True),
        abandon=driver.abandon, seconds=seconds,
        drain_s=float(traffic.get("drain_s", 0.0)), annotate=annotate)
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
    # Server side, from the driver's handles: how long a request sent
    # inside the window waited for a lane.
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
        # (seconds after the window opened, tokens) of every commit
        commits=wm["timeline"],
        engine_stats={"prefill": dict(engine.prefill_stats),
                      "kv": dict(engine.kv_stats),
                      "overlap": dict(engine.overlap_stats)},
        compiles_in_window=in_window,
        refused=sum(r.status == "refused" for r in load.records),
        abandoned=sum(r.abandoned for r in load.records),
        errors=sorted({r.error for r in load.records
                       if r.status == "error" and not r.abandoned})[:3])

    # Every statistic a cell's metrics may name: the manifest says
    # which of them the cell reports.
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
        "compiles_in_window": in_window,
        "committed_tokens_per_s": (wm["committed"][0]
                                   if wm["committed"] else None),
        # Prefill over the whole load (ramp and drain too): the pieces
        # the engine ran and the real prompt tokens they carried.
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

    if ctx.get("control"):
        params = make_weights()
    verdict = check.served_tokens(
        params, cfg_file, finished, traffic, seed, log, ctx["compiles"])
    return {"end_to_end": e2e, "attempted": wm["attempted"],
            "failed": wm["failed"], "counters": counters,
            "memory_peak_bytes": int(peak),
            "correct": bool(verdict["correct"] and in_window == 0
                            and wm["attempted"] > 0),
            "checks": verdict}
