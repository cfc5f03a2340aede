"""Continuous-batching engine throughput vs static-batch generate.

Serves a mixed-length synthetic request stream through
``serving.ServingEngine`` (slot-refill decode) and reports GENERATED
tokens/sec plus p50 TTFT and mean inter-token latency, with the
engine's ``overlap_ratio`` (host-harvest share hidden under device
compute) alongside.  ``--baseline`` also times the static-batch path the engine replaces — same requests
grouped into arrival-order batches of ``--slots``, each batch padded to
its longest prompt and decoded for its largest max_new (what
``generate()`` forces) — so the engine's win IS the padding/straggler
waste it removes.

``--trace-ab`` instead A/Bs the always-on flight recorder
(``runtime.events``) against its ``TTD_NO_TRACE=1`` kill switch on
identical passes of one engine, reporting the tok/s overhead
percentage — the committed proof the recorder is cheap enough to leave
on (``profiles/bench/trace_overhead_ab.jsonl``).

Every decode record carries ``mbu_pct`` (model-bandwidth utilization,
the serving analog of training MFU — null off-TPU where no bandwidth
table exists) beside tok/s, so the metric decode optimization is
judged by lands in every committed record.

Prints one JSON line per run (bench_lm.py conventions).
"""

import argparse
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))  # repo root (the package)
sys.path.insert(0, _HERE)                   # tools/ siblings

from bench_gateway import (  # noqa: E402 (shared helpers)
    _percentile,
    decode_mbu_fields,
)


def _requests(n, plo, phi, glo, ghi, vocab, seed):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [(list(rng.integers(1, vocab, int(rng.integers(plo, phi + 1)))),
             int(rng.integers(glo, ghi + 1))) for _ in range(n)]


def _run_engine_timed(eng, reqs):
    """One timed pass: submit everything, drive ``serve_step``, record
    per-request first-token and completion times (the serving-latency
    view ``run()`` cannot give).  Returns ``(wall_s, ttfts, itls,
    total_tokens_out)`` — ``itls`` are per-request mean inter-token
    gaps (completion-first)/(generated-1), requests with >1 generated
    token only."""
    ids = [eng.submit(p, m) for p, m in reqs]
    plens = {rid: len(p) for rid, (p, _) in zip(ids, reqs)}
    first, done_at, out = {}, {}, {}
    t0 = time.perf_counter()
    while eng.pending():
        done = eng.serve_step()
        now = time.perf_counter()
        for rid, toks in done.items():
            out[rid] = toks
            done_at[rid] = now
            if rid not in first and len(toks) > plens[rid]:
                first[rid] = now
        for rid, n in eng.progress().items():
            if rid not in first and n > plens[rid]:
                first[rid] = now
    wall = time.perf_counter() - t0
    ttfts = sorted(first[r] - t0 for r in ids if r in first)
    itls = []
    for rid in ids:
        gen = len(out[rid]) - plens[rid]
        if rid in first and rid in done_at and gen > 1:
            itls.append((done_at[rid] - first[rid]) / (gen - 1))
    return wall, ttfts, itls, sum(len(v) for v in out.values())


def bench_trace_ab(preset, slots, chunk, n_requests, prompt_range,
                   new_range, cache_len, seed, reps=3):
    """The flight-recorder overhead A/B: identical engine passes with
    the recorder ON (the always-on default) vs ``TTD_NO_TRACE=1`` (the
    kill switch).  ONE engine serves both legs — the jitted programs
    are shared, so the measured delta is purely the host-side
    span/instant recording the tentpole claims is ≤ 2 % tok/s.

    Noise discipline: single-pass walls on a shared host swing far
    more than the effect being measured, so the legs run as
    BACK-TO-BACK PAIRS (on, off) and the headline is the MEDIAN of the
    per-pair wall ratios — a scheduler spike inflates one pair's both
    legs (ratio survives) or one leg of one pair (median discards it),
    where min-wall-per-leg across minutes compares walls from
    different load regimes."""
    import jax
    import jax.numpy as jnp

    from tensorflow_train_distributed_tpu.models.llama import (
        LLAMA_PRESETS, LlamaModel,
    )
    from tensorflow_train_distributed_tpu.runtime import events
    from tensorflow_train_distributed_tpu.serving import ServingEngine

    cfg = LLAMA_PRESETS[preset]
    params = LlamaModel(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    reqs = _requests(n_requests, *prompt_range, *new_range,
                     min(cfg.vocab_size, 30_000), seed)
    gen_tokens = sum(m for _, m in reqs)
    eng = ServingEngine(cfg, params, slots=slots, chunk=chunk,
                        cache_len=cache_len)
    for p, m in reqs:                              # warmup: compiles
        eng.submit(p, m)
    eng.run()
    had_kill = os.environ.get("TTD_NO_TRACE")
    best = {True: None, False: None}
    ratios = []
    try:
        for i in range(max(1, reps)):
            walls = {}
            # Leg order alternates per pair ((on, off), (off, on), ...):
            # whatever systematic advantage the second-run leg of a
            # pair has (cache warmth, allocator state) cancels in the
            # median instead of biasing every ratio the same way.
            for trace_on in ((True, False) if i % 2 == 0
                             else (False, True)):
                if trace_on:
                    os.environ.pop("TTD_NO_TRACE", None)
                else:
                    os.environ["TTD_NO_TRACE"] = "1"
                rec = _run_engine_timed(eng, reqs)
                walls[trace_on] = rec[0]
                if best[trace_on] is None or rec[0] < best[trace_on][0]:
                    best[trace_on] = rec
            ratios.append(walls[True] / walls[False])
    finally:
        if had_kill is None:
            os.environ.pop("TTD_NO_TRACE", None)
        else:
            os.environ["TTD_NO_TRACE"] = had_kill
    ratios.sort()
    median_ratio = ratios[len(ratios) // 2]
    tps_on = gen_tokens / best[True][0]
    tps_off = gen_tokens / best[False][0]
    dev = jax.devices()[0]
    return {
        "metric": f"{preset}_serving_trace_overhead_pct",
        "value": round(100.0 * (median_ratio - 1.0), 3),
        "unit": "% tok/s lost, flight recorder on vs TTD_NO_TRACE=1 "
                "(median of per-pair wall ratios)",
        "pair_wall_ratios": [round(r, 4) for r in ratios],
        "trace_on_tokens_per_sec": round(tps_on, 1),
        "trace_off_tokens_per_sec": round(tps_off, 1),
        "trace_on_wall_s": round(best[True][0], 3),
        "trace_off_wall_s": round(best[False][0], 3),
        "events_in_ring": len(events.get_recorder()),
        "ring_capacity": events.get_recorder().capacity,
        "slots": slots,
        "chunk": chunk,
        "n_requests": n_requests,
        "gen_tokens": gen_tokens,
        "reps": reps,
        "backend": dev.platform,
        "device_kind": dev.device_kind,
    }


def bench_trace_fleet_ab(preset, slots, chunk, n_requests, prompt_range,
                         new_range, cache_len, seed, reps=3,
                         replicas=2):
    """The FLEET observability overhead A/B: a real subprocess pool
    (parent gateway process + ``replicas`` llama workers over the
    frame protocol) serving the same request set under THREE legs —
    ``off`` (``TTD_NO_TRACE=1`` + ``TTD_NO_CLOCK_SYNC=1``, no spool),
    ``trace`` (the pre-fleet flight recorder alone: rings on, relay
    on, sync killed, no spool), and ``full`` (the whole plane:
    PING/PONG clock sync on the stats heartbeat plus the
    crash-durable trace spool writing in parent and workers).  Two
    headlines fall out: ``full/off`` is the total cost of always-on
    fleet observability, and ``full/trace`` is the MARGINAL cost of
    what this plane added on top of the recorder the repo already
    shipped — the "spool+sync overhead" the tentpole's ≤2% bar
    names.

    Workers read their kill switches from their own environment, so
    each leg is its own pool spawned with the leg's env overlaid on
    the child; all pools are built and warmed up-front and the timed
    passes run as leg-order-rotating rounds with the parent-side env
    flipped around each pass, median of per-round wall ratios — the
    --trace-ab noise discipline.  During a pass the other pools'
    workers are idle (heartbeats only), which costs every leg the
    same.  NOTE the observer and the observed share cores: on a
    small host (the committed record's 1-CPU container) flusher and
    relay threads displace decode compute directly, so these numbers
    are an upper bound — on a multi-core host the plane rides spare
    cores and only the serving-thread ring appends remain."""
    import shutil
    import tempfile

    import jax

    from tensorflow_train_distributed_tpu.models.llama import (
        LLAMA_PRESETS,
    )
    from tensorflow_train_distributed_tpu.runtime import events
    from tensorflow_train_distributed_tpu.server.procpool import (
        ProcPool, WorkerSpec,
    )

    cfg = LLAMA_PRESETS[preset]
    reqs = _requests(n_requests, *prompt_range, *new_range,
                     min(cfg.vocab_size, 30_000), seed)
    gen_tokens = sum(m for _, m in reqs)
    factory_json = dict(preset=preset, init_seed=0, slots=slots,
                        chunk=chunk)
    if cache_len:
        factory_json["cache_len"] = cache_len
    spool_dir = tempfile.mkdtemp(prefix="ttd-fleet-ab-spool-")
    worker_env = {
        "off": {"TTD_NO_TRACE": "1", "TTD_NO_CLOCK_SYNC": "1"},
        "trace": {"TTD_NO_CLOCK_SYNC": "1"},
        "full": {"TTD_TRACE_SPOOL": spool_dir},
    }
    saved = {k: os.environ.get(k) for k in
             ("TTD_NO_TRACE", "TTD_NO_CLOCK_SYNC", "TTD_TRACE_SPOOL")}
    # Spawn every pool from a NEUTRAL parent env: WorkerSpec.env
    # OVERLAYS the inherited environment (it cannot unset keys), so a
    # leak from the parent would silently arm the wrong leg's workers.
    for k in saved:
        os.environ.pop(k, None)

    def arm(leg):
        """Parent-side leg flip: recording, the ping mint, and the
        parent spool all live in this process and re-read env (or are
        armed explicitly) around each pass."""
        for k in saved:
            os.environ.pop(k, None)
        os.environ.update(worker_env[leg])
        if leg == "full":
            events.get_recorder().start_spool(spool_dir)
            # Drain the ring backlog NOW: re-arming resets the spool
            # cursor, and the backlog serialize belongs to no leg.
            events.get_recorder().flush_spool()
        else:
            events.get_recorder().stop_spool()

    def timed_pass(pool):
        t0 = time.perf_counter()
        hs = [pool.submit(p, m) for p, m in reqs]
        for h in hs:
            h.result(timeout=600)
        return time.perf_counter() - t0

    legs = ("off", "trace", "full")
    pools = {}
    best = {leg: None for leg in legs}
    rounds = []
    sync_state = None
    spool_files = 0
    try:
        for leg in legs:
            spec = WorkerSpec(factory="llama", factory_json=factory_json,
                              env=worker_env[leg])
            pools[leg] = ProcPool(spec, replicas=replicas,
                                  max_queue=4 * n_requests,
                                  watchdog_timeout_s=300.0).start()
        for leg in legs:                    # warmup: worker compiles
            if not pools[leg].wait_ready(timeout=600):
                raise RuntimeError("fleet AB pool never became ready")
            arm(leg)
            timed_pass(pools[leg])
        for i in range(max(1, reps)):
            walls = {}
            for leg in (legs if i % 2 == 0 else legs[::-1]):
                arm(leg)
                w = timed_pass(pools[leg])
                walls[leg] = w
                if best[leg] is None or w < best[leg]:
                    best[leg] = w
            rounds.append(walls)
        # Committed proof the full leg really ran the plane: clocks
        # synced on every full-leg worker, spool segments on disk.
        sync_state = [s.get("clock") for s in
                      pools["full"].replica_states()]
        events.get_recorder().flush_spool()
        spool_files = len([n for n in os.listdir(spool_dir)
                           if n.startswith("spool-")])
    finally:
        for pool in pools.values():
            try:
                pool.join(timeout=60)
            except Exception:   # noqa: BLE001 — teardown best-effort
                pass
        events.get_recorder().stop_spool()
        shutil.rmtree(spool_dir, ignore_errors=True)
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    def med(pairs):
        rs = sorted(pairs)
        return rs[len(rs) // 2]

    total = med([w["full"] / w["off"] for w in rounds])
    trace_only = med([w["trace"] / w["off"] for w in rounds])
    marginal = med([w["full"] / w["trace"] for w in rounds])
    dev = jax.devices()[0]
    return {
        "metric": f"{preset}_serving_trace_fleet_overhead_pct",
        "value": round(100.0 * (marginal - 1.0), 3),
        "unit": "% tok/s lost to clock sync + crash-durable spool on "
                "top of the flight recorder (full/trace, median of "
                "per-round wall ratios over a subprocess worker pool)",
        "fleet_total_overhead_pct":
            round(100.0 * (total - 1.0), 3),
        "trace_only_overhead_pct":
            round(100.0 * (trace_only - 1.0), 3),
        "round_wall_ratios_full_vs_trace":
            sorted(round(w["full"] / w["trace"], 4) for w in rounds),
        "round_wall_ratios_full_vs_off":
            sorted(round(w["full"] / w["off"], 4) for w in rounds),
        "fleet_full_tokens_per_sec": round(gen_tokens / best["full"], 1),
        "fleet_off_tokens_per_sec": round(gen_tokens / best["off"], 1),
        "fleet_full_wall_s": round(best["full"], 3),
        "fleet_trace_wall_s": round(best["trace"], 3),
        "fleet_off_wall_s": round(best["off"], 3),
        "workers_synced": sum(1 for c in (sync_state or [])
                              if c and c.get("synced")),
        "spool_segments": spool_files,
        "replicas": replicas,
        "slots": slots,
        "chunk": chunk,
        "n_requests": n_requests,
        "gen_tokens": gen_tokens,
        "reps": reps,
        "backend": dev.platform,
        "device_kind": dev.device_kind,
    }


def bench_serving(preset, slots, chunk, n_requests, prompt_range,
                  new_range, cache_len, baseline, seed,
                  draft_preset="", speculative_k=0, kv_int8=False,
                  reps=3):
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflow_train_distributed_tpu.models.generate import generate
    from tensorflow_train_distributed_tpu.models.llama import (
        LLAMA_PRESETS, LlamaModel,
    )
    from tensorflow_train_distributed_tpu.serving import ServingEngine

    cfg = LLAMA_PRESETS[preset]
    if kv_int8:
        # int8 KV cache: half the cache bytes per decode
        # step — params are layout-independent, so the same tree serves.
        cfg = dataclasses.replace(cfg, kv_cache_int8=True)
    params = LlamaModel(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    reqs = _requests(n_requests, *prompt_range, *new_range,
                     min(cfg.vocab_size, 30_000), seed)
    gen_tokens = sum(m for _, m in reqs)

    draft_cfg = draft_params = None
    if draft_preset == "self":
        # Acceptance CEILING: the target drafts for itself (p == q, all
        # drafts accepted) — measures the speculative machinery's best
        # case and its mechanical overhead; pair with a random-init
        # draft (the floor) to bracket real trained drafts.
        draft_cfg, draft_params = cfg, params
    elif draft_preset:
        draft_cfg = LLAMA_PRESETS[draft_preset]
        if kv_int8:
            # The draft's caches quantize in lockstep with the
            # target's (the tools/serve.py --kv-int8 rule) — a
            # '_kv8'-named record must not secretly serve an fp-KV
            # draft.  The 'self' branch above shares cfg, already
            # replaced.
            draft_cfg = dataclasses.replace(draft_cfg,
                                            kv_cache_int8=True)
        draft_params = LlamaModel(draft_cfg).init(
            jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]

    # ONE engine for warmup + timed runs: the jitted programs are
    # keyed on the engine instance (static self), so a fresh engine
    # would pay every compile again inside the timed region.
    # run()/serve_step are reentrant (tests/test_serving.py) — stale
    # slot caches cannot contaminate.
    eng = ServingEngine(
        cfg, params, slots=slots, chunk=chunk, cache_len=cache_len,
        draft_config=draft_cfg, draft_params=draft_params,
        speculative_k=speculative_k if draft_cfg else 0)
    for p, m in reqs:                              # warmup: compiles
        eng.submit(p, m)
    eng.run()

    def one_pass(e):
        # Zero the accounting per pass so the committed ratio
        # describes the best pass's window only.
        for k in e.overlap_stats:
            e.overlap_stats[k] = 0 if isinstance(
                e.overlap_stats[k], int) else 0.0
        rec = _run_engine_timed(e, reqs)
        return rec + (dict(e.overlap_stats), e.overlap_ratio())

    def summarize(best):
        wall, ttfts, itls, total, stats, ratio = best
        return {
            "tokens_per_sec": round(gen_tokens / wall, 1),
            "wall_s": round(wall, 3),
            "ttft_ms_p50": round(1e3 * _percentile(ttfts, 0.5), 2),
            "inter_token_ms_mean": round(
                1e3 * sum(itls) / len(itls), 3) if itls else 0.0,
            "overlap_ratio": round(ratio, 3),
            "overlapped_harvests": stats["overlapped_harvests"],
        }, total

    # Best-of-``reps``: single-pass walls on a shared/loaded host are
    # noisy at these scales, and min-wall reads through scheduler noise.
    best_on = min((one_pass(eng) for _ in range(max(1, reps))),
                  key=lambda rec: rec[0])
    on_rec, total_len = summarize(best_on)
    dt = on_rec["wall_s"]
    dev = jax.devices()[0]
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    rows = cache_len or cfg.max_positions
    # Ceiling ('self') and floor (random-init) runs must be
    # distinguishable by metric name alone, not just the draft_preset
    # field — and int8-KV runs by the _kv8 suffix (the bench_lm
    # convention).
    name = (f"{preset}_serving_engine_spec_{draft_preset}"
            if draft_preset else f"{preset}_serving_engine")
    if kv_int8:
        name += "_kv8"
    rec = {
        "metric": f"{name}_tokens_per_sec",
        "value": on_rec["tokens_per_sec"],
        "unit": "generated tokens/sec",
        "wall_s": dt,
        "ttft_ms_p50": on_rec["ttft_ms_p50"],
        "inter_token_ms_mean": on_rec["inter_token_ms_mean"],
        "overlap_ratio": on_rec["overlap_ratio"],
        "overlapped_harvests": on_rec["overlapped_harvests"],
        "slots": slots,
        "chunk": chunk,
        "n_requests": n_requests,
        "gen_tokens": gen_tokens,
        "total_tokens_out": total_len,
        "fused_attn": eng.fused_attn(),
        "backend": dev.platform,
        "device_kind": dev.device_kind,
    }
    rec.update(decode_mbu_fields(cfg, n_params, slots, rows,
                                 on_rec["tokens_per_sec"], kv_int8))
    if kv_int8:
        rec["kv_cache"] = "int8"
        rec["kv_pool_bytes"] = eng.kv_pool_bytes()
    if draft_preset:
        rec["draft_preset"] = draft_preset
        rec["speculative_k"] = speculative_k
        s = eng.spec_stats
        if s["slot_rounds"]:
            # Fraction of drafted tokens accepted: each ACTIVE slot in a
            # round drafts k tokens (slot_rounds, not engine rounds).
            rec["acceptance_rate"] = round(
                s["drafted_accepted"] / (s["slot_rounds"]
                                         * speculative_k), 3)
    if baseline:
        def run_static():
            done = 0
            for i in range(0, len(reqs), slots):
                grp = reqs[i:i + slots]
                plen = max(len(p) for p, _ in grp)
                mnew = max(m for _, m in grp)
                if mnew == 0:
                    continue
                batch = np.zeros((len(grp), plen), np.int32)
                for j, (p, _) in enumerate(grp):
                    batch[j, plen - len(p):] = p  # left-pad: keeps the
                    # last prompt token at the shared final position so
                    # one batched generate covers the group.  The pad
                    # zeros are treated as real context (positions start
                    # at 0), so baseline OUTPUTS are not valid
                    # generations — the baseline is FLOP/timing-
                    # equivalent only, which is all the A/B compares.
                out = generate(cfg, params, jnp.asarray(batch), mnew)
                done += int(np.asarray(out).shape[1]) * len(grp)
            return done

        run_static()                               # warmup
        t0 = time.perf_counter()
        run_static()
        dt_static = time.perf_counter() - t0
        rec["static_batch_wall_s"] = round(dt_static, 3)
        rec["static_batch_tokens_per_sec"] = round(gen_tokens / dt_static, 1)
        rec["engine_speedup"] = round(dt_static / dt, 3)
    return rec


def bench_spec_adaptive_ab(preset, draft_preset, slots, chunk,
                           n_requests, prompt_range, new_range,
                           cache_len, seed, depths=(0, 2, 4, 8),
                           reps=3, wide_d_model=0):
    """The acceptance-adaptive speculation A/B: adaptive depth
    (``spec_depths`` buckets + DepthController) vs every FIXED depth
    in the bucket set, on a MIXED workload no single fixed depth can
    win — an easy phase (high-acceptance cheap draft: deep k
    amortizes target steps) plus a hard phase (random-init draft:
    acceptance ~0, every drafted token is wasted work and k=0 is
    optimal).  A fixed depth is tuned for one phase and pays on the
    other; the controller should ride each phase at its optimum, so
    the bar is adaptive ~= best fixed (<= 2% behind) AND >= 1.15x the
    worst fixed.

    Speculation only pays when the draft step is much cheaper than
    the target step, so the TARGET here is the preset deepened 4x
    with the upper residual blocks' output projections ZEROED — every
    upper block is x + 0 (an exact identity), so the deep model
    computes the preset's function at 4x the preset's per-step cost.
    The easy draft is the target's first quarter SHARING its weights:
    same logits, ~unit acceptance, ~1/4 the step cost — a synthetic
    stand-in for a well-trained draft (the 'self'/random bracket
    bench_serving documents, collapsed to its interesting corner).
    The hard draft is the same small config randomly initialized.

    Each policy gets TWO engines (one per phase — the phase is a
    property of the draft model, not the requests) warmed on its own
    phase's requests, so the adaptive engines compile their depth
    buckets outside the timed region (the hard engine walks
    deepest->0 during warmup; the easy engine never leaves the
    deepest bucket).  The fixed-0 comparator is a draft-free engine —
    plain decode, the honest 'no speculation' leg.

    Noise discipline: per ROUND, every policy runs its full mixed
    pass back-to-back, with policy order alternating between rounds;
    the headline is the MEDIAN over rounds of the per-round wall
    ratio adaptive/best-fixed (best fixed = the depth with the lowest
    median wall)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from tensorflow_train_distributed_tpu.models.llama import (
        LLAMA_PRESETS, LlamaModel,
    )
    from tensorflow_train_distributed_tpu.serving import ServingEngine

    draft_cfg = LLAMA_PRESETS[draft_preset or preset]
    if wide_d_model:
        # CPU-leg sizing: widen the preset until the target's weights
        # spill the last-level cache — decode goes weight-streaming
        # (bandwidth) bound, which is the regime where a multi-position
        # verify costs ~one step and speculation pays at all.  TPU
        # presets are already there; the tiny CPU preset is not.
        draft_cfg = dataclasses.replace(
            draft_cfg, d_model=wide_d_model,
            ffn_size=wide_d_model * 11 // 4,
            num_heads=8, num_kv_heads=4)
    cfg = dataclasses.replace(draft_cfg,
                              num_layers=4 * draft_cfg.num_layers)
    params = LlamaModel(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]

    def _zero_upper(path, leaf):
        # Upper blocks become exact identities: zero the residual
        # output projections (attention/out, mlp/wo), so the block
        # adds exact 0.0 to the stream.
        keys = [str(getattr(k, "key", k)) for k in path]
        if (keys and keys[0].startswith("layer_")
                and int(keys[0][len("layer_"):]) >= draft_cfg.num_layers
                and ("out" in keys or "wo" in keys)):
            return jnp.zeros_like(leaf)
        return leaf

    params = jax.tree_util.tree_map_with_path(_zero_upper, params)
    # Easy draft = the target's first quarter, sharing its weights —
    # the identity upper blocks make its logits the target's logits.
    easy_draft_params = {
        k: params[k] for k in
        ["token_embed", "final_norm", "lm_head"]
        + [f"layer_{i}" for i in range(draft_cfg.num_layers)]}
    bad_draft_params = LlamaModel(draft_cfg).init(
        jax.random.PRNGKey(7), jnp.zeros((1, 8), jnp.int32))["params"]
    vocab = min(cfg.vocab_size, 30_000)
    easy_reqs = _requests(n_requests, *prompt_range, *new_range,
                          vocab, seed)
    hard_reqs = _requests(n_requests, *prompt_range, *new_range,
                          vocab, seed + 1)
    gen_tokens = sum(m for _, m in easy_reqs + hard_reqs)
    deepest = max(depths)

    def make(policy, regime):
        d_cfg, d_params = ((draft_cfg, easy_draft_params)
                           if regime == "easy"
                           else (draft_cfg, bad_draft_params))
        if policy == "adaptive":
            kw = dict(speculative_k=deepest, spec_depths=depths)
        elif policy == 0:
            d_cfg = d_params = None               # plain decode
            kw = dict(speculative_k=0)
        else:
            kw = dict(speculative_k=policy)
        eng = ServingEngine(cfg, params, slots=slots, chunk=chunk,
                            cache_len=cache_len, draft_config=d_cfg,
                            draft_params=d_params, **kw)
        reqs = easy_reqs if regime == "easy" else hard_reqs
        for pr, m in reqs:                        # warmup: compiles
            eng.submit(pr, m)
        eng.run()
        return eng

    policies = ["adaptive"] + [int(k) for k in depths]
    engines = {p: {r: make(p, r) for r in ("easy", "hard")}
               for p in policies}
    walls = {p: [] for p in policies}
    for i in range(max(1, reps)):
        order = policies if i % 2 == 0 else list(reversed(policies))
        for pol in order:
            w = (_run_engine_timed(engines[pol]["easy"], easy_reqs)[0]
                 + _run_engine_timed(engines[pol]["hard"], hard_reqs)[0])
            walls[pol].append(w)

    def median(xs):
        xs = sorted(xs)
        return xs[len(xs) // 2]

    fixed = {k: median(walls[k]) for k in depths}
    best_k = min(fixed, key=fixed.get)
    worst_k = max(fixed, key=fixed.get)
    vs_best = sorted(a / b for a, b in
                     zip(walls["adaptive"], walls[best_k]))
    vs_worst = sorted(b / a for a, b in
                      zip(walls["adaptive"], walls[worst_k]))
    tele = {r: engines["adaptive"][r].spec_telemetry()
            for r in ("easy", "hard")}
    dev = jax.devices()[0]
    return {
        "metric": f"{preset}_serving_spec_adaptive_wall_ratio",
        "value": round(median(vs_best), 4),
        "unit": "x wall, adaptive depth vs best fixed depth on the "
                "mixed easy/hard workload (median of per-round wall "
                "ratios; <= 1.02 = within 2% of best fixed)",
        "vs_worst_fixed_speedup": round(median(vs_worst), 4),
        "best_fixed_k": best_k,
        "worst_fixed_k": worst_k,
        "depths": list(depths),
        "pair_wall_ratios_vs_best": [round(r, 4) for r in vs_best],
        "pair_wall_ratios_vs_worst": [round(r, 4) for r in vs_worst],
        "per_policy": {
            str(p): {
                "wall_s_median": round(median(walls[p]), 3),
                "tokens_per_sec": round(
                    gen_tokens / median(walls[p]), 1),
            } for p in policies},
        "adaptive_depth_rounds": {
            r: {str(d): v["rounds"]
                for d, v in tele[r].get("per_depth", {}).items()}
            for r in tele},
        "adaptive_switches": {
            r: tele[r].get("switches", 0) for r in tele},
        "slots": slots,
        "chunk": chunk,
        "n_requests_per_phase": n_requests,
        "gen_tokens": gen_tokens,
        "reps": reps,
        "wide_d_model": wide_d_model,
        "target_layers": cfg.num_layers,
        "draft_layers": draft_cfg.num_layers,
        "backend": dev.platform,
        "device_kind": dev.device_kind,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--preset", default="llama_125m")
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--chunk", type=int, default=8)
    p.add_argument("--requests", type=int, default=32)
    p.add_argument("--prompt-range", default="16,120",
                   help="lo,hi inclusive prompt lengths")
    p.add_argument("--new-range", default="16,128",
                   help="lo,hi inclusive max_new_tokens")
    p.add_argument("--cache-len", type=int, default=0,
                   help="0 -> config.max_positions")
    p.add_argument("--baseline", action="store_true",
                   help="also time the static-batch generate path")
    p.add_argument("--speculative-draft", default="",
                   help="llama preset for a draft model: speculative "
                        "serving A/B (random-init draft = the "
                        "acceptance FLOOR; 'self' = the target drafts "
                        "for itself, the acceptance CEILING — the pair "
                        "brackets real trained drafts)")
    p.add_argument("--speculative-k", type=int, default=4)
    p.add_argument("--kv-int8", action="store_true",
                   help="throughput run with the int8 KV cache "
                        "(kv_cache_int8 config): half the cache bytes "
                        "per decode step; metric name gains the _kv8 "
                        "suffix")
    p.add_argument("--trace-ab", action="store_true",
                   help="flight-recorder overhead A/B instead of the "
                        "throughput run: identical passes with the "
                        "recorder on (the always-on default) vs "
                        "TTD_NO_TRACE=1, reporting the tok/s overhead "
                        "percentage (committed record: "
                        "profiles/bench/trace_overhead_ab.jsonl)")
    p.add_argument("--trace-fleet-ab", action="store_true",
                   help="FLEET observability overhead A/B: a parent + "
                        "subprocess-worker pool serving with clock "
                        "sync, event relay, and the crash-durable "
                        "trace spool armed everywhere vs "
                        "TTD_NO_TRACE=1 + TTD_NO_CLOCK_SYNC=1 and no "
                        "spool (committed record: "
                        "profiles/bench/trace_fleet_ab.jsonl)")
    p.add_argument("--fleet-replicas", type=int, default=2,
                   help="--trace-fleet-ab only: subprocess workers "
                        "per pool leg")
    p.add_argument("--spec-adaptive-ab", action="store_true",
                   help="acceptance-adaptive speculation A/B instead "
                        "of the throughput run: adaptive depth vs "
                        "every fixed depth in --spec-depths, on a "
                        "mixed easy (self-draft) / hard (random-init "
                        "draft) workload no single fixed depth wins "
                        "(committed record: "
                        "profiles/bench/spec_adaptive_ab.jsonl)")
    p.add_argument("--spec-depths", default="0,2,4,8",
                   help="--spec-adaptive-ab only: comma-separated "
                        "depth buckets (also the fixed comparator "
                        "set)")
    p.add_argument("--spec-d-model", type=int, default=0,
                   help="--spec-adaptive-ab only: widen the preset to "
                        "this d_model so decode is weight-streaming "
                        "bound (the CPU leg's sizing; 0 = preset "
                        "unchanged, the TPU leg)")
    p.add_argument("--reps", type=int, default=3,
                   help="timed passes per leg; min wall is reported "
                        "(reads through host scheduler noise)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--platform", default="",
                   help="force a jax platform ('cpu' for smoke runs)")
    args = p.parse_args(argv)
    from tensorflow_train_distributed_tpu.runtime import compile_cache

    compile_cache.place_compile_cache()
    if args.platform:
        from tensorflow_train_distributed_tpu.runtime.mesh import (
            force_platform,
        )

        force_platform(args.platform)
    prompt_range = tuple(int(x) for x in args.prompt_range.split(","))
    new_range = tuple(int(x) for x in args.new_range.split(","))
    try:
        if args.trace_ab:
            rec = bench_trace_ab(args.preset, args.slots, args.chunk,
                                 args.requests, prompt_range,
                                 new_range, args.cache_len or None,
                                 args.seed, reps=args.reps)
        elif args.trace_fleet_ab:
            rec = bench_trace_fleet_ab(
                args.preset, args.slots, args.chunk,
                args.requests, prompt_range, new_range,
                args.cache_len or None, args.seed,
                reps=args.reps, replicas=args.fleet_replicas)
        elif args.spec_adaptive_ab:
            depths = tuple(int(x)
                           for x in args.spec_depths.split(","))
            draft = (args.speculative_draft
                     if args.speculative_draft != "self" else "")
            rec = bench_spec_adaptive_ab(
                args.preset, draft, args.slots, args.chunk,
                args.requests, prompt_range, new_range,
                args.cache_len or None, args.seed, depths,
                reps=args.reps, wide_d_model=args.spec_d_model)
        else:
            rec = bench_serving(args.preset, args.slots, args.chunk,
                                args.requests, prompt_range,
                                new_range,
                                args.cache_len or None,
                                args.baseline,
                                args.seed,
                                draft_preset=args.speculative_draft,
                                speculative_k=args.speculative_k,
                                kv_int8=args.kv_int8,
                                reps=args.reps)
    except Exception as e:
        if args.trace_ab:
            metric = f"{args.preset}_serving_trace_overhead_pct"
            unit = "% tok/s lost, flight recorder on vs TTD_NO_TRACE=1"
        elif args.trace_fleet_ab:
            metric = f"{args.preset}_serving_trace_fleet_overhead_pct"
            unit = ("% tok/s lost, clock sync + relay + spool armed "
                    "fleet-wide vs all kill switches")
        elif args.spec_adaptive_ab:
            metric = f"{args.preset}_serving_spec_adaptive_wall_ratio"
            unit = "x wall, adaptive depth vs best fixed depth"
        else:
            name = (f"{args.preset}_serving_engine_spec"
                    if args.speculative_draft
                    else f"{args.preset}_serving_engine")
            metric, unit = f"{name}_tokens_per_sec", "generated tokens/sec"
        print(json.dumps({
            "metric": metric, "value": 0.0, "unit": unit,
            "error": f"{type(e).__name__}: {e}"}), flush=True)
        return 1
    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
