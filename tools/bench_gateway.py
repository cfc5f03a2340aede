"""Closed-loop load generator for the HTTP serving gateway.

Drives ``tools/serve_http.py``'s gateway with ``--clients`` concurrent
closed-loop clients (each sends its next request only after the
previous one answers — the canonical serving-latency harness shape) and
reports the bench trajectory's first serving-latency datapoints: p50 /
p99 request latency, generated tokens/sec, mean TTFT and inter-token
latency (scraped from the gateway's own /metrics histograms), and the
shed rate (429s per attempt; a shed client honors Retry-After and
retries, so the loop stays closed under overload).

Self-contained by default — builds a random-init ``--preset`` engine
and an in-process gateway on an ephemeral port, so the bench needs no
checkpoint and runs on the CPU mesh (``--platform cpu``) or a real
chip alike.  ``--base-url`` points it at an externally launched
gateway instead (then engine flags here are ignored).

Prints one driver-parsable JSON line (bench_lm.py conventions).
"""

import argparse
import contextlib
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))

from tensorflow_train_distributed_tpu.runtime.lint.registry import (  # noqa: E402
    thread_role,
)

# Load-generation threads carry the ``loadgen`` role so the runtime
# lock sanitizer (and the flight recorder's forensics) can tell bench
# traffic from the gateway's own handler threads.
_loadgen_role = thread_role("loadgen")


def _requests_for(client: int, n: int, plo, phi, glo, ghi, vocab, seed):
    import numpy as np

    rng = np.random.default_rng(seed + 1000 * client)
    return [([int(t) for t in
              rng.integers(1, vocab, int(rng.integers(plo, phi + 1)))],
             int(rng.integers(glo, ghi + 1))) for _ in range(n)]


def decode_mbu_fields(cfg, n_params, slots, cache_len,
                      tokens_per_sec, kv_int8=False):
    """Model-bandwidth-utilization fields for a DECODE-side serving
    record — the serving analog of training MFU, so every committed
    engine/gateway record carries the headline metric decode
    optimization is judged by (bench_generate's convention, shared by
    bench_serving and bench_gateway).

    Byte model per decode step (one token for every slot): the cast
    params stream once + the slot-grid KV working set (2 tensors × L ×
    slots × cache_len × kv_heads × head_dim at the cache dtype; int8
    adds its f32 per-row scales).  Steps/sec is tokens_per_sec /
    slots — generated tok/s counts all lanes, a full step emits one
    token per lane.  ``mbu_pct`` is None off-TPU (no bandwidth table —
    the field still lands in every record so TPU reruns of the same
    harness fill it in).
    """
    import jax
    import jax.numpy as jnp

    from tensorflow_train_distributed_tpu.training.memory import tpu_peaks

    itemsize = jnp.dtype(cfg.dtype).itemsize
    kv_heads = cfg.num_kv_heads or cfg.num_heads
    head_dim = cfg.d_model // cfg.num_heads
    kv_rows = 2 * cfg.num_layers * slots * cache_len * kv_heads
    cache_bytes = kv_rows * head_dim * (1 if kv_int8 else itemsize)
    if kv_int8:
        cache_bytes += kv_rows * 4          # f32 per-row scales
    bytes_per_step = n_params * itemsize + cache_bytes
    out = {"decode_bytes_per_step": int(bytes_per_step),
           "mbu_pct": None}
    dev = jax.devices()[0]
    bw = (tpu_peaks(dev.device_kind)["hbm_bytes_per_sec"]
          if dev.platform == "tpu" else None)
    if bw and tokens_per_sec:
        steps_per_sec = tokens_per_sec / slots
        out["mbu_pct"] = round(
            100.0 * bytes_per_step * steps_per_sec / bw, 2)
    return out


def _post(base_url: str, body: dict, timeout: float):
    """(status, parsed_json, retry_after_s) — errors surface as status;
    network-level failures (timeout, refused, reset) as status 0, so a
    client thread never dies and every request lands in exactly one of
    n_ok / n_shed / n_failed."""
    req = urllib.request.Request(
        base_url + "/v1/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), 0.0
    except urllib.error.HTTPError as e:
        retry = float(e.headers.get("Retry-After") or 1.0)
        with contextlib.suppress(Exception):
            e.read()
        return e.code, None, retry
    except OSError:       # URLError, socket timeout, connection reset
        return 0, None, 0.0


class _Client(threading.Thread):
    """One closed-loop client: request → wait for answer → next."""

    def __init__(self, base_url, reqs, timeout, max_retries):
        super().__init__(daemon=True)
        self.base_url, self.reqs = base_url, reqs
        self.timeout, self.max_retries = timeout, max_retries
        self.latencies, self.gen_tokens = [], 0
        self.sheds = self.failures = 0

    @_loadgen_role
    def run(self):
        for prompt, max_new in self.reqs:
            body = {"prompt": prompt, "max_new": max_new}
            for _ in range(self.max_retries):
                t0 = time.perf_counter()
                status, obj, retry_after = _post(
                    self.base_url, body, self.timeout)
                if status == 200:
                    self.latencies.append(time.perf_counter() - t0)
                    self.gen_tokens += len(obj["tokens"]) - len(prompt)
                    break
                if status == 429:
                    self.sheds += 1
                    time.sleep(retry_after)
                    continue
                self.failures += 1
                break
            else:
                self.failures += 1


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    return sorted_vals[min(len(sorted_vals) - 1,
                           int(q * (len(sorted_vals) - 1) + 0.5))]


def _prom_sample(text: str, name: str) -> float:
    """One unlabeled sample value from a Prometheus text body (0.0
    when absent — external gateways may run older builds)."""
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


def _scrape(base_url: str) -> str:
    try:
        with urllib.request.urlopen(base_url + "/metrics",
                                    timeout=10) as r:
            return r.read().decode()
    except OSError:
        return ""


def _histogram_mean_ms(text: str, name: str, base: str = "") -> float:
    """Mean in ms of a cumulative histogram, optionally net of an
    earlier scrape ``base`` (isolates the timed window)."""
    count = (_prom_sample(text, f"{name}_count")
             - _prom_sample(base, f"{name}_count"))
    total = (_prom_sample(text, f"{name}_sum")
             - _prom_sample(base, f"{name}_sum"))
    return round(1e3 * total / count, 3) if count > 0 else 0.0


def _run_closed_loop(base_url, clients, requests_per_client,
                     prompt_range, new_range, vocab, seed, timeout):
    """Warmup + the closed-loop client fleet against ``base_url``;
    returns the latency/throughput record fields plus the gateway's own
    /metrics-derived TTFT / inter-token means and overlap ratio."""
    # Warmup: ONE request through the full path compiles every program
    # (prefill bucket + decode chunk) before the timed window.
    status, obj, _ = _post(base_url,
                           {"prompt": [1, 2, 3], "max_new": 4}, timeout)
    if status != 200:
        raise RuntimeError(f"warmup request failed with HTTP {status}")
    prom_base = _scrape(base_url)

    workers = [
        _Client(base_url,
                _requests_for(c, requests_per_client, *prompt_range,
                              *new_range, vocab, seed), timeout,
                max_retries=100)
        for c in range(clients)]
    t0 = time.perf_counter()
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    dt = time.perf_counter() - t0

    lats = sorted(l for w in workers for l in w.latencies)
    gen = sum(w.gen_tokens for w in workers)
    sheds = sum(w.sheds for w in workers)
    failures = sum(w.failures for w in workers)
    attempts = len(lats) + sheds + failures
    # TTFT / inter-token come from the gateway's own histograms (the
    # driver observes them chunk-granularly; a closed-loop client
    # cannot see first-token timing without streaming every request).
    # Histograms are cumulative since gateway start, so the means diff
    # the scrape taken before the fleet — the warmup request's
    # compile-laden TTFT never pollutes the numbers.
    prom = _scrape(base_url)
    return {
        "tokens_per_sec": round(gen / dt, 1) if dt else 0.0,
        "wall_s": round(dt, 3),
        "p50_latency_ms": round(1e3 * _percentile(lats, 0.50), 1),
        "p99_latency_ms": round(1e3 * _percentile(lats, 0.99), 1),
        "ttft_ms_mean": _histogram_mean_ms(
            prom, "ttd_gateway_ttft_seconds", prom_base),
        "inter_token_ms_mean": _histogram_mean_ms(
            prom, "ttd_gateway_inter_token_seconds", prom_base),
        "overlap_ratio": _prom_sample(prom, "ttd_engine_overlap_ratio"),
        "device_starved_s": round(
            _prom_sample(prom, "ttd_engine_device_starved_seconds")
            - _prom_sample(prom_base,
                           "ttd_engine_device_starved_seconds"), 4),
        "shed_rate": round(sheds / attempts, 4) if attempts else 0.0,
        "n_ok": len(lats),
        "n_shed": sheds,
        "n_failed": failures,
        "gen_tokens": gen,
    }


def bench_gateway(base_url, preset, slots, chunk, max_queue, clients,
                  requests_per_client, prompt_range, new_range,
                  cache_len, seed, timeout, replicas=1):
    loop_args = (clients, requests_per_client, prompt_range, new_range)

    def finish(rec):
        rec.update({
            "metric": f"{preset}_gateway_tokens_per_sec",
            "value": rec.pop("tokens_per_sec"),
            "unit": "generated tokens/sec",
            "clients": clients,
            "requests_per_client": requests_per_client,
            "slots": slots,
            "chunk": chunk,
            "max_queue": max_queue,
            "replicas": replicas,
        })
        return rec

    if base_url:
        # External gateway: its engine is whatever it was launched
        # with.
        vocab = 30_000       # conservative id ceiling
        return finish(_run_closed_loop(base_url, *loop_args, vocab,
                                       seed, timeout))

    import jax
    import jax.numpy as jnp

    from tensorflow_train_distributed_tpu.models.llama import (
        LLAMA_PRESETS, LlamaModel,
    )
    from tensorflow_train_distributed_tpu.server import ServingGateway
    from tensorflow_train_distributed_tpu.serving import ServingEngine

    cfg = LLAMA_PRESETS[preset]
    vocab = min(cfg.vocab_size, 30_000)
    params = LlamaModel(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]

    engines = [ServingEngine(cfg, params, slots=slots, chunk=chunk,
                             cache_len=cache_len)
               for _ in range(replicas)]
    gw = ServingGateway(engines if replicas > 1 else engines[0],
                        host="127.0.0.1", port=0,
                        max_queue=max_queue).start()
    try:
        rec = finish(_run_closed_loop(f"http://127.0.0.1:{gw.port}",
                                      *loop_args, vocab, seed, timeout))
    finally:
        gw.drain(timeout=30)
    dev = jax.devices()[0]
    rec["backend"] = dev.platform
    rec["device_kind"] = dev.device_kind
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    rows = cache_len or cfg.max_positions
    rec.update(decode_mbu_fields(cfg, n_params, slots, rows,
                                 rec["value"]))
    return rec


def _recovery_gap_ms(pool, kill, prompt, max_new, reps, timeout):
    """Failover-recovery latency: ONE streaming request; after its
    first committed chunk, ``kill(replica)`` murders the replica
    serving it; the headline is the widest inter-chunk gap the CLIENT
    observed — the failover hole (death detection + re-placement +
    resume prefill).  Median over ``reps`` runs."""
    gaps = []
    for _ in range(reps):
        h = pool.submit(list(prompt), max_new, stream=True,
                        timeout_s=timeout)
        it = h.iter_tokens()
        next(it)                          # first chunk: placed, decoding
        rep = pool._requests[h.id].replica
        t_kill = time.perf_counter()
        kill(rep)
        prev, worst = t_kill, 0.0
        for _chunk in it:
            now = time.perf_counter()
            worst = max(worst, now - prev)
            prev = now
        gaps.append(worst)
    gaps.sort()
    return round(1e3 * gaps[len(gaps) // 2], 1)


def bench_gateway_procs_ab(preset, slots, chunk, max_queue, clients,
                           requests_per_client, prompt_range,
                           new_range, cache_len, seed, timeout,
                           replicas=2, reps=3):
    """Out-of-process vs in-process replicas, one workload: two
    gateways (N in-process engine replicas; N subprocess workers built
    from the same preset/init seed) serve identical closed-loop client
    fleets as leg-order-alternating BACK-TO-BACK PAIRS — the headline
    wall ratio is the MEDIAN of per-pair ratios (the established
    noise discipline), with tok/s and the gateway-observed TTFT per
    leg.  A separate leg measures FAILOVER-RECOVERY latency on each
    pool: a streaming request's replica is killed after its first
    chunk (a real SIGKILL for the subprocess pool, the in-process
    kill9 vanish fault for the other) and the widest client-observed
    inter-chunk gap — death detection + re-placement + resume — is
    the recovery hole."""
    import jax
    import jax.numpy as jnp

    from tensorflow_train_distributed_tpu.models.llama import (
        LLAMA_PRESETS, LlamaModel,
    )
    from tensorflow_train_distributed_tpu.runtime import faults
    from tensorflow_train_distributed_tpu.server import (
        ProcPool, ServingGateway, WorkerSpec,
    )
    from tensorflow_train_distributed_tpu.serving import ServingEngine

    cfg = LLAMA_PRESETS[preset]
    vocab = min(cfg.vocab_size, 30_000)
    cache_len = cache_len or min(256, cfg.max_positions)
    params = LlamaModel(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    loop_args = (clients, requests_per_client, prompt_range, new_range)

    engines = [ServingEngine(cfg, params, slots=slots, chunk=chunk,
                             cache_len=cache_len)
               for _ in range(replicas)]
    for e in engines:                      # warm: compile before timing
        e.submit([1, 2, 3], 5)
        e.run()
    gw_in = ServingGateway(engines, host="127.0.0.1", port=0,
                           max_queue=max_queue).start()
    spec = WorkerSpec(
        factory="llama",
        factory_json=dict(preset=preset, init_seed=0, slots=slots,
                          chunk=chunk, cache_len=cache_len))
    pool = ProcPool(spec, replicas=replicas, max_queue=max_queue,
                    monitor_poll_s=0.02, restart_backoff_s=0.05)
    gw_proc = ServingGateway(pool, host="127.0.0.1", port=0).start()
    urls = {"in_process": f"http://127.0.0.1:{gw_in.port}",
            "procs": f"http://127.0.0.1:{gw_proc.port}"}
    try:
        if not pool.wait_ready(timeout=600.0):
            raise RuntimeError("subprocess workers failed to come up")
        best = {}
        ratios = []
        for i in range(max(1, reps)):
            walls = {}
            order = (("in_process", "procs") if i % 2 == 0
                     else ("procs", "in_process"))
            for leg in order:
                rec = _run_closed_loop(urls[leg], *loop_args, vocab,
                                       seed, timeout)
                walls[leg] = rec["wall_s"]
                if (leg not in best
                        or rec["wall_s"] < best[leg]["wall_s"]):
                    best[leg] = rec
            ratios.append(walls["procs"] / walls["in_process"])
        ratios.sort()

        # Failover-recovery legs (after the timed pairs: they kill
        # replicas).  Subprocess pool first — its scaler respawns the
        # corpse; the in-process pool uses a fresh third gateway so
        # the timed one above stays clean for the record's tok/s.
        rec_prompt = [1, 2, 3, 4]
        rec_new = max(64, new_range[1])
        import os as _os
        import signal as _signal

        recovery = {"procs": _recovery_gap_ms(
            pool, lambda rep: _os.kill(rep.driver.pid, _signal.SIGKILL),
            rec_prompt, rec_new, reps, timeout)}

        gaps = []
        for _ in range(reps):
            # A fresh pool per run: in-process replicas never
            # resurrect, so each kill9 spends one for good (the
            # subprocess pool above respawns its own corpses).  The
            # kill9 vanish fault is the in-process analog of SIGKILL,
            # armed after the first chunk, scoped to the replica
            # serving the stream — same measurement loop as the
            # subprocess leg, different kill.
            eng3 = [ServingEngine(cfg, params, slots=slots,
                                  chunk=chunk, cache_len=cache_len)
                    for _ in range(replicas)]
            for e in eng3:
                e.submit([1, 2, 3], 5)
                e.run()
            gw3 = ServingGateway(eng3, host="127.0.0.1", port=0,
                                 max_queue=max_queue).start()
            try:
                gaps.append(_recovery_gap_ms(
                    gw3.pool,
                    lambda rep: faults.arm(
                        f"serve:dispatch:1:kill9:replica={rep.idx}"),
                    rec_prompt, rec_new, 1, timeout))
            finally:
                faults.disarm()
                gw3.drain(timeout=30)
        gaps.sort()
        recovery["in_process"] = gaps[len(gaps) // 2]
    finally:
        gw_proc.drain(timeout=60)
        gw_in.drain(timeout=30)
    dev = jax.devices()[0]
    rec = {
        "metric": f"{preset}_gateway_proc_replicas_tokens_per_sec",
        "value": best["procs"]["tokens_per_sec"],
        "unit": "generated tokens/sec, subprocess workers "
                "(wall_ratio_median: procs/in-process, median of "
                "per-pair wall ratios)",
        "replicas": replicas,
        "slots": slots,
        "chunk": chunk,
        "cache_len": cache_len,
        "clients": clients,
        "requests_per_client": requests_per_client,
        "max_queue": max_queue,
        "reps": reps,
        "procs": best["procs"],
        "in_process": best["in_process"],
        "wall_ratio_median": round(ratios[len(ratios) // 2], 3),
        "pair_wall_ratios": [round(r, 4) for r in ratios],
        "failover_recovery_ms": recovery,
        "worker_restarts": pool.restarts_total(),
        "backend": dev.platform,
        "device_kind": dev.device_kind,
    }
    return rec


def bench_gateway_disagg_ab(preset, slots, chunk, max_queue, clients,
                            requests_per_client, prompt_range,
                            new_range, cache_len, seed, timeout,
                            decode_workers=2, reps=3):
    """Disaggregated vs co-located TCP fleets, one workload: two
    ``NetPool`` gateways — one behind a 1-prefill + N-decode role
    split, one behind N+1 role-``both`` workers — serve identical
    closed-loop client fleets (long-prompt-heavy, so placements cross
    the KV-block threshold and actually hand off) as
    leg-order-alternating BACK-TO-BACK PAIRS; the headline wall ratio
    is the MEDIAN of per-pair ratios (the established noise
    discipline).  The disagg legs also scrape the gateway's own
    handoff counters: ``handoff_bytes_per_request`` and the handoff
    count — the transfer tax the ratio is buying placement freedom
    with.  Workers are real ``tools/serve_worker.py`` daemons pinned
    to the CPU backend (same-host A/B — the harness measures the
    protocol + routing overhead, not cross-host bandwidth)."""
    import subprocess

    import jax

    from tensorflow_train_distributed_tpu.models.llama import (
        LLAMA_PRESETS,
    )
    from tensorflow_train_distributed_tpu.server import (
        NetPool, ServingGateway,
    )

    cfg = LLAMA_PRESETS[preset]
    vocab = min(cfg.vocab_size, 30_000)
    cache_len = cache_len or (prompt_range[1] + new_range[1] + 24)
    buckets = [8, 16, 32, 48]
    while buckets[-1] < prompt_range[1]:
        buckets.append(buckets[-1] * 2)
    loop_args = (clients, requests_per_client, prompt_range, new_range)
    spec_json = json.dumps(dict(preset=preset, init_seed=0,
                                slots=slots, chunk=chunk,
                                cache_len=cache_len,
                                prompt_buckets=buckets))
    here = os.path.dirname(os.path.abspath(__file__))

    def fleet(roles):
        pool = NetPool(host="127.0.0.1", port=0, scale_min=len(roles),
                       max_workers=len(roles), max_queue=max_queue,
                       monitor_poll_s=0.02)
        gw = ServingGateway(pool, host="127.0.0.1", port=0).start()
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        procs = [subprocess.Popen(
            [sys.executable, os.path.join(here, "serve_worker.py"),
             "--dial", f"127.0.0.1:{pool.port}",
             "--factory", "llama", "--json", spec_json,
             "--replica-id", str(i), "--role", role],
            cwd=os.path.dirname(here), env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
            for i, role in enumerate(roles)]
        return pool, gw, procs

    pool_d, gw_d, procs_d = fleet(
        ["prefill"] + ["decode"] * decode_workers)
    pool_c, gw_c, procs_c = fleet(["both"] * (decode_workers + 1))
    urls = {"disagg": f"http://127.0.0.1:{gw_d.port}",
            "colocated": f"http://127.0.0.1:{gw_c.port}"}
    try:
        for pool, what in ((pool_d, "disagg"), (pool_c, "colocated")):
            if not pool.wait_ready(timeout=600.0):
                raise RuntimeError(f"{what} workers failed to come up")
        if pool_d.workers_by_role() != {"prefill": 1,
                                        "decode": decode_workers}:
            raise RuntimeError("disagg fleet lost its role split")
        best = {}
        ratios = []
        handoffs_total = 0
        handoff_bytes_total = 0
        disagg_ok_total = 0
        for i in range(max(1, reps)):
            walls = {}
            order = (("disagg", "colocated") if i % 2 == 0
                     else ("colocated", "disagg"))
            for leg in order:
                base = _scrape(urls[leg])
                rec = _run_closed_loop(urls[leg], *loop_args, vocab,
                                       seed, timeout)
                prom = _scrape(urls[leg])
                if leg == "disagg":
                    # The transfer tax, from the gateway's own
                    # counters: bytes shipped per completed request
                    # and how many placements actually handed off.
                    rec["handoffs"] = int(
                        _prom_sample(prom,
                                     "ttd_gateway_handoff_seconds"
                                     "_count")
                        - _prom_sample(base,
                                       "ttd_gateway_handoff_seconds"
                                       "_count"))
                    handoffs_total += rec["handoffs"]
                    leg_bytes = int(
                        _prom_sample(prom,
                                     "ttd_gateway_handoff_bytes"
                                     "_total")
                        - _prom_sample(base,
                                       "ttd_gateway_handoff_bytes"
                                       "_total"))
                    handoff_bytes_total += leg_bytes
                    disagg_ok_total += rec["n_ok"]
                    rec["handoff_bytes_per_request"] = round(
                        leg_bytes / max(1, rec["n_ok"]), 1)
                walls[leg] = rec["wall_s"]
                if (leg not in best
                        or rec["wall_s"] < best[leg]["wall_s"]):
                    best[leg] = rec
            ratios.append(walls["disagg"] / walls["colocated"])
        ratios.sort()
        if handoffs_total == 0:
            raise RuntimeError(
                "disagg legs never handed off — the workload's "
                "prompts all fit one KV block; widen --prompt-range")
    finally:
        gw_d.drain(timeout=60)
        gw_c.drain(timeout=60)
        for proc in procs_d + procs_c:
            try:
                proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=15)
    dev = jax.devices()[0]
    return {
        "metric": f"{preset}_gateway_disagg_tokens_per_sec",
        "value": best["disagg"]["tokens_per_sec"],
        "unit": "generated tokens/sec, disaggregated prefill/decode "
                "TCP fleet (wall_ratio_median: disagg/colocated, "
                "median of per-pair wall ratios)",
        "prefill_workers": 1,
        "decode_workers": decode_workers,
        "colocated_workers": decode_workers + 1,
        "slots": slots,
        "chunk": chunk,
        "cache_len": cache_len,
        "prompt_buckets": buckets,
        "clients": clients,
        "requests_per_client": requests_per_client,
        "max_queue": max_queue,
        "reps": reps,
        "disagg": best["disagg"],
        "colocated": best["colocated"],
        "wall_ratio_median": round(ratios[len(ratios) // 2], 3),
        "pair_wall_ratios": [round(r, 4) for r in ratios],
        "handoffs_total": handoffs_total,
        # Aggregated over ALL disagg legs: later legs ride warm
        # prefix caches and hand off less, so a per-leg number from
        # the best (warmest) leg would underreport the transfer tax.
        "handoff_bytes_per_request": round(
            handoff_bytes_total / max(1, disagg_ok_total), 1),
        "backend": dev.platform,
        "device_kind": dev.device_kind,
    }


def bench_gateway_migrate_drain_ab(preset, slots, chunk, max_queue,
                                   cache_len, seed, timeout,
                                   replicas=2, streams=4, max_new=64,
                                   reps=5):
    """Drain-with-migration vs drain-by-failover, one workload: a
    replica serving live streams must go away (the staged-SIGTERM /
    scale-down story).  Leg A evacuates it — every lane live-migrates
    (KV rows shipped, decode resumes warm on the survivor); leg B
    kills it (the in-process kill9 vanish, SIGKILL semantics) so the
    same streams resume via failover re-prefill.  Both legs run as
    leg-order-alternating BACK-TO-BACK PAIRS on fresh gateways; the
    headline is the p99 of the widest client-observed inter-chunk gap
    across the victim's streams — the resume hole — and the MEDIAN of
    per-pair p99 ratios (migrate/failover), with the migrated KV
    bytes per moved request pulled from the flight recorder."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from tensorflow_train_distributed_tpu.models.llama import (
        LLAMA_PRESETS, LlamaModel,
    )
    from tensorflow_train_distributed_tpu.runtime import events, faults
    from tensorflow_train_distributed_tpu.server import ServingGateway
    from tensorflow_train_distributed_tpu.serving import ServingEngine

    cfg = LLAMA_PRESETS[preset]
    vocab = min(cfg.vocab_size, 30_000)
    cache_len = cache_len or min(256, cfg.max_positions)
    params = LlamaModel(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(seed)
    # One prompt shape for every stream and both legs: long enough
    # that a lane holds full KV blocks by disruption time (its export
    # ships real rows), max_new deep enough that every stream is
    # provably mid-generation when the replica goes away.
    prompts = [[int(t) for t in rng.integers(1, vocab, 24)]
               for _ in range(streams)]

    def one_leg(mode):
        engines = [ServingEngine(cfg, params, slots=slots, chunk=chunk,
                                 cache_len=cache_len)
                   for _ in range(replicas)]
        for e in engines:                  # warm: compile off the clock
            e.submit([1, 2, 3], 5)
            e.run()
        gw = ServingGateway(engines, host="127.0.0.1", port=0,
                            max_queue=max_queue).start()
        pool = gw.pool
        rec_ = events.get_recorder()
        cursor, _ = rec_.events_after(0)
        arrivals = [[] for _ in range(streams)]
        first = [threading.Event() for _ in range(streams)]
        errs = [None] * streams

        def consume(i, it):
            try:
                for _chunk in it:
                    arrivals[i].append(time.perf_counter())
                    first[i].set()
            except (RuntimeError, TimeoutError) as e:
                errs[i] = e
            finally:
                first[i].set()
        try:
            handles = [pool.submit(list(p), max_new, stream=True,
                                   timeout_s=timeout) for p in prompts]
            threads = [threading.Thread(
                target=consume, args=(i, h.iter_tokens()), daemon=True)
                for i, h in enumerate(handles)]
            for t in threads:
                t.start()
            for ev in first:
                if not ev.wait(timeout):
                    raise RuntimeError("stream never produced a chunk")
            if any(errs):
                raise RuntimeError(f"stream died pre-kill: {errs}")
            victim = pool._requests[handles[0].id].replica
            affected = [i for i, h in enumerate(handles)
                        if pool._requests[h.id].replica is victim]
            t0 = time.perf_counter()
            if mode == "migrate":
                pool._evacuate(victim)
                victim.driver.drain()
            else:
                faults.arm("serve:dispatch:1:kill9:"
                           f"replica={victim.idx}")
            for t in threads:
                t.join(timeout)
                if t.is_alive():
                    raise RuntimeError(f"{mode} leg: stream wedged")
            if any(errs):
                raise RuntimeError(f"{mode} leg stream error: {errs}")
            # The resume hole per affected stream: the widest
            # inter-chunk gap the CLIENT saw from the disruption on
            # (same shape as the failover-recovery leg of
            # --replica-procs).
            gaps = []
            for i in affected:
                prev, worst = t0, 0.0
                for ts in arrivals[i]:
                    if ts <= t0:
                        continue
                    worst = max(worst, ts - prev)
                    prev = ts
                gaps.append(1e3 * worst)
            gaps.sort()
            _, evs = rec_.events_after(cursor)
            moves = [e[5] for e in evs if e[0] == "request/migrate"]
            return {"p99_ms": round(_percentile(gaps, 0.99), 1),
                    "gaps_ms": [round(g, 1) for g in gaps],
                    "lanes_moved": len(moves),
                    "kv_bytes": sum(m.get("bytes", 0) for m in moves)}
        finally:
            faults.disarm()
            gw.drain(timeout=60)

    legs = {"migrate": [], "failover": []}
    ratios = []
    for i in range(max(1, reps)):
        order = (("migrate", "failover") if i % 2 == 0
                 else ("failover", "migrate"))
        pair = {}
        for leg in order:
            pair[leg] = one_leg(leg)
            legs[leg].append(pair[leg])
        ratios.append(max(1e-3, pair["migrate"]["p99_ms"])
                      / max(1e-3, pair["failover"]["p99_ms"]))
    ratios.sort()

    def med(leg):
        vals = sorted(r["p99_ms"] for r in legs[leg])
        return vals[len(vals) // 2]

    moved = sum(r["lanes_moved"] for r in legs["migrate"])
    kv_bytes = sum(r["kv_bytes"] for r in legs["migrate"])
    dev = jax.devices()[0]
    return {
        "metric": f"{preset}_gateway_migrate_drain_p99_resume_ms",
        "value": med("migrate"),
        "unit": "ms p99 client-observed resume gap, drain WITH live "
                "migration (p99_ratio_median: migrate/failover, "
                "median of per-pair p99 ratios)",
        "replicas": replicas,
        "slots": slots,
        "chunk": chunk,
        "cache_len": cache_len,
        "streams": streams,
        "max_new": max_new,
        "reps": reps,
        "migrate": {
            "p99_resume_ms_median": med("migrate"),
            "per_pair_p99_ms": [r["p99_ms"] for r in legs["migrate"]],
            "lanes_moved_total": moved,
            "kv_bytes_total": kv_bytes,
            "kv_bytes_per_migrated_request": (
                round(kv_bytes / moved) if moved else 0),
        },
        "failover": {
            "p99_resume_ms_median": med("failover"),
            "per_pair_p99_ms": [r["p99_ms"] for r in legs["failover"]],
        },
        "p99_ratio_median": round(ratios[len(ratios) // 2], 3),
        "pair_p99_ratios": [round(r, 4) for r in ratios],
        "backend": dev.platform,
        "device_kind": dev.device_kind,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--base-url", default="",
                   help="target an externally launched gateway instead "
                        "of building one in-process")
    p.add_argument("--preset", default="llama_tiny",
                   help="llama preset for the in-process gateway "
                        "(random-init weights — a THROUGHPUT/latency "
                        "harness, not a quality one)")
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--chunk", type=int, default=8)
    p.add_argument("--replicas", type=int, default=1,
                   help="engine replicas behind the in-process gateway "
                        "(load + KV-affinity routed; ignored with "
                        "--base-url)")
    p.add_argument("--replica-procs", action="store_true",
                   help="A/B subprocess replica workers "
                        "(server.procpool) against in-process "
                        "replicas on the same closed-loop workload: "
                        "tok/s + TTFT per leg, the median of per-pair "
                        "wall ratios, and a failover-recovery-latency "
                        "leg (real SIGKILL vs the in-process kill9 "
                        "vanish) — in-process runs only; uses "
                        "--replicas (min 2) workers per leg")
    p.add_argument("--disagg", action="store_true",
                   help="A/B a DISAGGREGATED TCP fleet (1 prefill + "
                        "--replicas decode serve_worker daemons, KV "
                        "handoff on long prompts) against a co-located "
                        "fleet of the same worker count on the same "
                        "closed-loop workload: tok/s + TTFT per leg, "
                        "the median of per-pair wall ratios, and the "
                        "gateway-scraped handoff bytes/request "
                        "(in-process runs only; CPU-pinned workers)")
    p.add_argument("--migrate-drain", action="store_true",
                   help="A/B draining a live replica WITH lane "
                        "migration (evacuation: KV shipped, decode "
                        "resumes warm) against drain-by-failover "
                        "(kill9 vanish: streams re-prefill on the "
                        "survivor) on fresh in-process gateways: "
                        "p99 client-observed resume gap per leg, the "
                        "median of per-pair p99 ratios, and migrated "
                        "KV bytes per moved request (in-process runs "
                        "only; uses --replicas, min 2)")
    p.add_argument("--max-queue", type=int, default=16)
    p.add_argument("--clients", type=int, default=8)
    p.add_argument("--requests-per-client", type=int, default=8)
    p.add_argument("--prompt-range", default="4,24",
                   help="lo,hi inclusive prompt lengths")
    p.add_argument("--new-range", default="8,32",
                   help="lo,hi inclusive max_new_tokens")
    p.add_argument("--cache-len", type=int, default=0,
                   help="0 -> config.max_positions")
    p.add_argument("--timeout", type=float, default=300.0,
                   help="client-side HTTP timeout per request")
    p.add_argument("--reps", type=int, default=3,
                   help="--replica-procs: back-to-back A/B pairs "
                        "(median of per-pair wall ratios)")
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
    if args.replica_procs and args.base_url:
        raise SystemExit("--replica-procs builds its own A/B gateways "
                         "in-process; it does not compose with "
                         "--base-url")
    if args.disagg and (args.base_url or args.replica_procs):
        raise SystemExit("--disagg builds its own A/B fleets "
                         "in-process; it composes with neither "
                         "--base-url nor --replica-procs")
    if args.migrate_drain and (args.base_url or args.replica_procs
                               or args.disagg):
        raise SystemExit("--migrate-drain builds its own A/B gateways "
                         "in-process; it composes with none of "
                         "--base-url, --replica-procs, --disagg")
    try:
        if args.migrate_drain:
            rec = bench_gateway_migrate_drain_ab(
                args.preset, args.slots, args.chunk,
                args.max_queue, args.cache_len or None,
                args.seed, args.timeout,
                replicas=max(2, args.replicas),
                reps=args.reps)
        elif args.disagg:
            rec = bench_gateway_disagg_ab(
                args.preset, args.slots, args.chunk,
                args.max_queue, args.clients,
                args.requests_per_client, prompt_range, new_range,
                args.cache_len or None, args.seed, args.timeout,
                decode_workers=max(2, args.replicas),
                reps=args.reps)
        elif args.replica_procs:
            rec = bench_gateway_procs_ab(
                args.preset, args.slots, args.chunk,
                args.max_queue, args.clients,
                args.requests_per_client, prompt_range, new_range,
                args.cache_len or None, args.seed, args.timeout,
                replicas=max(2, args.replicas),
                reps=args.reps)
        else:
            rec = bench_gateway(
                args.base_url, args.preset, args.slots, args.chunk,
                args.max_queue, args.clients,
                args.requests_per_client,
                prompt_range, new_range, args.cache_len or None,
                args.seed, args.timeout,
                replicas=max(1, args.replicas))
    except Exception as e:
        print(json.dumps({
            "metric": f"{args.preset}_gateway_tokens_per_sec",
            "value": 0.0, "unit": "generated tokens/sec",
            "error": f"{type(e).__name__}: {e}"}), flush=True)
        return 1
    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
