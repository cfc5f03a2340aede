"""Online HTTP serving gateway over the continuous-batching engine.

The ONLINE face of ``serving.ServingEngine`` — where ``tools/serve.py``
collects every request up front and exits when the batch finishes, this
launcher keeps the engine decoding while a threaded HTTP frontend
(``tensorflow_train_distributed_tpu.server``) accepts, sheds, streams,
and times out requests concurrently:

- ``POST /v1/generate``  {"prompt": [ids], "max_new": N, "seed": S?,
  "stream": bool?, "timeout_s": F?} → {"id", "prompt", "tokens"}
  (tokens = prompt + continuation, byte-identical to serve.py on the
  same requests); ``stream`` chunks tokens as they commit (NDJSON).
- ``GET /healthz``  liveness + occupancy (503 while draining).
- ``GET /metrics``  Prometheus text: request/token counters, queue
  depth, slot occupancy (decoding + prefilling lanes), TTFT /
  inter-token / latency histograms, the engine's overlap ratio,
  ``ttd_engine_device_starved_seconds`` (seconds the engine left the
  device with an empty queue while it had work pending), and
  the paged-KV cache economics: ``ttd_engine_kv_blocks_in_use`` /
  ``ttd_engine_kv_blocks_total`` (admission is block-keyed by
  default), ``ttd_engine_prefix_hit_tokens_total`` (prefill skipped
  via cross-request prefix sharing) and
  ``ttd_engine_kv_evictions_total``.

Robustness: admission queue bounded at ``--max-queue`` (beyond it: 429
with Retry-After), per-request deadlines (``--default-timeout`` /
per-request ``timeout_s`` → 504, slot freed), request-size and vocab
validation (``check_vocab_ids`` — same screens as serve.py), graceful
drain on SIGTERM/SIGINT (stop admitting, finish in-flight, flush
metrics).  With ``--replicas N`` the gateway fronts N independent
engine replicas (load + KV-affinity routing, per-replica health and
``--watchdog-timeout`` hung-dispatch detection in ``/healthz``,
deterministic failover that resumes a dead replica's requests on
survivors from their last streamed token, staged ``--drain-timeout``
drain); 503 only when NO replica can accept work.  With ≥2 replicas
the staged drain MIGRATES each draining replica's live lanes to
survivors first (KV blocks + decode state over ``MIGRATE`` frames —
no re-prefill, no stream interruption; ``TTD_NO_MIGRATION=1``
restores wait-then-drain), and ``/healthz`` reports each draining
replica's ``lanes_remaining``.  Model/engine flags
are shared with serve.py (``add_engine_args``), so both CLIs configure
every replica identically.

Examples:
  python tools/serve_http.py --config llama_tiny_sft \\
      --checkpoint-dir /ck --port 8000 --slots 8
  curl -s localhost:8000/v1/generate -d '{"prompt": [1,2,3], "max_new": 16}'
  curl -s localhost:8000/metrics | grep ttd_gateway
"""

import argparse
import logging
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))  # repo root (the package)
sys.path.insert(0, _HERE)                   # tools/ siblings

from sample import (  # noqa: E402 (tools/ sibling)
    check_vocab_ids,
    resolve_decoder_task,
)
from serve import (  # noqa: E402 (tools/ sibling)
    add_engine_args,
    build_engine,
    maybe_dense_moe_hint,
    parse_prefix_arg,
)


def make_vocab_validator(vocab_size: int):
    """check_vocab_ids wears SystemExit (the CLI convention); the
    gateway needs a 400, so rewrap — one shared screen either way."""
    from tensorflow_train_distributed_tpu.server import RequestError

    def _validate(prompt, max_new, seed):
        try:
            check_vocab_ids([[int(t) for t in prompt]], vocab_size)
        except SystemExit as e:
            raise RequestError(str(e))

    return _validate


def _serve_procs(args, cfg) -> int:
    """The out-of-process gateway: N subprocess workers behind a
    ``ProcPool``, each rebuilding the engine from THIS CLI's serialized
    flags (``serve.worker_engine_factory``), so parent screening and
    worker engines agree.  A worker SIGKILL/OOM/native crash fails one
    replica (classified in /healthz) and its requests resume on a
    survivor; the pool scales between --scale-min/--scale-max and
    respawns dead workers under --restart-budget."""
    from tensorflow_train_distributed_tpu.server import (
        ProcPool,
        ServingGateway,
        WorkerSpec,
    )

    spec = WorkerSpec(
        factory="serve:worker_engine_factory",
        factory_json=dict(vars(args)),
        pythonpath=(_HERE,),
    )
    scale_min = args.scale_min or args.replicas
    scale_max = max(args.scale_max or args.replicas, scale_min)
    pool = ProcPool(
        spec, replicas=args.replicas, scale_min=scale_min,
        scale_max=scale_max, max_queue=args.max_queue,
        validate=make_vocab_validator(cfg.vocab_size),
        default_timeout_s=args.default_timeout or None,
        retry_after_s=args.retry_after,
        watchdog_timeout_s=args.watchdog_timeout or None,
        idle_grace_s=args.idle_grace,
        max_restarts=args.restart_budget)
    gw = ServingGateway(pool, host=args.host, port=args.port,
                        default_max_new=args.max_new)
    gw.install_signal_handlers(drain_timeout=args.drain_timeout or None)
    gw.start()
    # Advertise the port only once every worker finished its handshake
    # (engine built + warm in the child) — the warm-up analog.
    print(f"waiting for {args.replicas} subprocess workers...",
          flush=True)
    if not pool.wait_ready(timeout=600.0):
        print("workers failed to come up inside 600s; draining",
              flush=True)
        gw.drain(timeout=30)
        return 1
    print(f"gateway listening on {args.host}:{gw.port} "
          f"(config={args.config}, replica-procs={args.replicas}, "
          f"scale=[{scale_min},{scale_max}], slots={args.slots}, "
          f"max_queue={args.max_queue})", flush=True)
    gw.wait()           # until SIGTERM/SIGINT drains
    return 0


def _serve_net(args, cfg) -> int:
    """The multi-host gateway: a ``NetPool`` listens on ``--listen``
    and standalone worker daemons (``tools/serve_worker.py``, any
    machine) dial in, HELLO their ``--role``, and become replicas.
    Dedicated prefill workers stage prompts and hand finished KV to
    decode workers over binary KV_HANDOFF frames (disaggregated
    serving; ``TTD_NO_DISAGG=1`` collapses the role split, workers
    stay connected).  Engine flags on THIS CLI only drive gateway-side
    screening — each worker builds its engine from its OWN flags."""
    from tensorflow_train_distributed_tpu.server import (
        NetPool,
        ServingGateway,
    )

    lhost, sep, lport = args.listen.rpartition(":")
    if not sep or not lport.isdigit():
        raise SystemExit(f"--listen wants HOST:PORT, got {args.listen!r}")
    scale_min = args.scale_min or args.replicas
    max_workers = max(args.scale_max or args.replicas, scale_min)
    pool = NetPool(
        host=lhost or "0.0.0.0", port=int(lport),
        scale_min=scale_min, max_workers=max_workers,
        max_queue=args.max_queue,
        validate=make_vocab_validator(cfg.vocab_size),
        default_timeout_s=args.default_timeout or None,
        retry_after_s=args.retry_after,
        watchdog_timeout_s=args.watchdog_timeout or None,
        max_restarts=args.restart_budget)
    gw = ServingGateway(pool, host=args.host, port=args.port,
                        default_max_new=args.max_new)
    gw.install_signal_handlers(drain_timeout=args.drain_timeout or None)
    gw.start()
    print(f"worker listener on {lhost or '0.0.0.0'}:{pool.port}; "
          f"waiting for {scale_min} dial-in workers...", flush=True)
    if not pool.wait_ready(timeout=600.0):
        print("workers failed to dial in inside 600s; draining",
              flush=True)
        gw.drain(timeout=30)
        return 1
    print(f"gateway listening on {args.host}:{gw.port} "
          f"(config={args.config}, dial-in workers, "
          f"scale_min={scale_min}, max_workers={max_workers}, "
          f"max_queue={args.max_queue})", flush=True)
    gw.wait()           # until SIGTERM/SIGINT drains
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    add_engine_args(p)
    p.add_argument("--host", default="0.0.0.0",
                   help="bind address (default: all interfaces)")
    p.add_argument("--port", type=int, default=8000,
                   help="0 = ephemeral (printed at startup)")
    p.add_argument("--max-queue", type=int, default=64,
                   help="admission bound: requests WAITING for a slot "
                        "beyond this are shed with 429 + Retry-After")
    p.add_argument("--default-timeout", type=float, default=0.0,
                   help="per-request deadline in seconds when the body "
                        "carries no timeout_s (0 = none); an expired "
                        "request answers 504 and frees its slot")
    p.add_argument("--retry-after", type=float, default=1.0,
                   help="Retry-After seconds on shed (429) responses")
    p.add_argument("--replicas", type=int, default=1,
                   help="engine replicas behind the gateway: with N>1 "
                        "admissions route by load + KV-prefix affinity, "
                        "each replica has its own health/watchdog, and "
                        "a request whose replica dies resumes on a "
                        "survivor from its last streamed token "
                        "(TTD_NO_FAILOVER=1 forces the single-engine "
                        "path)")
    p.add_argument("--listen", default="", metavar="HOST:PORT",
                   help="multi-host serving: listen here for "
                        "tools/serve_worker.py daemons to DIAL IN as "
                        "replicas (same frame protocol as "
                        "--replica-procs, across machines; workers "
                        "declare --role prefill|decode|both for "
                        "disaggregated prefill→decode KV handoff; "
                        "--replicas/--scale-min is the dial-in floor "
                        "wait_ready blocks on, --scale-max the fleet "
                        "cap; TTD_NO_DISAGG=1 collapses the role "
                        "split)")
    p.add_argument("--replica-procs", action="store_true",
                   help="run each replica as a SUBPROCESS worker "
                        "(server.procpool) speaking the length-prefixed "
                        "driver protocol: a replica OOM/native crash/"
                        "SIGKILL fails one worker, never the gateway, "
                        "and the pool scales elastically between "
                        "--scale-min/--scale-max "
                        "(TTD_NO_PROC_REPLICAS=1 falls back to "
                        "in-process replicas)")
    p.add_argument("--scale-min", type=int, default=0,
                   help="--replica-procs: never drain below this many "
                        "workers (0 = --replicas); dead workers are "
                        "respawned toward it under --restart-budget")
    p.add_argument("--scale-max", type=int, default=0,
                   help="--replica-procs: spawn up to this many workers "
                        "under queue pressure (0 = --replicas — no "
                        "scale-up)")
    p.add_argument("--restart-budget", type=int, default=8,
                   help="--replica-procs: total dead-worker respawns "
                        "before the pool stops resurrecting (a crash-"
                        "looping engine must not fork-bomb); respawns "
                        "back off exponentially")
    p.add_argument("--idle-grace", type=float, default=30.0,
                   help="--replica-procs: seconds of whole-pool idle "
                        "before ONE scale-up worker is drained back "
                        "(staged, never below --scale-min)")
    p.add_argument("--watchdog-timeout", type=float, default=30.0,
                   help="seconds a decode dispatch may run before the "
                        "replica is declared dead (hung-device "
                        "detection; 0 disables — size it above "
                        "worst-case XLA compile time or warm up first)")
    p.add_argument("--drain-timeout", type=float, default=0.0,
                   help="bound on the SIGTERM drain (replicas drain "
                        "one at a time; 0 = wait indefinitely)")
    return p


def build_gateway(args, cfg, is_moe, prefix_ids):
    """The in-process serving stack for parsed ``args``: checkpoint →
    engine(s) → unstarted ``ServingGateway``.  ``main`` starts it and
    waits on signals; ``chip_smoke.py`` starts it, drives it over HTTP
    and drains it, all in the one process that holds the chip."""
    from tensorflow_train_distributed_tpu.server import ServingGateway

    # One engine per replica, configured identically (each builds its
    # own caches and preloads the prefix into its own pool — replica
    # state stays fully independent so any one can die alone).  They
    # all land on the process's default (first) device: in-process
    # replicas share one chip, they do not spread over several.
    engines = [build_engine(args, cfg, is_moe, prefix_ids)
               for _ in range(args.replicas)]
    # Online: request lengths are unknowable at startup, so a dense-
    # dispatch MoE always gets the compile-storm warning.
    maybe_dense_moe_hint(engines[0])
    if args.replicas > 1:
        # Warm every replica before taking traffic: the decode program
        # (and one prefill shape) compiles now, so the first user
        # request is fast on every replica and the pool's
        # hung-dispatch watchdog never has to stare down a cold
        # compile (it additionally only arms after a replica's first
        # completed step).
        for i, eng in enumerate(engines):
            print(f"warming replica {i}...", flush=True)
            eng.submit([1], 1)
            eng.run()

    return ServingGateway(
        engines if args.replicas > 1 else engines[0],
        host=args.host, port=args.port, max_queue=args.max_queue,
        default_timeout_s=args.default_timeout or None,
        default_max_new=args.max_new,
        validate=make_vocab_validator(cfg.vocab_size),
        retry_after_s=args.retry_after,
        watchdog_timeout_s=args.watchdog_timeout or None)


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.replicas < 1:
        raise SystemExit(f"--replicas must be >= 1, got {args.replicas}")

    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    from tensorflow_train_distributed_tpu.runtime import compile_cache

    compile_cache.place_compile_cache()
    if args.platform:
        from tensorflow_train_distributed_tpu.runtime.mesh import (
            force_platform,
        )

        force_platform(args.platform)

    _, cfg, is_moe = resolve_decoder_task(args.config, "serving")
    prefix_ids = parse_prefix_arg(args, cfg)

    if args.listen:
        return _serve_net(args, cfg)
    if args.replica_procs:
        from tensorflow_train_distributed_tpu.server.procpool import (
            proc_replicas_killed,
        )

        if proc_replicas_killed():
            print("TTD_NO_PROC_REPLICAS=1: subprocess replicas "
                  "disabled, falling back to in-process replicas",
                  flush=True)
            args.replica_procs = False
    if args.replica_procs:
        return _serve_procs(args, cfg)
    gw = build_gateway(args, cfg, is_moe, prefix_ids)
    gw.install_signal_handlers(
        drain_timeout=args.drain_timeout or None)
    gw.start()
    print(f"gateway listening on {args.host}:{gw.port} "
          f"(config={args.config}, replicas={args.replicas}, "
          f"slots={args.slots}, max_queue={args.max_queue})", flush=True)
    gw.wait()           # until SIGTERM/SIGINT drains
    return 0


if __name__ == "__main__":
    sys.exit(main())
