#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process.  It finds the cell in ``BENCHMARK.json``, the cell's
configuration, traffic mix and per-layer metric readers in files of
their own (``benchmark/harness/manifest.py`` says where), makes inputs
and weights from ``--seed``, warms the cell's shapes (set-up), measures
for ``--seconds`` and checks what the timed path produced against the
plain reference.  Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.

The LAST line of standard output is the result: one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device`` and, traced, ``breakdown``.  Every earlier line is a JSON
object too (phases, medians beside tails, each number compared beside
its limit) and is for people; a traced run's last one before the result
is ``{"phase": "reduce", ...}``, what the capture cost after the window
(``reduce_capture``).
"""

from __future__ import annotations

import time

T_START = time.monotonic()      # process start, as near as Python gets

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

def _log(**record) -> None:
    print(json.dumps(record, default=_plain), flush=True)


def _plain(obj):
    try:
        return float(obj)
    except (TypeError, ValueError):
        return repr(obj)


def place_compile_cache() -> str:
    """JAX's persistent compilation cache, at a fixed place inside the
    checkout: the program's own ``runtime.compile_cache`` decides it
    (where ``JAX_COMPILATION_CACHE_DIR`` says if that is set, else
    ``<checkout>/.jax_cache``), so benchmark and program agree and no
    second piece of code sets a directory.  Every program is cached,
    however quick its compile, so a second run compiles nothing."""
    import jax

    from tensorflow_train_distributed_tpu.runtime import compile_cache

    path = compile_cache.place_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Tracer:
    """A profiler capture of part of the window, into a directory of
    the checkout that is emptied first."""

    def __init__(self, directory: str):
        self.directory = directory
        self.t0 = self.t1 = self.stop_s = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        jax.profiler.start_trace(self.directory)
        self.t0 = time.monotonic()

    def stop(self) -> None:
        import jax

        self.t1 = time.monotonic()
        jax.profiler.stop_trace()       # collects and writes the capture
        self.stop_s = time.monotonic() - self.t1


def reduce_capture(ctx: dict, per_layer, reader_of) -> tuple:
    """``(metrics, breakdown, device's busy_s and window_s)`` of a traced
    run, from the capture ``ctx["tracer"]`` wrote: the cell's
    ``per_layer`` entries through their readers (``reader_of(name)``),
    the operations that took most device time and the idle gaps by what
    the host was doing.

    Logs what that cost as ``{"phase": "reduce", ...}``: seconds of the
    capture's stop and export (inside the window), of loading it, of
    each reader by name, of ``top_ops`` and of ``attribute_gaps``, with
    the sizes they grow with: idle gaps, host events, device
    operations (PERF.md §7 has the table by cell), so that a run which
    is stopped at its time limit can be read from its log."""
    from benchmark.harness import trace as trace_lib

    tracer = ctx["tracer"]

    def timed(fn, *args, **kwargs):
        t0 = time.monotonic()
        value = fn(*args, **kwargs)
        return value, time.monotonic() - t0

    tr, load_s = timed(trace_lib.load_xplane,
                       trace_lib.find_xplane(tracer.directory))
    win = trace_lib.window(tr)
    if win is None:
        raise RuntimeError("the trace shows no device operation")
    ctx.update(trace=tr, trace_window=win)
    seen = {"busy_s": trace_lib.mean_busy_seconds(tr, *win),
            "window_s": win[1] - win[0]}
    metrics, readers_s = {}, {}
    for m in per_layer:
        value, readers_s[m["name"]] = timed(reader_of(m["name"]), ctx)
        if value is None:
            continue          # nothing to read in this cell
        if m["unit"] == "%" and value > 105.0:
            raise RuntimeError(
                f"{m['name']} reads {value:.1f}%: the count or the "
                f"time is wrong")
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    top, top_ops_s = timed(trace_lib.top_ops, tr, 10)
    idle, attribute_gaps_s = timed(trace_lib.attribute_gaps, tr, *win, n=10)
    ctx["log"](phase="reduce", stop_export_s=tracer.stop_s, load_s=load_s,
               readers_s=readers_s, top_ops_s=top_ops_s,
               attribute_gaps_s=attribute_gaps_s,
               idle_gaps=len(trace_lib.idle_gaps(tr.devices[0], *win)),
               host_events=len(tr.host),
               device_ops=sum(len(d.ops) for d in tr.devices))
    return metrics, {"device_ops": top, "idle_gaps": idle}, seen


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # For the builder's own readings of the lower-precision control
    # (PERF.md); the driver never passes it.
    p.add_argument("--control", default="",
                   help="run the program's lower-precision path instead "
                        "(int8): the run must come out not correct")
    p.add_argument("--rate-per-s", type=float, default=None,
                   help="open loop: offer this rate instead of the "
                        "traffic file's (the one-off sweep for the knee)")
    return p


def main(argv=None, *, root: str = REPO, require_platform="tpu",
         runners=None) -> int:
    args = build_parser().parse_args(argv)

    from benchmark.harness import manifest as manifest_lib

    man = manifest_lib.Manifest(root)
    cell = man.workload(args.workload)
    cfg_file = man.config(cell["config"])
    traffic = man.traffic(cell["traffic"])
    if args.rate_per_s is not None:
        traffic["arrivals"] = dict(traffic["arrivals"],
                                   rate_per_s=args.rate_per_s)

    import jax

    cache_dir = place_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if require_platform and platform != require_platform:
        print(f"benchmark: found platform {platform!r}, need "
              f"{require_platform!r}; no result", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"benchmark: cell needs {cell['chips']} chips, found "
              f"{len(devices)}; no result", file=sys.stderr)
        return 2
    devices = devices[:cell["chips"]]
    kind = devices[0].device_kind

    from benchmark.harness.compiles import Compiles
    from benchmark.harness.peaks import peaks_for

    peaks = peaks_for(kind) if platform == "tpu" else None
    compiles = Compiles()
    _log(phase="start", workload=args.workload, seed=args.seed,
         seconds=args.seconds, trace=args.trace, control=args.control,
         platform=platform, kind=kind, chips=cell["chips"],
         compile_cache=cache_dir)

    # The runner kind is a key of the traffic file, never the cell's
    # name: ``benchmark/harness/<kind>.py`` with a ``run(ctx)``.
    kind_name = traffic["kind"]
    if runners is not None:
        runner = runners[kind_name]
    else:
        import importlib
        import re

        if not re.fullmatch(r"[a-z_]+", kind_name):
            raise ValueError(f"bad runner kind {kind_name!r}")
        runner = importlib.import_module(
            f"benchmark.harness.{kind_name}").run

    setup = {}

    def window_opened(t_open: float) -> None:
        setup["setup_s"] = t_open - T_START
        setup["compile_s"] = compiles.total_s()
        setup["compiles"] = len(compiles.events)
        _log(phase="setup", setup_s=setup["setup_s"],
             compile_s=setup["compile_s"], compiles=setup["compiles"],
             cache_hits=compiles.hits, cache_misses=compiles.misses)

    def annotate(name: str):
        if not args.trace:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(name)

    tracer = (Tracer(os.path.join(root, ".bench_trace", args.workload))
              if args.trace else None)
    ctx = {"cell": cell, "config": cfg_file, "traffic": traffic,
           "seed": args.seed, "seconds": float(args.seconds),
           "devices": devices, "chips": cell["chips"], "peaks": peaks,
           "compiles": compiles, "tracer": tracer, "annotate": annotate,
           "window_opened": window_opened, "control": args.control,
           "log": _log, "root": root}
    result = runner(ctx)

    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": result["memory_peak_bytes"]}
    out = {"correct": bool(result["correct"]),
           "attempted": int(result["attempted"]),
           "failed": int(result["failed"])}
    if not args.trace:
        values = dict(result["end_to_end"], setup_s=setup["setup_s"])
        metrics = {}
        for m in man.end_to_end_for(args.workload):
            if m["name"] not in values:
                raise RuntimeError(
                    f"cell {args.workload} did not measure "
                    f"{m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        ctx.update(result=result, setup=setup)
        metrics, out["breakdown"], seen = reduce_capture(
            ctx, man.per_layer_for(args.workload), man.layer_reader)
        device.update(seen)
    out["metrics"] = metrics
    out["device"] = device
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
