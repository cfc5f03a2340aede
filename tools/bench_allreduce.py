"""Allreduce bus-bandwidth microbenchmark (a driver headline metric).

Measures the two collective paths the framework owns:

- **device**: XLA allreduce over the mesh's data axis (ICI on TPU) via
  ``parallel.collectives.allreduce_bus_bandwidth`` — the TPU-native
  equivalent of the reference's NCCL allreduce benchmark (NCCL busBW
  convention: ``2(k-1)/k · bytes/time``), directly comparable to
  ``nccl-tests`` numbers.
- **host** (``--host``): the native C++ TCP ring (``native/ringcoll``) over
  N localhost processes — the DCN/host-side fallback path.

Prints one JSON line per measurement, driver-style.

Usage::

    python tools/bench_allreduce.py                  # device path, real mesh
    python tools/bench_allreduce.py --size-mb 256
    python tools/bench_allreduce.py --host --world 4
    python tools/bench_allreduce.py --platform cpu --cpu-devices 8
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def bench_device(size_mb: float, iters: int, quant: str = "none") -> dict:
    import jax

    from tensorflow_train_distributed_tpu.parallel import collectives
    from tensorflow_train_distributed_tpu.runtime.mesh import (
        MeshConfig, build_mesh,
    )

    mesh = build_mesh(MeshConfig(data=-1))
    r = collectives.allreduce_bus_bandwidth(mesh, "data", size_mb=size_mb,
                                            iters=iters, quant=quant)
    out = {
        "metric": ("allreduce_bus_bandwidth_device" if quant == "none"
                   else "allreduce_bus_bandwidth_device_q8"),
        "value": round(r["bus_bandwidth_gbps"], 3),
        "unit": "GB/s",
        "devices": r["devices"],
        "message_bytes": r["message_bytes"],
        "backend": jax.default_backend(),
        "wire": r["wire"],
    }
    if "wire_bytes" in r:
        # Effective-f32 convention: the figure counts payload reduced,
        # wire_bytes the int8+scales actually moved (~4x less).
        out["wire_bytes"] = r["wire_bytes"]
    return out


def _host_worker(rank: int, world: int, peers: list[str], size_mb: float,
                 iters: int, algo: str, q) -> None:
    import time

    import numpy as np

    from tensorflow_train_distributed_tpu.native.ringcoll import (
        HostMesh, HostRing,
    )

    n = int(size_mb * 1e6 / 4)
    if algo == "ring":
        group = HostRing(rank, peers, timeout_ms=20_000)
        reduce_fn = group.allreduce
    elif algo == "ring_q8":
        # EQuARX-style quantized ring: int8+scales on the wire (~4x less
        # traffic).  bus_gbps reports EFFECTIVE f32 bandwidth (payload
        # reduced per second), so the win shows as a higher number — ON A
        # REAL NETWORK.  Measured on this 1-core box (loopback wire at
        # memory speed, all ranks sharing one core): 0.27 vs 0.42 GB/s —
        # the quantize/dequant CPU work is the bottleneck, not the wire.
        # The crossover: q8 wins when per-rank wire bandwidth is below
        # the per-core quant throughput (~1-2 GB/s) — cross-datacenter /
        # oversubscribed DCN, exactly the path this ring serves.
        group = HostRing(rank, peers, timeout_ms=20_000)
        reduce_fn = group.allreduce_q8
    else:
        group = HostMesh(rank, peers, timeout_ms=20_000)
        reduce_fn = lambda x: group.allreduce(x, algorithm=algo)  # noqa: E731
    x = np.ones(n, np.float32)
    reduce_fn(x)  # warmup
    t0 = time.perf_counter()
    for _ in range(iters):
        reduce_fn(x)
    dt = (time.perf_counter() - t0) / iters
    group.close()
    if rank == 0:
        bus = 2 * (world - 1) / world * n * 4 / dt
        q.put({"time_s": dt, "bus_gbps": bus / 1e9})


def bench_host(world: int, size_mb: float, iters: int,
               algo: str = "ring") -> dict:
    import multiprocessing as mp
    import queue as queue_mod

    from tensorflow_train_distributed_tpu.testing.multiprocess import (
        free_ports,
    )

    peers = [f"127.0.0.1:{p}" for p in free_ports(world)]
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [
        ctx.Process(target=_host_worker,
                    args=(r, world, peers, size_mb, iters, algo, q))
        for r in range(world)
    ]
    for p in procs:
        p.start()
    try:
        import time

        deadline = time.monotonic() + 120
        result = None
        while result is None:
            try:
                result = q.get(timeout=2)
            except queue_mod.Empty:
                failed = {p.name: p.exitcode for p in procs if p.exitcode}
                if failed:
                    raise RuntimeError(
                        f"{algo} workers exited nonzero before producing a "
                        f"result (e.g. a port race on setup): {failed}"
                    ) from None
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"host {algo} benchmark timed out after 120 s with "
                        "no result and no worker failure") from None
        for p in procs:
            p.join(timeout=30)
        failed = {p.name: p.exitcode for p in procs if p.exitcode}
        if failed:
            raise RuntimeError(f"{algo} workers exited nonzero: {failed}")
    finally:
        for p in procs:
            if p.is_alive():
                p.terminate()
            p.join(timeout=5)
    return {
        "metric": f"allreduce_bus_bandwidth_host_{algo}",
        "value": round(result["bus_gbps"], 3),
        "unit": "GB/s",
        "devices": world,
        "message_bytes": int(size_mb * 1e6),
        "backend": f"tcp_{algo}",
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--size-mb", type=float, default=64.0)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--host", action="store_true",
                   help="benchmark the native TCP ring instead of the "
                        "device mesh")
    p.add_argument("--world", type=int, default=4,
                   help="with --host: number of ring processes")
    p.add_argument("--algo", default="ring",
                   choices=["ring", "ring_q8", "hd", "shuffle"],
                   help="with --host: allreduce algorithm (ring is "
                        "bandwidth-optimal, hd latency-optimal, shuffle "
                        "single-hop; hd/shuffle need power-of-2 world)")
    p.add_argument("--quant", default="none", choices=["none", "int8"],
                   help="device path: benchmark the int8-wire quantized "
                        "allreduce (the trainer's grad-quant comm "
                        "program) instead of the exact f32 psum; the "
                        "figure stays EFFECTIVE f32 bandwidth, so the "
                        "~4x wire saving shows wherever the fabric is "
                        "the bottleneck (the host analog is --algo "
                        "ring_q8)")
    p.add_argument("--platform", default=None, choices=["cpu", "tpu"])
    p.add_argument("--cpu-devices", type=int, default=None)
    args = p.parse_args(argv)

    from tensorflow_train_distributed_tpu.runtime import compile_cache

    compile_cache.place_compile_cache()
    if args.platform or args.cpu_devices:
        from tensorflow_train_distributed_tpu.runtime.mesh import (
            force_platform,
        )

        force_platform(args.platform, args.cpu_devices)

    if args.host:
        if args.algo != "ring" and args.world & (args.world - 1):
            p.error(f"--algo {args.algo} requires a power-of-2 --world, "
                    f"got {args.world} (use --algo ring)")
        out = bench_host(args.world, args.size_mb, args.iters, args.algo)
    else:
        out = bench_device(args.size_mb, args.iters, args.quant)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
