"""Sweep ResNet bench configs × XLA flags on the live chip.

Each point runs ``bench.py`` in a fresh subprocess (XLA/libtpu flags only
apply at backend init) and records images/sec/chip.  Used to pick the
batch size and libtpu flags for the headline benchmark.

Usage: python tools/sweep_resnet.py [--quick]
"""

import argparse
import itertools
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BATCHES = [192, 256, 320, 384, 512]
FLAG_SETS = {
    "default": "",
    # Bigger scoped-vmem budget lets the fusion engine keep deeper
    # (BN-stat + elementwise) fusions resident; MaxText ships 81920.
    "vmem80m": "--xla_tpu_scoped_vmem_limit_kib=81920",
    "vmem112m": "--xla_tpu_scoped_vmem_limit_kib=114688",
}


def run_point(batch: int, flags: str, iters: int, config: str):
    env = dict(os.environ)
    if flags:
        env["LIBTPU_INIT_ARGS"] = flags
    cmd = [sys.executable, os.path.join(REPO, "bench.py"),
           "--configs", config, "--batch-per-chip", str(batch),
           "--iters", str(iters), "--families", "resnet"]
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         timeout=900, cwd=REPO)
    for line in reversed(out.stdout.strip().splitlines()):
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        return rec.get("value", 0.0), rec.get("error")
    return 0.0, f"no JSON (rc={out.returncode}): {out.stderr[-200:]!r}"


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true",
                   help="batch 256 only, default+vmem80m flags")
    p.add_argument("--iters", type=int, default=30)
    p.add_argument("--config", default="resnet50_s2d")
    args = p.parse_args()

    batches = [256] if args.quick else BATCHES
    flag_sets = ({k: FLAG_SETS[k] for k in ("default", "vmem80m")}
                 if args.quick else FLAG_SETS)
    results = {}
    for batch, (fname, flags) in itertools.product(batches,
                                                   flag_sets.items()):
        value, err = run_point(batch, flags, args.iters, args.config)
        key = f"b{batch}/{fname}"
        results[key] = value
        print(f"{key}: {value} img/s/chip"
              + (f"  ERROR: {err}" if err else ""), flush=True)
    best = max(results, key=results.get)
    print(json.dumps({"best": best, "value": results[best],
                      "sweep": results}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
