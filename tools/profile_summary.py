"""Summarize a jax.profiler trace: per-op-category device time per step.

The trace-reading half of the evidence pipeline: bench.py (and
``--profile-dir`` on the CLI) capture XPlane traces; this tool aggregates
the device plane's ``XLA Ops`` line into op-kind buckets (conv/matmul
fusions, BN statistics, converts, elementwise, copies, ...) so "where does
the step time go" is one command, not a notebook session.

Parses the raw ``xplane.pb`` with TensorFlow's bundled proto (same XPlane
stack the reference's profiler writes — SURVEY.md §5.1); no
tensorboard-plugin needed (its converter is binary-incompatible with the
installed TF in this env).

Usage:
  python tools/profile_summary.py profiles/bench/resnet50_s2d [--top 12]
  (positional arg: a trace dir containing plugins/profile/*/...xplane.pb,
   or a direct path to one .pb file)
"""

from __future__ import annotations

import argparse
import collections
import glob
import os
import re
import sys


def find_xplane(path: str) -> str:
    if path.endswith(".pb"):
        return path
    hits = sorted(glob.glob(
        os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not hits:
        raise SystemExit(f"no *.xplane.pb under {path}")
    return hits[-1]  # newest capture


def classify(name: str) -> str:
    """HLO op name → coarse category."""
    m = re.match(r"%([a-z-]+)", name)
    kind = m.group(1) if m else "other"
    if kind == "fusion":
        if "convolution" in name or re.search(r"\bconv", name):
            return "fusion:conv"
        if re.search(r"= \(f32\[\d+\]", name):
            return "fusion:reduce-stats"   # BN-style per-channel stats
        if re.search(r"= (bf16|f32|f16)\[[\d,]+\]", name):
            return "fusion:elementwise"
        return "fusion:other"
    if kind == "convert":
        return "convert(+fused reduce)"
    if kind in ("copy-start", "copy-done", "copy"):
        return "copy"
    if kind in ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all"):
        return "collective"
    if kind == "custom-call":
        return "custom-call (pallas/libtpu)"
    return kind


def summarize(pb_path: str, top: int = 12):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    xs = xplane_pb2.XSpace()
    with open(pb_path, "rb") as f:
        xs.ParseFromString(f.read())
    devices = [p for p in xs.planes
               if p.name.startswith("/device:") and p.lines]
    if not devices:
        raise SystemExit(f"{pb_path}: no device plane with events")
    out = []
    for plane in devices:
        md = plane.event_metadata
        steps_line = next((ln for ln in plane.lines if ln.name == "Steps"),
                          None)
        n_steps = max(len(steps_line.events), 1) if steps_line else 1
        ops_line = next((ln for ln in plane.lines if ln.name == "XLA Ops"),
                        None)
        if ops_line is None:
            continue
        agg = collections.Counter()
        cnt = collections.Counter()
        for ev in ops_line.events:
            cat = classify(md[ev.metadata_id].name)
            agg[cat] += ev.duration_ps
            cnt[cat] += 1
        total = sum(agg.values())
        rows = [(ps / 1e9 / n_steps, 100 * ps / total, cnt[c] // n_steps, c)
                for c, ps in agg.most_common(top)]
        out.append((plane.name, n_steps, total / 1e9 / n_steps, rows))
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("trace", help="trace dir or .xplane.pb file")
    p.add_argument("--top", type=int, default=12)
    args = p.parse_args(argv)
    pb = find_xplane(args.trace)
    print(f"# {pb}")
    for name, n_steps, ms_per_step, rows in summarize(pb, args.top):
        print(f"\n== {name}: {n_steps} steps, {ms_per_step:.2f} ms/step "
              "(XLA Ops line)")
        print(f"{'ms/step':>9}  {'share':>6}  {'ops/step':>8}  category")
        for ms, pct, n, cat in rows:
            print(f"{ms:9.2f}  {pct:5.1f}%  {n:8d}  {cat}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
