"""Render a flight-recorder dump: stage latencies + request waterfalls.

The offline face of ``runtime.events`` (the always-on span/instant ring
buffer), in the same spirit as ``tools/profile_summary.py`` for XPlane
captures: given a Chrome-trace JSON — fetched from a live gateway's
``GET /debug/trace?last_s=N`` or written by ``Recorder.save()`` — it
answers "where did the time go" (a per-stage latency table over span
names: count, mean, p50, p99, max; then an engine step by the stage
spans inside it, each with its SELF time, what no span names, and the
seconds a step the engine left the device starved or was away with its
caller) and "what happened to request X"
(``--request N``: that request's admission→prefill→decode→retire
waterfall, the offline twin of ``GET /v1/requests/<id>``).
``--requests`` lists every request id in the window with its terminal
status, and ``--journal supervisor.jsonl`` appends the supervisor's
attempt timeline so relaunches are part of the same report.

Usage:
  curl -s 'localhost:8000/debug/trace?last_s=300' > /tmp/trace.json
  python tools/trace_report.py /tmp/trace.json
  python tools/trace_report.py /tmp/trace.json --request 17
  python tools/trace_report.py /tmp/trace.json --requests \
      --journal /ckpt/supervisor.jsonl

Fleet observability (PR 20) adds two more faces:

- ``--fleet`` renders the CROSS-WORKER view of the same trace: the
  parent's ring already holds every proc/TCP worker's relayed events,
  offset-corrected by the PING/PONG clock sync and tagged
  ``replica=``/``clock_conf_s=`` — this groups them into per-replica
  lanes, prints each replica's clock-sync quality (from the export's
  ``otherData.fleet``), and measures every prefill→decode KV-handoff
  hop (``handoff/export`` span end → ``handoff/install`` span start)
  plus migration/failover hops.  With ``--request N`` the waterfall
  gains a lane column, so a disaggregated request reads top-to-bottom
  across the fleet.
- ``--post-mortem DIR`` reconstructs the last seconds before a death
  from a ``TTD_TRACE_SPOOL`` directory: each process's rotating JSONL
  segments (wall-anchored per segment header) joined with the parent's
  ``corpse-*.json`` snapshots (exit reason, clock offset, the last
  relayed events) and optionally ``--journal`` — the waterfall a
  SIGKILLed worker can no longer serve from ``/debug/trace``.

(The JSON itself also loads directly in Perfetto / chrome://tracing —
this tool is for terminals and incident notes.)
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import sys


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
    return sorted_vals[i]


def load_events(path: str) -> list:
    with open(path) as f:
        obj = json.load(f)
    evs = obj["traceEvents"] if isinstance(obj, dict) else obj
    if not isinstance(evs, list):
        raise SystemExit(f"{path}: not a Chrome trace (no traceEvents)")
    return evs


def load_other(path: str) -> dict:
    """The export's ``otherData`` (fleet states, roofline snapshot,
    spool status) — empty for bare event-array dumps."""
    with open(path) as f:
        obj = json.load(f)
    return dict(obj.get("otherData") or {}) if isinstance(obj, dict) \
        else {}


def stage_table(evs: list) -> list:
    """(name, count, total_ms, mean_ms, p50_ms, p99_ms, max_ms) per
    span name, busiest first."""
    durs = collections.defaultdict(list)
    for e in evs:
        if e.get("ph") == "X":
            durs[e["name"]].append(e.get("dur", 0.0) / 1e3)
    rows = []
    for name, ds in durs.items():
        ds.sort()
        total = sum(ds)
        rows.append((name, len(ds), total, total / len(ds),
                     _percentile(ds, 0.5), _percentile(ds, 0.99),
                     ds[-1]))
    rows.sort(key=lambda r: -r[2])
    return rows


def step_stages(evs: list) -> dict:
    """Where an engine step's host time goes, by the stage spans the
    engine records under ``engine/step``: every moment of a step
    belongs to the innermost span its thread had open (a span's SELF
    time), or to no span at all.  ``{"steps": n, "rows": [(name,
    self_mean_ms, self_p50_ms, self_p75_ms, steps_with_it)], busiest
    first, with ``(no span)`` among them, "starved_ms", "away_ms",
    "drains", "first_deferred", "span_ms"}``: the last five are sums of
    the steps' own counters (milliseconds the engine knew the device's
    queue empty while it had work, the caller's passes between steps,
    how often the queue was found empty, the prompts finished whose
    first token stayed on the device for a harvest to read) and of the
    steps' durations plus those passes; ``None`` for counters a trace
    predates.  The benchmark
    reads the same from the live ring (``benchmark/harness/
    step_stages.py``, which may not import this tool)."""
    by_thread = collections.defaultdict(list)
    steps = []
    for e in evs:
        if e.get("ph") != "X":
            continue
        key = (e.get("pid"), e.get("tid"))
        if e["name"] == "engine/step":
            steps.append((key, e["ts"], e.get("dur", 0.0),
                          e.get("args") or {}))
        else:
            by_thread[key].append((e["ts"], e.get("dur", 0.0), e["name"]))
    for inner in by_thread.values():
        inner.sort(key=lambda s: (s[0], -s[1]))
    own = collections.defaultdict(list)
    for key, t0, dur, _ in steps:
        end_of_step = t0 + dur
        mine = collections.Counter()
        stack, at = [], t0
        for k0, kdur, name in by_thread[key]:
            if not t0 <= k0 <= end_of_step:
                continue
            while stack and stack[-1][0] <= k0:
                end, top = stack.pop()
                mine[top] += max(end - at, 0.0)
                at = max(at, end)
            if k0 > at:
                mine[stack[-1][1] if stack else "(no span)"] += k0 - at
                at = k0
            stack.append((min(k0 + kdur, end_of_step), name))
        while stack:
            end, top = stack.pop()
            mine[top] += max(end - at, 0.0)
            at = max(at, end)
        mine["(no span)"] += max(end_of_step - at, 0.0)
        for name, us in mine.items():
            own[name].append(us / 1e3)
    n = len(steps)
    rows = []
    for name, ms in own.items():
        padded = sorted(ms + [0.0] * (n - len(ms)))
        rows.append((name, sum(ms) / n, _percentile(padded, 0.5),
                     _percentile(padded, 0.75),
                     sum(1 for v in ms if v > 0)))
    rows.sort(key=lambda r: -r[1])

    def total(attr):
        got = [a[attr] for _, _, _, a in steps if attr in a]
        return sum(got) if got else None

    away = total("away_ms")
    return {"steps": n, "rows": rows, "starved_ms": total("starved_ms"),
            "away_ms": away, "drains": total("drains"),
            "first_deferred": total("first_deferred"),
            "span_ms": sum(d for _, _, d, _ in steps) / 1e3 + (away or 0.0)}


def prefill_walk(evs: list) -> tuple:
    """(calls, rows walked, rows their caches have, rows a learned
    selection counted over) over the window's ``prefill/piece`` spans,
    one a call of one or several pieces, each read for its LAST piece:
    what that piece's attention read (``rows``, the engine's account by
    ``ops.attention.prefix_tiles_walked``) of a whole ``cache_len`` a
    piece (``cache_rows``), and what its choice of rows counted over
    to find its k-th score (``select_rows``, by ``ops.attention.
    select_tiles_counted``: 0 in a piece that keeps every row it sees,
    in a model with no selection and in a trace from before the
    attribute).  Spans from before the walk carry none and add
    nothing."""
    walked = [(a["rows"], a["cache_rows"], a.get("select_rows", 0))
              for a in (e.get("args") or {} for e in evs
                        if e.get("name") == "prefill/piece")
              if "rows" in a]
    return (len(walked), *(sum(w[i] for w in walked) for i in range(3)))


def rows_selected(evs: list) -> tuple:
    """(steps, rows attended, rows scored) over the window's
    ``engine/step`` spans of a program whose attention chooses its
    rows (``rows_selected`` of ``rows_scored``: means over the steps
    and layers of the decode chunk a step harvested, counted on the
    device over its live lanes).  Spans without them add nothing; a
    prefill piece walks every row its lane holds (``prefill_walk``)."""
    got = [(e["args"]["rows_selected"], e["args"]["rows_scored"])
           for e in evs if e.get("name") == "engine/step"
           and (e.get("args") or {}).get("rows_scored")]
    return (len(got), sum(s for s, _ in got), sum(r for _, r in got))


def instant_counts(evs: list) -> list:
    counts = collections.Counter(
        e["name"] for e in evs if e.get("ph") == "i")
    return counts.most_common()


def kv_cache_summary(evs: list) -> dict:
    """Paged-KV cache economics from the engine's flight-recorder
    events: ``kv/alloc`` spans land in the stage table like any other
    stage; this folds the instants' args into totals — prefix-hit
    count + tokens saved (prefill compute skipped), blocks evicted
    under pressure, and admissions refused for want of blocks — plus
    how many decode dispatches ran the FUSED paged-attention kernel
    (the ``decode/dispatch`` span's ``fused`` tag: the engine records
    at each dispatch whether its programs were compiled with
    ``ops.pallas_kernels.paged_attention`` or the XLA block-gather
    A/B leg), and the blocks that kernel's walk read over the window's
    dispatches against the whole block table (``engine/step``'s
    ``kv_blocks`` and ``kv_table_blocks``: the live share), and, where
    some layers see a sliding window, the blocks one such layer's walk
    of its rings read (``kv_window_blocks``: the window layers' share
    of what the lanes hold), and, where some layers keep a recurrent
    state and no rows, the bytes of state the steps' live lanes held
    beside the bytes of the rows walked (``state_bytes``, ``kv_bytes``,
    summed like the blocks).  Empty dict when the
    window has no paged-KV events."""
    out = {"prefix_hits": 0, "prefix_hit_tokens": 0,
           "evicted_blocks": 0, "refused_admissions": 0,
           "fused_attn_dispatches": 0, "kv_blocks": 0,
           "kv_table_blocks": 0, "kv_window_blocks": 0,
           "kv_bytes": 0, "state_bytes": 0}
    seen = False
    for e in evs:
        name = e.get("name", "")
        args = e.get("args") or {}
        if name == "decode/dispatch" and args.get("fused"):
            out["fused_attn_dispatches"] += 1
            seen = True
            continue
        if name == "engine/step" and args.get("kv_table_blocks"):
            # What the attention kernel's walk read at the step's
            # dispatch, of the slots x blocks-a-lane table it spans.
            out["kv_blocks"] += args.get("kv_blocks", 0)
            out["kv_table_blocks"] += args["kv_table_blocks"]
            out["kv_window_blocks"] += args.get("kv_window_blocks", 0)
            out["kv_bytes"] += args.get("kv_bytes", 0)
            out["state_bytes"] += args.get("state_bytes", 0)
            seen = True
            continue
        if not name.startswith("kv/"):
            continue
        seen = True
        if name == "kv/prefix_hit":
            out["prefix_hits"] += 1
            out["prefix_hit_tokens"] += args.get("tokens", 0)
        elif name == "kv/evict":
            out["evicted_blocks"] += args.get("blocks", 0)
        elif name == "kv/refused":
            out["refused_admissions"] += 1
    return out if seen else {}


def spec_depth_summary(evs: list) -> dict:
    """Speculative-depth timeline from the ``decode/dispatch`` spans'
    ``spec_k`` arg (the depth the engine chose for that round — the
    adaptive controller's decisions, or the constant ``--speculative-k``
    on a fixed engine).  Returns ``{}`` when no dispatch span carries
    ``spec_k`` (pre-adaptive trace).  ``segments`` collapses consecutive
    same-depth rounds into ``(start_ms_rel, depth, rounds)`` rows, so an
    oscillating controller is visible as a long segment list even when
    the per-depth totals look calm."""
    rounds = {}
    segments = []
    t0 = None
    for e in evs:
        if e.get("ph") != "X" or e.get("name") != "decode/dispatch":
            continue
        args = e.get("args") or {}
        if "spec_k" not in args:
            continue
        k = args["spec_k"]
        if t0 is None:
            t0 = e["ts"]
        rounds[k] = rounds.get(k, 0) + 1
        if segments and segments[-1][1] == k:
            segments[-1][2] += 1
        else:
            segments.append([(e["ts"] - t0) / 1e3, k, 1])
    if not rounds:
        return {}
    return {"rounds": rounds, "segments": segments,
            "switches": len(segments) - 1}


def migration_summary(evs: list) -> dict:
    """Live-migration economics from the pool's flight-recorder
    instants: every ``request/migrate`` hop (who moved where, at which
    token, how many KV bytes rode the MIGRATE frame), plus drain-time
    ``replica/evacuate`` and ``pool/defragment`` roll-ups — the
    "did the drain actually move my streams" answer next to the KV
    table's "did prefix caching engage".  Empty when the window has no
    migration events (single replica, or TTD_NO_MIGRATION=1)."""
    out = {"migrations": 0, "kv_bytes": 0, "warm_tokens": 0,
           "ms": [], "evacuations": 0, "evacuated_lanes": 0,
           "defrag_moves": 0, "hops": []}
    seen = False
    for e in evs:
        name = e.get("name", "")
        args = e.get("args") or {}
        if name == "request/migrate":
            seen = True
            out["migrations"] += 1
            out["kv_bytes"] += args.get("bytes", 0)
            out["warm_tokens"] += args.get("tokens", 0)
            out["ms"].append(args.get("ms", 0.0))
            out["hops"].append((args.get("request_id"),
                                args.get("from_replica"),
                                args.get("to_replica"),
                                args.get("resumed_at"),
                                args.get("bytes", 0)))
        elif name == "replica/evacuate":
            seen = True
            out["evacuations"] += 1
            out["evacuated_lanes"] += args.get("moved", 0)
        elif name == "pool/defragment":
            seen = True
            out["defrag_moves"] += args.get("moved", 0)
    return out if seen else {}


#: The trainer's step sub-spans (grad-quant split step) plus the parent
#: dispatch span — the denominator of the comm fraction.  The bucketed
#: overlap step adds ``train/step_barrier`` (the single host-blocking
#: point replacing the sequential pipeline's per-phase blocking).
_TRAIN_STEP_SPANS = ("train/step_dispatch", "train/grad_fwdbwd",
                     "train/grad_comm", "train/optimizer_apply",
                     "train/step_barrier")


def train_step_summary(evs: list) -> list:
    """Trainer step anatomy with a comm-fraction column.

    Under quantized gradient collectives the trainer's
    ``train/step_dispatch`` span splits into ``train/grad_fwdbwd`` /
    ``train/grad_comm`` / ``train/optimizer_apply`` sub-spans (each a
    blocking dispatch, so durations are device time).  This folds them
    into ``(span, count, total_ms, frac_of_step)`` rows where
    ``frac_of_step`` is the span's share of the step-dispatch total —
    the comm-fraction number the grad-quant A/B
    (``tools/bench_grad_quant.py``) is judged on, visible in any
    ``/debug/trace`` window.  Empty when the window has no grad-comm
    spans (unquantized trainer, or no training).

    Under the bucketed overlap step (``grad_overlap>1``) the comm/apply
    spans carry ``bucket=<i>, buckets=<K>`` attrs and meter DISPATCH
    time only — the blocking device wait collapses into the single
    ``train/step_barrier`` span, so the grad-comm fraction IS the
    realized-overlap number.  Bucket-tagged spans additionally break
    out as ``<span>[bucket=<i>]`` sub-rows under their total."""
    totals: dict = {}
    per_bucket: dict = {}
    for e in evs:
        name = e.get("name", "")
        if e.get("ph") != "X" or name not in _TRAIN_STEP_SPANS:
            continue
        row = totals.setdefault(name, [0, 0.0])
        row[0] += 1
        row[1] += e.get("dur", 0.0) / 1e3
        b = (e.get("args") or {}).get("bucket")
        if b is not None:
            brow = per_bucket.setdefault(name, {}).setdefault(
                int(b), [0, 0.0])
            brow[0] += 1
            brow[1] += e.get("dur", 0.0) / 1e3
    if "train/grad_comm" not in totals:
        return []
    step_ms = totals.get("train/step_dispatch", [0, 0.0])[1]
    if step_ms <= 0:        # engine-level runs without the fit loop
        step_ms = sum(ms for name, (_, ms) in totals.items()
                      if name != "train/step_barrier")
    rows = []
    for name in _TRAIN_STEP_SPANS:
        if name not in totals:
            continue
        n, ms = totals[name]
        rows.append((name, n, ms, (ms / step_ms if step_ms > 0 else 0.0)))
        for b in sorted(per_bucket.get(name, ())):
            bn, bms = per_bucket[name][b]
            rows.append((f"{name}[bucket={b}]", bn, bms,
                         (bms / step_ms if step_ms > 0 else 0.0)))
    return rows


def memory_summary(evs: list) -> dict:
    """Per-pool memory table from the memcheck sanitizer's
    ``memory/<pool>`` spans/instants (``TTD_MEMCHECK=1``): allocations
    charged, peak and last-seen live bytes, the declared budget with
    headroom %, and pre-raise near-misses (``memory/near_miss``
    instants past 90% of budget) — the "where is my HBM" answer the
    paged-KV and compile tables give for blocks and compiles.  Keyed
    by pool name; empty when the window has no memory events
    (sanitizer unarmed)."""
    pools: dict = {}
    for e in evs:
        name = e.get("name", "")
        args = e.get("args") or {}
        if name == "memory/near_miss":
            row = pools.setdefault(args.get("pool", "?"), {
                "allocs": 0, "peak_live": 0, "live": 0, "budget": 0,
                "near_misses": 0})
            row["near_misses"] += 1
            row["budget"] = max(row["budget"], args.get("budget", 0))
            continue
        if not name.startswith("memory/"):
            continue
        pool = args.get("pool") or name[len("memory/"):]
        row = pools.setdefault(pool, {
            "allocs": 0, "peak_live": 0, "live": 0, "budget": 0,
            "near_misses": 0})
        row["allocs"] += 1
        live = args.get("live", args.get("bytes", 0)) or 0
        row["peak_live"] = max(row["peak_live"], live)
        row["live"] = live                 # events are time-ordered
        row["budget"] = max(row["budget"], args.get("budget", 0))
    return pools


def compile_summary(evs: list) -> list:
    """Per-jit-site compilation table from the compilecheck sanitizer's
    ``compile/<site>`` spans (``TTD_COMPILECHECK=1``): how many
    signatures each site compiled in the window and what they cost —
    the "where did my decode step go" answer when the stall WAS a
    recompile.  Empty when the window has no compile spans (sanitizer
    unarmed, or a healthy steady state past warmup)."""
    per: dict = {}
    for e in evs:
        name = e.get("name", "")
        if e.get("ph") != "X" or not name.startswith("compile/"):
            continue
        site = name[len("compile/"):]
        row = per.setdefault(site, [0, 0.0])
        row[0] += 1
        row[1] += e.get("dur", 0.0) / 1e3
    return sorted(((site, n, ms) for site, (n, ms) in per.items()),
                  key=lambda r: -r[2])


def request_ids(evs: list) -> list:
    """(request_id, status) for every gateway request in the window
    (status from its retire instant; 'in-window' when none recorded)."""
    status: dict = {}
    for e in evs:
        args = e.get("args") or {}
        rid = args.get("request_id")
        if rid is None:
            continue
        if e["name"] == "request/retire":
            status[rid] = args.get("status", "?")
        else:
            status.setdefault(rid, "in-window")
    return sorted(status.items())


def request_waterfall(evs: list, request_id: int) -> list:
    """The request's events, driver + engine joined — the same
    latest-admission / rid-window rule as
    ``Recorder.request_timeline`` applied to exported JSON."""
    admit_t = None
    for e in evs:
        if (e["name"] == "request/admitted"
                and (e.get("args") or {}).get("request_id") == request_id):
            admit_t = e["ts"]
    rid = None
    grant_t = retire_t = None
    out = []
    for e in evs:
        args = e.get("args") or {}
        if (args.get("request_id") != request_id
                or (admit_t is not None and e["ts"] < admit_t)):
            continue
        out.append(e)
        if e["name"] == "request/engine_submit" and "rid" in args:
            rid, grant_t = args["rid"], e["ts"]
        if e["name"] == "request/retire":
            retire_t = e["ts"]
    if rid is not None:
        lo = grant_t - 1e3          # ts in microseconds; hi exact (the
        hi = retire_t if retire_t is not None else float("inf")
        #   retire follows every engine event of the request)
        for e in evs:
            args = e.get("args") or {}
            if ("request_id" not in args and args.get("rid") == rid
                    and lo <= e["ts"] <= hi):
                out.append(e)
    out.sort(key=lambda e: e["ts"])
    return out


def print_waterfall(evs: list, request_id: int) -> None:
    wf = request_waterfall(evs, request_id)
    if not wf:
        print(f"request {request_id}: no events in this window")
        return
    t0 = wf[0]["ts"]
    print(f"\n== request {request_id} waterfall "
          f"({len(wf)} events, t=0 at first event)")
    print(f"{'t_ms':>10}  {'dur_ms':>8}  event")
    for e in wf:
        args = dict(e.get("args") or {})
        args.pop("request_id", None)
        dur = f"{e['dur'] / 1e3:8.3f}" if "dur" in e else " " * 8
        extra = ("  " + " ".join(f"{k}={v}" for k, v in args.items())
                 if args else "")
        print(f"{(e['ts'] - t0) / 1e3:10.3f}  {dur}  {e['name']}{extra}")


def fleet_lanes(evs: list) -> dict:
    """Group events into per-replica lanes: ``replica`` from attrs
    (the relay stamps every worker event; pool pump threads stamp the
    parent's per-replica driver events), ``gateway`` for everything
    unstamped.  Each lane reports its event count, span of activity,
    and the worst clock-sync confidence seen (``clock_conf_s`` rides
    every relayed event — None means the lane never crossed a process
    boundary)."""
    lanes: dict = {}
    for e in evs:
        args = e.get("args") or {}
        lane = args.get("replica", "gateway")
        row = lanes.setdefault(str(lane), {
            "events": 0, "t_min": None, "t_max": None,
            "clock_conf_s": None, "relayed": 0})
        row["events"] += 1
        ts = e.get("ts", 0.0)
        row["t_min"] = ts if row["t_min"] is None else min(
            row["t_min"], ts)
        row["t_max"] = ts if row["t_max"] is None else max(
            row["t_max"], ts)
        conf = args.get("clock_conf_s")
        if conf is not None:
            row["relayed"] += 1
            if row["clock_conf_s"] is None or conf > row["clock_conf_s"]:
                row["clock_conf_s"] = conf
    return lanes


def fleet_hops(evs: list) -> list:
    """Every cross-worker hop in the window, measured:

    - ``kv_handoff``: the prefill→decode KV handoff — wire+install
      latency is the gap from the ``handoff/export`` span's END to the
      ``handoff/install`` span's START (both parent-recorded, one
      clock domain, positive by construction) for the same request;
    - ``migrate``: a live lane move (the instant's ``ms`` arg is the
      measured move time);
    - ``failover``: a re-admission on a survivor (no wire latency —
      the dead replica shipped nothing).

    Rows: (kind, request_id, from, to, hop_ms, detail)."""
    exports: dict = {}      # request_id -> (end_ts, prefill_replica)
    hops: list = []
    for e in evs:
        args = e.get("args") or {}
        rid = args.get("request_id")
        name = e.get("name", "")
        if name == "handoff/export" and e.get("ph") == "X":
            exports[rid] = (e["ts"] + e.get("dur", 0.0),
                            args.get("prefill_replica"))
        elif name == "handoff/install" and e.get("ph") == "X":
            exp = exports.get(rid)
            if exp is not None:
                hop_ms = (e["ts"] - exp[0]) / 1e3
                hops.append(("kv_handoff", rid, exp[1],
                             args.get("decode_replica"), hop_ms,
                             f"{args.get('bytes', 0)} bytes"))
        elif name == "request/kv_handoff":
            # Pre-span traces (or local installs): keep the terminal
            # instant visible even without a measured hop.
            if not any(h[0] == "kv_handoff" and h[1] == rid
                       for h in hops):
                hops.append(("kv_handoff", rid,
                             args.get("prefill_replica"),
                             args.get("decode_replica"), None,
                             f"{args.get('bytes', 0)} bytes"))
        elif name == "request/migrate":
            hops.append(("migrate", rid, args.get("from_replica"),
                         args.get("to_replica"), args.get("ms"),
                         f"{args.get('bytes', 0)} KV bytes, resumed at "
                         f"token {args.get('resumed_at')}"))
        elif name == "request/failover":
            hops.append(("failover", rid, args.get("from_replica"),
                         args.get("to_replica"), None,
                         f"resumed at token {args.get('resume_from')}"))
    return hops


def print_fleet(evs: list, other: dict,
                request_id: "int | None" = None) -> None:
    lanes = fleet_lanes(evs)
    print(f"\n== fleet view: {len(lanes)} lanes")
    states = {str(d.get("replica")): d for d in other.get("fleet", [])}
    print(f"  {'lane':>8}  {'events':>7}  {'relayed':>7}  "
          f"{'span_ms':>9}  {'clock_conf':>10}  state")
    for lane in sorted(lanes, key=lambda x: (x == "gateway", x)):
        row = lanes[lane]
        span_ms = ((row["t_max"] - row["t_min"]) / 1e3
                   if row["events"] else 0.0)
        conf = (f"±{row['clock_conf_s'] * 1e3:.2f}ms"
                if row["clock_conf_s"] is not None else "local")
        st = states.get(lane, {})
        extra = st.get("state", "")
        clock = st.get("clock") or {}
        if clock.get("synced"):
            extra += (f"  offset={clock.get('offset_s', 0) * 1e3:+.3f}ms"
                      f" rtt={clock.get('rtt_s', 0) * 1e3:.3f}ms")
        print(f"  {lane:>8}  {row['events']:7d}  {row['relayed']:7d}  "
              f"{span_ms:9.2f}  {conf:>10}  {extra}")
    hops = fleet_hops(evs)
    if hops:
        print(f"\n== fleet hops: {len(hops)}")
        print(f"  {'kind':>11}  {'request':>8}  {'from':>4}  {'to':>4}  "
              f"{'hop_ms':>8}  detail")
        for kind, rid, src, dst, ms, detail in hops:
            ms_s = f"{ms:8.3f}" if ms is not None else "      --"
            print(f"  {kind:>11}  {rid!s:>8}  {src!s:>4}  {dst!s:>4}  "
                  f"{ms_s}  {detail}")
    if request_id is not None:
        wf = request_waterfall(evs, request_id)
        if not wf:
            print(f"\nrequest {request_id}: no events in this window")
            return
        t0 = wf[0]["ts"]
        print(f"\n== request {request_id} fleet waterfall "
              f"({len(wf)} events, lane column = replica)")
        print(f"{'t_ms':>10}  {'dur_ms':>8}  {'lane':>8}  event")
        for e in wf:
            args = dict(e.get("args") or {})
            args.pop("request_id", None)
            lane = str(args.pop("replica", "gateway"))
            conf = args.pop("clock_conf_s", None)
            dur = f"{e['dur'] / 1e3:8.3f}" if "dur" in e else " " * 8
            extra = " ".join(f"{k}={v}" for k, v in args.items())
            if conf is not None:
                extra += f" (±{conf * 1e3:.2f}ms)"
            print(f"{(e['ts'] - t0) / 1e3:10.3f}  {dur}  {lane:>8}  "
                  f"{e['name']}{'  ' + extra if extra else ''}")


def roofline_table(other: dict) -> list:
    """(program, dispatches, gflops_per_s, gbytes_per_s, mfu_pct,
    mbu_pct) rows from the export's live roofline snapshot — empty
    when the trace predates PR 20 or TTD_COMPILECHECK was unarmed."""
    rows = []
    for prog, s in sorted((other.get("roofline") or {}).items()):
        rows.append((prog, s.get("dispatches", 0),
                     s.get("flops_per_s", 0.0) / 1e9,
                     s.get("bytes_per_s", 0.0) / 1e9,
                     s.get("mfu_pct"), s.get("mbu_pct")))
    return rows


# -- post-mortem (TTD_TRACE_SPOOL + corpse snapshots) ----------------------


def load_spool_dir(directory: str) -> dict:
    """Parse a spool directory: per-pid event streams (wall-anchored
    via each segment's header line) + the parent's corpse snapshots.

    Returns ``{"procs": {pid: {"events": [...], "dropped": n,
    "segments": n}}, "corpses": [...]}`` where each event is
    ``{"name", "ph", "wall_s", "mono_s", "dur", "attrs"}``."""
    procs: dict = {}
    for path in sorted(glob.glob(os.path.join(directory,
                                              "spool-*.jsonl"))):
        anchor = None       # (wall_anchor_s, mono_anchor_s) of segment
        pid = None
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue    # torn tail line: the crash wrote it
                if isinstance(rec, dict) and rec.get("spool"):
                    pid = rec.get("pid")
                    anchor = (float(rec.get("wall_anchor_s", 0.0)),
                              float(rec.get("mono_anchor_s", 0.0)))
                    row = procs.setdefault(pid, {
                        "events": [], "dropped": 0, "segments": 0})
                    row["segments"] += 1
                elif isinstance(rec, dict) and "dropped" in rec:
                    if pid in procs:
                        procs[pid]["dropped"] += int(rec["dropped"])
                    continue
                # One {"b": [...]} line per flush batch; bare event
                # arrays accepted too (hand-written fixtures).
                if anchor is None:
                    continue
                if isinstance(rec, dict):
                    batch = rec.get("b") or []
                elif isinstance(rec, list) and len(rec) >= 6:
                    batch = [rec]
                else:
                    batch = []
                for ev in batch:
                    if not isinstance(ev, list) or len(ev) < 6:
                        continue
                    name, ph, t0, dur, _tid, attrs = ev[:6]
                    procs[pid]["events"].append({
                        "name": name, "ph": ph,
                        "mono_s": t0,
                        "wall_s": t0 - anchor[1] + anchor[0],
                        "dur": dur, "attrs": attrs or {}})
    corpses = []
    for path in sorted(glob.glob(os.path.join(directory,
                                              "corpse-*.json"))):
        try:
            with open(path) as f:
                corpses.append(json.load(f))
        except (OSError, ValueError):
            continue
    for row in procs.values():
        row["events"].sort(key=lambda e: e["wall_s"])
    return {"procs": procs, "corpses": corpses}


def post_mortem_report(directory: str, last_s: float = 10.0) -> dict:
    """The reconstruction the dead process can no longer serve: for
    each corpse snapshot, the worker's own final ``last_s`` seconds of
    spooled events joined with the parent's view (exit reason, clock
    offset at death, the last relayed events).  ``timeline`` holds
    every process's tail merged on wall clock (spool segment anchors),
    tagged by pid."""
    spool = load_spool_dir(directory)
    deaths = []
    for c in spool["corpses"]:
        pid = c.get("pid")
        proc = spool["procs"].get(pid, {})
        evs = proc.get("events", [])
        cutoff = (evs[-1]["wall_s"] - last_s) if evs else 0.0
        deaths.append({
            "replica": c.get("replica"),
            "pid": pid,
            "reason": c.get("reason"),
            "returncode": c.get("returncode"),
            "drained": c.get("drained"),
            "clock": c.get("clock") or {},
            "wall_s": c.get("wall_s"),
            "events_relayed": c.get("events_relayed"),
            "last_relayed": c.get("last_events") or [],
            "final_events": [e for e in evs if e["wall_s"] >= cutoff],
            "spool_segments": proc.get("segments", 0),
            "spool_dropped": proc.get("dropped", 0),
        })
    timeline = []
    for pid, proc in spool["procs"].items():
        for e in proc["events"]:
            timeline.append(dict(e, pid=pid))
    timeline.sort(key=lambda e: e["wall_s"])
    return {"deaths": deaths, "timeline": timeline,
            "procs": sorted(spool["procs"]),
            "corpses": len(spool["corpses"])}


def print_post_mortem(directory: str, journal: "str | None" = None,
                      last_s: float = 10.0) -> None:
    rep = post_mortem_report(directory, last_s=last_s)
    print(f"# post-mortem: {directory} — "
          f"{len(rep['procs'])} spooled processes, "
          f"{rep['corpses']} corpse snapshots")
    if not rep["deaths"]:
        print("  no corpse snapshots: nothing died while the parent "
              "watched (or TTD_TRACE_SPOOL was unset in the parent)")
    for d in rep["deaths"]:
        clock = d["clock"] or {}
        sync = (f"offset={clock.get('offset_s', 0) * 1e3:+.3f}ms "
                f"±{clock.get('conf_s', 0) * 1e3:.2f}ms"
                if clock.get("synced") else "unsynced (HELLO estimate)")
        print(f"\n== death: replica={d['replica']} pid={d['pid']} "
              f"reason={d['reason']} rc={d['returncode']} "
              f"drained={d['drained']}")
        print(f"   clock at death: {sync}; "
              f"{d['events_relayed']} events relayed; spool: "
              f"{d['spool_segments']} segments, "
              f"{d['spool_dropped']} dropped")
        if d["final_events"]:
            t_end = d["final_events"][-1]["wall_s"]
            print(f"   final {last_s:.0f}s from its own spool "
                  f"({len(d['final_events'])} events, t=0 at death):")
            for e in d["final_events"][-40:]:
                attrs = e.get("attrs") or {}
                extra = " ".join(f"{k}={v}" for k, v in attrs.items())
                dur = (f" dur={e['dur'] * 1e3:.3f}ms"
                       if e.get("dur") else "")
                print(f"   {e['wall_s'] - t_end:9.3f}s  {e['name']}"
                      f"{dur}{'  ' + extra if extra else ''}")
        elif d["last_relayed"]:
            print(f"   no spool from the worker (its TTD_TRACE_SPOOL "
                  f"was unset?); last {len(d['last_relayed'])} events "
                  f"the parent relayed:")
            for name, ph, t0, dur, attrs in d["last_relayed"][-20:]:
                extra = " ".join(f"{k}={v}"
                                 for k, v in (attrs or {}).items())
                print(f"     {name}  {extra}")
    if journal:
        print_journal(journal)


def print_journal(path: str) -> None:
    print(f"\n== supervisor journal: {path}")
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            ev = rec.pop("event", "?")
            print("  " + ev.ljust(10)
                  + " ".join(f"{k}={v}" for k, v in rec.items()))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("trace", nargs="?", default=None,
                   help="Chrome-trace JSON (GET /debug/trace "
                        "output or Recorder.save()); optional with "
                        "--post-mortem")
    p.add_argument("--request", type=int, default=None,
                   help="render one request's waterfall")
    p.add_argument("--requests", action="store_true",
                   help="list request ids in the window with status")
    p.add_argument("--fleet", action="store_true",
                   help="cross-worker view: per-replica lanes, clock "
                        "quality, measured handoff/migration hops")
    p.add_argument("--post-mortem", default=None, metavar="DIR",
                   help="reconstruct the last seconds before a death "
                        "from a TTD_TRACE_SPOOL directory (spool "
                        "segments + corpse snapshots)")
    p.add_argument("--last-s", type=float, default=10.0,
                   help="post-mortem tail length per death "
                        "(default 10s)")
    p.add_argument("--journal", default=None,
                   help="supervisor JSONL to append as an attempt "
                        "timeline")
    args = p.parse_args(argv)
    if args.post_mortem is not None:
        print_post_mortem(args.post_mortem, journal=args.journal,
                          last_s=args.last_s)
        if args.trace is None:
            return 0
    if args.trace is None:
        p.error("a trace file is required unless --post-mortem is "
                "given")
    evs = load_events(args.trace)
    other = load_other(args.trace)
    print(f"# {args.trace}: {len(evs)} events")
    if args.fleet:
        print_fleet(evs, other, request_id=args.request)
        roof = roofline_table(other)
        if roof:
            print("\n== live roofline (per compiled program)")
            print(f"  {'dispatches':>10}  {'gflop/s':>9}  {'gbyte/s':>9}"
                  f"  {'mfu%':>6}  {'mbu%':>6}  program")
            for prog, n, gf, gb, mfu, mbu in roof:
                mfu_s = f"{mfu:6.2f}" if mfu is not None else "    --"
                mbu_s = f"{mbu:6.2f}" if mbu is not None else "    --"
                print(f"  {n:10d}  {gf:9.3f}  {gb:9.3f}  {mfu_s}  "
                      f"{mbu_s}  {prog}")
        if args.journal:
            print_journal(args.journal)
        return 0

    rows = stage_table(evs)
    if rows:
        print(f"\n{'count':>7}  {'total_ms':>10}  {'mean_ms':>9}  "
              f"{'p50_ms':>8}  {'p99_ms':>8}  {'max_ms':>8}  span")
        for name, n, total, mean, p50, p99, mx in rows:
            print(f"{n:7d}  {total:10.2f}  {mean:9.3f}  {p50:8.3f}  "
                  f"{p99:8.3f}  {mx:8.3f}  {name}")
    stages = step_stages(evs)
    if stages["steps"]:
        n = stages["steps"]
        print(f"\n== engine step by stage ({n} steps; self time: what "
              f"no span nested in it covers)")
        print(f"{'mean_ms':>9}  {'p50_ms':>8}  {'p75_ms':>8}  "
              f"{'steps':>6}  span")
        for name, mean, p50, p75, seen in stages["rows"]:
            print(f"{mean:9.3f}  {p50:8.3f}  {p75:8.3f}  {seen:6d}  "
                  f"{name}")
        if stages["starved_ms"] is not None:
            span = stages["span_ms"]
            print(f"  device starved     {stages['starved_ms'] / 1e3:.3f} s"
                  f" of {span / 1e3:.3f} s of steps and the caller's "
                  f"passes ({100.0 * stages['starved_ms'] / span:.2f}%): "
                  f"{stages['starved_ms'] / n:.3f} ms a step, queue "
                  f"found empty {stages['drains'] / n:.2f} times a step")
            print(f"  away (the caller)  {stages['away_ms'] / 1e3:.3f} s "
                  f"({100.0 * stages['away_ms'] / span:.2f}%): "
                  f"{stages['away_ms'] / n:.3f} ms between two steps")
        if stages["first_deferred"] is not None:
            print(f"  first tokens left on the device for a harvest "
                  f"{stages['first_deferred']} "
                  f"({stages['first_deferred'] / n:.2f} a step)")
    pieces, walked, held, counted = prefill_walk(evs)
    if held:
        print(f"  prefill/piece attention walked {walked} of {held} "
              f"cache rows in the last pieces of {pieces} calls: share "
              f"walked "
              f"{walked / held:.3f}"
              + (f", share the selection counted over "
                 f"{counted / held:.3f}" if counted else ""))
    spans_n, attended, scored = rows_selected(evs)
    if scored:
        print(f"  learned selection attended {attended:.0f} of "
              f"{scored:.0f} rows scored in {spans_n} decode steps: "
              f"share selected {attended / scored:.3f}")
    inst = instant_counts(evs)
    if inst:
        print(f"\n{'count':>7}  instant")
        for name, n in inst:
            print(f"{n:7d}  {name}")

    kv = kv_cache_summary(evs)
    if kv:
        print("\n== paged KV cache")
        print(f"  prefix hits        {kv['prefix_hits']}"
              f"  ({kv['prefix_hit_tokens']} prompt tokens skipped)")
        print(f"  evicted blocks     {kv['evicted_blocks']}")
        print(f"  refused admissions {kv['refused_admissions']}")
        print(f"  fused-attn dispatches {kv['fused_attn_dispatches']}"
              f"  (decode chunks through ops.pallas_kernels."
              f"paged_attention)")
        if kv["kv_table_blocks"]:
            print(f"  kv blocks walked   {kv['kv_blocks']} of "
                  f"{kv['kv_table_blocks']} in the slots x blocks "
                  f"table: live share "
                  f"{100.0 * kv['kv_blocks'] / kv['kv_table_blocks']:.1f}%")
        if kv["kv_window_blocks"] and kv["kv_blocks"]:
            print(f"  a window layer read {kv['kv_window_blocks']} of the "
                  f"{kv['kv_blocks']} blocks its lanes hold: window "
                  f"share "
                  f"{100.0 * kv['kv_window_blocks'] / kv['kv_blocks']:.1f}%")
        if kv["state_bytes"]:
            held = kv["state_bytes"] + kv["kv_bytes"]
            print(f"  recurrent state    {kv['state_bytes']} bytes held by "
                  f"the steps' live lanes beside {kv['kv_bytes']} bytes of "
                  f"rows walked: state share "
                  f"{100.0 * kv['state_bytes'] / held:.1f}%")

    spec = spec_depth_summary(evs)
    if spec:
        print("\n== speculative depth (spec_k on decode/dispatch)")
        by_depth = " ".join(f"k={k}:{n}" for k, n
                            in sorted(spec["rounds"].items()))
        print(f"  rounds by depth    {by_depth}")
        print(f"  depth switches     {spec['switches']}")
        print(f"  {'start_ms':>10}  {'depth':>5}  {'rounds':>6}")
        for start, k, n in spec["segments"]:
            print(f"  {start:10.3f}  {k:5d}  {n:6d}")

    mig = migration_summary(evs)
    if mig:
        ms = sorted(mig["ms"])
        print("\n== live migration")
        print(f"  migrations         {mig['migrations']}"
              f"  ({mig['kv_bytes']} KV bytes shipped, "
              f"{mig['warm_tokens']} warm tokens installed)")
        if ms:
            print(f"  move time ms       p50={_percentile(ms, 0.5):.3f}"
                  f" p99={_percentile(ms, 0.99):.3f} max={ms[-1]:.3f}")
        print(f"  drain evacuations  {mig['evacuations']}"
              f"  ({mig['evacuated_lanes']} lanes moved)")
        print(f"  defrag moves       {mig['defrag_moves']}")
        if mig["hops"]:
            print(f"  {'request':>9}  {'from':>4}  {'to':>4}  "
                  f"{'at_tok':>6}  {'kv_bytes':>9}")
            for rid, src, dst, at, nbytes in mig["hops"]:
                print(f"  {rid!s:>9}  {src!s:>4}  {dst!s:>4}  "
                      f"{at!s:>6}  {nbytes:9d}")

    anatomy = train_step_summary(evs)
    if anatomy:
        print("\n== train step anatomy (grad-quant split step)")
        print(f"{'count':>7}  {'total_ms':>10}  {'comm-frac':>9}  span")
        for name, n, ms, frac in anatomy:
            frac_s = (f"{frac:9.3f}" if name != "train/step_dispatch"
                      else " " * 9)
            print(f"{n:7d}  {ms:10.2f}  {frac_s}  {name}")

    memory = memory_summary(evs)
    if memory:
        print("\n== memory pools (memcheck spans)")
        print(f"{'allocs':>7}  {'peak_MiB':>9}  {'live_MiB':>9}  "
              f"{'budget_MiB':>10}  {'headroom':>8}  {'near-miss':>9}"
              f"  pool")
        for pool in sorted(memory):
            row = memory[pool]
            mib = 1024.0 * 1024.0
            budget = row["budget"]
            headroom = (f"{100.0 * (1 - row['peak_live'] / budget):7.1f}%"
                        if budget else "      --")
            print(f"{row['allocs']:7d}  {row['peak_live'] / mib:9.2f}  "
                  f"{row['live'] / mib:9.2f}  "
                  f"{(budget / mib) if budget else 0:10.2f}  "
                  f"{headroom}  {row['near_misses']:9d}  {pool}")

    compiles = compile_summary(evs)
    if compiles:
        print("\n== compilations (compilecheck spans)")
        print(f"{'count':>7}  {'total_ms':>10}  site")
        for site, n, ms in compiles:
            print(f"{n:7d}  {ms:10.2f}  {site}")

    if args.requests:
        ids = request_ids(evs)
        print(f"\n== requests in window: {len(ids)}")
        for rid, status in ids:
            print(f"  {rid:>8}  {status}")
    if args.request is not None:
        print_waterfall(evs, args.request)
    if args.journal:
        print_journal(args.journal)
    return 0


if __name__ == "__main__":
    sys.exit(main())
