"""The program's own spans, as the per-layer readers take them.

The program records its spans twice with one call
(``runtime/events.py``): into a ring on the host's monotonic clock,
which holds every span of the run and outlives the engine, and, while a
profiler capture runs, into the capture's host plane on the profiler's
clock.  Readers of counts and of host self time over the whole window
use the ring (``window_steps``: ``Step``s with their start, duration,
self time, attrs, the seconds of each span name recorded inside them,
and whether the capture overlapped them); readers that set spans
against the device's operations use the capture (``program_spans``).

A program without these spans (a parent commit that predates them)
gives ``None`` everywhere here, never an error.
"""

from __future__ import annotations

import collections

from tensorflow_train_distributed_tpu.runtime import events

from benchmark.harness import stats

Step = collections.namedtuple("Step", "t0 dur self_s attrs children traced")

STEP = "engine/step"


def window_steps(ctx: dict):
    """``(steps, dropped)``: the ``engine/step`` spans that began inside
    the measured window (``counters["t_open"]`` for ``"seconds"``), each
    with its self time: its duration less the ``*/wait`` spans (reads
    that block on the device) its thread recorded inside it, wherever
    they end.  ``traced`` marks a step that overlaps the profiler's
    capture, whose Python tracer slows the host's own work.
    ``dropped`` is what the ring lapped of the window, for the log.
    ``None`` when the program records no such span."""
    between = getattr(events.get_recorder(), "spans_between", None)
    if between is None:
        return None
    counters = ctx["result"]["counters"]
    t0 = counters["t_open"]
    # Children of the window's last step may begin after it closes.
    spans, dropped = between(t0, float("inf"))
    tracer = ctx.get("tracer")
    capture = (getattr(tracer, "t0", None), getattr(tracer, "t1", None))
    return [s for s in steps_of(spans, capture)
            if s.t0 < t0 + counters["seconds"]], dropped


def self_times_ms(ctx: dict, phase: str):
    """Self time in ms of every step of the window that ran clear of
    the profiler's capture, logged under ``phase`` with the steps'
    whole durations and, apart (``traced``), the same for the steps
    the capture overlapped; ``None`` when there is no step to read."""
    got = window_steps(ctx)
    if not got or not got[0]:
        return None
    steps, dropped = got
    traced = [s for s in steps if s.traced]
    clear = [s for s in steps if not s.traced] or steps
    t_open = ctx["result"]["counters"]["t_open"]
    ctx["log"](phase=phase, steps=len(steps), ring_dropped=dropped,
               traced=_summary(traced) if traced and clear is not steps
               else None, **_summary(clear),
               # every step: seconds into the window, self ms, traced
               timeline=[(round(s.t0 - t_open, 3),
                          round(1e3 * s.self_s, 3), int(s.traced))
                         for s in steps])
    return [1e3 * s.self_s for s in clear]


def _summary(steps) -> dict:
    def ms(children, n=1):
        return {k: 1e3 * v / n for k, v in children.most_common()}

    inside = collections.Counter()
    for s in steps:
        inside.update(s.children)
    slowest = max(steps, key=lambda s: s.self_s)
    return {"n": len(steps),
            "self_ms": stats.summarize([1e3 * s.self_s for s in steps]),
            "step_ms": stats.summarize([1e3 * s.dur for s in steps]),
            # where a step's time goes, by the spans inside it (nested
            # ones count under each of their names)
            "inside_ms_mean": ms(inside, len(steps)),
            "slowest": {"self_ms": 1e3 * slowest.self_s,
                        "step_ms": 1e3 * slowest.dur,
                        "attrs": slowest.attrs,
                        "inside_ms": ms(slowest.children)}}


def steps_of(spans, capture=(None, None)) -> list:
    """Ring tuples ``(name, ph, t0, dur, tid, attrs)`` -> ``Step``s;
    ``capture``: the monotonic interval a profiler's capture ran for."""
    c0, c1 = capture
    inner = collections.defaultdict(list)
    for name, _, t0, dur, tid, _ in spans:
        if name != STEP:
            inner[tid].append((t0, dur, name))
    steps = []
    for name, _, t0, dur, tid, attrs in spans:
        if name != STEP:
            continue
        children = collections.Counter()
        for k0, kdur, kname in inner[tid]:
            if t0 <= k0 <= t0 + dur:
                children[kname] += kdur
        waited = sum(d for n, d in children.items() if n.endswith("/wait"))
        steps.append(Step(
            t0, dur, max(dur - waited, 0.0), attrs or {}, children,
            c0 is not None and t0 < c1 and t0 + dur > c0))
    return steps


def program_spans(trace) -> list:
    """The host events of a capture that are spans of the program's
    contract (``runtime.events.CONTRACT``); ``[]`` when the program has
    no contract or the capture none of its spans."""
    in_contract = getattr(events, "in_contract", None)
    if in_contract is None:
        return []
    return [ev for ev in trace.host if in_contract(ev.name)]
