"""Who owns a step's host time and the device's idle: what the readers
of the engine's stage spans and starved-device counters share.

The engine records every stage of a step's host work as a span under
``engine/step`` (``prefill/stage``, ``prefill/cache``,
``prefill/dispatch``, ``prefill/insert``, ``decode/stage`` beside the
older ``decode/dispatch``, ``*/wait``, ``decode/harvest``,
``prefill/piece``, ``kv/alloc``) and counts on its own clock what the
device was left without (``starved_ms``, ``drains``, ``away_ms`` on
``engine/step``: ``runtime/events.py``).  From the ring, over the
window's steps clear of the profiler's capture:

- ``stage_table``: a step's duration less the union of ALL spans its
  thread recorded inside it is the host time that still has no name;
  each span's self time is what no span nested in it covers;
- ``starved``: the counters' sums and shares.

From the capture, on the device's clock:

- ``idle_by_owner``: the first device's idle intervals against the
  contract's spans, innermost owner first
  (``idle_unowned_pct.longprompt``'s reduction, as one sweep over the
  spans' boundaries with the idle summed ahead, so that a capture of
  40,000 gaps costs what one of 40 does);
- ``ring_twins`` / ``join_in_order`` / ``line_at``: a capture's spans
  given their ring attrs (the capture keeps names and times only; the
  two clocks differ by a constant), each ``prefill/dispatch`` joined to
  the execution it launched, and the least-squares line of device time
  on the rows walked.

A program without these spans or counters (a parent commit) gives
``None`` everywhere here, never an error.
"""

from __future__ import annotations

import bisect
import collections
import heapq

from tensorflow_train_distributed_tpu.runtime import events

from benchmark.harness import scope_table, spans, stats, trace

STEP = spans.STEP
#: The spans this round of instrumentation added: a program that
#: records none of them has no stage table to read.
STAGES = ("prefill/stage", "prefill/cache", "prefill/dispatch",
          "prefill/insert", "decode/stage")
UNNAMED = "(no span)"

Step = collections.namedtuple("Step", "t0 dur attrs traced inner")


# -- the ring --------------------------------------------------------------


def window_steps(ctx: dict):
    """``(steps, dropped)``: the ``engine/step`` spans that began in
    the measured window, each with the spans its thread recorded inside
    it (``inner``: ``(t0, dur, name)`` by start) and whether the
    profiler's capture overlapped it.  ``None`` when the program's
    recorder predates ``spans_between``."""
    between = getattr(events.get_recorder(), "spans_between", None)
    if between is None:
        return None
    counters = ctx["result"]["counters"]
    t_open = counters["t_open"]
    spans, dropped = between(t_open, float("inf"))
    tracer = ctx.get("tracer")
    capture = (getattr(tracer, "t0", None), getattr(tracer, "t1", None))
    return [s for s in steps_of(spans, capture)
            if s.t0 < t_open + counters["seconds"]], dropped


def steps_of(spans, capture=(None, None)) -> list:
    """Ring tuples ``(name, ph, t0, dur, tid, attrs)`` -> ``Step``s."""
    c0, c1 = capture
    by_thread = collections.defaultdict(list)
    for name, _, t0, dur, tid, _ in spans:
        if name != STEP:
            by_thread[tid].append((t0, dur, name))
    for inner in by_thread.values():
        inner.sort()
    starts = {tid: [s[0] for s in inner]
              for tid, inner in by_thread.items()}
    steps = []
    for name, _, t0, dur, tid, attrs in spans:
        if name != STEP:
            continue
        inner = by_thread.get(tid, [])
        lo = bisect.bisect_left(starts.get(tid, []), t0)
        hi = bisect.bisect_right(starts.get(tid, []), t0 + dur)
        steps.append(Step(t0, dur, attrs or {},
                          c0 is not None and t0 < c1 and t0 + dur > c0,
                          inner[lo:hi]))
    return sorted(steps)


def clear_steps(ctx: dict):
    """``(clear, traced, dropped)`` of the window's steps: those the
    capture's Python tracer did not slow, and those it did."""
    got = window_steps(ctx)
    if not got or not got[0]:
        return None
    steps, dropped = got
    traced = [s for s in steps if s.traced]
    return [s for s in steps if not s.traced] or steps, traced, dropped


def self_seconds(step: Step) -> dict:
    """``{name: seconds}`` of a step by the innermost span open at each
    moment (a span's self time: what no span nested in it covers), and
    under ``UNNAMED`` what no span covers at all.  Spans that overlap
    without nesting count once, under the one that began later."""
    own = collections.Counter()
    end_of_step = step.t0 + step.dur
    stack = []              # [end, name] of the spans open at ``at``
    at = step.t0

    def close(until):
        nonlocal at
        while stack and stack[-1][0] <= until:
            end, name = stack.pop()
            own[name] += max(end - at, 0.0)
            at = max(at, end)

    for t0, dur, name in sorted(step.inner, key=lambda s: (s[0], -s[1])):
        close(t0)
        if t0 > at:
            own[stack[-1][1] if stack else UNNAMED] += t0 - at
            at = t0
        stack.append([min(t0 + dur, end_of_step), name])
    close(float("inf"))
    own[UNNAMED] += max(end_of_step - at, 0.0)
    return dict(own)


def stage_table(steps) -> dict:
    """For the log: ms a step by span (``ms_mean``, nested spans under
    each of their names), the self time of each (mean, median and 75th
    percentile over the steps, a step without the span counting zero),
    the steps that recorded it, and the slowest step's self times by
    span (slowest by what lies outside its ``*/wait``s)."""
    n = len(steps)
    whole = collections.Counter()
    own = collections.defaultdict(list)
    seen = collections.Counter()
    slowest = None
    for s in steps:
        mine = self_seconds(s)
        for t0, dur, name in s.inner:
            whole[name] += dur
        for name in {name for _, _, name in s.inner}:
            seen[name] += 1
        for name, secs in mine.items():
            own[name].append(secs)
        busy = s.dur - sum(v for k, v in mine.items()
                           if k.endswith("/wait"))
        if slowest is None or busy > slowest[0]:
            slowest = (busy, s, mine)
    rows = {}
    for name, values in own.items():
        padded = values + [0.0] * (n - len(values))
        rows[name] = {
            "ms_mean": 1e3 * whole[name] / n if name != UNNAMED else None,
            "self_ms_mean": 1e3 * sum(values) / n,
            "self_ms_p50": 1e3 * stats.median(padded),
            "self_ms_p75": 1e3 * stats.percentile(padded, 75.0),
            "steps": seen[name] if name != UNNAMED else n}
    busy, step, mine = slowest
    return {"n": n,
            "by_span": dict(sorted(rows.items(),
                                   key=lambda kv: -kv[1]["self_ms_mean"])),
            "slowest": {"step_ms": 1e3 * step.dur, "self_ms": 1e3 * busy,
                        "attrs": step.attrs,
                        "self_ms_by_span": {
                            k: 1e3 * v for k, v in sorted(
                                mine.items(), key=lambda kv: -kv[1])}}}


def has_stages(steps) -> bool:
    return any(name in STAGES for s in steps for _, _, name in s.inner)


def starved(steps):
    """The starved-device counters over ``steps``: the sums, the share
    of the steps' time (each step's duration and what the caller held
    the engine for before it), drains a step.  ``None`` where no step
    carries the counters."""
    counted = [s for s in steps if "starved_ms" in s.attrs]
    if not counted:
        return None
    starved_ms = sum(s.attrs["starved_ms"] for s in counted)
    away_ms = sum(s.attrs.get("away_ms", 0.0) for s in counted)
    span_ms = 1e3 * sum(s.dur for s in counted) + away_ms
    return {"steps": len(counted), "starved_ms": starved_ms,
            "away_ms": away_ms, "span_ms": span_ms,
            "pct": 100.0 * starved_ms / span_ms if span_ms > 0 else 0.0,
            "drains_a_step": sum(s.attrs.get("drains", 0)
                                 for s in counted) / len(counted)}


# -- the capture -----------------------------------------------------------


def program_spans(ctx: dict) -> list:
    """The capture's host events that are spans of the program's
    contract, by start; read once a run and kept in ``ctx`` (the
    capture holds a hundred thousand frames of the Python tracer beside
    them)."""
    if "_program_spans" not in ctx:
        ctx["_program_spans"] = sorted(
            spans.program_spans(ctx["trace"]), key=lambda ev: ev.start)
    return ctx["_program_spans"]


def device_idle(ctx: dict) -> "Idle":
    """The first device's idle intervals in the traced window, summed
    ahead; read once a run and kept in ``ctx``."""
    if "_device_idle" not in ctx:
        ctx["_device_idle"] = Idle(trace.idle_gaps(
            ctx["trace"].devices[0], *ctx["trace_window"]))
    return ctx["_device_idle"]


class Idle:
    """The idle seconds of disjoint sorted intervals before any time."""

    def __init__(self, gaps):
        self.starts = [s for s, _ in gaps]
        self.ends = [e for _, e in gaps]
        self.before = [0.0]
        for s, e in gaps:
            self.before.append(self.before[-1] + (e - s))

    def until(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        return self.before[i] - max(self.ends[i - 1] - t, 0.0)

    def inside(self, intervals) -> float:
        return sum(self.until(e) - self.until(s) for s, e in intervals)


def idle_by_owner(program, idle: "Idle"):
    """``idle_unowned_pct.longprompt``'s reduction: of the idle
    intervals ``idle`` holds (disjoint, sorted), the seconds each working
    span of ``program`` below ``engine/step`` owns (any but the step
    and the ``*/wait``s, on any thread; where several cover a moment
    the shortest, the innermost, owns it), and what is left by where it
    lies.  ``(idle_s, by_span_s, unowned_s, unowned_by_place_s)``."""
    idle_s = idle.before[-1]
    owners = [ev for ev in program if ev.name != STEP
              and not ev.name.endswith("/wait")]
    # One sweep over the owners' boundaries: between two of them the
    # open spans do not change, and the innermost is the shortest
    # (of equals the first in the reader's order, by duration).
    rank = {id(ev): i for i, ev in enumerate(
        sorted(owners, key=lambda ev: ev.dur))}
    opening = sorted(owners, key=lambda ev: ev.start)
    bounds = sorted({ev.start for ev in owners}
                    | {ev.start + ev.dur for ev in owners})
    owned = collections.Counter(dict.fromkeys(
        (ev.name for ev in owners), 0.0))
    live, nxt = [], 0                   # heap of (rank, end, name)
    for lo, hi in zip(bounds, bounds[1:]):
        while nxt < len(opening) and opening[nxt].start <= lo:
            ev = opening[nxt]
            heapq.heappush(live, (rank[id(ev)], ev.start + ev.dur,
                                  ev.name))
            nxt += 1
        while live and live[0][1] <= lo:
            heapq.heappop(live)
        if live:
            owned[live[0][2]] += idle.until(hi) - idle.until(lo)
    by_span = dict(owned.most_common())
    unowned = idle_s - sum(by_span.values())

    def spans_of(named):
        return trace.union((ev.start, ev.start + ev.dur)
                           for ev in program if named(ev.name))

    first = min(ev.start for ev in program)
    last = max(ev.start + ev.dur for ev in program)
    rest = trace.subtract([(first, last)], trace.union(
        (ev.start, ev.start + ev.dur) for ev in owners))
    places = {"capture edges": unowned - idle.inside(rest)}
    for place, named in (("*/wait", lambda name: name.endswith("/wait")),
                         ("engine/step alone", STEP.__eq__)):
        outside = trace.subtract(rest, spans_of(named))
        places[place] = idle.inside(rest) - idle.inside(outside)
        rest = outside
    places["between steps"] = idle.inside(rest)
    return idle_s, by_span, unowned, places


def _clock_offset(ctx: dict, tolerance_s: float):
    """Seconds by which the capture's clock is ahead of the ring's
    (``time.monotonic``), or ``None``.  One call records a span into
    both sinks, so the twins' starts differ by one constant: the
    constant, tried from each ring span that could be the twin of the
    capture's first, under which most contract spans of the capture
    (nine in ten of a sample of 200: a thread may lose its turn between
    the two clocks' readings) find a ring span of their name within
    ``tolerance_s``.  Read once a run and kept in ``ctx``."""
    if "_clock_offset" in ctx:
        return ctx["_clock_offset"]
    offset = None
    mine = program_spans(ctx)
    between = getattr(events.get_recorder(), "spans_between", None)
    tracer = ctx.get("tracer")
    if mine and between is not None and getattr(
            tracer, "t0", None) is not None:
        ring = collections.defaultdict(list)
        for name, _, t0, _, _, _ in between(tracer.t0 - 2.0,
                                            tracer.t1 + 2.0)[0]:
            ring[name].append(t0)
        for starts in ring.values():
            starts.sort()
        sample = mine[::max(len(mine) // 200, 1)]

        def fits(candidate):
            n = 0
            for ev in sample:
                starts = ring.get(ev.name, ())
                want = ev.start - candidate
                i = bisect.bisect_left(starts, want)
                n += any(0 <= j < len(starts)
                         and abs(starts[j] - want) <= tolerance_s
                         for j in (i - 1, i))
            return n

        best = max(((fits(mine[0].start - t0), mine[0].start - t0)
                    for t0 in ring.get(mine[0].name, ())),
                   default=(0, None))
        if best[0] >= 0.9 * len(sample):
            offset = best[1]
    ctx["_clock_offset"] = offset
    return offset


def ring_twins(ctx: dict, name: str, tolerance_s: float = 5e-4):
    """The capture's spans called ``name``, by start, each with the
    attrs its twin in the ring carries (the capture keeps names and
    times only): ``[(event, attrs)]``.  Both hold the same spans in the
    same order, so from the ring span nearest the capture's first they
    pair one to one; ``None`` when the capture has no such span, the
    program no ring, the clocks cannot be set against each other
    (``_clock_offset``), or a pair lies 20 ms apart (a span one sink
    lacks has shifted them)."""
    mine = [ev for ev in program_spans(ctx) if ev.name == name]
    offset = _clock_offset(ctx, tolerance_s) if mine else None
    if offset is None:
        return None
    tracer = ctx["tracer"]
    ring = sorted((e[2], e[5] or {})
                  for e in events.get_recorder().spans_between(
                      tracer.t0 - 2.0, tracer.t1 + 2.0, name=name)[0])
    starts = [t0 for t0, _ in ring]
    want = mine[0].start - offset
    i = bisect.bisect_left(starts, want)
    first = min((j for j in (i - 1, i) if 0 <= j < len(starts)),
                key=lambda j: abs(starts[j] - want), default=None)
    if first is None or first + len(mine) > len(ring):
        return None
    twins = ring[first:first + len(mine)]
    if any(abs(ev.start - offset - t0) > 0.02
           for ev, (t0, _) in zip(mine, twins)):
        return None
    return [(ev, attrs) for ev, (_, attrs) in zip(mine, twins)]


def join_in_order(spans, executions, after=(None, None)):
    """Each launch span with the execution it launched.  Both lists lie
    in order on one stream, so from a point where the two are known to
    be level (``after``: a time no earlier launch is later than, and
    the time by which everything launched before it has run:
    ``level_point``) they pair one to one, as far as both reach;
    without such a point the first span takes the first execution that
    begins after it.  ``None`` if any pair has the execution before its
    span: a launch the lists do not share has shifted them."""
    launch_cut, run_cut = after
    if launch_cut is not None:
        spans = [sp for sp in spans if sp[0].start >= launch_cut]
    if not spans:
        return []
    run_cut = spans[0][0].start if run_cut is None else run_cut
    pairs = list(zip(spans, (ex for ex in executions
                             if ex.start >= run_cut)))
    if any(ex.start < ev.start for (ev, _), ex in pairs):
        return None
    return [(attrs, ex) for (_, attrs), ex in pairs]


def level_point(program, chunks, slack_s: float = 3e-3):
    """``(launch cut, run cut)``: the first point of the capture at
    which the host's launches and the device's executions are known to
    be level, so that launches at or after the one pair in order with
    executions at or after the other.  A launch runs behind whatever
    was queued before it (a chunk and the pieces of a step or two), so
    the capture's first span is not level with its first execution.

    - The end of a ``prefill/wait``: the host read the newest piece's
      token, so nothing is queued behind it (both cuts are that time).
    - A ``decode/wait`` that blocked returns when its chunk, the one
      dispatched a step earlier, has run: the chunk's execution is the
      ``chunks`` entry that ends within ``slack_s`` before the wait
      does, and everything launched after that chunk's
      ``decode/dispatch`` runs after it.

    ``(None, None)`` where the capture holds neither."""
    points = []
    waits = [ev for ev in program if ev.name == "prefill/wait"]
    if waits:
        end = waits[0].start + waits[0].dur
        points.append((end, end))
    steps = [ev for ev in program if ev.name == STEP]
    dispatches = [ev.start for ev in program
                  if ev.name == "decode/dispatch"]
    ends = sorted(ev.start + ev.dur for ev in chunks)
    for wait in program:
        if wait.name != "decode/wait" or wait.dur < slack_s:
            continue
        end = wait.start + wait.dur
        i = bisect.bisect_right(ends, end) - 1
        own = [s for s in steps
               if s.start <= wait.start < s.start + s.dur]
        if i < 0 or ends[i] < end - slack_s or not own:
            continue
        before = [d for d in dispatches if d < wait.start]
        # the step's own dispatch, if it came first, is the successor's
        back = 2 if before and before[-1] >= own[-1].start else 1
        if len(before) >= back:
            points.append((before[-back], ends[i]))
            break
    return min(points, key=lambda p: p[1], default=(None, None))


def line_at(points, at: float) -> dict:
    """The least-squares line through ``points`` (x, y) read at ``at``:
    ``{"value", "intercept", "slope", "residual_spread", "fit"}``.
    With fewer than three points or one x among them there is no line:
    the value is the mean of y and ``fit`` says ``"mean"``."""
    xs = [float(x) for x, _ in points]
    ys = [float(y) for _, y in points]
    n = len(points)
    mean_x, mean_y = sum(xs) / n, sum(ys) / n
    sxx = sum((x - mean_x) ** 2 for x in xs)
    if n < 3 or sxx == 0.0:
        return {"value": mean_y, "intercept": None, "slope": None,
                "residual_spread": None, "fit": "mean"}
    slope = sum((x - mean_x) * (y - mean_y)
                for x, y in zip(xs, ys)) / sxx
    intercept = mean_y - slope * mean_x
    residuals = [y - (intercept + slope * x) for x, y in zip(xs, ys)]
    return {"value": intercept + slope * at, "intercept": intercept,
            "slope": slope, "fit": "line",
            "residual_spread": (stats.percentile(residuals, 75.0)
                                - stats.percentile(residuals, 25.0))}


# -- the readers' bodies -------------------------------------------------------
#
# A per-layer metric is a file of its own with a ``read(ctx)``
# (``harness/manifest.py``); where two cells' metrics read one thing,
# their files call one function here.


def read_device_starved(ctx: dict, phase: str):
    """Percent of the clear steps' time (durations and the caller's
    passes between them) in which the engine knew the device's queue
    empty while it had work: Σ ``starved_ms`` ÷ Σ (duration +
    ``away_ms``).  Logs drains a step, the sums, and ``starved_cover``:
    the same counters over the steps the capture holds whole beside
    the capture's own idle in their extent."""
    got = clear_steps(ctx)
    clear = got and starved(got[0])
    if not clear:
        return None
    ctx["log"](phase=phase, ring_dropped=got[2], **clear,
               traced=starved(got[1]) if got[1] else None,
               starved_cover=_starved_cover(ctx))
    return clear["pct"]


def _starved_cover(ctx: dict):
    """Over the ``engine/step``s the capture holds whole (each from the
    caller's pass before it to its end, so that they tile the capture):
    the engine's ``starved_ms`` beside the first device's idle in the
    same extent.  The counter charges an empty queue to the step that
    enqueues next, so one step may carry a little of the one before;
    the sums are what compare.  ``None`` without a capture, twins or
    the counter."""
    tr = ctx.get("trace")
    twins = ring_twins(ctx, STEP) if tr is not None and tr.devices else None
    if not twins:
        return None
    lo, hi = ctx["trace_window"]
    idle = device_idle(ctx)
    rows = []
    for ev, attrs in twins:
        t0 = ev.start - 1e-3 * attrs.get("away_ms", 0.0)
        if "starved_ms" in attrs and t0 >= lo and ev.start + ev.dur <= hi:
            rows.append((attrs["starved_ms"],
                         1e3 * idle.inside([(t0, ev.start + ev.dur)])))
    if not rows:
        return None
    starved_ms = sum(s for s, _ in rows)
    idle_ms = sum(i for _, i in rows)
    return {"steps": len(rows), "starved_ms": starved_ms,
            "idle_ms": idle_ms,
            "covered": starved_ms / idle_ms if idle_ms > 0 else None,
            # (starved, idle) ms of each step, in order
            "by_step_ms": [(round(s, 3), round(i, 3)) for s, i in rows]}


def read_driver_away(ctx: dict, phase: str):
    """Median ``away_ms`` over the clear steps: what the engine's
    caller (``server/driver.py``'s loop: submissions taken in, commits
    handed to streams, retirements) holds it for between two steps.
    The 75th percentile and the sum's share of the steps' time go to
    the log."""
    got = clear_steps(ctx)
    away = got and [s.attrs["away_ms"] for s in got[0]
                    if "away_ms" in s.attrs]
    if not away:
        return None
    span_ms = 1e3 * sum(s.dur for s in got[0]) + sum(away)
    ctx["log"](phase=phase, ring_dropped=got[2],
               away_ms=stats.summarize(away),
               share_pct=100.0 * sum(away) / span_ms,
               traced_away_ms=stats.summarize(
                   [s.attrs.get("away_ms", 0.0) for s in got[1]])
               if got[1] else None)
    return stats.median(away)


def read_step_unnamed(ctx: dict, phase: str):
    """Median over the clear steps of a step's duration less the union
    of all spans its thread recorded inside it, in ms; its log is the
    stage table."""
    got = clear_steps(ctx)
    if not got or not has_stages(got[0]):
        return None
    clear, traced, dropped = got
    table = stage_table(clear)
    ctx["log"](phase=phase, ring_dropped=dropped, **table,
               traced=stage_table(traced) if traced else None)
    # every step has an UNNAMED entry, so the row's median is the steps'
    return table["by_span"][UNNAMED]["self_ms_p50"]


def read_idle_unowned(ctx: dict, phase: str):
    """Percent of the first device's idle seconds in the traced window
    that no working span of the contract below ``engine/step`` covers
    (``idle_by_owner``); seconds by owner and what is left by place go
    to the log.  ``None`` for a program without the stage spans."""
    program = program_spans(ctx)
    if (not ctx["trace"].devices
            or not any(ev.name == STEP for ev in program)
            or not any(ev.name in STAGES for ev in program)):
        return None
    lo, hi = ctx["trace_window"]
    idle_s, by_span, unowned, places = idle_by_owner(
        program, device_idle(ctx))
    ctx["log"](phase=phase, window_s=hi - lo, idle_s=idle_s,
               by_span_s=by_span, unowned_s=unowned,
               unowned_by_place_s=places)
    return 100.0 * unowned / idle_s if idle_s > 0 else 0.0


def read_piece_at(ctx: dict, phase: str, rows: int):
    """Device ms of one prefill piece whose attention walks ``rows``
    cache rows: every WHOLE execution of ``_prefill_piece`` in the
    capture joined to the ``prefill/dispatch`` span that launched it
    (the target's: a draft's piece is another program), and the
    least-squares line of device ms on the span's ``rows`` read at
    ``rows`` (the mean where there is no
    line to fit: the log says which).  A piece is a fixed part and a
    part that grows with the rows walked, and a capture's pieces lie
    wherever the schedule put them: the line compares like with
    like."""
    twins = ring_twins(ctx, "prefill/dispatch")
    if not twins:
        return None
    lo, _ = ctx["trace_window"]
    modules = sorted(ctx["trace"].devices[0].modules,
                     key=lambda ev: ev.start)
    whole = [ev for ev in scope_table.whole_executions(
        ctx, modules, "_prefill_piece") if "_draft_" not in ev.name]
    level = level_point(program_spans(ctx),
                        [ev for ev in modules if "_decode_chunk" in ev.name])
    pairs = join_in_order([(ev, a) for ev, a in twins
                           if not a.get("draft")], whole, level)
    if not pairs:
        return None
    points = [(a["rows"], 1e3 * ex.dur) for a, ex in pairs if "rows" in a]
    if not points:
        return None
    fit = line_at(points, rows)
    ctx["log"](phase=phase, at_rows=rows, pairs=len(points),
               fit=fit["fit"], value_ms=fit["value"],
               intercept_ms=fit["intercept"],
               slope_us_a_row=None if fit["slope"] is None
               else 1e3 * fit["slope"],
               residual_spread_ms=fit["residual_spread"],
               mean_ms=sum(y for _, y in points) / len(points),
               level_at_s=None if level[1] is None else level[1] - lo,
               # (rows, device ms) of each pair, in order
               by_pair=[(int(x), round(y, 3)) for x, y in points])
    return fit["value"]
