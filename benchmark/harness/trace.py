"""Reduction of a profiler trace to the numbers the benchmark prints.

A ``Trace`` is a neutral structure: per device, the operations that ran
on it and the executions of whole programs (the profiler's "XLA Ops"
and "XLA Modules" lines); and the host's spans.  ``load_xplane`` fills
it from a ``.xplane.pb`` with nothing but JAX; ``load_json`` from the
small hand-built trace the tests keep.  All times are seconds on the
trace's own clock.

What is computed:

- busy time: the union of the intervals in which an operation ran on a
  device (overlapping operations count once); idle share is one minus
  busy over the window;
- per-program and per-operation time;
- idle gaps, each attributed to the host span that covers most of it.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import gzip
import json
import os
import re

Event = collections.namedtuple("Event", "name start dur")

@dataclasses.dataclass
class DevicePlane:
    name: str
    ops: list            # Event
    modules: list        # Event


@dataclasses.dataclass
class Trace:
    devices: list        # DevicePlane
    host: list           # Event (every host span, any thread)


# -- interval arithmetic -----------------------------------------------------


def union(intervals) -> list:
    """Merge [start, end) intervals; returns them sorted and disjoint."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _spans(events) -> list:
    return [(ev.start, ev.start + ev.dur) for ev in events]


# -- reductions ----------------------------------------------------------------


def window(trace: Trace):
    """[first device operation's start, last one's end) over all
    devices: the traced window as the devices saw it."""
    spans = [sp for d in trace.devices for sp in _spans(d.ops or d.modules)]
    if not spans:
        return None
    return min(s for s, _ in spans), max(e for _, e in spans)


def busy_seconds(plane: DevicePlane, lo=None, hi=None) -> float:
    u = union(_spans(plane.ops or plane.modules))
    if lo is not None:
        u = clip(u, lo, hi)
    return total(u)


def mean_busy_seconds(trace: Trace, lo=None, hi=None) -> float:
    return sum(busy_seconds(d, lo, hi) for d in trace.devices) / max(
        len(trace.devices), 1)


def idle_share_pct(trace: Trace, lo: float, hi: float):
    """Percent of [lo, hi) in which no operation ran, mean over the
    devices; None for an empty window."""
    if hi <= lo:
        return None
    return 100.0 * (1.0 - mean_busy_seconds(trace, lo, hi) / (hi - lo))


def idle_gaps(plane: DevicePlane, lo: float, hi: float) -> list:
    busy = clip(union(_spans(plane.ops or plane.modules)), lo, hi)
    return subtract([(lo, hi)], busy)


def program_events(plane: DevicePlane, needle: str) -> list:
    """Executions of the program whose name contains ``needle``."""
    return sorted((ev for ev in plane.modules if needle in ev.name),
                  key=lambda ev: ev.start)


def mean_execution_seconds(plane: DevicePlane, needle: str):
    """Mean device time of one execution of a program, or None when
    the trace holds none."""
    evs = program_events(plane, needle)
    if not evs:
        return None
    return sum(ev.dur for ev in evs) / len(evs)


def op_events(plane: DevicePlane, *needles: str) -> list:
    """Operations whose name contains every needle (a kernel's events:
    its call-site name and ``tpu_custom_call``)."""
    return [ev for ev in plane.ops if all(n in ev.name for n in needles)]


def gaps_between(plane: DevicePlane, needle: str) -> list:
    """Idle seconds on the device between consecutive executions of a
    program: the interval from one's end to the next's start, less
    whatever other work ran in it."""
    evs = program_events(plane, needle)
    busy = union(_spans(plane.ops or plane.modules))
    out = []
    for a, b in zip(evs, evs[1:]):
        lo, hi = a.start + a.dur, b.start
        if hi <= lo:
            out.append(0.0)
            continue
        out.append(total(subtract([(lo, hi)], clip(busy, lo, hi))))
    return out


CONTAINER_RE = re.compile(r"^%?(while|conditional|call)[.\s=]")


OP_RE = re.compile(r"^(%?[\w.\-]+) = (\(?[a-z0-9]+\[[^\]]*\])[^ ]* ([\w\-]+)\(")


def short_op_name(name: str) -> str:
    """``%fusion.139 = bf16[32,18944]{...} fusion(...)`` ->
    ``%fusion.139 fusion bf16[32,18944]``: the profiler names an
    operation by its whole HLO line, too long to read in a ledger.  A
    name of another form is kept as it is."""
    m = OP_RE.match(name)
    return f"{m.group(1)} {m.group(3)} {m.group(2)}" if m else name


def top_ops(trace: Trace, n: int = 10) -> list:
    """[[name, seconds]] of the operations that took most device time,
    summed over executions and averaged over devices.  Loops and
    conditionals are left out: their events span the operations inside
    them, which are listed themselves."""
    agg = collections.Counter()
    for d in trace.devices:
        for ev in d.ops:
            if CONTAINER_RE.match(ev.name):
                continue
            agg[ev.name] += ev.dur
    k = max(len(trace.devices), 1)
    return [[short_op_name(name), secs / k]
            for name, secs in agg.most_common(n)]


WAITING = ("acquire", "wait", "sleep", "select", "poll", "iter_tokens",
           "queue.py", "threading.py", "$<unknown> get")


def _owner(live, glo: float, ghi: float, prefer: str):
    """The event of ``live`` (in start order) that the gap [glo, ghi)
    goes to by ``attribute_gaps``' rule; of equals the first wins."""
    half = 0.5 * (ghi - glo)
    inner = most = None
    for ev in live:
        cov = min(ghi, ev.start + ev.dur) - max(glo, ev.start)
        if cov <= 0:
            continue
        if most is None or cov > most[0]:
            most = (cov, ev)
        if cov >= half:
            key = (not ev.name.startswith(prefer), ev.dur)
            if inner is None or key < inner[0]:
                inner = (key, ev)
    return inner[1] if inner else most[1] if most else None


def attribute_gaps(trace: Trace, lo: float, hi: float, n: int = 10,
                   prefer: str = "bench/") -> list:
    """[[label, seconds]]: the first device's idle time inside [lo, hi),
    grouped by what the host was doing in each gap.

    A gap goes to the *innermost* host span that covers at least half
    of it: the shortest such span, which says most about what ran.  A
    span of the benchmark's own (``prefer``) wins over others.  Spans
    in which a thread only waits (locks, queues, sleeps: ``WAITING``)
    are passed over, since some thread is always waiting.  A gap no
    span half covers goes to the working span that covers most of it,
    or to ``(no host span)``.  Of equals the first in start order wins.

    One sweep over gaps and events together, both by start: an event
    is ``live`` from the first gap that ends after its start until a
    gap begins at or after its end (the gaps are disjoint, so it can
    cover none later), and a gap looks at the live events alone: the
    threads' open frames and what starts inside it (14 to 38 in the
    cells' captures), not at every event that began before it.  A
    capture twice as long, or of a program twice as fast, costs twice
    as much."""
    if not trace.devices:
        return []
    host = sorted((ev for ev in trace.host
                   if not any(w in ev.name for w in WAITING)),
                  key=lambda ev: ev.start)
    agg = collections.Counter()
    live, nxt = [], 0
    for glo, ghi in idle_gaps(trace.devices[0], lo, hi):
        live = [ev for ev in live if ev.start + ev.dur > glo]
        while nxt < len(host) and host[nxt].start < ghi:
            if host[nxt].start + host[nxt].dur > glo:
                live.append(host[nxt])
            nxt += 1
        pick = _owner(live, glo, ghi, prefer)
        agg[pick.name if pick else "(no host span)"] += ghi - glo
    return [[name, secs] for name, secs in agg.most_common(n)]


# -- loaders ---------------------------------------------------------------------


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return hits[-1]


def load_xplane(path: str) -> Trace:
    """Read a profiler capture.  Device planes are those named
    ``/device:TPU:<n>`` (any ``/device:`` plane that has an operations
    line); their "XLA Ops" line gives operations and "XLA Modules"
    program executions.  Every line of every ``/host:`` plane is a
    thread of host spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [Event(ev.name, ev.start_ns * 1e-9,
                                 ev.duration_ns * 1e-9)
                           for ev in line.events]
                elif line.name == "XLA Modules":
                    modules = [Event(ev.name, ev.start_ns * 1e-9,
                                     ev.duration_ns * 1e-9)
                               for ev in line.events]
            if ops or modules:
                devices.append(DevicePlane(plane.name, ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(ev.name, ev.start_ns * 1e-9,
                                  ev.duration_ns * 1e-9)
                            for ev in line.events)
    devices.sort(key=lambda d: d.name)
    return Trace(devices, host)


def load_json(path: str) -> Trace:
    """The hand-built fixture format: ``{"devices": [{"name", "ops":
    [[name, start, dur], ...], "modules": [...]}], "host": [...]}``."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        raw = json.load(f)
    return Trace(
        [DevicePlane(d["name"], [Event(*e) for e in d.get("ops", [])],
                     [Event(*e) for e in d.get("modules", [])])
         for d in raw["devices"]],
        [Event(*e) for e in raw.get("host", [])])
