"""Idle time of the device that no span of the program explains: the
share of the first device's idle seconds in the traced window that no
working span of the program's contract below ``engine/step`` (any but
the step itself and the ``*/wait``s), on any thread, covers.  The spans
are the capture's own host events, on the device's clock.  Idle seconds
by owner (the innermost such span) go to the log, and so does what is
left, by where it lies: under a step and none of its children, under a
``*/wait``, between steps, and at the capture's edges, before the first
span that began inside it or after the last (a span open when the
capture starts is not in it).  Layer: device.  Moves ``gap_p90_ms``."""

import collections

from benchmark.harness import spans, trace


def read(ctx):
    tr = ctx["trace"]
    program = spans.program_spans(tr)
    steps = [ev for ev in program if ev.name == spans.STEP]
    if not steps or not tr.devices:
        return None
    lo, hi = ctx["trace_window"]
    left = trace.idle_gaps(tr.devices[0], lo, hi)
    idle = unowned = trace.total(left)
    owned = collections.Counter()
    owners = [ev for ev in program if ev.name != spans.STEP
              and not ev.name.endswith("/wait")]
    for ev in sorted(owners, key=lambda ev: ev.dur):   # innermost first
        left = trace.subtract(left, [(ev.start, ev.start + ev.dur)])
        owned[ev.name] += unowned - trace.total(left)
        unowned = trace.total(left)

    # What is left, by where it lies (a wait may be in the capture
    # without its step, which was open when the capture began).
    first = min(ev.start for ev in program)
    last = max(ev.start + ev.dur for ev in program)
    rest = trace.clip(left, first, last)
    places = {"capture edges": unowned - trace.total(rest)}
    for place, named in (("*/wait", lambda name: name.endswith("/wait")),
                         ("engine/step alone", spans.STEP.__eq__)):
        outside = trace.subtract(rest, trace.union(
            (ev.start, ev.start + ev.dur) for ev in program
            if named(ev.name)))
        places[place] = trace.total(rest) - trace.total(outside)
        rest = outside
    places["between steps"] = trace.total(rest)
    ctx["log"](phase="idle_by_span", window_s=hi - lo, idle_s=idle,
               by_span_s=dict(owned.most_common()), unowned_s=unowned,
               unowned_by_place_s=places)
    return 100.0 * unowned / idle if idle > 0 else 0.0
