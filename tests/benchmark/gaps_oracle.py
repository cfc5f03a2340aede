"""The oracle of ``trace.attribute_gaps``: the function as it stood before
PR 28, its body unchanged (every idle gap against every host event, so
G x H: a 4 s capture of ``glm47-flash-1chip.ctx-decode`` did not reduce
in 23 minutes).  The sweep that took its place has to give the same
list, label for label and second for second; the tests hold it to this
one on the fixtures and on generated traces."""

import collections

from benchmark.harness import trace as trace_lib
from benchmark.harness.trace import WAITING


def attribute_gaps(trace, lo: float, hi: float, n: int = 10,
                   prefer: str = "bench/") -> list:
    """[[label, seconds]]: the first device's idle time inside [lo, hi),
    grouped by what the host was doing in each gap.

    A gap goes to the *innermost* host span that covers at least half
    of it: the shortest such span, which says most about what ran.  A
    span of the benchmark's own (``prefer``) wins over others.  Spans
    in which a thread only waits (locks, queues, sleeps: ``WAITING``)
    are passed over, since some thread is always waiting.  A gap no
    span half covers goes to the working span that covers most of it,
    or to ``(no host span)``."""
    if not trace.devices:
        return []
    host = sorted((ev for ev in trace.host
                   if not any(w in ev.name for w in WAITING)),
                  key=lambda ev: ev.start)
    agg = collections.Counter()
    for glo, ghi in trace_lib.idle_gaps(trace.devices[0], lo, hi):
        half = 0.5 * (ghi - glo)
        inner = most = None
        for ev in host:
            if ev.start >= ghi:
                break
            cov = min(ghi, ev.start + ev.dur) - max(glo, ev.start)
            if cov <= 0:
                continue
            if most is None or cov > most[0]:
                most = (cov, ev)
            if cov >= half:
                key = (not ev.name.startswith(prefer), ev.dur)
                if inner is None or key < inner[0]:
                    inner = (key, ev)
        pick = inner[1] if inner else most[1] if most else None
        agg[pick.name if pick else "(no host span)"] += ghi - glo
    return [[name, secs] for name, secs in agg.most_common(n)]
