"""Share of the traced window in which no operation ran on the device
(one minus the union of the device's operation intervals over the
window).  Layer: device."""

from benchmark.harness import trace


def read(ctx):
    return trace.idle_share_pct(ctx["trace"], *ctx["trace_window"])
