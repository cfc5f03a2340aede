"""Idle time of the device that no span of the program explains, in the
closed-loop cells: ``idle_unowned_pct.longprompt``'s reduction (the
capture's contract spans against the first device's idle intervals,
the innermost working span below ``engine/step`` owning what it
covers) with the stage spans as owners: ``prefill/stage``,
``prefill/cache``, ``prefill/dispatch``, ``prefill/insert``,
``decode/stage``.  Idle seconds by owner and what is left by place go
to the log.  ``None`` for a program without those spans.  Layer:
device.  Moves ``serve_tokens_per_s``."""

from benchmark.harness import step_stages


def read(ctx):
    return step_stages.read_idle_unowned(ctx, "idle_by_span.serve")
