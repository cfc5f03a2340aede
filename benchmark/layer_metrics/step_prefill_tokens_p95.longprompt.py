"""Prompt tokens that share an engine step with a decode chunk: the
95th percentile of ``prefill_tokens`` (real prompt tokens of the pieces
the step ran, as the engine counts them) over the ``engine/step`` spans
of the window that also dispatched a chunk.  Every token of the cell
waits on such a step.  Read from the program's ring of spans.  Layer:
engine host loop.  Moves ``gap_p90_ms``."""

from benchmark.harness import spans, stats


def read(ctx):
    got = spans.window_steps(ctx)
    if not got:
        return None
    steps, dropped = got
    ran = [s for s in steps if s.attrs.get("lanes")]
    if not ran:
        return None
    tokens = [s.attrs.get("prefill_tokens", 0) for s in ran]
    ctx["log"](phase="step_prefill_tokens", steps=len(steps),
               dispatched=len(ran), ring_dropped=dropped,
               with_prefill=sum(t > 0 for t in tokens),
               prefill_tokens=stats.summarize(tokens),
               pieces=stats.summarize([s.attrs.get("pieces", 0)
                                       for s in ran]),
               # requests still in the engine's queue when a step ends
               queued=stats.summarize([s.attrs.get("queued", 0)
                                       for s in steps]),
               budget=ctx["traffic"].get("engine", {}).get(
                   "prefill_budget"))
    return stats.percentile(tokens, 95.0)
