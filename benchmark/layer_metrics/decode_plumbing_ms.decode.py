"""Device time a decode step spends moving the KV pools about: inside
the executions of the ``_decode_chunk`` program, the operations under
none of the program's scopes (``harness/scopes.py``) that are not the
attention kernel, per decode step.  By exclusion these are the slices,
updates, reshapes and copies of whole pools that the layer loop
carries.  The table by scope goes to the log.  Layer: engine programs.
Moves ``serve_tokens_per_s``."""

from benchmark.harness import scopes


def read(ctx):
    tracer = ctx["tracer"]
    if tracer is None:
        return None
    ops, programs = scopes.load(tracer.directory)
    runs = [ev for ev in programs if "_decode_chunk" in ev.name]
    seconds = scopes.by_scope(ops, runs) if runs else None
    if seconds is None:
        return None
    chunk = ctx["result"]["counters"]["chunk"]
    steps = len(runs) * chunk
    table = {k: 1e3 * v / steps for k, v in sorted(
        seconds.items(), key=lambda kv: -kv[1])}
    ctx["log"](phase="decode_ms_per_step_by_scope", executions=len(runs),
               by_scope_ms=table, sum_ms=sum(table.values()),
               program_ms=1e3 * sum(ev.dur for ev in runs) / steps,
               # An execution the capture's edge cut short counts as a
               # whole one above, as in decode_step_ms.decode; the
               # longest one is whole.
               longest_execution_ms_per_step=1e3 * max(
                   ev.dur for ev in runs) / chunk)
    return table.get(scopes.PLUMBING, 0.0)
