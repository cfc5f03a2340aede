"""Rows attended over rows held, in percent, over the decode steps of
the capture: ``engine/step``'s ``rows_selected`` over its
``rows_scored``, the counts the decode chunk made on the device over
its live lanes and returned with its tokens (means over the chunk's
steps and layers).  What the learned selection leaves of a decode
step's attention: the latent rows it reads of those the lane holds.  A
prefill piece is left out: it walks every row held with the choice as
a mask (``prefill/piece``'s ``rows``), so the choice saves it nothing.
Layer: engine host loop.  Moves ``serve_tokens_per_s``."""

from benchmark.harness import spans


def read(ctx):
    got = spans.window_steps(ctx)
    if not got:
        return None
    steps = [s.attrs for s in got[0]
             if s.traced and s.attrs.get("rows_scored")]
    scored = sum(a["rows_scored"] for a in steps)
    if not scored:
        return None
    selected = sum(a["rows_selected"] for a in steps)
    ctx["log"](phase="rows_selected_share.longctx", steps=len(steps),
               rows_scored=scored, rows_selected=selected)
    return 100.0 * selected / scored
