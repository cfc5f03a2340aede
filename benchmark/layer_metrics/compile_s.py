"""Seconds of backend compilation during set-up (``jax.monitoring``
compile durations up to the opening of the window).  Layer: CLI /
launcher and set-up.  Moves ``setup_s``."""


def read(ctx):
    return ctx["setup"]["compile_s"]
