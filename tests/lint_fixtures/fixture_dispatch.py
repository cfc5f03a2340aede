"""Planted dispatch-purity / jit hazards (see __init__.py).

Stub decorators keep the module import-free for the AST checker.
"""
import os
import time


def dispatch_critical(fn):
    return fn


class jax:                                  # noqa: N801 — AST stand-in
    @staticmethod
    def jit(fn=None, **kw):
        return fn if fn is not None else (lambda f: f)


class jnp:                                  # noqa: N801
    @staticmethod
    def zeros(n):
        return [0] * n


class np:                                   # noqa: N801 — AST stand-in
    class random:                           # noqa: N801
        @staticmethod
        def rand():
            return 0.5


@dispatch_critical
def dispatch_window(carry, toks):
    # PLANTED: four host-sync hazards inside the decode window.
    toks.block_until_ready()                # finding
    first = float(toks)                     # finding
    if os.environ.get("TTD_NO_TRACE"):      # finding: slow env read
        pass
    t = time.time()                         # finding: wall clock
    return first, t


@jax.jit
def traced_step(x):
    # PLANTED: trace-time nondeterminism + host sync inside jit.
    t = time.monotonic()                    # finding
    r = np.random.rand()                    # finding: frozen at trace
    print(x)                                # finding
    return x.item() + t + r                 # finding


def _static_arg_hazard():
    f = jax.jit(lambda n, x: x, static_argnums=(0,))
    x = jnp.zeros(4)
    return f(jnp.zeros(2), x)               # finding: traced static arg
