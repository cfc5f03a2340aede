"""The builder's planted faults for a ``serve_sink`` cell: ONE fault
planted in the program, then ``benchmark/run.py``'s own ``main`` and its
own comparison, so that what ``correct`` says of each fault is the
harness's verdict under the traffic file's limits and no reading judged
on paper.  The driver never runs this; the readings are in PERF.md
section 2.

    python3 -m benchmark.harness.faults_sink <fault> --seed <n>
        [--workload mimo-v25-1chip.agent-context] [--seconds 51]

- ``nosink``: a window layer's softmax starts from no sink (its logits
  read -inf, the parameter stays in the tree and in the reference): what
  a kernel or a piece's walk that forgot its starting maximum and sum
  would serve.
- ``noscale``: the projected values are not scaled
  (``attention_value_scale`` left out).
- ``window127``: a window layer's query sees one key fewer than its
  window, in a piece's walk, in the ring's mask and in the paged kernel.
- ``neighbour``: a decode step's paged read takes every lane's rows from
  the NEXT lane's block table, in both kinds of layer: wrong rows, the
  fault ``served_gap_max`` is held against.

The reference imports nothing of the program, so it stays sound.
"""

from __future__ import annotations

import argparse
import contextlib
import sys

FAULTS = ("nosink", "noscale", "window127", "neighbour")


@contextlib.contextmanager
def planted(fault: str):
    """The program with ``fault`` in it, put right again on the way
    out."""
    import jax.numpy as jnp

    from tensorflow_train_distributed_tpu.models import layers
    from tensorflow_train_distributed_tpu.ops import pallas_kernels as pk

    mha = layers.MultiHeadAttention
    sound = {(mha, "_sink_logits"): mha._sink_logits,
             (mha, "_value"): mha._value,
             (mha, "_cache_attend"): mha._cache_attend,
             (pk, "paged_attention"): pk.paged_attention,
             (pk, "ring_mask"): pk.ring_mask}

    def narrower(fn):
        def call(*args, window=None, **kw):
            return fn(*args, window=(None if window is None
                                     else window - 1), **kw)
        return call

    if fault == "nosink":
        def no_sink(self):
            out = sound[mha, "_sink_logits"](self)
            return None if out is None else jnp.full_like(out, -jnp.inf)

        mha._sink_logits = no_sink
    elif fault == "noscale":
        mha._value = lambda self, x, kv_heads: self._proj(
            x, kv_heads, "value", self._v_dim)
    elif fault == "window127":
        mha._cache_attend = narrower(sound[mha, "_cache_attend"])
        pk.paged_attention = narrower(sound[pk, "paged_attention"])
        pk.ring_mask = lambda lengths, q_len, rows, window: sound[
            pk, "ring_mask"](lengths, q_len, rows, window - 1)
    elif fault == "neighbour":
        def next_lanes(q, k_pool, v_pool, table, lengths, **kw):
            return sound[pk, "paged_attention"](
                q, k_pool, v_pool, jnp.roll(table, -1, axis=0), lengths,
                **kw)

        pk.paged_attention = next_lanes
    else:
        raise ValueError(f"unknown fault {fault!r}: one of {FAULTS}")
    try:
        yield
    finally:
        for (owner, name), fn in sound.items():
            setattr(owner, name, fn)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("fault", choices=FAULTS)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--workload", default="mimo-v25-1chip.agent-context")
    p.add_argument("--seconds", type=float, default=51.0)
    args = p.parse_args(argv)
    from benchmark import run

    with planted(args.fault):
        return run.main(["--workload", args.workload, "--seed",
                         str(args.seed), "--seconds", str(args.seconds),
                         "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
