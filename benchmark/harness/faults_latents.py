"""The builder's planted faults for a ``serve_latents`` cell: ONE fault
planted in the program, then ``benchmark/run.py``'s own ``main`` and its
own comparison, so that what ``correct`` says of each fault is the
harness's verdict under the traffic file's limits and no reading judged
on paper.  The driver never runs this; the readings are in PERF.md
section 2.

    python3 -m benchmark.harness.faults_latents <fault> --seed <n>
        [--workload dots3-note-1chip.transcript-notes] [--seconds 51]

- ``window512``: a window layer's query sees one key fewer than its
  window, in a piece's walk and in the paged step (kernel and gathered
  ring alike).
- ``ringoff``: a decode step writes its ring row one table entry off
  (the insert's copy of the ring is sound): the rows a later step reads
  at their own entries are stale.
- ``nokvscale``: ``a_kv`` left out of the window kind: its normalised
  kv latent goes into the cache and the up-projection unscaled (the
  query latent and the full kind keep theirs).
- ``neighbour``: a decode step's absorbed kernel takes every lane's
  rows through the NEXT lane's table, in both kinds of layer (a window
  layer's ring, a full layer's chosen rows): wrong rows, the fault
  ``served_gap_max`` is held against.

The reference imports nothing of the program, so it stays sound.
"""

from __future__ import annotations

import argparse
import contextlib
import sys

FAULTS = ("window512", "ringoff", "nokvscale", "neighbour")


@contextlib.contextmanager
def planted(fault: str):
    """The program with ``fault`` in it, put right again on the way
    out."""
    import jax.numpy as jnp

    from tensorflow_train_distributed_tpu.models import layers
    from tensorflow_train_distributed_tpu.ops import attention
    from tensorflow_train_distributed_tpu.ops import pallas_kernels as pk

    latent = layers.LatentAttention
    sound = {(attention, "prefix_attention"): attention.prefix_attention,
             (pk, "paged_latent_attention"): pk.paged_latent_attention,
             (layers, "_paged_dest"): layers._paged_dest,
             (latent, "_rows"): latent._rows,
             (latent, "_rescaled"): latent._rescaled}

    def narrower(fn):
        inside = []             # the walk calls itself by query blocks

        def call(*args, window=None, **kw):
            if inside or window is None:
                return fn(*args, window=window, **kw)
            inside.append(fn)
            try:
                return fn(*args, window=window - 1, **kw)
            finally:
                inside.pop()
        return call

    if fault == "window512":
        attention.prefix_attention = narrower(
            sound[attention, "prefix_attention"])
        pk.paged_latent_attention = narrower(
            sound[pk, "paged_latent_attention"])
    elif fault == "ringoff":
        def next_entry(table, positions, block_size, blocks, ring_of=None):
            if ring_of is not None:
                table = jnp.roll(table, -1, axis=1)
            return sound[layers, "_paged_dest"](
                table, positions, block_size, blocks, ring_of)

        layers._paged_dest = next_entry
    elif fault == "nokvscale":
        making_rows_of = []     # the window kind's rows, while made

        def rows(self, x, positions):
            making_rows_of.append(self.window is not None)
            try:
                return sound[latent, "_rows"](self, x, positions)
            finally:
                making_rows_of.pop()

        def rescaled(self, c, d_model):
            if making_rows_of and making_rows_of[-1]:
                return c
            return sound[latent, "_rescaled"](self, c, d_model)

        latent._rows, latent._rescaled = rows, rescaled
    elif fault == "neighbour":
        def next_lanes(q, pool, table, lengths, **kw):
            return sound[pk, "paged_latent_attention"](
                q, pool, jnp.roll(table, -1, axis=0), lengths, **kw)

        pk.paged_latent_attention = next_lanes
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
    p.add_argument("--workload",
                   default="dots3-note-1chip.transcript-notes")
    p.add_argument("--seconds", type=float, default=51.0)
    args = p.parse_args(argv)
    from benchmark import run

    with planted(args.fault):
        return run.main(["--workload", args.workload, "--seed",
                         str(args.seed), "--seconds", str(args.seconds),
                         "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
