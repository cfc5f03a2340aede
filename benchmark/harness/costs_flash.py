"""Operations of a prefill call's attention over plain K/V rows (the
walk that ``pallas_kernels.prefix_flash_attention`` runs), for a decoder
whose full and window layers have head counts and sizes of their own
(``mimo_v2``: ``costs_sink``'s key names).

As ``harness/costs.py``: what the mathematics requires.  Only the
(query, key) pairs a query SEES are counted: a full layer's query at
position p sees p + 1 keys, a window layer's ``min(p + 1, window)``;
not the masked half of a tile on the diagonal, not the keys of a tile
that lie behind a window, not the rows a padded query adds.  Where the
call's position is known only in whole tiles (the span's ``rows``) it
is taken at its least, so the count is never more than the kernel
computed and a share of the matrix unit's peak built on it cannot pass
100%.  ``cfg`` is a configuration file (the source's own key names).
"""

from __future__ import annotations

from benchmark.harness import costs_sink

#: ``ops.attention.PREFIX_TILE``: the tile ``prefill/dispatch``'s
#: ``rows`` is counted in (the host's rule, whatever the kernel's own).
ROWS_TILE = 512


def first_position_least(rows: int, padded: int, tile: int = ROWS_TILE):
    """The least position the first query of a call of ``padded``
    queries can have when the walk of its last piece reached ``rows``
    cache rows in whole tiles of ``tile``: the call's last query sits
    at ``end - 1`` with ``rows - tile < end <= rows``."""
    return max(0, rows - tile + 1 - padded)


def visible_pairs(first: int, queries: int, window=None) -> int:
    """(query, key) pairs that ``queries`` consecutive queries from
    position ``first`` see: ``p + 1`` keys at position p, at most
    ``window``."""
    if window is None:
        return queries * first + queries * (queries + 1) // 2
    ramp = max(0, min(queries, window - 1 - first))   # p + 1 < window
    seen = ramp * first + ramp * (ramp + 1) // 2
    return seen + (queries - ramp) * window


def call_flops(cfg: dict, rows: int, padded: int, tokens: int) -> dict:
    """``{"full": operations, "window": operations}`` of one call's
    attention, every layer of each kind: its ``tokens`` real queries
    (of ``padded``) from ``first_position_least`` on, QK^T over
    ``head_dim`` and PV over ``v_head_dim`` for every query head, two
    operations a multiply-add (2 x 64 x (192 + 128) a pair at the
    published sizes)."""
    first = first_position_least(rows, padded)
    layers = costs_sink.layers_by_kind(cfg)
    out = {}
    for kind, window in (("full", None),
                         ("window", cfg["sliding_window"])):
        pre = "swa_" if kind == "window" else ""
        per_pair = 2.0 * cfg[pre + "num_attention_heads"] * (
            cfg[pre + "head_dim"] + cfg[pre + "v_head_dim"])
        out[kind] = layers[kind] * per_pair * visible_pairs(
            first, tokens, window)
    return out
