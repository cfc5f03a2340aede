"""Operations and bytes of what a decoder whose full and window layers
differ in their KV heads and whose key and value heads differ in size
(``mimo_v2``) adds to ``costs_moe``: the paged decode attention over
two kinds of cache with two row shapes, by the blocks each kind's walk
reaches.

As ``harness/costs.py``: what the mathematics requires, nothing a
particular schedule adds (the zeros a 192-wide head's queries are
padded with to whole lane tiles, the neighbour's columns a head's
contraction then meets; the blocks a walk's last step repeats.  A
block's rows past the lane's length are the one exception: a walk is
counted in whole blocks, as the step reports it, so the bytes are a
little over the rows' own and a share comes out a little high, never
past what the kernel moved).  The sink costs a logit a head and no
row.  ``cfg`` is a configuration file (the source's own key names).
"""

from __future__ import annotations

KINDS = {0: "full", 1: "window"}


def kv_row_bytes(cfg: dict, kind: str, bytes_per_value: int = 2) -> int:
    """Bytes of one cached position of one layer of ``kind``: a key of
    ``head_dim`` and a value of ``v_head_dim`` of every KV head the
    kind has (2,560 in a full layer and 5,120 in a window layer, bf16,
    at the published sizes)."""
    pre = "swa_" if kind == "window" else ""
    return (cfg[pre + "num_key_value_heads"]
            * (cfg[pre + "head_dim"] + cfg[pre + "v_head_dim"])
            * bytes_per_value)


def layers_by_kind(cfg: dict) -> dict:
    """``{"full": layers of that kind that run, "window": ...}`` over
    the first ``num_hidden_layers`` entries of
    ``hybrid_layer_pattern``."""
    run = cfg["hybrid_layer_pattern"][:cfg["num_hidden_layers"]]
    return {name: run.count(flag) for flag, name in KINDS.items()}


def paged_attention_step(cfg: dict, kv_blocks: float,
                         kv_window_blocks: float, block_size: int,
                         lanes: int) -> tuple:
    """(operations, bytes) of the decode attention kernel's calls of ONE
    step, every layer and lane: a full layer reads ``kv_blocks`` blocks
    (``engine/step``: what the lanes hold), a window layer
    ``kv_window_blocks`` (what their windows reach), each block once as
    keys and values at the kind's own row; a layer's ``H`` query heads
    meet every row read (QK^T over ``head_dim`` and PV over
    ``v_head_dim``: 2 x H x (head_dim + v_head_dim) operations a row,
    40,960 at the published sizes); queries come in and outputs go out
    once a layer."""
    flops = nbytes = 0.0
    for kind, blocks in (("full", kv_blocks), ("window", kv_window_blocks)):
        pre = "swa_" if kind == "window" else ""
        heads = cfg[pre + "num_attention_heads"]
        dk, dv = cfg[pre + "head_dim"], cfg[pre + "v_head_dim"]
        rows = blocks * block_size
        n = layers_by_kind(cfg)[kind]
        flops += n * 2.0 * heads * (dk + dv) * rows
        nbytes += n * (rows * kv_row_bytes(cfg, kind)
                       + 2.0 * lanes * heads * (dk + dv))
    return flops, nbytes
