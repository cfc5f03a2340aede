"""Operations and bytes of what a decoder whose attention layers differ
by a pattern (``laguna``) adds to ``costs_moe``: the paged decode
attention over two kinds of cache, by the blocks each kind's walk
reaches.

As ``harness/costs.py``: what the mathematics requires, nothing a
particular schedule adds (the blocks a walk's last step repeats, a
block's rows past the lane's length are the one exception: a walk is
counted in whole blocks, as the step reports it, so the bytes are a
little over the rows' own and a share comes out a little high, never
past what the kernel moved).  ``cfg`` is a configuration file (the
source's own key names).
"""

from __future__ import annotations


def kv_row_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """Bytes of one cached position of one layer: a key and a value of
    every KV head (4,096 in bf16 at the published sizes)."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * bytes_per_value


def layers_by_kind(cfg: dict) -> dict:
    """``{"full": [query heads of each full layer that runs],
    "window": [...]}`` over the first ``num_hidden_layers`` entries of
    the file's per-layer lists."""
    out = {"full": [], "window": []}
    for i in range(cfg["num_hidden_layers"]):
        kind = {"full_attention": "full",
                "sliding_attention": "window"}[cfg["layer_types"][i]]
        out[kind].append(cfg["num_attention_heads_per_layer"][i])
    return out


def paged_attention_step(cfg: dict, kv_blocks: float,
                         kv_window_blocks: float, block_size: int,
                         lanes: int) -> tuple:
    """(operations, bytes) of the decode attention kernel's calls of ONE
    step, every layer and lane: a full layer reads ``kv_blocks`` blocks
    (``engine/step``: what the lanes hold), a window layer
    ``kv_window_blocks`` (what their windows reach), each block once as
    keys and values; a layer's ``H`` query heads meet every row read
    (QK^T and PV: 4 x H x head_dim operations a row); queries come in
    and outputs go out once a layer."""
    hd = cfg["head_dim"]
    row = kv_row_bytes(cfg)
    flops = nbytes = 0.0
    for kind, blocks in (("full", kv_blocks), ("window", kv_window_blocks)):
        for heads in layers_by_kind(cfg)[kind]:
            rows = blocks * block_size
            flops += 4.0 * heads * hd * rows
            nbytes += rows * row + 2.0 * 2 * lanes * heads * hd
    return flops, nbytes
