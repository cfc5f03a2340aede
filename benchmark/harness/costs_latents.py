"""Operations and bytes of what a decoder with latent attention of two
kinds (``dots3_note``) adds to ``costs_moe`` and ``costs_share``: the
absorbed decode kernel over the window layers' RINGS, by the blocks
their walks reach.

As ``harness/costs.py``: what the mathematics requires, nothing a
particular schedule adds (the zero tail of a stored row: 1,088 values
lie in 1,152; the blocks a walk's last step repeats.  A block's rows
outside the query's window are the one exception: a walk is counted in
whole blocks, as the step reports it, so the bytes are a little over
the window's own rows and a share comes out a little high, never past
what the kernel moved).  ``cfg`` is a configuration file (the source's
own key names).
"""

from __future__ import annotations


def window_layers(cfg: dict) -> int:
    """Window layers that run: the ``sliding_attention`` entries among
    the first ``num_hidden_layers`` of ``layer_types``."""
    return cfg["layer_types"][:cfg["num_hidden_layers"]].count(
        "sliding_attention")


def full_layers(cfg: dict) -> int:
    """Full layers that run: the ``full_attention`` entries among the
    first ``num_hidden_layers`` of ``layer_types`` (the layers whose
    prefill pieces run ``prefix_flash_latent``)."""
    return cfg["layer_types"][:cfg["num_hidden_layers"]].count(
        "full_attention")


def window_row_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """Bytes of one cached position of one window layer as the kernel
    must move it: the normed kv latent and the one rotary key (2,176 in
    bf16 at the published sizes; stored 2,304 wide)."""
    return (cfg["swa_kv_lora_rank"] + cfg["swa_qk_rope_head_dim"]
            ) * bytes_per_value


def latent_window_step(cfg: dict, kv_window_blocks: float, block_size: int,
                       lanes: int) -> tuple:
    """(operations, bytes) of the windowed absorbed decode kernel's
    calls of ONE step, every window layer and lane: a layer reads
    ``kv_window_blocks`` blocks of its rings (``engine/step``: what the
    lanes' windows reach, by the kernel's own walk rule), each row once
    as key and as value: every head's query meets it over rank + rope
    values and every head's probability weighs its rank values; the
    absorbed queries (rank + rope a head) come in and the latent
    outputs (rank a head) go out once a layer."""
    h = cfg["swa_num_attention_heads"]
    rank, rope = cfg["swa_kv_lora_rank"], cfg["swa_qk_rope_head_dim"]
    rows = kv_window_blocks * block_size
    n = window_layers(cfg)
    flops = n * 2.0 * h * (2 * rank + rope) * rows
    nbytes = n * (rows * window_row_bytes(cfg)
                  + 2.0 * lanes * h * (2 * rank + rope))
    return flops, nbytes
