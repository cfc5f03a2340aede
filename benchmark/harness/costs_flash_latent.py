"""Operations of a prefill call's attention over LATENT rows (the walk
that ``pallas_kernels.prefix_flash_latent`` runs), for a decoder whose
attention chooses ``index_topk`` of the rows a query sees
(``deepseek_v32``: the source's own key names).

As ``harness/costs_flash.py``, whose counting of positions this reuses:
what the mathematics requires.  A query at position p SEES ``min(p + 1,
index_topk)`` rows, whatever the dense walk computes under its mask:
QK^T over ``qk_nope_head_dim + qk_rope_head_dim`` and PV over
``v_head_dim`` for every head; and each row the call's queries can see
is up-projected once a layer (``kv_lora_rank`` into a key's and a
value's head sizes for every head).  Where the call's position is known
only in whole tiles (the span's ``rows``) it is taken at its least, so
the count is never more than the kernel computed and a share of the
matrix unit's peak built on it cannot pass 100%.  ``cfg`` is a
configuration file.
"""

from __future__ import annotations

from benchmark.harness import costs_flash


def call_flops(cfg: dict, rows: int, padded: int, tokens: int) -> dict:
    """``{"attend": operations, "up_project": operations}`` of one
    call's attention, every layer: its ``tokens`` real queries (of
    ``padded``) from ``costs_flash.first_position_least`` on, two
    operations a multiply-add (2 x 128 x (192 + 128) a pair seen and 2
    x 512 x 128 x (128 + 128) a row at the published sizes)."""
    first = costs_flash.first_position_least(rows, padded)
    heads, layers = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    per_pair = 2.0 * heads * (cfg["qk_nope_head_dim"]
                              + cfg["qk_rope_head_dim"]
                              + cfg["v_head_dim"])
    per_row = 2.0 * heads * cfg["kv_lora_rank"] * (
        cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
    return {
        "attend": layers * per_pair * costs_flash.visible_pairs(
            first, tokens, cfg.get("index_topk") or None),
        "up_project": layers * per_row * (first + tokens)}
