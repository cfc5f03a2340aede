"""Operations and bytes of the latent-attention, routed-expert block
(``glm4_moe_lite``), from shapes and the counts a step reports.

As ``harness/costs.py``: what the mathematics requires, nothing a
particular schedule adds (tile padding of the grouped matmuls, the
zero tail of a stored cache row).  ``cfg`` is a configuration file
(the source's own key names).
"""

from __future__ import annotations


def gmm_layer_call(cfg: dict, experts_hit: float, rows: int,
                   bytes_per_weight: int = 2) -> tuple:
    """(operations, bytes) of ONE expert layer's routed SwiGLU in one
    step: the three grouped matmuls over ``rows`` token copies (tokens
    x experts per token) that reach ``experts_hit`` distinct experts.
    Weights of the experts hit are read once (three kernels of
    hidden x expert width each); the rows go in to the gate and up
    products, their float32 results out, the bf16 hidden rows in to the
    down product and its float32 rows out."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = 2.0 * rows * 3 * d * f
    weights = experts_hit * 3 * d * f * bytes_per_weight
    moved = rows * (2 * d * 2 + 2 * f * 4 + f * 2 + d * 4)
    return flops, weights + moved


def latent_row_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """Bytes of one cached position of one layer: the normed kv latent
    and the one rotary key (1,152 in bf16 at the published sizes)."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * bytes_per_value


def latent_attention_call(cfg: dict, blocks: float, block_size: int,
                          lanes: int, q_len: int = 1) -> tuple:
    """(operations, bytes) of ONE call of the absorbed decode kernel:
    one layer, one step, every lane.  The ``blocks`` the lanes walk are
    read once, a row serving as key and as value: every head's query
    meets it over rank + rope values and every head's probability
    weighs its rank values; queries are read and outputs written
    once."""
    h = cfg["num_attention_heads"]
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    rows = blocks * block_size
    flops = 2.0 * q_len * h * (2 * rank + rope) * rows
    nbytes = (rows * latent_row_bytes(cfg)
              + 2.0 * lanes * q_len * h * (2 * rank + rope))
    return flops, nbytes


def experts_hit_expected(experts: int, rows: int) -> float:
    """Distinct experts ``rows`` uniform, independent choices reach."""
    return experts * (1.0 - (1.0 - 1.0 / experts) ** rows)
