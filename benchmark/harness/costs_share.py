"""Operations and bytes of what one chip's share of a ``deepseek_v32``
deployment adds to ``costs_moe``: the index scores of a paged decode
step and the grouped matmuls over the experts held here.

As ``harness/costs.py``: what the mathematics requires, nothing a
particular schedule adds (the ``-inf`` tail of a lane's scores, tile
padding).  ``cfg`` is a configuration file (the source's own key
names; ``n_routed_experts`` counts the experts held).
"""

from __future__ import annotations

from benchmark.harness import costs_moe


def index_key_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """Bytes of one cached position's index key in one layer (256 in
    bf16 at the published sizes)."""
    return cfg["index_head_dim"] * bytes_per_value


def index_scores_call(cfg: dict, rows: float, lanes: int,
                      q_len: int = 1) -> tuple:
    """(operations, bytes) of ONE call of ``paged_index_scores``: one
    layer, one step, every lane.  ``rows`` index keys (what the lanes
    hold, summed: ``engine/step``'s ``rows_scored``) are read once and
    meet every head of their lane's query: 2 x heads x dim operations a
    row for the products, 3 x heads for ReLU, weight and sum; a float32
    score a row goes out; queries and their heads' weights come in
    once."""
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    flops = rows * q_len * hi * (2.0 * di + 3.0)
    nbytes = (rows * index_key_bytes(cfg) + rows * q_len * 4.0
              + lanes * q_len * hi * (2.0 * di + 4.0))
    return flops, nbytes


def held_gmm_layer_call(cfg: dict, experts_hit: float, rows: float,
                        bytes_per_weight: int = 2) -> tuple:
    """(operations, bytes) of ONE expert layer's routed SwiGLU over the
    experts held here: ``costs_moe.gmm_layer_call`` with ``rows`` the
    (token, choice) pairs that fell on held experts (the step's pairs x
    ``routed_here``) and ``experts_hit`` of the held experts reached.
    Rows routed elsewhere cost the mathematics nothing."""
    return costs_moe.gmm_layer_call(cfg, experts_hit, rows,
                                    bytes_per_weight)
