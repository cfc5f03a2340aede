"""Operations and bytes of what a decoder with delta-rule
linear-attention layers (``bailing_hybrid``) adds to ``costs_moe``: the
recurrence of a linear layer, a step at a time (the decode kernel) and
over a prefill call's rows (the scan).

As ``harness/costs.py``: what the mathematics requires, nothing a
particular schedule adds.  The recurrence ITSELF is counted, a token
and head at a time (decay the state, read it against k, write the
correction, read it against q: 8 x d_k x d_v operations), so that a
later change of the chunk size or of the kernel leaves the count alone:
the chunked form's triangular solve and its products within a chunk are
a schedule's, not the mathematics'.  ``cfg`` is a configuration file
(the source's own key names).
"""

from __future__ import annotations


def linear_layers(cfg: dict) -> int:
    """How many of the layers that run are linear: all but those where
    ``(i + 1) % layer_group_size == 0``."""
    return sum((i + 1) % cfg["layer_group_size"] != 0
               for i in range(cfg["num_hidden_layers"]))


def state_bytes(cfg: dict) -> int:
    """Bytes of one lane's state in one linear layer: heads x d_k x d_v
    float32 (2,097,152 at the published sizes)."""
    return cfg["num_attention_heads"] * cfg["head_dim"] ** 2 * 4


def token_flops(cfg: dict) -> float:
    """Operations of the recurrence for one token in one layer."""
    return 8.0 * cfg["num_attention_heads"] * cfg["head_dim"] ** 2


def state_step_call(cfg: dict, lanes: int) -> tuple:
    """(operations, bytes) of ONE call of ``delta_state_step``: one
    layer, one step, every lane.  A lane's state is read once and
    written once; beside it the step's rows come in (q, k and the log
    decay a key channel, v and the write strength a value channel as
    the kernel takes it, float32) and o goes out."""
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    rows = lanes * h * (3 * d + 2 * d + d) * 4
    return lanes * token_flops(cfg), 2.0 * lanes * state_bytes(cfg) + rows


def scan_call(cfg: dict, rows: float, calls: float = 1.0) -> tuple:
    """(operations, bytes) of the recurrence of ONE linear layer over
    ``rows`` real tokens in ``calls`` prefill calls: a token's q, k, v
    in (bf16), its log decay in and its o out (float32), and the
    lane's state read and written once a call."""
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    moved = rows * h * d * (3 * 2 + 4 + 4)
    return rows * token_flops(cfg), moved + 2.0 * calls * state_bytes(cfg)
