"""Operations and bytes the algorithm needs, from shapes alone.

These are the numerators of every utilization the benchmark prints.
They count what the mathematics requires: recomputation under remat is
not counted, attention under a sliding window is counted as windowed,
and the embedding lookup costs no operations.  ``cfg`` is a
configuration file's ``model`` object (the source's own key names).
"""

from __future__ import annotations


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    k = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return d, h, k, hd, cfg["intermediate_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one decoder layer that take part in a matmul: q, k,
    v, o and the three SwiGLU matrices (biases and norms excluded)."""
    d, h, k, hd, f, _, _ = _dims(cfg)
    return d * h * hd + 2 * d * k * hd + h * hd * d + 3 * d * f


def matmul_params(cfg: dict) -> int:
    """Matmul weights of the whole model as cut: layers and the output
    head.  The input embedding is a lookup and is not counted."""
    d, _, _, _, _, v, n = _dims(cfg)
    return n * layer_matmul_params(cfg) + d * v


def mean_attended_keys(seq: int, window=None) -> float:
    """Mean number of keys a query attends in one causal sequence of
    ``seq`` tokens; query i sees min(i + 1, window) keys."""
    if not window or window >= seq:
        return (seq + 1) / 2.0
    return (window * (window + 1) / 2.0 + (seq - window) * window) / seq


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward operations per token at sequence length ``seq``: two per
    matmul weight, plus QK^T and PV over the keys each query attends."""
    _, h, _, hd, _, _, n = _dims(cfg)
    attn = 4.0 * h * hd * mean_attended_keys(seq, cfg.get("sliding_window"))
    return 2.0 * matmul_params(cfg) + n * attn


def weight_bytes(cfg: dict, bytes_per_weight: int = 2) -> int:
    """Bytes of weights one decode step reads: every matmul weight once,
    plus norms and q/k/v biases (the embedding table is only gathered,
    ``batch`` rows of it, and is left out)."""
    d, h, k, hd, _, _, n = _dims(cfg)
    small = n * 2 * d + d
    if cfg.get("attention_bias"):
        small += n * (h * hd + 2 * k * hd)
    return (matmul_params(cfg) + small) * bytes_per_weight


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    """Bytes of keys and values one cached position holds, all layers."""
    _, _, k, hd, _, _, n = _dims(cfg)
    return 2 * n * k * hd * bytes_per_value


def decode_step_bytes(cfg: dict, lane_lengths, bytes_per_weight: int = 2,
                      bytes_per_value: int = 2) -> float:
    """Bytes one decode step over the given lanes has to move: the
    weights once and each lane's keys and values at its real length."""
    return (weight_bytes(cfg, bytes_per_weight)
            + kv_bytes_per_token(cfg, bytes_per_value)
            * float(sum(lane_lengths)))


def paged_attention_call(cfg: dict, lane_lengths) -> tuple:
    """(operations, bytes) of ONE call of the decode attention kernel:
    one layer, one step, every lane.  Each lane's query (H heads) meets
    that lane's cached keys and values (K heads) once: QK^T and PV are
    4 * H * hd operations a cached position; keys and values are read
    once (2 * K * hd values a position), queries read and outputs
    written once."""
    _, h, k, hd, _, _, _ = _dims(cfg)
    positions = float(sum(lane_lengths))
    lanes = len(lane_lengths)
    flops = 4.0 * h * hd * positions
    nbytes = 2.0 * (2 * k * hd * positions + 2 * lanes * h * hd)
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least seconds the chip could take, which side bounds it)."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def share_pct(numerator: float, denominator: float, what: str) -> float:
    """A share of a peak in percent.  A reading above 105% means the
    operations or bytes are counted too high, or the time leaves out
    part of the work: that is a bug in the count, so it raises."""
    pct = 100.0 * numerator / denominator
    if pct > 105.0:
        raise ValueError(
            f"{what} reads {pct:.1f}% of its peak: the count or the time "
            f"is wrong ({numerator:.6g} / {denominator:.6g})")
    return pct
