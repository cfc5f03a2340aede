"""Plain reference of the MiMo-V2.5 language model's block (``mimo_v2``;
the model of MiMo-V2-Flash), as one chip of an expert-parallel
deployment computes it.

As published (``XiaomiMiMo/MiMo-V2.5`` ``config.json``; the vision and
audio towers and the multi-token-prediction layers are not run).
Pre-norm decoder layer, RMSNorm ``n(x) = x / sqrt(mean(x^2) + eps) *
scale``, no bias anywhere::

    h = x + Attn_l(n1(x))
    y = h + F_l(n2(h))

**Attention** of layer ``l``.  ``hybrid_layer_pattern[l]`` is 0 for a
*full* layer and 1 for a *window* layer.  ``H = num_attention_heads``
query heads in both; ``G = num_key_value_heads`` KV heads in a full
layer, ``swa_num_key_value_heads`` in a window layer; a query and a key
head are ``d_k = head_dim`` wide and a value head ``d_v = v_head_dim``
(the ``swa_`` keys say the same sizes for a window layer)::

    q = n W_q -> [T, H, d_k]   k = n W_k -> [T, G, d_k]
    v = attention_value_scale * (n W_v) -> [T, G, d_v]
    q, k: the first int(partial_rotary_factor * d_k) values of a head
          rotated (half-split) at the token's position, base rope_theta
          (full) or swa_rope_theta (window); the rest pass
    s[t, j] = q_t . k_j / sqrt(d_k)   for j <= t (full)
                                      or t - sliding_window < j <= t
    full:   p[t, j] = exp(s[t, j] - m) / sum_i exp(s[t, i] - m)
    window: p[t, j] = exp(s[t, j] - m)
                      / (exp(b_h - m) + sum_i exp(s[t, i] - m)),
            m = max(b_h, max_i s[t, i])
    o_t = sum_j p[t, j] v_j -> [H, d_v]     (query head i reads KV head
                                             i // (H / G))
    Attn = concat(o) W_o                    (H d_v -> hidden)

``b_h`` is one learned float a query head of a window layer
(``add_swa_attention_sink_bias``; the tree's ``attention/sink/bias``
[H]): it takes mass in the denominator and carries no value, no row
stands behind it.  A full layer has none
(``add_full_attention_sink_bias`` false).

**F** is a SwiGLU ``Wdown (silu(Wgate x) * (Wup x))`` of width
``intermediate_size`` where ``moe_layer_freq[l]`` is 0 and the expert
layer where it is 1::

    s = sigmoid(x Wr)            [all experts of the deployment], float32
    chosen = top num_experts_per_tok of s + b      b: correction bias
    g = s[chosen] / (sum s[chosen] + 1e-20)        (norm_topk_prob)
    F(x) = sum_{e chosen AND held here} g_e E_e(x)

every ``E`` a SwiGLU of width ``moe_intermediate_size``, the gate on the
expert's output, no scaling of the gates (``routed_scaling_factor``
null) and no shared expert (``n_shared_experts`` null).  **The share**:
the router is as wide as the deployment has experts (its kernel's
width); the weight tree holds the kernels of ``n_routed_experts`` of
them, experts ``[experts_offset, experts_offset + n_routed_experts)``,
and what the other chips' experts would add is left out, here as in the
program; the residual is whole.  Logits are ``n_f(x) Whead`` over the
vocabulary slice the tree holds (no tying).

Everything is float32 with ``precision=HIGHEST`` on every matmul.  No
kernels, no cache, no ring, no batching: one sequence, layer by layer,
every layer's scores over every key under its mask.  So that 64 heads
over 26 k rows fit beside 7 GB of weights, the work is cut into pieces
that change no number's definition: attention one KV head's group of
query heads at a time and within it in blocks of query rows (each block
sees every key and masks what it may not), the dense layer's hidden
width in slices, the experts one after another over every token.

Departures from the published description, each the configuration
file's ``assumed``: the sink in the denominator only; the value scale
on the projected values; half-split rotary layout; no query/key norm;
``attention_chunk_size`` changes no equation.  Besides: the weights
arrive in the type the benchmark made them in (bf16 for serving) and
are widened to float32 a piece at a time; the sequence is padded to a
multiple of ``PAD`` (padding sits after every real position, so
causality keeps it invisible); logits are computed only at the
positions asked for.

``cfg`` is the configuration file (the source's own keys); the weight
tree is what ``weights.make_params`` fills for the program's
``MoeLmModel``: ``layer_<i>/attention/{query,key,value,out}`` and, in a
window layer, ``attention/sink/bias``; ``layer_<i>/{mlp |
moe/{router,bias,experts}}``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
PAD = 512          # sequence lengths are padded to a multiple of this
Q_BLOCK = 256      # query rows per attention block
FFN_SLICE = 2048   # hidden columns of a dense SwiGLU at a time
ROW_BLOCK = 4352   # positions per block of the position-wise F


def _f32(x):
    return x.astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(_f32(a), _f32(b), precision=HIGHEST)


def rms_norm(x, scale, eps):
    x = _f32(x)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(scale)


def rope(x, positions, theta: float, share: float):
    """x [S, H, hd]: the first ``int(share * hd)`` values of each head
    rotated (half-split) at ``positions`` [S], frequencies ``theta **
    (-2i / r)`` over the rotated ``r`` values; the rest as they are."""
    r = int(x.shape[-1] * share)
    freqs = 1.0 / float(theta) ** (
        jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x[..., :r], 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., r:]], -1)


def swiglu(x, w, slices: int = 1):
    """``Wdown (silu(Wgate x) * (Wup x))``, the hidden width taken in
    ``slices`` equal runs of columns and summed."""
    gate, up, down = (w[k]["kernel"] for k in ("wi_gate", "wi_up", "wo"))
    hidden = gate.shape[-1]

    def run(y, i):
        cols = jax.lax.dynamic_slice_in_dim
        g = cols(gate, i * (hidden // slices), hidden // slices, axis=1)
        u = cols(up, i * (hidden // slices), hidden // slices, axis=1)
        d = cols(down, i * (hidden // slices), hidden // slices, axis=0)
        return y + _mm(jax.nn.silu(_mm(x, g)) * _mm(x, u), d), None

    y, _ = jax.lax.scan(run, jnp.zeros(x.shape[:-1] + (down.shape[-1],),
                                       jnp.float32), jnp.arange(slices))
    return y


def _blocks(n: int, want: int) -> int:
    """The largest block of at most ``want`` rows that divides ``n``."""
    return next(b for b in range(min(want, n), 0, -1) if n % b == 0)


def grouped_attention(q, k, v, positions, window, sink):
    """Causal attention of one sequence; q [S, H, dk], k [S, KV, dk],
    v [S, KV, dv], KV head ``g`` serving query heads ``[g H/KV, (g+1)
    H/KV)``.  Query ``i`` sees key ``j`` iff ``0 <= i - j`` and, under a
    ``window``, ``i - j < window``.  ``sink`` [H] or None: a logit a
    query head that joins the softmax's denominator and has no value.
    One KV head at a time, query rows in blocks."""
    s, h, dk = q.shape
    kv, dv = k.shape[1], v.shape[-1]
    blk = _blocks(s, Q_BLOCK)
    scale = dk ** -0.5

    def one_group(args):
        q_g, k_g, v_g, b_g = args     # [S, H/KV, dk], [S, dk], [S, dv]

        def block(rows):
            q_b, pos_b = rows
            scores = jnp.einsum("qhd,kd->hqk", q_b, k_g,
                                precision=HIGHEST) * scale
            back = pos_b[:, None] - positions[None, :]
            ok = back >= 0
            if window is not None:
                ok &= back < window
            scores = jnp.where(ok[None], scores, -jnp.inf)
            m = scores.max(axis=-1, keepdims=True)
            if sink is not None:
                m = jnp.maximum(m, b_g[:, None, None])
            e = jnp.exp(scores - m)
            total = e.sum(axis=-1, keepdims=True)
            if sink is not None:
                total = total + jnp.exp(b_g[:, None, None] - m)
            return jnp.einsum("hqk,kd->qhd", e / total, v_g,
                              precision=HIGHEST)

        out = jax.lax.map(block, (q_g.reshape(-1, blk, h // kv, dk),
                                  positions.reshape(-1, blk)))
        return out.reshape(s, h // kv, dv)

    b = jnp.zeros((h,), jnp.float32) if sink is None else _f32(sink)
    out = jax.lax.map(one_group, (
        q.reshape(s, kv, h // kv, dk).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2),
        b.reshape(kv, h // kv)))
    return out.transpose(1, 0, 2, 3).reshape(s, h, dv)


def attention(n, a, cfg, layer: int, positions):
    """``Attn_l`` of the normed rows ``n`` [S, D]."""
    s = n.shape[0]
    heads = cfg["num_attention_heads"]
    windowed = cfg["hybrid_layer_pattern"][layer] == 1
    if windowed:
        kv, dk, dv = (cfg["swa_num_key_value_heads"], cfg["swa_head_dim"],
                      cfg["swa_v_head_dim"])
        theta, window = cfg["swa_rope_theta"], cfg["sliding_window"]
        sink = (a["sink"]["bias"] if cfg["add_swa_attention_sink_bias"]
                else None)
    else:
        kv, dk, dv = (cfg["num_key_value_heads"], cfg["head_dim"],
                      cfg["v_head_dim"])
        theta, window = cfg["rope_theta"], None
        sink = (a["sink"]["bias"] if cfg["add_full_attention_sink_bias"]
                else None)
    share = cfg["partial_rotary_factor"]
    q = rope(_mm(n, a["query"]["kernel"]).reshape(s, heads, dk),
             positions, theta, share)
    k = rope(_mm(n, a["key"]["kernel"]).reshape(s, kv, dk), positions,
             theta, share)
    v = cfg["attention_value_scale"] * _mm(
        n, a["value"]["kernel"]).reshape(s, kv, dv)
    o = grouped_attention(q, k, v, positions, window, sink)
    return _mm(o.reshape(s, heads * dv), a["out"]["kernel"])


def gates(n, m, cfg):
    """Gate of every expert of the deployment for every token [S, E]:
    zero where the expert was not chosen."""
    s = jax.nn.sigmoid(_mm(n, m["router"]["kernel"]))
    _, chosen = jax.lax.top_k(s + _f32(m["bias"]),
                              cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    g = picked
    if cfg.get("norm_topk_prob", True):
        g = g / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    g = g * (cfg.get("routed_scaling_factor") or 1.0)
    rows = jnp.arange(s.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(g)


def routed_part(n, m, cfg):
    """What the experts held here add: experts ``[experts_offset,
    experts_offset + held)`` of the router's, ``held`` the kernels the
    tree has."""
    stacked = {k: v["kernel"] for k, v in m["experts"].items()}
    held = stacked["wo"].shape[0]
    g = jax.lax.dynamic_slice_in_dim(
        gates(n, m, cfg), cfg.get("experts_offset", 0), held, axis=1)

    def one(y, expert):
        w, g_e = expert
        return y + g_e[:, None] * swiglu(n, jax.tree.map(
            lambda k: {"kernel": k}, w)), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(n), (stacked, g.T))
    return y


def expert_layer(n, m, cfg):
    return routed_part(n, m, cfg)       # no shared expert


def block(x, w, cfg, layer: int, positions):
    """Decoder layer ``layer`` on x [S, D]; ``w`` is its weights.  ``F``
    acts on each position alone, so it is taken in blocks of rows."""
    eps = cfg["layernorm_epsilon"]
    x = x + attention(rms_norm(x, w["attn_norm"]["scale"], eps),
                      w["attention"], cfg, layer, positions)

    def f(rows):
        n = rms_norm(rows, w["mlp_norm"]["scale"], eps)
        if cfg["moe_layer_freq"][layer] == 1:
            return rows + expert_layer(n, w["moe"], cfg)
        hidden = w["mlp"]["wo"]["kernel"].shape[0]
        return rows + swiglu(n, w["mlp"],
                             slices=hidden // _blocks(hidden, FFN_SLICE))

    blk = _blocks(x.shape[0], ROW_BLOCK)
    return jax.lax.map(f, x.reshape(-1, blk, x.shape[-1])).reshape(x.shape)


_KEYS = ("num_hidden_layers", "num_attention_heads",
         "num_key_value_heads", "swa_num_key_value_heads", "head_dim",
         "v_head_dim", "swa_head_dim", "swa_v_head_dim",
         "attention_value_scale", "add_swa_attention_sink_bias",
         "add_full_attention_sink_bias", "partial_rotary_factor",
         "rope_theta", "swa_rope_theta", "sliding_window",
         "layernorm_epsilon", "num_experts_per_tok", "norm_topk_prob",
         "routed_scaling_factor", "experts_offset")
_LISTS = ("hybrid_layer_pattern", "moe_layer_freq")


def _static(cfg: dict) -> tuple:
    """The keys the forward pass reads, hashable (a jit's static
    argument); ``_dynamic`` is its inverse."""
    n = cfg["num_hidden_layers"]
    return (tuple((k, cfg.get(k)) for k in _KEYS if k in cfg)
            + tuple((k, tuple(cfg[k][:n])) for k in _LISTS))


def _dynamic(cfg_items) -> dict:
    return dict(cfg_items)


def _logits(params, tokens, rows, cfg_items):
    """Logits [len(rows), V] of one padded sequence: embedding, the
    layers one after another, the final norm and the head at ``rows``."""
    cfg = _dynamic(cfg_items)
    x = _f32(jnp.take(params["token_embed"]["embedding"], tokens, axis=0))
    positions = jnp.arange(tokens.shape[0])
    for i in range(cfg["num_hidden_layers"]):
        x = block(x, params[f"layer_{i}"], cfg, i, positions)
    return _mm(rms_norm(x[rows], params["final_norm"]["scale"],
                        cfg["layernorm_epsilon"]),
               params["lm_head"]["kernel"])


def _pad(tokens, n_rows, pad_to, rows_to):
    padded = max(-(-tokens // PAD) * PAD, int(pad_to or 0))
    return padded, max(int(rows_to or 0), n_rows)


def logits_at(params, cfg: dict, tokens, positions, pad_to=None,
              rows_to=None):
    """Float32 logits [len(positions), V] of one sequence at the given
    positions (row i predicts token i + 1), at the padded shapes of
    ``served_gaps``."""
    import numpy as np

    n = len(positions)
    padded, rows_n = _pad(len(tokens), n, pad_to, rows_to)
    toks = np.zeros(padded, np.int32)
    toks[:len(tokens)] = np.asarray(tokens)
    rows = np.zeros(rows_n, np.int32)
    rows[:n] = np.asarray(positions)
    return _logits_jit(params, toks, rows, _static(cfg))[:n]


_logits_jit = jax.jit(_logits, static_argnums=(3,))


@functools.partial(jax.jit, static_argnums=(4,))
def _gaps_jit(params, tokens, rows, served, cfg_items):
    lg = _logits(params, tokens, rows, cfg_items)
    got = jnp.take_along_axis(lg, served[:, None], axis=-1)[:, 0]
    return lg.max(axis=-1) - got


def served_gaps(params, cfg: dict, prompt, served, pad_to=None,
                rows_to=None):
    """For one finished request: at every served position, how far the
    served token's reference logit lies below the reference's best
    (zero where the served token is the reference's own first choice).
    Returns a numpy array [len(served)].  Everything on the device runs
    at the padded shapes (``pad_to`` positions, ``rows_to`` served
    rows), so one compiled program serves a whole traffic mix."""
    import numpy as np

    n = len(served)
    seq = list(prompt) + list(served[:-1])
    padded, rows_n = _pad(len(seq), n, pad_to, rows_to)
    tokens = np.zeros(padded, np.int32)
    tokens[:len(seq)] = seq
    rows = np.zeros(rows_n, np.int32)
    rows[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    want = np.zeros(rows_n, np.int32)
    want[:n] = served
    gaps = _gaps_jit(params, tokens, rows, want, _static(cfg))
    return np.asarray(gaps)[:n]
