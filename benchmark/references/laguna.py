"""Plain reference of the Laguna-S-2.1 block (``laguna``), as one chip
of an expert-parallel deployment computes it.

As published (``poolside/Laguna-S-2.1`` ``config.json``).  Pre-norm
decoder layer, RMSNorm ``n(x) = x / sqrt(mean(x^2) + eps) * scale``::

    h = x + Attn_l(n1(x))
    y = h + F_l(n2(h))

**Attention** of layer ``l`` (grouped queries: ``H_l =
num_attention_heads_per_layer[l]`` query heads over
``num_key_value_heads`` KV heads of ``head_dim``; no bias, no
query/key norm)::

    q = n W_q -> [T, H_l, hd]      k = n W_k, v = n W_v -> [T, KV, hd]
    q, k = rope_l(q), rope_l(k)
    P = softmax(q k^T / sqrt(hd)  over the keys j that query i sees)
    o = P v                        each KV head serving H_l / KV heads
    g = sigmoid(n W_g) -> [T, H_l] o <- o * g[..., None]   (per-head gate)
    Attn = concat(o) W_o

``layer_types[l]`` says what a query sees and how it is rotated, by
``rope_parameters[layer_types[l]]``.  ``full_attention``: every ``j <=
i``; the first ``partial_rotary_factor x hd`` values of a head are
rotated (half-split) at YaRN's frequencies (each of the frequencies
blended between itself and itself / factor by a linear ramp between the
correction dims of ``beta_fast`` and ``beta_slow`` over
``original_max_position_embeddings``) with cos and sin multiplied by
``attention_factor``; the rest of the head passes unrotated and
unscaled.  ``sliding_attention``: ``0 <= i - j < sliding_window``; the
whole head rotated at ``rope_theta``, no scaling.

**F** is a SwiGLU ``Wdown (silu(Wgate x) * (Wup x))`` of width
``intermediate_size`` where ``mlp_layer_types[l]`` is ``dense`` and the
expert layer where it is ``sparse``::

    s = sigmoid(x Wr)            [all experts of the deployment], float32
    chosen = top num_experts_per_tok of s + b      b: correction bias
    g = s[chosen] / (sum s[chosen] + 1e-20) * moe_routed_scaling_factor
    F(x) = sum_{e chosen AND held here} g_e E_e(x) + E_shared(x)

every ``E`` a SwiGLU of width ``moe_intermediate_size``, the gate on the
expert's output.  **The share**: the router is as wide as the
deployment has experts (its kernel's width); the weight tree holds the
kernels of ``num_experts`` of them, experts ``[experts_offset,
experts_offset + num_experts)``, and what the other chips' experts
would add is left out, here as in the program; the shared expert and
the residual are whole.  Logits are ``n_f(x) Whead`` over the
vocabulary slice the tree holds (no tying).

Everything is float32 with ``precision=HIGHEST`` on every matmul.  No
kernels, no cache, no ring, no batching: one sequence, layer by layer,
every layer's scores over every key under its mask.  So that 72 heads
over 17 k rows fit beside 6 GB of weights, the work is cut into pieces
that change no number's definition: attention one KV head's group of
query heads at a time and within it in blocks of query rows (each block
sees every key and masks what it may not), the dense layer's hidden
width in slices, the experts one after another over every token.

Departures from the published description, each the configuration
file's ``assumed``: the router scores by sigmoid with a correction bias
in the choice; the gate is a sigmoid of a bias-free projection of the
layer's normed input; half-split rotary layout with
``attention_factor`` on the rotated part alone; pre-norm residuals.
Besides: the weights arrive in the type the benchmark made them in
(bf16 for serving) and are widened to float32 a piece at a time; the
sequence is padded to a multiple of ``PAD`` (padding sits after every
real position, so causality keeps it invisible); logits are computed
only at the positions asked for.

``cfg`` is the configuration file (the source's own keys); the weight
tree is what ``weights.make_params`` fills for the program's
``MoeLmModel``: ``layer_<i>/attention/{query,key,value,gate,out}``,
``layer_<i>/{mlp | moe/{router,bias,experts,shared_mlp}}``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
PAD = 512          # sequence lengths are padded to a multiple of this
Q_BLOCK = 512      # query rows per attention block
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


def inv_freq(dim: int, rule: dict):
    """The ``dim / 2`` rotary frequencies of one kind of layer
    (``rope_parameters[kind]``): plain, or YaRN's."""
    theta = float(rule["rope_theta"])
    freqs = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if rule["rope_type"] == "default":
        return freqs
    if rule["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {rule['rope_type']!r}")
    factor = float(rule["factor"])
    old = float(rule["original_max_position_embeddings"])

    def correction_dim(turns):
        return (dim * math.log(old / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(rule["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rule["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return freqs / factor * ramp + freqs * (1.0 - ramp)


def rope(x, positions, rule: dict):
    """x [S, H, hd]: the first ``partial_rotary_factor x hd`` values of
    each head rotated (half-split) at ``positions`` [S], cos and sin
    times ``attention_factor``; the rest as they are."""
    r = int(x.shape[-1] * float(rule.get("partial_rotary_factor", 1)))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq(r, rule)
    m = float(rule.get("attention_factor", 1.0))
    sin, cos = m * jnp.sin(ang)[:, None, :], m * jnp.cos(ang)[:, None, :]
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


def grouped_attention(q, k, v, positions, window):
    """Causal attention of one sequence; q [S, H, hd], k, v [S, KV, hd],
    KV head ``g`` serving query heads ``[g H/KV, (g+1) H/KV)``.  Query
    ``i`` sees key ``j`` iff ``0 <= i - j`` and, under a ``window``,
    ``i - j < window``.  One KV head at a time, query rows in blocks."""
    s, h, hd = q.shape
    kv = k.shape[1]
    blk = _blocks(s, Q_BLOCK)
    scale = hd ** -0.5

    def one_group(args):
        q_g, k_g, v_g = args                 # [S, H/KV, hd], [S, hd] x 2

        def block(rows):
            q_b, pos_b = rows
            scores = jnp.einsum("qhd,kd->hqk", q_b, k_g,
                                precision=HIGHEST) * scale
            back = pos_b[:, None] - positions[None, :]
            ok = back >= 0
            if window is not None:
                ok &= back < window
            p = jax.nn.softmax(jnp.where(ok[None], scores, -jnp.inf),
                               axis=-1)
            return jnp.einsum("hqk,kd->qhd", p, v_g, precision=HIGHEST)

        out = jax.lax.map(block, (q_g.reshape(-1, blk, h // kv, hd),
                                  positions.reshape(-1, blk)))
        return out.reshape(s, h // kv, hd)

    out = jax.lax.map(one_group, (
        q.reshape(s, kv, h // kv, hd).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(1, 0, 2, 3).reshape(s, h, hd)


def attention(n, a, cfg, layer: int, positions):
    """``Attn_l`` of the normed rows ``n`` [S, D]."""
    s = n.shape[0]
    hd, kv = cfg["head_dim"], cfg["num_key_value_heads"]
    heads = cfg["num_attention_heads_per_layer"][layer]
    kind = cfg["layer_types"][layer]
    rule = cfg["rope_parameters"][kind]
    window = (cfg["sliding_window"] if kind == "sliding_attention"
              else None)
    q = rope(_mm(n, a["query"]["kernel"]).reshape(s, heads, hd),
             positions, rule)
    k = rope(_mm(n, a["key"]["kernel"]).reshape(s, kv, hd), positions,
             rule)
    v = _mm(n, a["value"]["kernel"]).reshape(s, kv, hd)
    o = grouped_attention(q, k, v, positions, window)
    gating = cfg["gating_types"][layer]
    if gating == "per_head":
        o = o * jax.nn.sigmoid(_mm(n, a["gate"]["kernel"]))[..., None]
    elif gating is not None:
        raise ValueError(f"unknown gating {gating!r}")
    return _mm(o.reshape(s, heads * hd), a["out"]["kernel"])


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
    g = g * cfg["moe_routed_scaling_factor"]
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
    return routed_part(n, m, cfg) + swiglu(n, m["shared_mlp"])


def block(x, w, cfg, layer: int, positions):
    """Decoder layer ``layer`` on x [S, D]; ``w`` is its weights.  ``F``
    acts on each position alone, so it is taken in blocks of rows."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(rms_norm(x, w["attn_norm"]["scale"], eps),
                      w["attention"], cfg, layer, positions)

    def f(rows):
        n = rms_norm(rows, w["mlp_norm"]["scale"], eps)
        if cfg["mlp_layer_types"][layer] == "sparse":
            return rows + expert_layer(n, w["moe"], cfg)
        hidden = w["mlp"]["wo"]["kernel"].shape[0]
        return rows + swiglu(n, w["mlp"],
                             slices=hidden // _blocks(hidden, FFN_SLICE))

    blk = _blocks(x.shape[0], ROW_BLOCK)
    return jax.lax.map(f, x.reshape(-1, blk, x.shape[-1])).reshape(x.shape)


_KEYS = ("num_hidden_layers", "num_key_value_heads", "head_dim",
         "rms_norm_eps", "num_experts_per_tok", "norm_topk_prob",
         "moe_routed_scaling_factor", "sliding_window", "experts_offset")
_LISTS = ("layer_types", "mlp_layer_types", "gating_types",
          "num_attention_heads_per_layer")


def _static(cfg: dict) -> tuple:
    """The keys the forward pass reads, hashable (a jit's static
    argument); ``_dynamic`` is its inverse."""
    n = cfg["num_hidden_layers"]
    return (tuple((k, cfg.get(k)) for k in _KEYS if k in cfg)
            + tuple((k, tuple(cfg[k][:n])) for k in _LISTS)
            + (("rope_parameters", tuple(
                (kind, tuple(sorted(rule.items())))
                for kind, rule in sorted(cfg["rope_parameters"].items()))),))


def _dynamic(cfg_items) -> dict:
    cfg = dict(cfg_items)
    cfg["rope_parameters"] = {kind: dict(rule)
                              for kind, rule in cfg["rope_parameters"]}
    return cfg


def _logits(params, tokens, rows, cfg_items):
    """Logits [len(rows), V] of one padded sequence: embedding, the
    layers one after another, the final norm and the head at ``rows``."""
    cfg = _dynamic(cfg_items)
    x = _f32(jnp.take(params["token_embed"]["embedding"], tokens, axis=0))
    positions = jnp.arange(tokens.shape[0])
    for i in range(cfg["num_hidden_layers"]):
        x = block(x, params[f"layer_{i}"], cfg, i, positions)
    return _mm(rms_norm(x[rows], params["final_norm"]["scale"],
                        cfg["rms_norm_eps"]),
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
