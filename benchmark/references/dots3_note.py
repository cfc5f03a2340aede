"""Plain reference of dots3-note-prev's language model (``dots3_note``),
as one chip of an expert-parallel deployment computes it.

As published (``dots-studio/dots3-note-prev`` ``config.json``; the two
towers are not run).  Pre-norm decoder layer, RMSNorm ``n(x) = x /
sqrt(mean(x^2) + eps) * scale``::

    h = x + Attn_l(n1(x))
    y = h + F(n2(h))

**Attention** is latent attention of TWO kinds.  Layer ``l`` is *full*
where ``layer_types[l] == "full_attention"`` and *window* where
``"sliding_attention"``; a window layer reads its sizes under the
``swa_`` keys (heads, ranks, key and value sizes, rotary base), a full
layer under the plain ones.  With ``H`` heads, ranks ``r_q`` / ``r_kv``,
a key of ``n + p`` and a value of ``v``::

    c_q  = a_q * norm(x Wq_a)              [r_q]    a_q  = sqrt(D / r_q)
    q    = c_q Wq_b         -> H x (q_n [n] | q_p [p]), q_p rotated
    [c | k_r] = x Wkv_a                    [r_kv | p], k_r rotated
    c_kv = a_kv * norm(c)                           a_kv = sqrt(D / r_kv)
    [k_n | v] = c_kv Wkv_b  -> H x ([n] | [v])
    s[t, j, i] = (q_n[t,i] . k_n[j,i] + q_p[t,i] . k_r[j]) / sqrt(n + p)
    o[t, i]    = sum_j softmax_j(s[t, j, i]) v[j, i]   over the rows t sees
    g[t]       = sigmoid(x Wg)             [H]
    Attn       = concat_i(g[t, i] * o[t, i]) Wo

(``x`` the layer's normed input, ``D`` the hidden size; the one ``k_r``
is shared by every head).  **The rows a query sees**: in a window layer
``t - W < j <= t`` with ``W = sliding_window_size``; in a full layer the
``index_topk`` rows its indexer picks (DeepSeek-V3.2's)::

    qI_h = rope_head((c_q WI_q)[h])        h = 1..Hi, [index_head_dim]
    kI   = rope_head(LayerNorm(x WI_k))    [index_head_dim]
    w_h  = (x WI_w)[h] * Hi^-0.5 * index_head_dim^-0.5
    I[t, j] = sum_h w_h[t] * relu(qI_h[t] . kI[j])          j <= t
    S_t  = the min(index_topk, t + 1) positions of largest I[t, j]
           (ties to the lower position: ``lax.top_k``)

``rope_head`` rotates the leading ``p`` values of an index head at the
full layers' frequencies.  A window layer has no indexer.

**F** is a SwiGLU ``Wdown (silu(Wgate x) * (Wup x))`` of width
``intermediate_size`` in the first ``first_k_dense_replace`` layers and
the expert layer in every other::

    z  = sigmoid(x Wr)          [all experts of the deployment], float32
    chosen = top num_experts_per_tok of z + b     (b: correction bias)
    g = z[chosen] / (sum z[chosen] + 1e-20) * routed_scaling_factor
    F(x) = sum_{i chosen AND held here} g_i E_i(x) + E_shared(x)

every ``E`` a SwiGLU of width ``moe_intermediate_size``.  **The share**:
the router is as wide as the deployment has experts (its kernel's
width); the weight tree holds the kernels of ``n_routed_experts`` of
them, experts ``[experts_offset, experts_offset + n_routed_experts)``,
and what the other chips' experts would add is left out, here as in the
program; the shared expert and the residual are whole.  Logits are
``n_f(x) Whead`` over the vocabulary slice the tree holds (no tying).

Everything is float32 with ``precision=HIGHEST`` on every matmul.  No
kernels, no cache, no ring, no absorption of ``Wkv_b``, no batching: one
sequence, layer by layer, the window and the choice each a mask over
full causal attention.  So that 128 heads over 16 k rows fit beside 9 GB
of weights the work is cut into pieces that change no number's
definition: the selection in blocks of query rows, attention a group of
heads at a time and within it in blocks of query rows (each block sees
every key and masks what it may not), the output projection summed over
the groups, the dense layer's hidden width in slices, the experts one
after another over every token.

Departures from the published description, each under the ``assumed``
key of the configuration file it belongs to:
- ``apply_mla_qkv_lora_rescale``: the two constants ``a_q``, ``a_kv``
  on the normalised latents (the source says only that a rescale is
  applied);
- ``sliding_window_size``: ``W`` keys of which the token itself is one;
- ``attention_gate_type`` / ``swa_attention_gate_type`` ``headwise``:
  one sigmoid a head from the layer's normed input, on the head's
  output before ``Wo``;
- ``indexer``: a window layer has none; index keys in the weights' own
  type with no Hadamard turn (orthogonal: it cancels in the product),
  index scores float32;
- ``rope_layout``: half-split ("rotate_half"), in the attention and in
  the indexer;
- ``weights``: seeded, widened to float32 a piece at a time;
and, as in every reference here: the sequence is padded to a multiple
of ``PAD`` (padding sits after every real position, so causality keeps
it invisible), logits are computed only at the positions asked for,
and the multi-token-prediction block is no part of the next-token
pass.

``cfg`` is the configuration file (the source's own keys); the weight
tree is what ``weights.make_params`` fills for the program's
``MoeLmModel``: ``layer_<i>/attention/{q_a,q_norm,q_b,kv_a,kv_norm,
kv_b,out,gate}`` and, in a full layer, ``{index_q,index_k,
index_k_norm,index_w}``; ``layer_<i>/{mlp | moe/{router,bias,experts,
shared_mlp}}``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
PAD = 512          # sequence lengths are padded to a multiple of this
Q_BLOCK = 256      # query rows per attention block
I_BLOCK = 128      # query rows per block of the selection
HEAD_GROUP = 8     # heads attended at a time
FFN_SLICE = 2048   # hidden columns of a dense SwiGLU at a time
ROW_BLOCK = 4096   # positions per block of the position-wise F


def _f32(x):
    return x.astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(_f32(a), _f32(b), precision=HIGHEST)


def rms_norm(x, scale, eps):
    x = _f32(x)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(scale)


def layer_norm(x, scale, bias, eps):
    x = _f32(x)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * _f32(scale) + _f32(bias)


def kind_sizes(cfg: dict, layer: int) -> dict:
    """Layer ``layer``'s sizes as the file states them: the plain keys
    for a full layer, the ``swa_`` ones for a window layer, under one
    set of names."""
    name = cfg["layer_types"][layer]
    if name not in ("full_attention", "sliding_attention"):
        raise ValueError(f"layer_types[{layer}] = {name!r}")
    window = name == "sliding_attention"
    pre = "swa_" if window else ""
    return {
        "heads": cfg[pre + "num_attention_heads"],
        "r_q": cfg[pre + "q_lora_rank"], "r_kv": cfg[pre + "kv_lora_rank"],
        "nope": cfg[pre + "qk_nope_head_dim"],
        "rope": cfg[pre + "qk_rope_head_dim"],
        "v": cfg[pre + "v_head_dim"],
        "theta": float(cfg[pre + "rope_theta"]),
        "window": cfg["sliding_window_size"] if window else None,
    }


def rope(x, positions, theta: float):
    """x [S, H, r]; half-split rotary embedding at ``positions`` [S],
    frequencies ``theta ** (-2i / r)`` (``rope_scaling`` is null)."""
    r = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = positions.astype(jnp.float32)[:, None] * freqs      # [S, r/2]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def rope_head(x, positions, r: int, theta: float):
    """The indexer's rotation: the leading ``r`` values of x [S, H, hd]."""
    return jnp.concatenate([rope(x[..., :r], positions, theta), x[..., r:]],
                           -1)


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


def chosen_rows(c_q, n, a, cfg, sizes, positions):
    """uint8 [S, S / 8]: row t holds, as packed bits (``unpack_rows``),
    the marks of S_t, the positions query t of a FULL layer attends."""
    s = n.shape[0]
    hi, di, top = (cfg["index_n_heads"], cfg["index_head_dim"],
                   cfg["index_topk"])
    r, theta = sizes["rope"], sizes["theta"]
    k_i = rope_head(layer_norm(
        _mm(n, a["index_k"]["kernel"]), a["index_k_norm"]["scale"],
        a["index_k_norm"]["bias"], 1e-6)[:, None, :], positions, r,
        theta)[:, 0]
    w = _mm(n, a["index_w"]["kernel"]) * (hi ** -0.5 * di ** -0.5)
    kpos = jnp.arange(s)
    blk = _blocks(s, I_BLOCK)

    def block(args):
        c_b, w_b, qpos = args
        q_b = rope_head(_mm(c_b, a["index_q"]["kernel"]).reshape(
            blk, hi, di), qpos, r, theta)
        scores = jax.nn.relu(jnp.einsum("qhd,kd->qhk", q_b, k_i,
                                        precision=HIGHEST))
        index = jnp.einsum("qhk,qh->qk", scores, w_b, precision=HIGHEST)
        visible = kpos[None, :] <= qpos[:, None]
        index = jnp.where(visible, index, -jnp.inf)
        # The top-k as a set, without a scatter: everything above the
        # k-th largest score, and of the rows equal to it the first
        # that fit (``lax.top_k`` breaks ties to the lower position).
        kth = jax.lax.top_k(index, min(top, s))[0][:, -1:]
        above, level = index > kth, index == kth
        room = min(top, s) - jnp.sum(above, axis=-1, keepdims=True)
        marked = above | (level & (jnp.cumsum(level, axis=-1) <= room))
        return jnp.packbits(marked & visible, axis=-1)

    return jax.lax.map(block, (
        c_q.reshape(-1, blk, c_q.shape[-1]), w.reshape(-1, blk, hi),
        positions.reshape(-1, blk))).reshape(s, -1)


def window_rows(positions, window: int):
    """``chosen_rows``'s packed marks for a WINDOW layer: query t sees
    ``t - window < j <= t``."""
    gap = positions[:, None] - positions[None, :]
    return jnp.packbits((gap >= 0) & (gap < window), axis=-1)


def unpack_rows(packed, s: int):
    """Bool [..., s] of the packed marks."""
    return jnp.unpackbits(packed, axis=-1, count=s).astype(jnp.bool_)


def attention(q, k, v, allowed, scale):
    """Attention of one sequence over the rows ``allowed`` (packed
    [S, S / 8]) marks; q, k [S, H, hd], v [S, H, vd].  Query rows are
    taken in blocks."""
    s = q.shape[0]
    blk = _blocks(s, Q_BLOCK)

    def block(args):
        q_b, ok = args
        scores = jnp.einsum("qhd,khd->hqk", q_b, k,
                            precision=HIGHEST) * scale
        p = jax.nn.softmax(
            jnp.where(unpack_rows(ok, s)[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (q.reshape(-1, blk, *q.shape[1:]),
                              allowed.reshape(-1, blk, allowed.shape[-1])))
    return out.reshape(s, *out.shape[2:])


def latent_attention(n, a, cfg, sizes, positions, residual=None):
    """``Attn`` of the normed input n [S, D] for a layer of ``sizes``
    (``kind_sizes``), added to ``residual`` where one is given (the
    groups of heads are summed onto it); ``a`` the layer's ``attention``
    weights."""
    h, nope, rp = sizes["heads"], sizes["nope"], sizes["rope"]
    vd, rank, theta = sizes["v"], sizes["r_kv"], sizes["theta"]
    eps = cfg["rms_norm_eps"]
    s, d = n.shape
    c_q = rms_norm(_mm(n, a["q_a"]["kernel"]), a["q_norm"]["scale"], eps)
    kv = _mm(n, a["kv_a"]["kernel"])
    c_kv = rms_norm(kv[:, :rank], a["kv_norm"]["scale"], eps)
    if cfg["apply_mla_qkv_lora_rescale"]:
        # assumed.apply_mla_qkv_lora_rescale
        c_q = c_q * (d / sizes["r_q"]) ** 0.5
        c_kv = c_kv * (d / rank) ** 0.5
    k_r = rope(kv[:, None, rank:], positions, theta)          # [S, 1, rp]
    if sizes["window"] is None:
        allowed = chosen_rows(c_q, n, a, cfg, sizes, positions)
    else:
        # assumed.sliding_window_size; assumed.indexer
        allowed = window_rows(positions, sizes["window"])
    scale = (nope + rp) ** -0.5
    gate = jax.nn.sigmoid(_mm(n, a["gate"]["kernel"]))        # [S, H]
    g = _blocks(h, HEAD_GROUP)
    q_b = a["q_b"]["kernel"].reshape(-1, h // g, g * (nope + rp))
    kv_b = a["kv_b"]["kernel"].reshape(rank, h // g, g * (nope + vd))
    out = a["out"]["kernel"].reshape(h // g, g * vd, -1)

    def group(y, w):
        wq, wkv, wo, gate_g = w
        q = _mm(c_q, wq).reshape(s, g, nope + rp)
        q = jnp.concatenate(
            [q[..., :nope], rope(q[..., nope:], positions, theta)], -1)
        up = _mm(c_kv, wkv).reshape(s, g, nope + vd)
        k = jnp.concatenate(
            [up[..., :nope], jnp.broadcast_to(k_r, (s, g, rp))], -1)
        att = attention(q, k, up[..., nope:], allowed, scale)
        # assumed.attention_gate_type
        att = att * gate_g[:, :, None]
        return y + _mm(att.reshape(s, g * vd), wo), None

    y, _ = jax.lax.scan(
        group, jnp.zeros((s, out.shape[-1]), jnp.float32)
        if residual is None else residual,
        (jnp.moveaxis(q_b, 1, 0), jnp.moveaxis(kv_b, 1, 0), out,
         jnp.moveaxis(gate.reshape(s, h // g, g), 1, 0)))
    return y


def gates(n, m, cfg):
    """Gate of every expert of the deployment for every token [S, E]:
    zero where the expert was not chosen (``noaux_tc``, one group)."""
    z = jax.nn.sigmoid(_mm(n, m["router"]["kernel"]))
    _, chosen = jax.lax.top_k(z + _f32(m["bias"]),
                              cfg["num_experts_per_tok"])
    g = jnp.take_along_axis(z, chosen, axis=-1)
    if cfg.get("norm_topk_prob", True):
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    g = g * cfg["routed_scaling_factor"]
    rows = jnp.arange(z.shape[0])[:, None]
    return jnp.zeros_like(z).at[rows, chosen].set(g)


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
    """Decoder layer ``layer`` on x [S, D]; ``w`` is that layer's
    weights.  ``F`` acts on each position alone, so it is taken in
    blocks of rows."""
    eps = cfg["rms_norm_eps"]
    x = latent_attention(rms_norm(x, w["attn_norm"]["scale"], eps),
                         w["attention"], cfg, kind_sizes(cfg, layer),
                         positions, residual=x)

    def f(rows):
        n = rms_norm(rows, w["mlp_norm"]["scale"], eps)
        if "moe" in w:
            return rows + expert_layer(n, w["moe"], cfg)
        hidden = w["mlp"]["wo"]["kernel"].shape[0]
        return rows + swiglu(n, w["mlp"],
                             slices=hidden // _blocks(hidden, FFN_SLICE))

    blk = _blocks(x.shape[0], ROW_BLOCK)
    return jax.lax.map(f, x.reshape(-1, blk, x.shape[-1])).reshape(x.shape)


_KEYS = ("num_hidden_layers", "rms_norm_eps", "sliding_window_size",
         "apply_mla_qkv_lora_rescale", "num_experts_per_tok",
         "norm_topk_prob", "routed_scaling_factor", "index_n_heads",
         "index_head_dim", "index_topk", "experts_offset")
_KIND_KEYS = ("num_attention_heads", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "rope_theta")


def _static(cfg: dict) -> tuple:
    keys = _KEYS + _KIND_KEYS + tuple("swa_" + k for k in _KIND_KEYS)
    return tuple((k, cfg[k]) for k in keys if k in cfg) + (
        ("layer_types", tuple(cfg["layer_types"])),)


def _logits(params, tokens, rows, cfg_items):
    """Logits [len(rows), V] of one padded sequence: embedding, the
    layers one after another, the final norm and the head at ``rows``."""
    cfg = dict(cfg_items)
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
