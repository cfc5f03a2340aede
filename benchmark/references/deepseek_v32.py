"""Plain reference of the DeepSeek-V3.2-Exp block (``deepseek_v32``), as
one chip of an expert-parallel deployment computes it.

As published (``deepseek-ai/DeepSeek-V3.2-Exp`` ``config.json`` and the
source repository's ``inference/model.py``).  Pre-norm decoder layer,
RMSNorm ``n(x) = x / sqrt(mean(x^2) + eps) * scale``::

    h = x + Attn(n1(x))
    y = h + F(n2(h))

**Attention** (multi-head latent attention, H heads, over the rows a
learned indexer chooses)::

    c_q = norm(x Wq_a)                     [q_lora_rank]
    q   = c_q Wq_b          -> H x (q_nope [nope] | q_rope [rope])
    [c_kv | k_r] = x Wkv_a                 [kv_lora_rank | rope]
    [k_nope | v] = norm(c_kv) Wkv_b  -> H x ([nope] | [v_head])
    k = [k_nope | rope(k_r)]   (the one k_r shared by all heads)
    q = [q_nope | rope(q_rope)]

    qI_h = rope_head((c_q WI_q)[h])        h = 1..Hi, [index_head_dim]
    kI   = rope_head(LayerNorm(x WI_k))    [index_head_dim]
    w_h  = (x WI_w)[h] * Hi^-0.5 * index_head_dim^-0.5
    I[t, s] = sum_h w_h[t] * relu(qI_h[t] . kI[s])
    S_t  = the min(index_topk, t + 1) positions s <= t of largest
           I[t, s] (ties to the lower position: ``lax.top_k``)

    Attn = softmax(q k^T * scale, over S_t) v  ->  Wo

``rope`` uses YaRN frequencies (each of the rope/2 frequencies blended
between itself and itself / factor by a linear ramp between the
correction dims of ``beta_fast`` and ``beta_slow`` over
``original_max_position_embeddings``; cos and sin unscaled), ``scale`` is
``(nope + rope)^-0.5 * (0.1 * mscale_all_dim * ln(factor) + 1)^2``, and
``rope_head`` rotates the leading ``rope`` dims of an indexer head.

**F** is a SwiGLU ``Wdown (silu(Wgate x) * (Wup x))`` of width
``intermediate_size`` in the first ``first_k_dense_replace`` layers and
the expert layer in every other::

    s  = sigmoid(x Wr)          [all experts of the deployment], float32
    s' = s + b                  b: correction bias
    the experts lie in n_group equal groups; a group scores the sum of
    its two largest s'; the topk_group best groups stay
    chosen = top num_experts_per_tok of s' among the groups that stay
    g = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor
    F(x) = sum_{i chosen AND held here} g_i E_i(x) + E_shared(x)

every ``E`` a SwiGLU of width ``moe_intermediate_size``.  **The share**:
the router is as wide as the deployment has experts (its kernel's
width); the weight tree holds the kernels of ``n_routed_experts`` of
them, experts ``[experts_offset, experts_offset + n_routed_experts)``,
and what the other chips' experts would add is left out, here as in the
program; the shared expert and the residual are whole.  Logits are
``n_f(x) Whead`` over the vocabulary slice the tree holds (no tying).

Everything is float32 with ``precision=HIGHEST`` on every matmul.  No
kernels, no cache, no absorption of ``Wkv_b``, no batching: one
sequence, layer by layer.  So that 128 heads over 25 k rows fit beside
9 GB of weights, the work is cut into pieces that change no number's
definition: the selection in blocks of query rows, attention a group of
heads at a time and within it in blocks of query rows (each block sees
every key and masks what it may not), the output projection summed over
the groups, the dense layer's hidden width in slices, the experts one
after another over every token.

Departures from the published description:
- the source rotates ``qI`` and ``kI`` by a Hadamard matrix and stores
  ``kI`` in FP8 with a scale: the rotation is orthogonal and cancels in
  the product, and the configuration states bf16 index keys, so neither
  is here;
- the weights arrive in the type the benchmark made them in (bf16 for
  serving) and are widened to float32 a piece at a time;
- the sequence is padded to a multiple of ``PAD`` (padding sits after
  every real position, so causality keeps it invisible);
- logits are computed only at the positions asked for;
- rotary embedding in the half-split ("rotate_half") layout, in the
  attention and in the indexer (the configuration file's ``assumed``);
- the multi-token-prediction block is no part of the next-token pass.

``cfg`` is the configuration file (the source's own keys); the weight
tree is what ``weights.make_params`` fills for the program's
``MoeLmModel``: ``layer_<i>/attention/{q_a,q_norm,q_b,kv_a,kv_norm,
kv_b,out,index_q,index_k,index_k_norm,index_w}``,
``layer_<i>/{mlp | moe/{router,bias,experts,shared_mlp}}``.
"""

from __future__ import annotations

import functools
import math

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


def yarn_inv_freq(dim: int, theta: float, scaling: dict):
    """The ``dim / 2`` rotary frequencies under YaRN."""
    factor = float(scaling["factor"])
    old = float(scaling["original_max_position_embeddings"])

    def correction_dim(turns):
        return (dim * math.log(old / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    freqs = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    return freqs / factor * ramp + freqs * (1.0 - ramp)


def softmax_scale(cfg: dict) -> float:
    scaling = cfg["rope_scaling"]
    mscale = 0.1 * scaling["mscale_all_dim"] * math.log(
        scaling["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 \
        * mscale * mscale


def rope(x, positions, cfg):
    """x [S, H, rope]; half-split rotary embedding at ``positions`` [S]
    with the configuration's YaRN frequencies."""
    freqs = yarn_inv_freq(x.shape[-1], float(cfg["rope_theta"]),
                          cfg["rope_scaling"])
    ang = positions.astype(jnp.float32)[:, None] * freqs      # [S, r/2]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def rope_head(x, positions, cfg):
    """The indexer's rotation: the leading rope dims of x [S, H, hd]."""
    r = cfg["qk_rope_head_dim"]
    return jnp.concatenate([rope(x[..., :r], positions, cfg), x[..., r:]],
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


def chosen_rows(c_q, n, a, cfg, positions):
    """uint8 [S, S / 8]: row t holds, as packed bits (``unpack_rows``),
    the marks of S_t, the positions query t attends."""
    s = n.shape[0]
    hi, di, top = (cfg["index_n_heads"], cfg["index_head_dim"],
                   cfg["index_topk"])
    k_i = rope_head(layer_norm(
        _mm(n, a["index_k"]["kernel"]), a["index_k_norm"]["scale"],
        a["index_k_norm"]["bias"], 1e-6)[:, None, :], positions, cfg)[:, 0]
    w = _mm(n, a["index_w"]["kernel"]) * (hi ** -0.5 * di ** -0.5)
    kpos = jnp.arange(s)
    blk = _blocks(s, I_BLOCK)

    def block(args):
        c_b, w_b, qpos = args
        q_b = rope_head(_mm(c_b, a["index_q"]["kernel"]).reshape(
            blk, hi, di), qpos, cfg)
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


def unpack_rows(packed, s: int):
    """Bool [..., s] of ``chosen_rows``'s packed marks."""
    return jnp.unpackbits(packed, axis=-1, count=s).astype(jnp.bool_)


def attention(q, k, v, allowed, scale):
    """Attention of one sequence over the rows ``allowed`` (packed
    [S, S / 8], ``chosen_rows``) marks; q, k [S, H, hd], v [S, H, vd].
    Query rows are taken in blocks."""
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


def latent_attention(n, a, cfg, positions, residual=None):
    """``Attn`` of the normed input n [S, D], added to ``residual``
    where one is given (the groups of heads are summed onto it); ``a``
    the layer's ``attention`` weights."""
    h = cfg["num_attention_heads"]
    nope, rp = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, rank, eps = cfg["v_head_dim"], cfg["kv_lora_rank"], \
        cfg["rms_norm_eps"]
    s = n.shape[0]
    c_q = rms_norm(_mm(n, a["q_a"]["kernel"]), a["q_norm"]["scale"], eps)
    kv = _mm(n, a["kv_a"]["kernel"])
    c_kv = rms_norm(kv[:, :rank], a["kv_norm"]["scale"], eps)
    k_r = rope(kv[:, None, rank:], positions, cfg)            # [S, 1, rp]
    allowed = chosen_rows(c_q, n, a, cfg, positions)
    scale = softmax_scale(cfg)
    g = _blocks(h, HEAD_GROUP)
    q_b = a["q_b"]["kernel"].reshape(-1, h // g, g * (nope + rp))
    kv_b = a["kv_b"]["kernel"].reshape(rank, h // g, g * (nope + vd))
    out = a["out"]["kernel"].reshape(h // g, g * vd, -1)

    def group(y, w):
        wq, wkv, wo = w
        q = _mm(c_q, wq).reshape(s, g, nope + rp)
        q = jnp.concatenate(
            [q[..., :nope], rope(q[..., nope:], positions, cfg)], -1)
        up = _mm(c_kv, wkv).reshape(s, g, nope + vd)
        k = jnp.concatenate(
            [up[..., :nope], jnp.broadcast_to(k_r, (s, g, rp))], -1)
        att = attention(q, k, up[..., nope:], allowed, scale)
        return y + _mm(att.reshape(s, g * vd), wo), None

    y, _ = jax.lax.scan(
        group, jnp.zeros((s, out.shape[-1]), jnp.float32)
        if residual is None else residual,
        (jnp.moveaxis(q_b, 1, 0), jnp.moveaxis(kv_b, 1, 0), out))
    return y


def gates(n, m, cfg):
    """Gate of every expert of the deployment for every token [S, E]:
    zero where the expert was not chosen."""
    s = jax.nn.sigmoid(_mm(n, m["router"]["kernel"]))
    choice = s + _f32(m["bias"])
    groups = cfg.get("n_group", 1)
    if groups > 1:
        t, e = choice.shape
        grouped = choice.reshape(t, groups, e // groups)
        score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, stay = jax.lax.top_k(score, cfg["topk_group"])
        stays = jnp.zeros((t, groups), jnp.bool_).at[
            jnp.arange(t)[:, None], stay].set(True)
        choice = jnp.where(stays[:, :, None], grouped,
                           -jnp.inf).reshape(t, e)
    _, chosen = jax.lax.top_k(choice, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    g = picked
    if cfg.get("norm_topk_prob", True):
        g = g / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    g = g * cfg["routed_scaling_factor"]
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


def block(x, w, cfg, positions):
    """One decoder layer on x [S, D]; ``w`` is that layer's weights.
    ``F`` acts on each position alone, so it is taken in blocks of
    rows."""
    eps = cfg["rms_norm_eps"]
    x = latent_attention(rms_norm(x, w["attn_norm"]["scale"], eps),
                         w["attention"], cfg, positions, residual=x)

    def f(rows):
        n = rms_norm(rows, w["mlp_norm"]["scale"], eps)
        if "moe" in w:
            return rows + expert_layer(n, w["moe"], cfg)
        hidden = w["mlp"]["wo"]["kernel"].shape[0]
        return rows + swiglu(n, w["mlp"],
                             slices=hidden // _blocks(hidden, FFN_SLICE))

    blk = _blocks(x.shape[0], ROW_BLOCK)
    return jax.lax.map(f, x.reshape(-1, blk, x.shape[-1])).reshape(x.shape)


_KEYS = ("num_hidden_layers", "num_attention_heads", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "rms_norm_eps",
         "rope_theta", "num_experts_per_tok", "norm_topk_prob",
         "routed_scaling_factor", "n_group", "topk_group", "index_n_heads",
         "index_head_dim", "index_topk", "experts_offset")


def _static(cfg: dict) -> tuple:
    return tuple((k, cfg.get(k)) for k in _KEYS if k in cfg) + (
        ("rope_scaling", tuple(sorted(cfg["rope_scaling"].items()))),)


def _logits(params, tokens, rows, cfg_items):
    """Logits [len(rows), V] of one padded sequence: embedding, the
    layers one after another, the final norm and the head at ``rows``."""
    cfg = dict(cfg_items)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    x = _f32(jnp.take(params["token_embed"]["embedding"], tokens, axis=0))
    positions = jnp.arange(tokens.shape[0])
    for i in range(cfg["num_hidden_layers"]):
        x = block(x, params[f"layer_{i}"], cfg, positions)
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
