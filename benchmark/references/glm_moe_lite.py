"""Plain reference of the GLM-4.7-Flash block (``glm4_moe_lite``).

As published (``zai-org/GLM-4.7-Flash`` ``config.json`` and the
DeepSeek-V2 / V3 papers its block follows).  Pre-norm decoder layer,
RMSNorm ``n(x) = x / sqrt(mean(x^2) + eps) * scale``::

    h = x + Attn(n1(x))
    y = h + F(n2(h))

**Attention** (multi-head latent attention, H heads)::

    c_q = norm(x Wq_a)                     [q_lora_rank]
    q   = c_q Wq_b          -> H x (q_nope [nope] | q_rope [rope])
    [c_kv | k_r] = x Wkv_a                 [kv_lora_rank | rope]
    [k_nope | v] = norm(c_kv) Wkv_b  -> H x ([nope] | [v_head])
    k = [k_nope | rope(k_r)]   (the one k_r shared by all heads)
    q = [q_nope | rope(q_rope)]
    Attn = softmax(q k^T / sqrt(nope + rope), causal) v  ->  Wo

**F** is a SwiGLU ``Wdown (silu(Wgate x) * (Wup x))`` of width
``intermediate_size`` in the first ``first_k_dense_replace`` layers and
the expert layer in every other::

    s = sigmoid(x Wr)                      [n_routed_experts], float32
    chosen = top num_experts_per_tok of (s + b)     b: correction bias
    g = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor
    F(x) = sum_i g_i E_i(x) + E_shared(x)

every ``E`` a SwiGLU of width ``moe_intermediate_size`` (``n_group`` =
``topk_group`` = 1, so the grouped choice is the plain top-k;
``norm_topk_prob`` true).  Logits are ``n_f(x) Whead`` (no tying).

Everything is float32 with ``precision=HIGHEST`` on every matmul.  No
kernels, no cache, no absorption of ``Wkv_b`` into the query, no
batching: one sequence, layer by layer, attention in blocks of query
rows, the experts one after another, each over every token and weighted
by its gate (zero where it was not chosen).

Departures from the published description:
- the weights arrive in the type the benchmark made them in (bf16 for
  serving) and are widened to float32 one layer (one expert) at a time;
- the sequence is padded to a multiple of ``PAD`` (padding sits after
  every real position, so causality keeps it invisible);
- logits are computed only at the positions asked for;
- rotary embedding in the half-split ("rotate_half") layout over the
  ``qk_rope_head_dim`` dims (the configuration file's ``assumed``: with
  seeded weights the interleaved layout is a fixed permutation of
  columns of ``Wq_b`` and ``Wkv_a``);
- the multi-token-prediction block (``num_nextn_predict_layers``) is no
  part of the next-token forward pass and is left out.

``cfg`` is the configuration file (the source's own keys); the weight
tree is what ``weights.make_params`` fills for the program's
``MoeLmModel``: ``layer_<i>/attention/{q_a,q_norm,q_b,kv_a,kv_norm,
kv_b,out}``, ``layer_<i>/{mlp | moe/{router,bias,experts,shared_mlp}}``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
PAD = 512          # sequence lengths are padded to a multiple of this
Q_BLOCK = 512      # query rows per attention block


def _f32(x):
    return x.astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(_f32(a), _f32(b), precision=HIGHEST)


def rms_norm(x, scale, eps):
    x = _f32(x)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(scale)


def rope(x, positions, theta):
    """x [S, H, hd]; half-split rotary embedding at ``positions`` [S]."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * freqs      # [S, hd/2]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(x, w):
    return _mm(jax.nn.silu(_mm(x, w["wi_gate"]["kernel"]))
               * _mm(x, w["wi_up"]["kernel"]), w["wo"]["kernel"])


def attention(q, k, v):
    """Causal attention of one sequence; q, k [S, H, hd], v [S, H, vd].
    Query rows are taken in blocks; each block sees every key and masks
    what it may not."""
    s, _, hd = q.shape
    kpos = jnp.arange(s)
    outs = []
    for start in range(0, s, Q_BLOCK):
        qb = q[start:start + Q_BLOCK]
        qpos = start + jnp.arange(qb.shape[0])
        scores = jnp.einsum("qhd,khd->hqk", qb, k,
                            precision=HIGHEST) / jnp.sqrt(float(hd))
        scores = jnp.where((kpos[None, :] <= qpos[:, None])[None],
                           scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST))
    return jnp.concatenate(outs, axis=0)


def latent_attention(n, a, cfg, positions):
    """``Attn`` of the normed input n [S, D]; ``a`` the layer's
    ``attention`` weights."""
    h = cfg["num_attention_heads"]
    nope, rp = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    theta = float(cfg["rope_theta"])
    c_q = rms_norm(_mm(n, a["q_a"]["kernel"]), a["q_norm"]["scale"], eps)
    q = _mm(c_q, a["q_b"]["kernel"]).reshape(-1, h, nope + rp)
    q = jnp.concatenate(
        [q[..., :nope], rope(q[..., nope:], positions, theta)], -1)
    kv = _mm(n, a["kv_a"]["kernel"])
    c_kv = rms_norm(kv[:, :rank], a["kv_norm"]["scale"], eps)
    k_r = rope(kv[:, None, rank:], positions, theta)          # [S, 1, rp]
    up = _mm(c_kv, a["kv_b"]["kernel"]).reshape(
        -1, h, nope + cfg["v_head_dim"])
    k = jnp.concatenate(
        [up[..., :nope], jnp.broadcast_to(k_r, (k_r.shape[0], h, rp))], -1)
    att = attention(q, k, up[..., nope:])
    return _mm(att.reshape(att.shape[0], -1), a["out"]["kernel"])


def gates(n, m, cfg):
    """Gate of every expert for every token [S, E]: zero where the
    expert was not chosen."""
    s = jax.nn.sigmoid(_mm(n, m["router"]["kernel"]))
    _, chosen = jax.lax.top_k(s + _f32(m["bias"]),
                              cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    g = picked
    if cfg.get("norm_topk_prob", True):
        g = g / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    g = g * cfg["routed_scaling_factor"]
    rows = jnp.arange(s.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(g)


def expert_layer(n, m, cfg):
    g = gates(n, m, cfg)

    def one(y, expert):
        w, g_e = expert
        return y + g_e[:, None] * swiglu(n, jax.tree.map(
            lambda k: {"kernel": k}, w)), None

    stacked = {k: v["kernel"] for k, v in m["experts"].items()}
    y, _ = jax.lax.scan(one, jnp.zeros_like(n), (stacked, g.T))
    return y + swiglu(n, m["shared_mlp"])


def block(x, w, cfg, positions):
    """One decoder layer on x [S, D]; ``w`` is that layer's weights."""
    eps = cfg["rms_norm_eps"]
    x = x + latent_attention(rms_norm(x, w["attn_norm"]["scale"], eps),
                             w["attention"], cfg, positions)
    n = rms_norm(x, w["mlp_norm"]["scale"], eps)
    if "moe" in w:
        return x + expert_layer(n, w["moe"], cfg)
    return x + swiglu(n, w["mlp"])


_KEYS = ("num_hidden_layers", "num_attention_heads", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "rms_norm_eps",
         "rope_theta", "num_experts_per_tok", "norm_topk_prob",
         "routed_scaling_factor")


def _static(cfg: dict) -> tuple:
    return tuple((k, cfg.get(k)) for k in _KEYS)


def _logits(params, tokens, rows, cfg_items):
    """Logits [len(rows), V] of one padded sequence: embedding, the
    layers one after another, the final norm and the head at ``rows``."""
    cfg = dict(cfg_items)
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
