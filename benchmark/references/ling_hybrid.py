"""Plain reference of the Ling-3.0-flash block (``bailing_hybrid``), as
one chip of an expert-parallel deployment computes it.

As published (``inclusionAI/Ling-3.0-flash`` ``config.json``).  Pre-norm
decoder layer, RMSNorm ``n(x) = x / sqrt(mean(x^2) + eps) * scale``::

    h = x + Attn_l(n1(x))
    y = h + F_l(n2(h))

Layer ``l`` is **latent** where ``(l + 1) % layer_group_size == 0`` and
**linear** otherwise.  Both kinds have ``H = num_attention_heads`` heads
and a per-head output gate ``gamma = sigmoid(n Wg)`` [T, H] that
multiplies each head's output before ``Wo``
(``gated_attention_proj_granularity_type: head_wise``).

**Linear layer** (Kimi Delta Attention, arXiv:2510.26692: the gated
delta rule with a decay per key channel; ``d = head_dim`` key and value
channels a head, no rotary, no positions)::

    q~, k~, v~ = n Wq, n Wk, n Wv                              [T, H d]
    q, k, v = silu(conv(q~)), silu(conv(k~)), silu(conv(v~))
        conv: depthwise, causal, short_conv_kernel_size taps, zeros
        before the sequence: y_t = sum_i w_i x_(t - K + 1 + i)
    q = q / sqrt(sum_h q^2 + 1e-6) * d^-1/2    k = k / sqrt(sum_h k^2 + 1e-6)
    g = kda_lower_bound * sigmoid(exp(A_h) * (n Wf + b))   [T, H, d] in (-5, 0)
    beta = sigmoid(n Wb)                                        [T, H]
    S_0 = 0;  S_ = diag(exp g_t) S_(t-1)
    S_t = S_ + beta_t k_t (v_t - S_^T k_t)^T;   o_t = S_t^T q_t
    Attn = concat_h(gamma_h * rmsnorm_d(o_h) * w) Wo

token by token (``lax.scan`` over the positions), the state float32.

**Latent layer** (DeepSeek-V2's, without a query rank: ``q_lora_rank``
null)::

    q = n Wq -> [T, H, nope + rope]
    [c_kv | k_r] = n Wkva           c_kv <- rmsnorm(c_kv)  [kv_lora_rank]
    [k_nope | v] = c_kv Wkvb -> [T, H, nope + v_head_dim]
    q_r, k_r <- rope(q_r), rope(k_r)   (rope_theta, no scaling; k_r one
                                        key shared by the heads)
    P = softmax([q_nope|q_r] [k_nope|k_r]^T / sqrt(nope + rope), causal)
    Attn = concat_h(gamma_h * P v) Wo

**F** is a SwiGLU ``Wdown (silu(Wgate x) * (Wup x))`` of width
``intermediate_size`` in the first ``first_k_dense_replace`` layers and
the expert layer after them::

    s = sigmoid(x Wr)          [all experts of the deployment], float32
    choice = s + b; the experts lie in n_group equal groups, a group
        scores the sum of its two best choices, the topk_group best
        groups stay; chosen = top num_experts_per_tok of the rest
    g = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor
    F(x) = sum_{e chosen AND held here} g_e E_e(x) + E_shared(x)

every ``E`` a SwiGLU of width ``moe_intermediate_size``
(``expert_swiglu_limit_list`` / ``share_expert_swiglu_limit_list`` are 0
on every layer that runs: no clamp).  **The share**: the router is as
wide as the deployment has experts; the weight tree holds the kernels of
``num_experts`` of them, experts ``[experts_offset, experts_offset +
num_experts)``, and what the other chips' experts would add is left out,
here as in the program; the shared expert and the residual are whole.
Logits are ``n_f(x) Whead`` over the vocabulary slice the tree holds.

Everything is float32 with ``precision=HIGHEST`` on every matmul.  No
kernels, no cache, no chunks, no batching: one sequence, layer by layer.
So that it fits beside 10 GB of weights, the latent layer's attention
takes its query rows in blocks (each block sees every key and masks what
it may not) and the position-wise F its rows in blocks; neither changes
a number's definition.

Departures from the published description, each the configuration
file's ``assumed``: the output gate on both kinds of layer as
``sigmoid`` of a bias-free projection of the layer's normed input; the
convolution without bias; ``use_qk_norm`` read as the delta rule's own
l2 normalisation (epsilon 1e-6 under the root) with ``d^-1/2`` on q; the
bounded form of the decay, ``kda_lower_bound * sigmoid(exp(A) (a +
dt_bias))``, which ``kda_safe_gate`` selects; the output norm with one
learned weight a channel of a head, shared by the heads; no norm behind
the latent layer's up-projection; half-split rotary layout; the state
in float32.  Besides: the weights arrive in the type the benchmark made
them in (bf16 for serving) and are widened to float32 where they are
used; the sequence is padded to a multiple of ``PAD`` (padding sits
after every real position, so causality keeps it invisible, and a
recurrent state is read only at real positions); logits are computed
only at the positions asked for.

``cfg`` is the configuration file (the source's own keys); the weight
tree is what ``weights.make_params`` fills for the program's
``MoeLmModel``: ``layer_<i>/attention/{query,key,value,conv_q,conv_k,
conv_v,decay,a_log,beta,gate,out_norm,out}`` (linear) or
``{query,kv_a,kv_norm,kv_b,gate,out}`` (latent),
``layer_<i>/{mlp | moe/{router,bias,experts,shared_mlp}}``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
PAD = 512          # sequence lengths are padded to a multiple of this
Q_BLOCK = 512      # query rows per attention block of the latent layer
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


def rope(x, positions, theta: float):
    """x [S, H, r] rotated (half-split) at ``positions`` [S]."""
    r = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


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


def is_latent(cfg: dict, layer: int) -> bool:
    return (layer + 1) % cfg["layer_group_size"] == 0


def short_conv(x, taps):
    """Depthwise causal convolution of x [S, C] by ``taps`` [K, C]:
    ``y_t = sum_i taps_i x_(t - K + 1 + i)``, zeros before row 0."""
    k = taps.shape[0]
    ext = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return sum(_f32(taps[i]) * ext[i:i + x.shape[0]] for i in range(k))


def decay_log(n, a, cfg):
    """``g`` [S, H, d]: the log of each step's decay of each key
    channel, in ``(kda_lower_bound, 0)``."""
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    if not cfg.get("kda_safe_gate", False):
        raise ValueError("the reference has the bounded decay alone")
    raw = (_mm(n, a["decay"]["kernel"]) + _f32(a["decay"]["bias"])
           ).reshape(-1, h, d)
    rate = jnp.exp(_f32(a["a_log"]["bias"]))[:, None]
    return cfg["kda_lower_bound"] * jax.nn.sigmoid(rate * raw)


def linear_attention(n, a, cfg):
    """``Attn`` of a linear layer on the normed rows n [S, D], token by
    token."""
    s = n.shape[0]
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    if cfg.get("num_kv_heads_for_linear_attn", 0) not in (0, h):
        raise ValueError("as many key/value heads as query heads")

    def mixed(name, taps):
        y = short_conv(_mm(n, a[name]["kernel"]), a[taps]["kernel"])
        if cfg.get("linear_silu", True):
            y = jax.nn.silu(y)
        return y.reshape(s, h, d)

    q, k, v = (mixed("query", "conv_q"), mixed("key", "conv_k"),
               mixed("value", "conv_v"))

    def l2norm(u):
        return u * jax.lax.rsqrt(jnp.sum(u * u, -1, keepdims=True) + 1e-6)

    q, k = l2norm(q) * d ** -0.5, l2norm(k)
    g = decay_log(n, a, cfg)
    beta = jax.nn.sigmoid(_mm(n, a["beta"]["kernel"]))          # [S, H]

    def token(state, row):
        q_t, k_t, v_t, g_t, b_t = row             # [H, d] x 4, [H]
        decayed = jnp.exp(g_t)[:, :, None] * state
        seen = jnp.einsum("hcv,hc->hv", decayed, k_t, precision=HIGHEST)
        state = decayed + (k_t[:, :, None]
                           * (b_t[:, None] * (v_t - seen))[:, None, :])
        return state, jnp.einsum("hcv,hc->hv", state, q_t,
                                 precision=HIGHEST)

    _, o = jax.lax.scan(token, jnp.zeros((h, d, d), jnp.float32),
                        (q, k, v, g, beta))
    o = rms_norm(o, a["out_norm"]["scale"], cfg["rms_norm_eps"])
    o = o * jax.nn.sigmoid(_mm(n, a["gate"]["kernel"]))[..., None]
    return _mm(o.reshape(s, h * d), a["out"]["kernel"])


def causal_attention(q, k, v, positions, scale):
    """q, k [S, H, hd], v [S, H, vd]: query rows in blocks, each block
    over every key under the causal mask."""
    s = q.shape[0]
    blk = _blocks(s, Q_BLOCK)

    def block(rows):
        q_b, pos_b = rows
        scores = jnp.einsum("qhd,khd->hqk", q_b, k,
                            precision=HIGHEST) * scale
        ok = pos_b[:, None] >= positions[None, :]
        p = jax.nn.softmax(jnp.where(ok[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (q.reshape((-1, blk) + q.shape[1:]),
                              positions.reshape(-1, blk)))
    return out.reshape((s,) + out.shape[2:])


def latent_attention(n, a, cfg, positions):
    """``Attn`` of a latent layer on the normed rows n [S, D]."""
    h = cfg["num_attention_heads"]
    nope, rp = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    theta = float(cfg["rope_theta"])
    if cfg.get("q_lora_rank") is not None or cfg.get("rope_scaling"):
        raise ValueError("the reference has no query rank and no rotary "
                         "scaling")
    q = _mm(n, a["query"]["kernel"]).reshape(-1, h, nope + rp)
    q = jnp.concatenate(
        [q[..., :nope], rope(q[..., nope:], positions, theta)], -1)
    kv = _mm(n, a["kv_a"]["kernel"])
    c_kv = rms_norm(kv[:, :rank], a["kv_norm"]["scale"], eps)
    k_r = rope(kv[:, None, rank:], positions, theta)          # [S, 1, rp]
    up = _mm(c_kv, a["kv_b"]["kernel"]).reshape(
        -1, h, nope + cfg["v_head_dim"])
    k = jnp.concatenate(
        [up[..., :nope], jnp.broadcast_to(k_r, (k_r.shape[0], h, rp))], -1)
    o = causal_attention(q, k, up[..., nope:], positions,
                         (nope + rp) ** -0.5)
    o = o * jax.nn.sigmoid(_mm(n, a["gate"]["kernel"]))[..., None]
    return _mm(o.reshape(o.shape[0], -1), a["out"]["kernel"])


def chosen(s, m, cfg):
    """The experts each token chooses [S, num_experts_per_tok], from the
    router's scores ``s`` [S, E] and the layer's correction bias."""
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
    return jax.lax.top_k(choice, cfg["num_experts_per_tok"])[1]


def gates(n, m, cfg):
    """Gate of every expert of the deployment for every token [S, E]:
    zero where the expert was not chosen."""
    s = jax.nn.sigmoid(_mm(n, m["router"]["kernel"]))
    picks = chosen(s, m, cfg)
    picked = jnp.take_along_axis(s, picks, axis=-1)
    g = picked
    if cfg.get("norm_topk_prob", True):
        g = g / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    g = g * cfg["routed_scaling_factor"]
    rows = jnp.arange(s.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, picks].set(g)


def routed_part(n, m, cfg):
    """What the experts held here add: experts ``[experts_offset,
    experts_offset + held)`` of the router's, ``held`` the kernels the
    tree has; each held expert over every token, a dense loop."""
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


def attention(n, a, cfg, layer: int, positions):
    if is_latent(cfg, layer):
        return latent_attention(n, a, cfg, positions)
    return linear_attention(n, a, cfg)


def attended(x, w, cfg, layer: int, positions):
    """``h = x + Attn_l(n1(x))`` of decoder layer ``layer`` on x [S, D]."""
    return x + attention(rms_norm(x, w["attn_norm"]["scale"],
                                  cfg["rms_norm_eps"]),
                         w["attention"], cfg, layer, positions)


def block(x, w, cfg, layer: int, positions):
    """Decoder layer ``layer`` on x [S, D]; ``w`` is its weights.  ``F``
    acts on each position alone, so it is taken in blocks of rows."""
    eps = cfg["rms_norm_eps"]
    x = attended(x, w, cfg, layer, positions)

    def f(rows):
        n = rms_norm(rows, w["mlp_norm"]["scale"], eps)
        if layer >= cfg["first_k_dense_replace"]:
            return rows + expert_layer(n, w["moe"], cfg)
        hidden = w["mlp"]["wo"]["kernel"].shape[0]
        return rows + swiglu(n, w["mlp"],
                             slices=hidden // _blocks(hidden, FFN_SLICE))

    blk = _blocks(x.shape[0], ROW_BLOCK)
    return jax.lax.map(f, x.reshape(-1, blk, x.shape[-1])).reshape(x.shape)


_KEYS = ("num_hidden_layers", "num_attention_heads", "head_dim",
         "layer_group_size", "first_k_dense_replace", "rms_norm_eps",
         "kda_lower_bound", "kda_safe_gate", "linear_silu",
         "num_kv_heads_for_linear_attn", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "rope_theta", "rope_scaling", "num_experts_per_tok", "n_group",
         "topk_group", "norm_topk_prob", "routed_scaling_factor",
         "experts_offset")


def _static(cfg: dict) -> tuple:
    """The keys the forward pass reads, hashable (a jit's static
    argument)."""
    return tuple((k, cfg[k]) for k in _KEYS if k in cfg)


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
