"""Plain reference of the dense decoder the two configurations share.

Pre-norm decoder block as published for Qwen2.5 and Mistral-7B:

    h = x + Wo . attention(rope(Wq n1(x) + bq), rope(Wk n1(x) + bk),
                           Wv n1(x) + bv)
    y = h + Wdown . (silu(Wgate n2(h)) * (Wup n2(h)))

with RMSNorm ``n(x) = x / sqrt(mean(x^2) + eps) * scale``, rotary
embedding in the half-split ("rotate_half") layout, grouped-query
attention (query head h reads key/value head h // (H / K)), causal
masking and, where the configuration has ``sliding_window`` W, each
query attending the last W positions including itself.  Logits are
``n_f(x) . Whead`` (no tying).

Everything is float32 with ``precision=HIGHEST`` on every matmul (on a
TPU a float32 matmul otherwise runs in bf16 passes).  No kernels, no
cache, no batching: one sequence at a time, layer by layer, attention
in blocks of query rows so the score matrix stays small.

Departures from the published description, all forced by size:
- the weights arrive in the type the benchmark made them in (bf16 for
  serving) and are widened to float32 layer by layer, so a float32 copy
  of the whole model never exists;
- the sequence is padded to a multiple of ``PAD`` (padding sits after
  every real position, so causality keeps it invisible);
- logits are computed only at the positions asked for.

``cfg`` is a configuration file's ``model`` object; the weight tree is
the layout the benchmark's ``weights.make_params`` fills (stacked
layers under ``layers/stack/block``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
PAD = 512          # sequence lengths are padded to a multiple of this
Q_BLOCK = 512      # query rows per attention block


def _mm(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, positions, theta):
    """x [S, H, hd]; half-split rotary embedding at ``positions`` [S]."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * freqs      # [S, hd/2]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window=None):
    """Causal grouped-query attention of one sequence.  q [S, H, hd];
    k, v [S, K, hd]; returns [S, H, hd].  Query rows are taken in
    blocks; each block sees every key and masks what it may not."""
    s, h, hd = q.shape
    kh = k.shape[1]
    rep = h // kh
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    kpos = jnp.arange(s)
    outs = []
    for start in range(0, s, Q_BLOCK):
        qb = q[start:start + Q_BLOCK]
        qpos = start + jnp.arange(qb.shape[0])
        scores = jnp.einsum("qhd,khd->hqk", qb, k,
                            precision=HIGHEST) / jnp.sqrt(float(hd))
        ok = kpos[None, :] <= qpos[:, None]
        if window:
            ok &= kpos[None, :] > qpos[:, None] - window
        scores = jnp.where(ok[None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST))
    return jnp.concatenate(outs, axis=0)


def block(x, w, cfg, positions):
    """One decoder layer on x [S, D]; ``w`` is that layer's weights."""
    mm = _mm
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kh = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    eps = cfg["rms_norm_eps"]
    a = w["attention"]
    n = rms_norm(x, w["attn_norm"]["scale"], eps)

    def proj(name, heads):
        y = mm(n, a[name]["kernel"])
        if "bias" in a[name]:
            y = y + a[name]["bias"].astype(jnp.float32)
        return y.reshape(-1, heads, hd)

    q = rope(proj("query", h), positions, cfg["rope_theta"])
    k = rope(proj("key", kh), positions, cfg["rope_theta"])
    v = proj("value", kh)
    att = attention(q, k, v, cfg.get("sliding_window"))
    x = x + mm(att.reshape(-1, h * hd), a["out"]["kernel"])
    n = rms_norm(x, w["mlp_norm"]["scale"], eps)
    m = w["mlp"]
    gate = mm(n, m["wi_gate"]["kernel"])
    up = mm(n, m["wi_up"]["kernel"])
    return x + mm(jax.nn.silu(gate) * up, m["wo"]["kernel"])


def _static(cfg: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "rope_theta", "sliding_window")
    return tuple((k, cfg.get(k)) for k in keys)


def _logits(params, tokens, rows, cfg_items):
    """Logits [len(rows), V] of one padded sequence: embedding, the
    layers one after another (a scan, so a float32 copy of one layer is
    all that exists at a time), the final norm and the head at ``rows``."""
    cfg = dict(cfg_items)
    x = jnp.take(params["token_embed"]["embedding"], tokens,
                 axis=0).astype(jnp.float32)
    positions = jnp.arange(tokens.shape[0])

    def layer(x, w):
        return block(x, w, cfg, positions), None

    x, _ = jax.lax.scan(layer, x, params["layers"]["stack"]["block"])
    return _mm(rms_norm(x[rows], params["final_norm"]["scale"],
                        cfg["rms_norm_eps"]),
               params["lm_head"]["kernel"])


def logits_at(params, cfg: dict, tokens, positions, pad_to=None,
              rows_to=None):
    """Float32 logits [len(positions), V] of one sequence at the given
    positions (row i predicts token i + 1).  The sequence is padded to
    a multiple of ``PAD`` (or to ``pad_to``) and ``rows_to`` rows are
    computed (one compiled shape for a whole traffic mix); the first
    ``len(positions)`` are returned."""
    tokens = jnp.asarray(tokens, jnp.int32)
    s = tokens.shape[0]
    padded = max(-(-s // PAD) * PAD, int(pad_to or 0))
    tokens = jnp.pad(tokens, (0, padded - s))
    pos = jnp.asarray(positions, jnp.int32)
    n = pos.shape[0]
    pos = jnp.pad(pos, (0, max(int(rows_to or 0) - n, 0)))
    return _logits_jit(params, tokens, pos, _static(cfg))[:n]


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
    padded = max(-(-len(seq) // PAD) * PAD, int(pad_to or 0))
    rows_n = max(int(rows_to or 0), n)
    tokens = np.zeros(padded, np.int32)
    tokens[:len(seq)] = seq
    rows = np.zeros(rows_n, np.int32)
    rows[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    want = np.zeros(rows_n, np.int32)
    want[:n] = served
    gaps = _gaps_jit(params, tokens, rows, want, _static(cfg))
    return np.asarray(gaps)[:n]
