"""``pallas_kernels.prefix_flash_attention``, interpreted on the CPU,
against its oracle ``ops.attention.prefix_attention`` and against the
whole masked expression in float32, at the head counts and sizes of the
three families whose prefill pieces run it; a call of several pieces is
the pieces run apart, to the bit; a sink of ``-inf`` is no sink, to the
bit; and the rule that says when a walk is the kernel's
(``layers.flash_walk_ok``).  What the chip's compiler makes of the
kernel at the published shapes is ``tests/test_tpu_aot_compile.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflow_train_distributed_tpu.models import layers
from tensorflow_train_distributed_tpu.ops import (
    attention, pallas_kernels as pk,
)

BF16 = jnp.bfloat16
# heads, kv_heads, key head, value head, window, sink
KINDS = {
    "mimo-full-h64kv4-k192v128": (64, 4, 192, 128, None, False),
    "mimo-window128-h64kv8-k192v128-sink": (64, 8, 192, 128, 128, True),
    "laguna-full-h48kv8-d128": (48, 8, 128, 128, None, False),
    "laguna-window512-h72kv8-d128": (72, 8, 128, 128, 512, False),
    "qwen-h28kv4-d128": (28, 4, 128, 128, None, False),
}


def _case(kind, lanes, q_len, cache_len, dtype=BF16, seed=0):
    heads, kvh, hd, vd, window, sink = KINDS[kind] if isinstance(
        kind, str) else kind
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (lanes, heads, q_len, hd), dtype)
    k = jax.random.normal(ks[1], (lanes, cache_len, kvh, hd), dtype)
    v = jax.random.normal(ks[2], (lanes, cache_len, kvh, vd), dtype)
    sinks = (1.0 + jax.random.normal(ks[3], (heads,), jnp.float32)
             if sink else None)
    return q, k, v, window, sinks


def _every_head(rows, heads):
    """[B, T, kv_heads, D] -> [B, heads, T, D], as ``_cache_attend``'s
    ``heads``."""
    return [jnp.repeat(c, heads // c.shape[2], axis=2).transpose(0, 2, 1, 3)
            for c in rows]


def _walk(q, k, v, start, window, sinks, **kw):
    return attention.prefix_attention(
        q, (k, v), start, lambda rows: _every_head(rows, q.shape[1]),
        window=window, sink_logits=sinks, **kw)


def _whole_f32(q, k, v, start, window, sinks):
    """The masked expression over every row, float32 throughout."""
    q_len, cache_len = q.shape[2], k.shape[1]
    pos = start[:, None, None] + jnp.arange(q_len)[:, None]
    kv_pos = jnp.arange(cache_len)
    seen = kv_pos <= pos
    if window is not None:
        seen &= pos - kv_pos < window
    kf, vf = _every_head([k.astype(jnp.float32), v.astype(jnp.float32)],
                         q.shape[1])
    return attention.dot_product_attention(
        q.astype(jnp.float32), kf, vf, mask=seen[:, None],
        sink_logits=sinks)


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_kernel_is_the_walk_and_the_whole_expression(kind, monkeypatch):
    """Two lanes with unequal ``start``, one of them 0 (under a window:
    a window that reaches behind row 0), two query blocks each, a cache
    that is no multiple of the tile and whose last, ragged tile the
    longer lane's queries reach.  At the families' head counts and
    sizes, in blocks and tiles a quarter of the chip's (the interpreter
    is slow)."""
    bq, tk = 128, 256
    monkeypatch.setattr(pk, "PREFIX_FLASH_BLOCK_Q", bq)
    monkeypatch.setattr(pk, "PREFIX_FLASH_TILE", tk)
    q_len = 2 * bq
    cache_len = 4 * tk + tk // 2 + 8
    q, k, v, window, sinks = _case(kind, 2, q_len, cache_len)
    start = jnp.asarray([0, cache_len - q_len], jnp.int32)
    got = pk.prefix_flash_attention(q, k, v, start, window=window,
                                    sink_logits=sinks, interpret=True)
    assert got.shape == (*q.shape[:-1], v.shape[-1]) and got.dtype == BF16
    assert not np.isnan(np.asarray(got, np.float32)).any()
    # bf16 outputs of magnitude ~1: an ulp is 2^-8; the walk rounds its
    # scores to bf16 before the softmax and the kernel does not, which
    # is the finer side (closer to float32 in the mean).
    walk = _walk(q, k, v, start, window, sinks, block=bq)
    whole = _whole_f32(q, k, v, start, window, sinks)
    err = lambda a: np.abs(np.asarray(got, np.float32)       # noqa: E731
                           - np.asarray(a, np.float32))
    assert err(walk).max() < 3e-2
    assert err(whole).max() < 2e-2
    walk_err = np.abs(np.asarray(walk, np.float32) - np.asarray(whole))
    assert err(whole).mean() <= walk_err.mean() * 1.05


@pytest.mark.parametrize("kind", ["full", "window-sink", "wide-key"])
def test_a_call_of_four_pieces_is_the_four_pieces_to_the_bit(
        kind, monkeypatch):
    """Each query block walks its own tiles by its own position, so a
    call over four pieces gives every row the bits the pieces' calls
    give it; in float32 the kernel IS the walk (one tile rule, one
    arithmetic, no score to round)."""
    monkeypatch.setattr(pk, "PREFIX_FLASH_BLOCK_Q", 16)
    monkeypatch.setattr(pk, "PREFIX_FLASH_TILE", 32)
    shape = {"full": (8, 2, 16, 8, None, False),
             "window-sink": (8, 4, 16, 16, 40, True),
             "wide-key": (8, 4, 192, 128, None, False)}[kind]
    piece, first = 32, 48            # (a start no multiple of the tile)
    q, k, v, window, sinks = _case(shape, 1, 4 * piece, 300,
                                   dtype=jnp.float32, seed=3)

    def kernel(q, at):
        return pk.prefix_flash_attention(
            q, k, v, jnp.asarray([at], jnp.int32), window=window,
            sink_logits=sinks, interpret=True)

    whole = kernel(q, first)
    apart = jnp.concatenate(
        [kernel(q[:, :, i * piece:(i + 1) * piece], first + i * piece)
         for i in range(4)], axis=2)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(apart))
    walk = _walk(q, k, v, jnp.asarray([first], jnp.int32), window, sinks,
                 tile=32, block=16)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(walk),
                               atol=2e-6, rtol=0)


@pytest.mark.parametrize("window", [None, 40])
def test_a_sink_of_minus_infinity_is_no_sink_to_the_bit(window, monkeypatch):
    monkeypatch.setattr(pk, "PREFIX_FLASH_BLOCK_Q", 16)
    monkeypatch.setattr(pk, "PREFIX_FLASH_TILE", 32)
    q, k, v, _, _ = _case((8, 2, 16, 8, None, False), 2, 32, 100,
                          dtype=jnp.float32, seed=5)
    start = jnp.asarray([0, 61], jnp.int32)
    none = pk.prefix_flash_attention(q, k, v, start, window=window,
                                     interpret=True)
    gone = pk.prefix_flash_attention(
        q, k, v, start, window=window, interpret=True,
        sink_logits=jnp.full((8,), -jnp.inf, jnp.float32))
    np.testing.assert_array_equal(np.asarray(none), np.asarray(gone))
    real = pk.prefix_flash_attention(
        q, k, v, start, window=window, interpret=True,
        sink_logits=jnp.zeros((8,), jnp.float32))
    assert np.abs(np.asarray(real) - np.asarray(none)).max() > 1e-3


def _rows(kvh=4, hd=128, vd=128, dtype=BF16, cache_len=4096):
    return (jax.ShapeDtypeStruct((1, cache_len, kvh, hd), dtype),
            jax.ShapeDtypeStruct((1, cache_len, kvh, vd), dtype))


@pytest.mark.parametrize("what, q_len, rows, scales, backend, want", [
    ("qwen's piece", 1024, _rows(), None, "tpu", True),
    ("mimo's keys of 192", 4096, _rows(8, 192, 128), None, "tpu", True),
    ("a decode step", 1, _rows(), None, "tpu", False),
    ("a speculative block", 5, _rows(), None, "tpu", False),
    ("queries that are no whole blocks", 1000, _rows(), None, "tpu", False),
    ("an int8 cache", 1024, _rows(dtype=jnp.int8), (1, 1), "tpu", False),
    ("a float32 cache", 1024, _rows(dtype=jnp.float32), None, "tpu", False),
    ("heads of 64", 1024, _rows(16, 64, 64), None, "tpu", False),
    ("values of 192", 1024, _rows(4, 192, 192), None, "tpu", False),
    ("the CPU", 1024, _rows(), None, "cpu", False),
])
def test_a_walk_is_the_kernels_by_what_the_call_can_see(
        what, q_len, rows, scales, backend, want, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.delenv("TTD_NO_PALLAS", raising=False)
    assert layers.flash_walk_ok(q_len, *rows, scales) is want, what
    # the layers of a cache tree: a depth scan's twelve, an int8 layer
    # none, a latent layer none
    tree = {"scan": {"attention": {
        "key_cache": jax.ShapeDtypeStruct((12, *rows[0].shape),
                                          rows[0].dtype),
        "value_cache": jax.ShapeDtypeStruct((12, *rows[1].shape),
                                            rows[1].dtype),
        **({"kv_scales": 1} if scales else {})}},
        "latent": {"latent_cache": jax.ShapeDtypeStruct((1, 64, 640), BF16)},
        "index": jnp.zeros((1,), jnp.int32)}
    assert layers.flash_walk_layers(tree, q_len) == (12 if want else 0)


def test_the_kill_switch_and_a_mesh_keep_the_walk(monkeypatch, mesh_2d):
    rows = _rows()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("TTD_NO_PALLAS", raising=False)
    assert layers.flash_walk_ok(1024, *rows)
    with jax.set_mesh(mesh_2d):
        assert not layers.flash_walk_ok(1024, *rows)
    monkeypatch.setenv("TTD_NO_PALLAS", "1")
    assert not layers.flash_walk_ok(1024, *rows)
