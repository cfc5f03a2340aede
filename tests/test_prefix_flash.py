"""``pallas_kernels.prefix_flash_attention``, interpreted on the CPU,
against its oracle ``ops.attention.prefix_attention`` and against the
whole masked expression in float32, at the head counts and sizes of the
three families whose prefill pieces run it; a call of several pieces is
the pieces run apart, to the bit; a sink of ``-inf`` is no sink, to the
bit; and the rule that says when a walk is the kernel's
(``layers.flash_walk_ok``).  ``pallas_kernels.prefix_flash_latent``
(the same walk over latent rows, up-projected in the kernel, with and
without the learned choice's ``keep``) is held to the same oracle as
further cases of the same tests, at the head sizes of the three latent
families, and its rule is ``layers.latent_walk_ok``.  What the chip's
compiler makes of the kernels at the published shapes is
``tests/test_tpu_aot_compile.py``'s.
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


# heads, nope, rope, value head, latent, keep: the latent families' head
# sizes, at a test's width (few heads, a latent of one lane tile).
LATENT = {
    "glm-latent-k192r64v256": (5, 192, 64, 256, 128, False),
    "ling-latent-k128r64v128": (4, 128, 64, 128, 128, False),
    "deepseek-latent-k128r64v128-keep": (8, 128, 64, 128, 128, True),
}
#: A row's last lane tile: the rotary key, zeros after it.
TAIL = 128


def _latent_case(kind, lanes, q_len, cache_len, start, dtype=BF16, seed=0):
    """``(q_nope, q_rope, rows, kv_b, keep)``.  Where ``kind``
    chooses, ``keep`` marks about a third of the rows a query sees and
    the query's own, and for the even queries NO row of the cache's
    second tile (a whole tile empty for some query); else None."""
    heads, nope, rope, vd, rank, choose = LATENT[kind] if isinstance(
        kind, str) else kind
    ks = jax.random.split(jax.random.key(seed), 5)
    qn = jax.random.normal(ks[0], (lanes, heads, q_len, nope), dtype)
    qr = jax.random.normal(ks[1], (lanes, heads, q_len, rope), dtype)
    tail = TAIL if rope <= TAIL else rope
    rows = jnp.pad(jax.random.normal(ks[2], (lanes, cache_len, rank + rope),
                                     dtype),
                   ((0, 0), (0, 0), (0, tail - rope)))
    kv_b = (jax.random.normal(ks[3], (rank, heads, nope + vd), jnp.float32)
            * rank ** -0.5).astype(dtype)
    keep = None
    if choose:
        pos = start[:, None, None] + jnp.arange(q_len)[None, :, None]
        row = jnp.arange(cache_len)[None, None, :]
        tile = pk.PREFIX_LATENT_TILE
        empty = ((row >= tile) & (row < 2 * tile)
                 & (jnp.arange(q_len)[None, :, None] % 2 == 0))
        keep = ((jax.random.bernoulli(ks[4], 0.3, (lanes, q_len, cache_len))
                 & ~empty) | (row == pos)) & (row <= pos)
    return qn, qr, rows, kv_b, keep


def _up_projected(rows, kv_b, nope, rope):
    """``LatentAttention._up_project``, heads leading: (k, v) of every
    head from rows [B, T, store]."""
    rank, heads = kv_b.shape[:2]
    kv = jnp.einsum("btc,chd->bthd", rows[..., :rank], kv_b)
    k_r = jnp.broadcast_to(rows[..., None, rank:rank + rope],
                           (*rows.shape[:2], heads, rope))
    k = jnp.concatenate([kv[..., :nope], k_r], axis=-1)
    return [k.transpose(0, 2, 1, 3), kv[..., nope:].transpose(0, 2, 1, 3)]


def _latent_walk(qn, qr, rows, kv_b, keep, start, scale, **kw):
    nope, rope = qn.shape[-1], qr.shape[-1]
    return attention.prefix_attention(
        jnp.concatenate([qn, qr], axis=-1), rows, start,
        lambda r: _up_projected(r, kv_b, nope, rope),
        softmax_scale=scale, keep=keep, **kw)


def _latent_whole_f32(qn, qr, rows, kv_b, keep, start, scale):
    """The masked expression over every row in float32, from keys and
    values rounded to the rows' type as the cache's walk makes them."""
    q_len, cache_len = qn.shape[2], rows.shape[1]
    pos = start[:, None, None] + jnp.arange(q_len)[:, None]
    seen = jnp.arange(cache_len) <= pos
    if keep is not None:
        seen &= keep
    k, v = (t.astype(jnp.float32) for t in _up_projected(
        rows, kv_b, qn.shape[-1], qr.shape[-1]))
    return attention.dot_product_attention(
        jnp.concatenate([qn, qr], axis=-1).astype(jnp.float32), k, v,
        mask=seen[:, None], softmax_scale=scale)


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


@pytest.mark.parametrize("kind", sorted(KINDS) + sorted(LATENT))
def test_the_kernel_is_the_walk_and_the_whole_expression(kind, monkeypatch):
    """Two lanes with unequal ``start``, one of them 0 (under a window:
    a window that reaches behind row 0), two query blocks each, a cache
    that is no multiple of the tile and whose last, ragged tile the
    longer lane's queries reach.  At the families' head counts and
    sizes, in blocks and tiles a quarter of the chip's (the interpreter
    is slow).  The latent kinds: the same through
    ``prefix_flash_latent``, rows up-projected in the kernel; under
    ``keep`` a whole tile is empty for every other query."""
    bq, tk = 128, 256
    monkeypatch.setattr(pk, "PREFIX_FLASH_BLOCK_Q", bq)
    monkeypatch.setattr(pk, "PREFIX_FLASH_TILE", tk)
    monkeypatch.setattr(pk, "PREFIX_LATENT_BLOCK_Q", bq)
    monkeypatch.setattr(pk, "PREFIX_LATENT_TILE", tk)
    q_len = 2 * bq
    cache_len = 4 * tk + tk // 2 + 8
    start = jnp.asarray([0, cache_len - q_len], jnp.int32)
    if kind in LATENT:
        case = _latent_case(kind, 2, q_len, cache_len, start)
        scale = (case[0].shape[-1] + case[1].shape[-1]) ** -0.5
        got = pk.prefix_flash_latent(*case[:4], start, keep=case[4],
                                     softmax_scale=scale, interpret=True)
        assert got.shape == (*case[0].shape[:-1], LATENT[kind][3])
        walk = _latent_walk(*case, start, scale, tile=tk, block=bq)
        whole = _latent_whole_f32(*case, start, scale)
    else:
        q, k, v, window, sinks = _case(kind, 2, q_len, cache_len)
        got = pk.prefix_flash_attention(q, k, v, start, window=window,
                                        sink_logits=sinks, interpret=True)
        assert got.shape == (*q.shape[:-1], v.shape[-1])
        walk = _walk(q, k, v, start, window, sinks, block=bq)
        whole = _whole_f32(q, k, v, start, window, sinks)
    assert got.dtype == BF16
    assert not np.isnan(np.asarray(got, np.float32)).any()
    # bf16 outputs of magnitude ~1: an ulp is 2^-8; the walk rounds its
    # scores to bf16 before the softmax and the kernel does not, which
    # is the finer side (closer to float32 in the mean).
    err = lambda a: np.abs(np.asarray(got, np.float32)       # noqa: E731
                           - np.asarray(a, np.float32))
    assert err(walk).max() < 3e-2
    assert err(whole).max() < 2e-2
    walk_err = np.abs(np.asarray(walk, np.float32) - np.asarray(whole))
    assert err(whole).mean() <= walk_err.mean() * 1.05


@pytest.mark.parametrize("kind", ["full", "window-sink", "wide-key",
                                  "latent", "latent-keep"])
def test_a_call_of_four_pieces_is_the_four_pieces_to_the_bit(
        kind, monkeypatch):
    """Each query block walks its own tiles by its own position, so a
    call over four pieces gives every row the bits the pieces' calls
    give it; in float32 the kernel IS the walk (one tile rule, one
    arithmetic, no score to round).  The latent kinds: a piece is one
    query block of ``prefix_flash_latent`` and the call four."""
    monkeypatch.setattr(pk, "PREFIX_FLASH_BLOCK_Q", 16)
    monkeypatch.setattr(pk, "PREFIX_FLASH_TILE", 32)
    monkeypatch.setattr(pk, "PREFIX_LATENT_BLOCK_Q", 32)
    monkeypatch.setattr(pk, "PREFIX_LATENT_TILE", 32)
    piece, first = 32, 48            # (a start no multiple of the tile)
    at0 = jnp.asarray([first], jnp.int32)
    if kind.startswith("latent"):
        qn, qr, rows, kv_b, keep = _latent_case(
            (4, 16, 8, 16, 128, kind == "latent-keep"), 1, 4 * piece, 300,
            at0, dtype=jnp.float32, seed=3)

        def kernel(sl, at):
            return pk.prefix_flash_latent(
                qn[:, :, sl], qr[:, :, sl], rows, kv_b,
                jnp.asarray([at], jnp.int32),
                keep=None if keep is None else keep[:, sl],
                softmax_scale=0.2, interpret=True)

        walk = _latent_walk(qn, qr, rows, kv_b, keep, at0, 0.2, tile=32,
                            block=32)
    else:
        shape = {"full": (8, 2, 16, 8, None, False),
                 "window-sink": (8, 4, 16, 16, 40, True),
                 "wide-key": (8, 4, 192, 128, None, False)}[kind]
        q, k, v, window, sinks = _case(shape, 1, 4 * piece, 300,
                                       dtype=jnp.float32, seed=3)

        def kernel(sl, at):
            return pk.prefix_flash_attention(
                q[:, :, sl], k, v, jnp.asarray([at], jnp.int32),
                window=window, sink_logits=sinks, interpret=True)

        walk = _walk(q, k, v, at0, window, sinks, tile=32, block=16)
    whole = kernel(slice(None), first)
    apart = jnp.concatenate(
        [kernel(slice(i * piece, (i + 1) * piece), first + i * piece)
         for i in range(4)], axis=2)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(apart))
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


# -- the latent rows' kernel ------------------------------------------------

@pytest.mark.parametrize("q_len", [128, 256])
@pytest.mark.parametrize("kind", sorted(LATENT))
def test_a_latent_call_of_one_block_and_of_two_from_any_start(
        kind, q_len, monkeypatch):
    """``q_len`` of one query block and of two, three lanes at unlike
    ``start`` (0, inside a tile, the cache's end) over a cache of NO
    whole tiles, in float32, where the kernel is the walk to the last
    bits: the tile rule, the zeroed rows past the cache, the bias of
    ``keep`` (a whole tile empty for every other query) and the
    up-projection in the kernel are the walk's."""
    monkeypatch.setattr(pk, "PREFIX_LATENT_BLOCK_Q", 128)
    monkeypatch.setattr(pk, "PREFIX_LATENT_TILE", 128)
    cache_len = 3 * 128 + 72
    start = jnp.asarray([0, 77, cache_len - q_len], jnp.int32)
    case = _latent_case(kind, 3, q_len, cache_len, start,
                        dtype=jnp.float32, seed=7)
    got = pk.prefix_flash_latent(*case[:4], start, keep=case[4],
                                 softmax_scale=0.07, interpret=True)
    walk = _latent_walk(*case, start, 0.07, tile=128, block=128)
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_allclose(np.asarray(got), np.asarray(walk),
                               atol=5e-6, rtol=0)


def _latent_rows(store=640, dtype=BF16, cache_len=4096):
    return jax.ShapeDtypeStruct((1, cache_len, store), dtype)


DEEPSEEK = dict(rank=512, nope=128, rope=64, vd=128)


@pytest.mark.parametrize("what, q_len, rows, sizes, backend, want", [
    ("deepseek's piece", 1024, _latent_rows(), DEEPSEEK, "tpu", True),
    ("ling's call of four", 4096, _latent_rows(), DEEPSEEK, "tpu", True),
    ("half a piece", 512, _latent_rows(), DEEPSEEK, "tpu", True),
    ("glm's key head of 192", 1024, _latent_rows(),
     dict(rank=512, nope=192, rope=64, vd=256), "tpu", False),
    ("a decode step", 1, _latent_rows(), DEEPSEEK, "tpu", False),
    ("a speculative block", 5, _latent_rows(), DEEPSEEK, "tpu", False),
    ("queries that are no whole blocks", 1536, _latent_rows(), DEEPSEEK,
     "tpu", False),
    ("a float32 cache", 1024, _latent_rows(dtype=jnp.float32), DEEPSEEK,
     "tpu", False),
    ("an int8 cache", 1024, _latent_rows(dtype=jnp.int8), DEEPSEEK, "tpu",
     False),
    ("a test-size row", 1024, _latent_rows(store=128),
     dict(rank=32, nope=12, rope=8, vd=16), "tpu", False),
    ("a rotary key that shares its tile", 1024, _latent_rows(store=512),
     dict(rank=448, nope=128, rope=64, vd=128), "tpu", False),
    ("values of 192", 1024, _latent_rows(),
     dict(rank=512, nope=128, rope=64, vd=192), "tpu", False),
    ("the CPU", 1024, _latent_rows(), DEEPSEEK, "cpu", False),
])
def test_a_latent_walk_is_the_kernels_by_what_the_call_can_see(
        what, q_len, rows, sizes, backend, want, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.delenv("TTD_NO_PALLAS", raising=False)
    assert layers.latent_walk_ok(q_len, rows, **sizes) is want, what
    # the layers of a cache tree: a depth scan's five latent layers,
    # none where the model's sizes are not given, and the plain rows
    # beside them by their own rule
    tree = {"scan": {"attention": {
        "latent_cache": jax.ShapeDtypeStruct((5, *rows.shape), rows.dtype),
        "index_cache": jax.ShapeDtypeStruct((5, 1, 4096, 128), BF16)}},
        "plain": {"key_cache": _rows()[0], "value_cache": _rows()[1]},
        "index": jnp.zeros((1,), jnp.int32)}
    plain = int(layers.flash_walk_ok(q_len, *_rows()))
    assert layers.flash_walk_layers(tree, q_len) == plain
    assert layers.flash_walk_layers(tree, q_len, sizes) == plain + (
        5 if want else 0)


def test_the_kill_switch_and_a_mesh_keep_the_latent_walk(
        monkeypatch, mesh_2d):
    rows = _latent_rows()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("TTD_NO_PALLAS", raising=False)
    assert layers.latent_walk_ok(1024, rows, **DEEPSEEK)
    with jax.set_mesh(mesh_2d):
        assert not layers.latent_walk_ok(1024, rows, **DEEPSEEK)
    monkeypatch.setenv("TTD_NO_PALLAS", "1")
    assert not layers.latent_walk_ok(1024, rows, **DEEPSEEK)


def test_a_steps_heads_fit_the_kernels_fast_memory():
    """``_latent_group`` at the published shapes: a divisor of the
    heads, sixteen where they fit (DeepSeek's and Ling's piece), fewer
    for a call of four pieces, whose blocks grow with the call."""
    group = lambda heads, q_len, nope, vd: pk._latent_group(  # noqa: E731
        heads, q_len, 1024, 512, nope, 128, vd, 512, 2)
    assert group(128, 1024, 128, 128) == 16
    assert group(32, 1024, 128, 128) == 16
    assert group(32, 4096, 128, 128) == 4
    assert group(20, 2048, 192, 256) == 5
    assert group(7, 1 << 20, 128, 128) == 1


# -- who asks for the latent kernel ------------------------------------------

def _lowered(program, family, engages, monkeypatch):
    """The text of ``program`` (a method of ``ServingEngine``) lowered
    for a tiny engine of ``family`` with the latent kernel's rule
    saying ``engages`` to every walk of more than one query."""
    from benchmark.harness import weights
    from tensorflow_train_distributed_tpu.models import llama, moe
    from tensorflow_train_distributed_tpu.serving import ServingEngine

    cfg = (moe.MOE_PRESETS.get(family)
           or llama.LLAMA_PRESETS[family])
    model = (moe.MoeLmModel if isinstance(cfg, moe.MoeConfig)
             else llama.LlamaModel)(cfg)
    boxed = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    params = weights.make_params(weights.plain_shapes(boxed)["params"], 7,
                                 jnp.float32)
    monkeypatch.setattr(pk, "prefix_flash_latent_engages",
                        lambda q_len, rows, **sizes: engages and q_len > 1)
    monkeypatch.setattr(pk, "fused_attn_interpret", lambda: True)
    monkeypatch.setattr(pk, "PREFIX_LATENT_BLOCK_Q", 8)
    monkeypatch.setattr(pk, "PREFIX_LATENT_TILE", 16)
    eng = ServingEngine(cfg, params, slots=2, chunk=2, cache_len=64,
                        kv_block_size=8, prefill_chunk=16)
    method = getattr(ServingEngine, program)
    while not hasattr(method, "lower"):        # past the compile sanitizer
        method = method.__wrapped__
    if program == "_prefill_piece":
        return method.lower(
            eng, eng._variables, eng._cache_struct(1),
            jax.ShapeDtypeStruct((1, 16), jnp.int32), jnp.int32(3),
            jnp.uint32(0), jnp.int32(0)).as_text()
    ints = jax.ShapeDtypeStruct((2,), jnp.int32)
    return method.lower(
        eng, eng._variables, eng._cache_struct(2, grid=True),
        ints, jax.ShapeDtypeStruct((2,), jnp.uint32), ints).as_text()


@pytest.mark.parametrize("program", ["_prefill_piece", "_decode_chunk"])
@pytest.mark.parametrize("family, latent", [
    ("llama_tiny", False), ("laguna_tiny", False),
    ("deepseek_v32_tiny", True), ("ling_tiny", True)])
def test_only_a_latent_familys_piece_program_asks_for_the_latent_kernel(
        family, latent, program, monkeypatch):
    """A program lowered with the latent kernel's rule saying yes to
    every walk it is asked about is, for a model whose attention is not
    latent, the text it is with the rule saying no (nothing of it asks:
    the text is what it was before the kernel existed), and so is every
    family's decode chunk, whose steps read the paged pools.  A latent
    family's piece program moves."""
    moved = latent and program == "_prefill_piece"
    assert (_lowered(program, family, True, monkeypatch)
            != _lowered(program, family, False, monkeypatch)) is moved
