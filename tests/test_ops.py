"""Op-level tests: attention reference semantics, RoPE, shared losses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflow_train_distributed_tpu.models.layers import apply_rope
from tensorflow_train_distributed_tpu.ops.attention import (
    dot_product_attention,
    multihead_attention_kernel,
    prefix_attention,
    prefix_tiles_walked,
)
from tensorflow_train_distributed_tpu.ops.losses import softmax_cross_entropy


def _qkv(shape=(2, 2, 16, 8), kv_len=None, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], shape)
    kv_shape = shape if kv_len is None else (*shape[:2], kv_len, shape[-1])
    k = jax.random.normal(ks[1], kv_shape)
    v = jax.random.normal(ks[2], kv_shape)
    return q, k, v


def _require_pallas_interpret():
    """Import the pallas TPU flash kernel + interpret mode, or skip."""
    try:
        from jax.experimental.pallas import tpu as pltpu
        from jax.experimental.pallas.ops.tpu import flash_attention as fa
    except ImportError:
        pytest.skip("pallas tpu ops unavailable")
    if not hasattr(pltpu, "force_tpu_interpret_mode"):
        pytest.skip("force_tpu_interpret_mode unavailable")
    return pltpu, fa


class TestAttention:
    def test_causal_masks_future(self):
        q, k, v = _qkv()
        out = dot_product_attention(q, k, v, causal=True)
        # First query position attends only to key 0 → equals v[..., 0, :].
        np.testing.assert_allclose(np.asarray(out[..., 0, :]),
                                   np.asarray(v[..., 0, :]), rtol=1e-5)

    def test_causal_bottom_right_aligned(self):
        # q_len 4 over kv_len 8: query i sees keys 0..(4+i).
        q, k, v = _qkv(shape=(1, 1, 4, 8), kv_len=8)
        out = dot_product_attention(q, k, v, causal=True)
        full_q = jnp.concatenate([jnp.zeros((1, 1, 4, 8)), q], axis=2)
        full = dot_product_attention(full_q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(full[..., 4:, :]), rtol=1e-4)

    def test_fully_masked_row_no_nan(self):
        q, k, v = _qkv()
        mask = jnp.zeros((1, 1, 16, 16), bool)  # everything masked
        out = dot_product_attention(q, k, v, mask=mask)
        assert np.isfinite(np.asarray(out)).all()

    def test_kernel_dispatch_matches_reference_on_cpu(self):
        q, k, v = _qkv()
        out = multihead_attention_kernel(q, k, v, causal=True)
        ref = dot_product_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6)


    def test_segment_mask_matches_reference(self):
        """Packed-segment attention: derived dense mask on the reference
        path, and (via pallas interpret mode) the SegmentIds fast path the
        TPU takes — both must agree with first principles."""
        rng = np.random.default_rng(3)
        B, H, S, D = 1, 2, 256, 64
        q, k, v = (jnp.asarray(rng.standard_normal((B, H, S, D)),
                               jnp.float32) for _ in range(3))
        seg = jnp.asarray(np.repeat([1, 2, 3, 0], S // 4)[None], jnp.int32)
        out_kernel = multihead_attention_kernel(
            q, k, v, causal=True, segment_ids=seg)  # reference on CPU
        mask = seg[:, None, :, None] == seg[:, None, None, :]
        want = dot_product_attention(q, k, v, causal=True, mask=mask)
        np.testing.assert_allclose(np.asarray(out_kernel), np.asarray(want),
                                   atol=1e-6)
        # The TPU fast path: pallas flash kernel with SegmentIds, run in
        # interpret mode so CPU CI covers its *semantics* (pad segment 0,
        # causal alignment, scale) against the same oracle.
        pltpu, fa = _require_pallas_interpret()
        with pltpu.force_tpu_interpret_mode():
            out_flash = fa.flash_attention(
                q, k, v, segment_ids=fa.SegmentIds(q=seg, kv=seg),
                causal=True, sm_scale=D**-0.5)
        np.testing.assert_allclose(np.asarray(out_flash), np.asarray(want),
                                   atol=2e-6)


#: A 64-row cache in tiles of 16: up to four tiles to walk.
_CACHE, _TILE = 64, 16


def _prefix_case(form, dtype, q_len, lanes=1, seed=0):
    """(q, cache, kv_of) for ``prefix_attention``: ``grouped`` is the
    dense family's cache (2 KV heads serving 4, repeated a tile at a
    time), ``latent`` the latent family's (rows of rank 6 + 2 rotary
    values up-projected to 4 heads of 4 + 2 key and 5 value dims, as
    ``layers.LatentAttention._up_project`` does)."""
    ks = jax.random.split(jax.random.key(seed), 4)
    if form == "grouped":
        q = jax.random.normal(ks[0], (lanes, 4, q_len, 8), dtype)
        cache = tuple(jax.random.normal(k, (lanes, _CACHE, 2, 8), dtype)
                      for k in ks[1:3])

        def kv_of(rows):
            return [jnp.repeat(r, 2, axis=2).transpose(0, 2, 1, 3)
                    for r in rows]
    else:
        q = jax.random.normal(ks[0], (lanes, 4, q_len, 6), dtype)
        cache = jax.random.normal(ks[1], (lanes, _CACHE, 8), dtype)
        w = jax.random.normal(ks[2], (6, 4, 9), dtype) * 0.4

        def kv_of(rows):
            kv = jnp.einsum("btc,chd->bthd", rows[..., :6], w)
            k_r = jnp.broadcast_to(rows[..., None, 6:],
                                   (*rows.shape[:2], 4, 2))
            k = jnp.concatenate([kv[..., :4], k_r], axis=-1)
            return [t.transpose(0, 2, 1, 3) for t in (k, kv[..., 4:])]
    return q, cache, kv_of


def _whole_cache_attention(q, cache, kv_of, start):
    """Today's expression: every row of the cache under the mask."""
    pos = jnp.asarray(start)[:, None] + jnp.arange(q.shape[2])
    mask = jnp.arange(_CACHE)[None, None, :] <= pos[:, :, None]
    return dot_product_attention(q, *kv_of(cache), mask=mask[:, None])


class TestPrefixAttention:
    """``prefix_attention``: a call on a linear KV cache walks the row
    tiles its lanes hold, with a running softmax, and is the masked
    attention over the whole cache."""

    @pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-5),
                                            (jnp.bfloat16, 2e-2)],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("form", ["grouped", "latent"])
    @pytest.mark.parametrize("q_len", [1, 7, _TILE, 2 * _TILE])
    @pytest.mark.parametrize("start", ["0", "tile-1", "tile", "end"])
    def test_is_the_masked_attention_over_the_whole_cache(
            self, start, q_len, form, dtype, tol):
        start = {"0": 0, "tile-1": _TILE - 1, "tile": _TILE,
                 "end": _CACHE - q_len}[start]
        q, cache, kv_of = _prefix_case(form, dtype, q_len)
        out = prefix_attention(q, cache, jnp.array([start]), kv_of,
                               tile=_TILE)
        ref = _whole_cache_attention(q, cache, kv_of, [start])
        assert out.dtype == ref.dtype and out.shape == ref.shape
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=tol, rtol=tol)

    @pytest.mark.parametrize("form", ["grouped", "latent"])
    @pytest.mark.parametrize("start, q_len", [(0, 7), (_TILE - 1, 1),
                                              (_TILE, _TILE), (9, 2 * _TILE)])
    def test_rows_past_the_last_walked_tile_are_never_read(
            self, start, q_len, form):
        """NaN in every row past the walked tiles changes nothing, where
        the whole-cache expression gives NaN (a masked weight of 0 times
        NaN): the witness that those rows are not read, not up-projected,
        not repeated.  Rows inside the last walked tile past the last
        query stay masked, as they always were."""
        q, cache, kv_of = _prefix_case(form, jnp.float32, q_len)
        tiles = int(prefix_tiles_walked(np.array([start]), q_len, _TILE,
                                        _CACHE))
        assert tiles < _CACHE // _TILE
        poisoned = jax.tree.map(
            lambda c: c.at[:, tiles * _TILE:].set(jnp.nan), cache)
        clean = prefix_attention(q, cache, jnp.array([start]), kv_of,
                                 tile=_TILE)
        out = prefix_attention(q, poisoned, jnp.array([start]), kv_of,
                               tile=_TILE)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))
        assert np.isnan(np.asarray(_whole_cache_attention(
            q, poisoned, kv_of, [start]))).any()

    @pytest.mark.parametrize("form", ["grouped", "latent"])
    def test_lanes_at_different_positions_walk_to_the_longest(self, form):
        """One trip count a call: the longest lane's.  Every lane still
        gets its own mask, and the tiles past the longest are not read."""
        starts, q_len = [3, 30, 17], 5
        q, cache, kv_of = _prefix_case(form, jnp.float32, q_len, lanes=3)
        assert int(prefix_tiles_walked(
            np.array(starts), q_len, _TILE, _CACHE)) == 3
        poisoned = jax.tree.map(
            lambda c: c.at[:, 3 * _TILE:].set(jnp.nan), cache)
        out = jax.jit(lambda q, c, s: prefix_attention(
            q, c, s, kv_of, tile=_TILE))(q, poisoned, jnp.array(starts))
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(_whole_cache_attention(q, cache, kv_of, starts)),
            atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("form", ["grouped", "latent"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    def test_a_cache_of_one_tile_is_the_whole_cache_expression_to_the_bit(
            self, dtype, form):
        q, cache, kv_of = _prefix_case(form, dtype, 7, lanes=2)
        starts = [5, 40]
        out = prefix_attention(q, cache, jnp.array(starts), kv_of,
                               tile=_CACHE)
        np.testing.assert_array_equal(
            np.asarray(out, np.float32),
            np.asarray(_whole_cache_attention(q, cache, kv_of, starts),
                       np.float32))

    @pytest.mark.parametrize("start", [0, 20, 37])
    def test_a_cache_that_is_no_multiple_of_the_tile(self, start):
        """40 rows in tiles of 16: the third tile starts at row 24, and
        the rows it shares with the second count once."""
        q, cache, kv_of = _prefix_case("grouped", jnp.float32, 3)
        cache = tuple(c[:, :40] for c in cache)
        out = prefix_attention(q, cache, jnp.array([start]), kv_of,
                               tile=_TILE)
        mask = jnp.arange(40)[None, :] <= start + jnp.arange(3)[:, None]
        ref = dot_product_attention(q, *kv_of(cache),
                                    mask=mask[None, None])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("tile, cache_len", [(16, 64), (16, 40),
                                                 (512, 4096), (64, 32)])
    def test_the_walk_rule_is_a_brute_force_count(self, tile, cache_len):
        """Tiles holding a row some query may see: never none, never
        past the cache (an overrun lane included), the longest lane's."""
        n_tiles = -(-cache_len // tile)
        for q_len in (1, 7, tile, 2 * tile):
            for starts in ([0], [tile - 1], [tile], [cache_len - 1],
                           [cache_len + 3 * tile], [0, 2 * tile + 1, 5]):
                last = min(max(starts) + q_len, cache_len) - 1
                want = max(1, sum(1 for t in range(n_tiles)
                                  if t * tile <= last))
                for xp in (np, jnp):
                    got = int(prefix_tiles_walked(
                        xp.asarray(starts), q_len, tile, cache_len))
                    assert got == want and 1 <= got <= n_tiles


class TestRope:
    def test_relative_phase(self):
        # RoPE property: <rot(q,p1), rot(k,p2)> depends only on p1-p2.
        x = jax.random.normal(jax.random.key(0), (1, 1, 1, 8))
        y = jax.random.normal(jax.random.key(1), (1, 1, 1, 8))
        pos = lambda p: jnp.full((1, 1), p)
        dot = lambda a, b: float(jnp.sum(a * b))
        d1 = dot(apply_rope(x, pos(3)), apply_rope(y, pos(1)))
        d2 = dot(apply_rope(x, pos(7)), apply_rope(y, pos(5)))
        assert abs(d1 - d2) < 1e-4

    def test_zero_position_identity(self):
        x = jax.random.normal(jax.random.key(0), (1, 4, 2, 8))
        out = apply_rope(x, jnp.zeros((1, 4), jnp.int32))
        np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=1e-6)


class TestLosses:
    def test_matches_manual_ce(self):
        logits = jnp.array([[2.0, 0.0, 0.0], [0.0, 3.0, 0.0]])
        labels = jnp.array([0, 1])
        loss, acc = softmax_cross_entropy(logits, labels)
        manual = -np.log(np.exp([2.0, 3.0]) /
                         (np.exp([2.0, 3.0]) + 2)).mean()
        np.testing.assert_allclose(float(loss), manual, rtol=1e-6)
        assert float(acc) == 1.0

    def test_weights_select_tokens(self):
        logits = jnp.array([[10.0, 0.0], [0.0, 10.0]])
        labels = jnp.array([0, 0])  # second is wrong
        w_first = jnp.array([1.0, 0.0])
        loss, acc = softmax_cross_entropy(logits, labels, weights=w_first)
        assert float(acc) == 1.0 and float(loss) < 1e-3
        loss2, acc2 = softmax_cross_entropy(logits, labels,
                                            weights=1 - w_first)
        assert float(acc2) == 0.0 and float(loss2) > 5.0

    def test_label_smoothing_raises_floor(self):
        logits = jnp.array([[100.0, 0.0]])
        labels = jnp.array([0])
        loss0, _ = softmax_cross_entropy(logits, labels)
        loss_s, _ = softmax_cross_entropy(logits, labels,
                                          label_smoothing=0.1)
        assert float(loss_s) > float(loss0)


def test_flash_backward_stays_in_pallas():
    """The flash kernel's custom VJP IS the training-path
    backward — the grad jaxpr contains the pallas bwd kernels and NO
    materialized [S, S] score tensor anywhere (the buffer whose absence
    makes long-context training fit)."""
    pltpu, fa = _require_pallas_interpret()

    rng = np.random.default_rng(0)
    B, H, S, D = 1, 2, 256, 64
    q, k, v = (jnp.asarray(rng.standard_normal((B, H, S, D)),
                           jnp.float32) for _ in range(3))

    def loss(q_, k_, v_):
        return fa.flash_attention(q_, k_, v_, causal=True,
                                  sm_scale=D**-0.5).sum()

    with pltpu.force_tpu_interpret_mode():
        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    text = str(jaxpr)
    # fwd + dq + dkv kernels: >= 2 pallas calls proves the BACKWARD runs
    # in pallas, not just the forward (3 observed on jax 0.9).
    assert text.count("pallas_call") >= 2, text.count("pallas_call")

    def all_avals(jx):
        # recurse through call/scan/custom_vjp sub-jaxprs generically —
        # but NOT into pallas_call kernels: their in-VMEM block tiles are
        # S×S here (block = min(512, S)) by design, and excluding them
        # must not depend on how jax happens to store the kernel jaxpr.
        for eqn in jx.eqns:
            if "pallas" in str(eqn.primitive):
                continue
            for var in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(var, "aval", None)
                if aval is not None and hasattr(aval, "shape"):
                    yield aval.shape
            for val in eqn.params.values():
                sub = getattr(val, "jaxpr", None)
                if sub is not None:
                    yield from all_avals(sub)
                if isinstance(val, (list, tuple)):
                    for v_ in val:
                        s_ = getattr(v_, "jaxpr", None)
                        if s_ is not None:
                            yield from all_avals(s_)

    def count_score_tensors(jx):
        return sum(1 for s in all_avals(jx)
                   if len(s) >= 2 and s[-1] == S and s[-2] == S)

    # Kernel-internal BLOCK tiles are fine; a full [B, H, S, S] (or any
    # S×S trailing pair) would be the materialized scores.
    assert count_score_tensors(jaxpr.jaxpr) == 0

    # Negative control: the reference einsum path MUST trip the detector,
    # or the assertion above is vacuous.
    ref_jaxpr = jax.make_jaxpr(jax.grad(
        lambda q_, k_, v_: dot_product_attention(
            q_, k_, v_, causal=True).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    assert count_score_tensors(ref_jaxpr.jaxpr) > 0
