"""Hand-rolled pallas kernels vs their pure-jax oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflow_train_distributed_tpu.ops import pallas_kernels as pk


def _rand(shape, seed=0, dtype=np.float32):
    return np.random.default_rng(seed).standard_normal(shape).astype(dtype)


class TestRmsNorm:
    @pytest.mark.parametrize("shape", [(4, 256), (2, 17, 384), (1, 128)])
    def test_forward_matches_reference(self, shape):
        x = _rand(shape)
        s = 1.0 + 0.1 * _rand(shape[-1:], seed=1)
        got = pk.rms_norm(x, s, use_pallas=True, interpret=True)
        want = pk.rms_norm_reference(x, s)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_forward_bf16(self):
        x = _rand((8, 256)).astype(jnp.bfloat16)
        s = np.ones((256,), np.float32)
        got = pk.rms_norm(x, s, use_pallas=True, interpret=True)
        want = pk.rms_norm_reference(x, s)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=2e-2)

    def test_gradients_match_reference(self):
        x = _rand((6, 256))
        s = 1.0 + 0.1 * _rand((256,), seed=1)

        def loss_pallas(x, s):
            y = pk.rms_norm(x, s, use_pallas=True, interpret=True)
            return jnp.sum(jnp.sin(y))

        def loss_ref(x, s):
            return jnp.sum(jnp.sin(pk.rms_norm_reference(x, s)))

        gx, gs = jax.grad(loss_pallas, argnums=(0, 1))(x, s)
        rx, rs = jax.grad(loss_ref, argnums=(0, 1))(x, s)
        np.testing.assert_allclose(gx, rx, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(gs, rs, rtol=1e-4, atol=1e-5)

    def test_rows_not_multiple_of_block(self):
        # 300 rows with block 256 → ragged last block must still be exact.
        x = _rand((300, 128))
        s = np.ones((128,), np.float32)
        got = pk.rms_norm(x, s, use_pallas=True, interpret=True)
        np.testing.assert_allclose(
            got, pk.rms_norm_reference(x, s), rtol=2e-5, atol=2e-5)


class TestFusedCrossEntropy:
    @pytest.mark.parametrize("n,v", [(16, 512), (8, 1000), (32, 2048 + 77)])
    def test_forward_matches_reference(self, n, v):
        logits = 4.0 * _rand((n, v))
        labels = np.random.default_rng(1).integers(0, v, n).astype(np.int32)
        got = pk.fused_cross_entropy(logits, labels, use_pallas=True,
                                     interpret=True)
        want = pk.cross_entropy_reference(logits, labels)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_multi_dim_batch(self):
        logits = _rand((2, 5, 300))
        labels = np.random.default_rng(1).integers(0, 300, (2, 5)).astype(
            np.int32)
        got = pk.fused_cross_entropy(logits, labels, use_pallas=True,
                                     interpret=True)
        assert got.shape == (2, 5)
        np.testing.assert_allclose(
            got, pk.cross_entropy_reference(logits, labels),
            rtol=1e-5, atol=1e-5)

    def test_gradient_matches_reference(self):
        n, v = 12, 700
        logits = 2.0 * _rand((n, v))
        labels = np.random.default_rng(2).integers(0, v, n).astype(np.int32)
        w = _rand((n,), seed=3)  # weighted mean exercises nontrivial g

        def loss_pallas(lg):
            per = pk.fused_cross_entropy(lg, labels, use_pallas=True,
                                         interpret=True)
            return jnp.sum(per * w)

        def loss_ref(lg):
            return jnp.sum(pk.cross_entropy_reference(lg, labels) * w)

        g = jax.grad(loss_pallas)(logits)
        r = jax.grad(loss_ref)(logits)
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5)

    def test_extreme_logits_stable(self):
        logits = np.array([[1e4, -1e4, 0.0, 50.0]] * 8, np.float32)
        logits = np.pad(logits, ((0, 0), (0, 124)))  # V=128
        labels = np.zeros((8,), np.int32)
        got = pk.fused_cross_entropy(logits, labels, use_pallas=True,
                                     interpret=True)
        assert np.all(np.isfinite(np.asarray(got)))
        np.testing.assert_allclose(
            got, pk.cross_entropy_reference(logits, labels), rtol=1e-5)

    def test_jnp_fallback_path(self):
        logits = _rand((4, 64))
        labels = np.array([0, 5, 63, 7], np.int32)
        got = pk.fused_cross_entropy(logits, labels, use_pallas=False)
        np.testing.assert_allclose(
            got, pk.cross_entropy_reference(logits, labels), rtol=1e-6)


def test_env_kill_switch_disables_pallas(monkeypatch):
    """TTD_NO_PALLAS=1 (the chip-playbook A/B switch) forces the
    pure-jax path regardless of backend; explicit overrides still win."""
    monkeypatch.setenv("TTD_NO_PALLAS", "1")
    assert pk._use_pallas(None) is False
    assert pk._use_pallas(True) is True
    # "0"/"false" mean OFF — TTD_NO_PALLAS=0 must NOT disable kernels.
    monkeypatch.setenv("TTD_NO_PALLAS", "0")
    assert pk._use_pallas(None) is (__import__("jax").default_backend()
                                    == "tpu")
    monkeypatch.setenv("TTD_NO_PALLAS", "false")
    assert pk._use_pallas(None) is (__import__("jax").default_backend()
                                    == "tpu")
    monkeypatch.delenv("TTD_NO_PALLAS")
    # Default is backend-keyed (cpu in tests → False).
    assert pk._use_pallas(None) is False


class TestPagedAttention:
    """The fused paged-attention decode kernel vs its pure-jax oracle
    (``paged_attention_reference`` — the exact math of the engine's
    XLA block-gather leg).  A gather has no math; attention does, so
    the bar is tight-tolerance numerics, with layout/masking cases
    pinned exactly: GQA head groups, ragged per-lane lengths,
    scratch-block-0 lanes, and a stale/garbage block-table lane (the
    overlap scheduler's reset-lane case)."""

    @staticmethod
    def _mk(lanes, q_len, heads, kvh, hd=8, nb=9, bs=4, n_blk=5,
            seed=0, lengths=None):
        rng = np.random.default_rng(seed)
        kp = jnp.asarray(rng.normal(size=(nb, bs, kvh * hd)).astype(
            np.float32))
        vp = jnp.asarray(rng.normal(size=(nb, bs, kvh * hd)).astype(
            np.float32))
        table = jnp.asarray(rng.integers(0, nb, (lanes, n_blk)).astype(
            np.int32))
        if lengths is None:
            lengths = rng.integers(0, n_blk * bs - q_len + 1, lanes)
        lengths = jnp.asarray(np.asarray(lengths, np.int32))
        q = jnp.asarray(rng.normal(
            size=(lanes, q_len, heads, hd)).astype(np.float32))
        return q, kp, vp, table, lengths

    @pytest.mark.parametrize("heads,kvh,q_len", [
        (4, 2, 1),    # GQA, single-token decode step
        (4, 1, 3),    # MQA-extreme, speculative verify block
        (2, 2, 2),    # MHA, multi-token
    ])
    def test_kernel_matches_oracle(self, heads, kvh, q_len):
        q, kp, vp, table, lengths = self._mk(3, q_len, heads, kvh)
        ref = pk.paged_attention_reference(q, kp, vp, table, lengths)
        out = pk.paged_attention(q, kp, vp, table, lengths,
                                 use_pallas=True, interpret=True)
        assert out.shape == q.shape
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_ragged_lane_lengths(self):
        # Length 0 (fresh lane: only its own new rows visible), a
        # mid-block length, and a block-aligned one — all in one grid.
        q, kp, vp, table, lengths = self._mk(
            3, 2, 4, 2, lengths=[0, 7, 16])
        ref = pk.paged_attention_reference(q, kp, vp, table, lengths)
        out = pk.paged_attention(q, kp, vp, table, lengths,
                                 use_pallas=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_scratch_block_zero_lane_masked(self):
        """A reset lane (table all scratch-0, length 0 — what the
        engine's ``_reset_lanes`` leaves behind) must produce the
        oracle's exact garbage-in-garbage-out and stay finite: the
        masking gives query i exactly rows 0..i of the scratch block,
        never NaN."""
        q, kp, vp, table, lengths = self._mk(3, 2, 4, 2)
        table = table.at[1].set(0)
        lengths = lengths.at[1].set(0)
        ref = pk.paged_attention_reference(q, kp, vp, table, lengths)
        out = pk.paged_attention(q, kp, vp, table, lengths,
                                 use_pallas=True, interpret=True)
        assert np.all(np.isfinite(np.asarray(out)))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_stale_garbage_table_lane_isolated(self):
        """The overlap scheduler's one garbage chunk: a lane whose
        table holds stale physical ids (blocks now owned by OTHERS)
        must not perturb its neighbors — their rows are read-only to
        the attention, so the healthy lanes' outputs are BITWISE equal
        with and without the garbage lane's corruption."""
        q, kp, vp, table, lengths = self._mk(3, 1, 4, 2)
        clean = pk.paged_attention(q, kp, vp, table, lengths,
                                   use_pallas=True, interpret=True)
        garbage_table = table.at[1].set(
            jnp.asarray([8, 8, 3, 1, 2], jnp.int32))
        dirty = pk.paged_attention(q, kp, vp, garbage_table, lengths,
                                   use_pallas=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(clean[0]),
                                      np.asarray(dirty[0]))
        np.testing.assert_array_equal(np.asarray(clean[2]),
                                      np.asarray(dirty[2]))

    def test_int8_pool_dequant_matches_oracle(self):
        rng = np.random.default_rng(3)
        nb, bs, kvh, hd = 7, 4, 2, 8
        q, _, _, table, lengths = self._mk(3, 2, 4, kvh, hd=hd, nb=nb,
                                           bs=bs, seed=3)
        kp = jnp.asarray(rng.integers(-127, 128,
                                      (nb, bs, kvh * hd)).astype(np.int8))
        vp = jnp.asarray(rng.integers(-127, 128,
                                      (nb, bs, kvh * hd)).astype(np.int8))
        ks = jnp.asarray((np.abs(rng.normal(size=(nb, bs, kvh)))
                          .astype(np.float32) / 127.0) + 1e-3)
        vs = jnp.asarray((np.abs(rng.normal(size=(nb, bs, kvh)))
                          .astype(np.float32) / 127.0) + 1e-3)
        ref = pk.paged_attention_reference(q, kp, vp, table, lengths,
                                           k_scales=ks, v_scales=vs)
        out = pk.paged_attention(q, kp, vp, table, lengths,
                                 k_scales=ks, v_scales=vs,
                                 use_pallas=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_cpu_path_uses_reference(self):
        # On this CPU backend the public entry must route to the
        # reference — BITWISE equal (it IS the reference), the property
        # that makes TTD_NO_PALLAS parity trivial off-TPU.
        q, kp, vp, table, lengths = self._mk(2, 1, 2, 2)
        out = pk.paged_attention(q, kp, vp, table, lengths)
        ref = pk.paged_attention_reference(q, kp, vp, table, lengths)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    # The walk: ``_paged_fold`` table entries to a step, as far as a
    # lane's length reaches.  bs 4 and an 11-block table give a fold of
    # 11 (one step), which hides the step edges; the cases pin a fold
    # of 4, which 11 does not divide.
    BS, N_BLK, FOLD = 4, 11, 4

    @classmethod
    def _walk_case(cls, q_len, heads, kvh, int8, seed=0):
        """One lane at every length where the walk's count of blocks or
        of steps changes, and one that overran its table."""
        bs, n_blk, fold = cls.BS, cls.N_BLK, cls.FOLD
        lengths = [0, 1, bs - 1, bs, fold * bs - 1, fold * bs,
                   fold * bs + 1, n_blk * bs - q_len, n_blk * bs + 3]
        lanes, nb, hd = len(lengths), 1 + len(lengths) * n_blk, 8
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.normal(size=(lanes, q_len, heads, hd)),
                        jnp.float32)
        # Every lane owns its blocks (1 + lane * n_blk ...), so a block
        # a lane's length does not reach belongs to no one else either.
        table = jnp.asarray(
            1 + np.arange(lanes * n_blk).reshape(lanes, n_blk), jnp.int32)
        shape = (nb, bs, kvh * hd)     # a row as the cache stores it
        scales = {}
        if int8:
            kp, vp = (jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
                      for _ in range(2))
            scales = {
                name: jnp.asarray(np.abs(rng.normal(size=(nb, bs, kvh)))
                                  / 127 + 1e-3, jnp.float32)
                for name in ("k_scales", "v_scales")}
        else:
            kp, vp = (jnp.asarray(rng.normal(size=shape), jnp.float32)
                      for _ in range(2))
        return q, kp, vp, table, jnp.asarray(lengths, jnp.int32), scales

    @pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
    @pytest.mark.parametrize("heads,kvh", [(28, 4), (4, 4)],
                             ids=["gqa28-4", "mha"])
    @pytest.mark.parametrize("q_len", [1, 4])
    def test_ragged_walk_matches_oracle(self, q_len, heads, kvh, int8,
                                        monkeypatch):
        monkeypatch.setattr(pk, "_paged_fold", lambda *shape: self.FOLD)
        q, kp, vp, table, lengths, scales = self._walk_case(
            q_len, heads, kvh, int8)
        if not int8:    # the cell's storage: bf16 rows, f32 arithmetic
            q, kp, vp = (a.astype(jnp.bfloat16) for a in (q, kp, vp))
        ref = pk.paged_attention_reference(
            q.astype(jnp.float32), kp, vp, table, lengths, **scales)
        out = pk.paged_attention(q, kp, vp, table, lengths, **scales,
                                 use_pallas=True, interpret=True)
        assert out.dtype == q.dtype
        tol = 1e-5 if int8 else 1e-2       # bf16 rounds the output
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), rtol=tol, atol=tol)

    @pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
    @pytest.mark.parametrize("q_len", [1, 4])
    def test_walk_stops_at_the_lanes_length(self, q_len, int8,
                                            monkeypatch):
        """Every block a lane's length does not reach is poisoned (NaN
        rows, or NaN scales under int8 rows) and the output does not
        move: the walk reads ``paged_blocks_walked`` blocks of a lane's
        table and nothing behind them."""
        monkeypatch.setattr(pk, "_paged_fold", lambda *shape: self.FOLD)
        q, kp, vp, table, lengths, scales = self._walk_case(
            q_len, 4, 2, int8, seed=5)
        clean = pk.paged_attention(q, kp, vp, table, lengths, **scales,
                                   use_pallas=True, interpret=True)
        reach = np.asarray(pk.paged_blocks_walked(
            np.asarray(lengths), q_len, self.BS, self.N_BLK))
        assert reach.tolist() == [
            min(max(-(-(int(n) + q_len) // self.BS), 1), self.N_BLK)
            for n in lengths]
        dead = np.concatenate([[0]] + [
            np.asarray(table[lane, n:]) for lane, n in enumerate(reach)])
        assert 0 < len(dead) < kp.shape[0]
        if int8:
            scales = {k: v.at[dead].set(jnp.nan) for k, v in scales.items()}
        else:
            kp, vp = kp.at[dead].set(jnp.nan), vp.at[dead].set(jnp.nan)
        dirty = pk.paged_attention(q, kp, vp, table, lengths, **scales,
                                   use_pallas=True, interpret=True)
        assert np.all(np.isfinite(np.asarray(clean)))
        np.testing.assert_array_equal(np.asarray(dirty), np.asarray(clean))


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("interpret", [False, True],
                         ids=["reference", "kernel"])
def test_block0_reads_one_layer_of_a_pool_that_holds_several(int8,
                                                             interpret):
    """The depth scan carries every layer's blocks in one pool, one
    layer after the other: read through ``block0 = layer * blocks`` the
    whole pool gives, to the bit, what the layer's own blocks give
    alone (the scales are the layer's, numbered by the table itself)."""
    layers, (q_len, heads, kvh) = 3, (2, 4, 2)
    cases = [TestPagedAttention._walk_case(q_len, heads, kvh, int8,
                                           seed=layer)
             for layer in range(layers)]
    q, _, _, table, lengths, _ = cases[0]
    nb = cases[0][1].shape[0]
    k_all, v_all = (jnp.concatenate([c[i] for c in cases])
                    for i in (1, 2))
    kw = dict(use_pallas=True, interpret=True) if interpret else {}
    for layer, (_, kp, vp, _, _, scales) in enumerate(cases):
        alone = pk.paged_attention(q, kp, vp, table, lengths, **scales,
                                   **kw)
        stacked = pk.paged_attention(
            q, k_all, v_all, table, lengths, **scales,
            block0=jnp.int32(layer * nb), **kw)
        assert np.all(np.isfinite(np.asarray(alone)))
        np.testing.assert_array_equal(np.asarray(stacked),
                                      np.asarray(alone))


# ---------------------------------------------------------------------------
# The three paged walks at a wide step (``PAGED_FOLD_ROWS``)
# ---------------------------------------------------------------------------

# Blocks of 4 rows and a step of 32: 8 table entries a step, as 512 rows
# are 32 blocks of 16.  A context of 19 blocks is two steps and 3 blocks.
_W_BS, _W_ROWS, _W_BLOCKS = 4, 32, 19
#: walk -> (window, the ring's blocks): a ring shorter than a step is one
#: step of 6 entries; a ring of 12 is a step of 8 and one of up to 3.
_WALKS = {"table": (None, _W_BLOCKS), "ring<step": (13, 6),
          "ring=2steps": (37, 12)}
_WALK_KERNELS = ("attn", "attn-sink", "attn-int8", "latent", "index")


def _wide_walk_case(kernel, walk, q_len, seed=0):
    """One lane at every length where a wide step's count of blocks,
    of copies or of steps changes; each lane owns its table's blocks.
    Returns ``(call, table, lengths, pools)``: ``call(pools, **kw)`` is
    the kernel's public entry (``use_pallas=False``: its reference)."""
    bs, rows, n_ctx = _W_BS, _W_ROWS, _W_BLOCKS
    window, n_blk = _WALKS[walk]
    cache_len = n_ctx * bs
    lengths = [0, 1, bs, rows - 1, rows, rows + 1, cache_len - q_len]
    if window is None:
        lengths.append(cache_len + 3)       # a lane that overran its table
    lanes, nb = len(lengths), 1 + len(lengths) * n_blk
    rng = np.random.default_rng(seed)
    table = jnp.asarray(
        1 + np.arange(lanes * n_blk).reshape(lanes, n_blk), jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    walk_kw = dict(cache_len=cache_len)
    if window is not None:
        walk_kw["window"] = window

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    if kernel == "latent":
        heads, row, rank = 5, 24, 16
        q = normal(lanes, q_len, heads, row)
        pools = {"pool": normal(nb, bs, row)}

        def call(pools, **kw):
            return pk.paged_latent_attention(
                q, pools["pool"], table, lengths, value_dim=rank,
                scale=0.125, **walk_kw, **kw)
        return call, table, lengths, pools
    if kernel == "index":
        heads, dim = 3, 8
        q, w = normal(lanes, q_len, heads, dim), normal(lanes, q_len, heads)
        pools = {"pool": normal(nb, bs, dim)}

        def call(pools, **kw):
            return pk.paged_index_scores(q, w, pools["pool"], table, lengths,
                                         **walk_kw, **kw)
        return call, table, lengths, pools
    heads, kvh, hd = 4, 2, 8
    q = normal(lanes, q_len, heads, hd)
    extra = {}
    if kernel == "attn-sink":
        extra["sink_logits"] = normal(heads)
    if kernel == "attn-int8":
        pools = {name: jnp.asarray(
            rng.integers(-127, 128, (nb, bs, kvh * hd)), jnp.int8)
            for name in ("k_pool", "v_pool")}
        pools.update({name: jnp.asarray(
            np.abs(rng.normal(size=(nb, bs, kvh))) / 127 + 1e-3, jnp.float32)
            for name in ("k_scales", "v_scales")})
    else:
        pools = {"k_pool": normal(nb, bs, kvh * hd),
                 "v_pool": normal(nb, bs, kvh * hd)}

    def call(pools, **kw):
        pools = dict(pools)
        return pk.paged_attention(
            q, pools.pop("k_pool"), pools.pop("v_pool"), table, lengths,
            **pools, **extra, **walk_kw, **kw)
    return call, table, lengths, pools


def _blocks_not_held(table, lengths, q_len, walk):
    """Block 0 and every block of a lane's table that its walk does not
    read: behind its length, or outside the span of its window's ring."""
    window, n_blk = _WALKS[walk]
    lengths = np.asarray(lengths)
    live = np.asarray(pk.paged_blocks_walked(lengths, q_len, _W_BS,
                                             _W_BLOCKS, window))
    first = np.asarray(pk.paged_first_block(lengths, _W_BS, window)
                       ) * np.ones_like(live)
    dead = [0]
    for lane, (f, n) in enumerate(zip(first, live)):
        held = {int(f + e) % n_blk for e in range(n)}
        dead += [int(table[lane, e]) for e in range(n_blk)
                 if e not in held]
    return np.asarray(dead)


@pytest.mark.parametrize("q_len", [1, 3])
@pytest.mark.parametrize("kernel,walk", [
    (k, w) for k in _WALK_KERNELS for w in _WALKS
    if w == "table" or k in ("attn", "attn-sink", "latent")])
def test_wide_step_reads_what_a_lane_holds_and_nothing_else(
        kernel, walk, q_len, monkeypatch):
    """The three paged walks against their references with a step of
    several blocks, lanes at every edge of a step, and every block a
    lane does not hold poisoned (NaN rows; NaN scales under int8
    rows): the kernel, which is given the poisoned pools, copies the
    blocks a lane holds and no others, and what a step's buffer holds
    beside them (the scratch's first contents, an earlier lane's rows)
    reaches no output."""
    monkeypatch.setattr(pk, "PAGED_FOLD_ROWS", _W_ROWS)
    assert pk._paged_fold(_W_BS, _WALKS[walk][1], row_bytes=256) == min(
        _WALKS[walk][1], _W_ROWS // _W_BS)
    call, table, lengths, pools = _wide_walk_case(kernel, walk, q_len)
    want = np.asarray(call(pools, use_pallas=False))
    dead = _blocks_not_held(table, lengths, q_len, walk)
    assert 0 < len(dead) < next(iter(pools.values())).shape[0]
    poisoned = {name: pool.at[dead].set(jnp.nan)
                if pool.dtype != jnp.int8 else pool
                for name, pool in pools.items()}
    got = np.asarray(call(poisoned, use_pallas=True, interpret=True))
    assert got.shape == want.shape
    if kernel == "index":       # -inf past a query's position, in both
        np.testing.assert_array_equal(np.isneginf(got), np.isneginf(want))
        assert not np.isnan(got).any()
    else:
        assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def _kv_row_bytes(kvh, hd, dtype, q_rows, vd=None):
    """``_step_row_bytes`` of ``paged_attention`` over pools of ``kvh``
    heads of ``hd`` keys and ``vd`` values a row."""
    pools = [jax.ShapeDtypeStruct((1, 16, kvh * d), dtype)
             for d in (hd, vd or hd)]
    return pk._step_row_bytes(pools, q_rows, widened=True)


@pytest.mark.parametrize("name,row_bytes,rows", [
    # The cells' widths fold the constant's rows ...
    ("qwen25_7b", _kv_row_bytes(4, 128, jnp.bfloat16, 7), 512),
    ("laguna-kv8d128-q3", _kv_row_bytes(8, 128, jnp.bfloat16, 18), 512),
    ("mimo-kv4k192v128", _kv_row_bytes(4, 192, jnp.bfloat16, 16, vd=128),
     512),
    ("dots3-latent-h128-q3", pk._step_row_bytes(
        [jax.ShapeDtypeStruct((1, 16, 640), jnp.bfloat16)], 384), 512),
    # ... a wider row fewer, in whole lane tiles of rows ...
    ("kv12d128", _kv_row_bytes(12, 128, jnp.bfloat16, 4), 384),
    ("kv16d128", _kv_row_bytes(16, 128, jnp.bfloat16, 2), 256),
    # ... and the MHA presets never under the 128 rows they had.
    ("llama2_7b-int8", _kv_row_bytes(32, 128, jnp.int8, 1), 128),
    ("llama2_7b", _kv_row_bytes(32, 128, jnp.bfloat16, 1), 128),
    ("gemma_7b-q3", _kv_row_bytes(16, 256, jnp.bfloat16, 3), 128),
    ("llama2_13b", _kv_row_bytes(40, 128, jnp.bfloat16, 1), 128),
])
def test_a_steps_rows_follow_what_a_row_costs_in_fast_memory(
        name, row_bytes, rows):
    """``_paged_fold``: ``PAGED_FOLD_ROWS`` is the most a step holds; a
    step's rows by ``_step_row_bytes`` stay within ``_PAGED_STEP_VMEM``
    wherever that leaves a lane tile of rows or more, and a short table
    or ring is one step whatever the width."""
    bs = 16
    assert pk._paged_fold(bs, 4096, row_bytes) * bs == rows
    assert rows == pk._LANES or rows * row_bytes <= pk._PAGED_STEP_VMEM
    assert rows == pk.PAGED_FOLD_ROWS or (
        (rows + pk._LANES) * row_bytes > pk._PAGED_STEP_VMEM)
    assert pk._paged_fold(bs, 5, row_bytes) == 5


def _count_eqns(jaxpr, name):
    """Equations of primitive ``name`` in ``jaxpr`` and every jaxpr
    inside it (a loop's body, a branch, a kernel)."""
    total = 0
    for eqn in jaxpr.eqns:
        total += eqn.primitive.name == name
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (list, tuple))
                          else [value]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    total += _count_eqns(inner, name)
    return total


def _tpu_walk(kernel):
    """One instance of a paged walk at a served width, as shapes:
    ``(fn, args, copies a site)``."""
    bf16, i32 = jnp.bfloat16, jnp.int32
    lanes, bs, n_blk = 8, 16, 256
    shape = jax.ShapeDtypeStruct
    tail = (shape((lanes, n_blk), i32), shape((lanes,), i32))

    def pool(row):
        return shape((1 + lanes * n_blk, bs, row), bf16)

    if kernel == "attn":
        return (lambda q, k, v, t, n: pk.paged_attention(
            q, k, v, t, n, cache_len=n_blk * bs, use_pallas=True),
            (shape((lanes, 1, 28, 128), bf16), pool(512), pool(512), *tail),
            2)
    if kernel == "latent":
        return (lambda q, p, t, n: pk.paged_latent_attention(
            q, p, t, n, value_dim=512, scale=0.0625, cache_len=n_blk * bs,
            use_pallas=True),
            (shape((lanes, 1, 20, 640), bf16), pool(640), *tail), 1)
    return (lambda q, w, p, t, n: pk.paged_index_scores(
        q, w, p, t, n, cache_len=n_blk * bs, use_pallas=True),
        (shape((lanes, 1, 64, 128), bf16),
         shape((lanes, 1, 64), jnp.float32), pool(128), *tail), 1)


@pytest.mark.parametrize("kernel", ["attn", "latent", "index"])
def test_a_walks_traced_size_does_not_grow_with_the_steps_width(
        kernel, monkeypatch):
    """What every process pays before a compile cache's key exists is
    the trace and the lowering of each kernel instance, and that
    follows the kernel's size.  A step's copies are rolled loops
    (``_walk_copies``: one over groups of ``_COPY_GROUP`` entries, one
    over the entries left), so at each of its two sites (the first
    step's copies, the next step's) the kernel holds a group's starts
    and one more for each pool, and two waits a pool, whatever
    ``PAGED_FOLD_ROWS``; and it lowers for a TPU.  (A count of
    equations, not of seconds: a clock makes a test unsteady.)"""
    counts = {}
    for rows in (128, 512, 1024):
        monkeypatch.setattr(pk, "PAGED_FOLD_ROWS", rows)
        fn, args, pools = _tpu_walk(kernel)   # a new function: no cached trace
        traced = jax.jit(fn).trace(*args)
        assert "tpu_custom_call" in traced.lower(
            lowering_platforms=("tpu",)).as_text()
        counts[rows] = tuple(_count_eqns(traced.jaxpr.jaxpr, name)
                             for name in ("dma_start", "dma_wait"))
    assert set(counts.values()) == {
        (2 * (pk._COPY_GROUP + 1) * pools, 2 * pools)}, counts


def test_fused_attn_kill_switches(monkeypatch):
    """TTD_NO_PALLAS wins over everything (the XLA block-gather leg,
    as for every other kernel); TTD_FUSED_ATTN_INTERPRET forces the
    kernel ON off-TPU (the CPU parity-test path); default follows the
    backend."""
    monkeypatch.setenv("TTD_NO_PALLAS", "1")
    assert pk.use_fused_paged_attention() is False
    monkeypatch.setenv("TTD_FUSED_ATTN_INTERPRET", "1")
    assert pk.use_fused_paged_attention() is False  # TTD_NO_PALLAS wins
    monkeypatch.delenv("TTD_NO_PALLAS")
    assert pk.use_fused_paged_attention() is True
    assert pk.fused_attn_interpret() is (
        __import__("jax").default_backend() != "tpu")
    monkeypatch.delenv("TTD_FUSED_ATTN_INTERPRET")
    assert pk.use_fused_paged_attention() is (
        __import__("jax").default_backend() == "tpu")
    assert pk.fused_attn_interpret() is False
    # "0"/"false" mean OFF for both flags (the env_flag parser).
    monkeypatch.setenv("TTD_NO_PALLAS", "0")
    monkeypatch.setenv("TTD_FUSED_ATTN_INTERPRET", "false")
    assert pk.use_fused_paged_attention() is (
        __import__("jax").default_backend() == "tpu")


class TestPagedKvGather:
    """The serving engine's paged-KV gather: the scalar-prefetch block
    copy must move exactly the reference's bytes (a gather has no math
    to drift — bit-identity or bust)."""

    @pytest.mark.parametrize("cache_len", [16, 14])  # aligned + ragged
    def test_kernel_matches_reference(self, cache_len):
        rng = np.random.default_rng(0)
        pool = jnp.asarray(
            rng.normal(size=(9, 4, 16)).astype(np.float32))
        table = jnp.asarray(
            rng.integers(0, 9, (3, 4)).astype(np.int32))
        ref = pk.paged_kv_gather_reference(pool, table, cache_len)
        out = pk.paged_kv_gather(pool, table, cache_len, interpret=True)
        assert out.shape == (3, cache_len, 16)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))

    def test_reference_row_semantics(self):
        # Lane b's logical row p must be pool[table[b, p//bs], p%bs].
        pool = jnp.arange(6 * 2 * 1, dtype=jnp.float32).reshape(6, 2, 1)
        table = jnp.asarray([[3, 1, 0]], jnp.int32)
        out = np.asarray(
            pk.paged_kv_gather_reference(pool, table, 6))[0, :, 0]
        assert out.tolist() == [6.0, 7.0, 2.0, 3.0, 0.0, 1.0]

    def test_cpu_path_uses_reference(self):
        # On this CPU backend the public entry must route to the
        # reference (no pallas lowering attempted).
        pool = jnp.zeros((3, 2, 1))
        table = jnp.zeros((1, 2), jnp.int32)
        out = pk.paged_kv_gather(pool, table, 4)
        assert out.shape == (1, 4, 1)
