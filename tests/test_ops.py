"""Op-level tests: attention reference semantics, RoPE, shared losses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflow_train_distributed_tpu.models.layers import apply_rope
from tensorflow_train_distributed_tpu.ops.attention import (
    dot_product_attention,
    multihead_attention_kernel,
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
