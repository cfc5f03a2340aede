"""Sliding-window attention: oracle, chunked O(S·w) path, model wiring.

The window semantics are the Mistral convention — each query sees the
last ``window`` keys including itself.  ``local_attention_chunked`` must
match the exactly-masked oracle bit-for-tolerance, the dispatcher must
route combinations (packing, decode cache) to correctly masked paths,
and the Llama config plumbing must reach the layer.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # compile-heavy: full-suite tier

import jax
import jax.numpy as jnp

from tensorflow_train_distributed_tpu.ops.attention import (
    dot_product_attention,
    local_attention_chunked,
    multihead_attention_kernel,
)


def _qkv(rng, b=2, h=3, s=64, d=16, dtype=np.float32):
    def t():
        return jnp.asarray(rng.normal(0, 1, (b, h, s, d)).astype(dtype))

    return t(), t(), t()


class TestChunkedMatchesOracle:
    @pytest.mark.parametrize("s,w", [(64, 16), (128, 32), (48, 24),
                                     (64, 32)])
    def test_forward_parity(self, s, w):
        rng = np.random.default_rng(s + w)
        q, k, v = _qkv(rng, s=s)
        oracle = dot_product_attention(q, k, v, causal=True, window=w)
        got = local_attention_chunked(q, k, v, window=w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(oracle),
                                   rtol=2e-5, atol=2e-5)

    def test_gradient_parity(self):
        rng = np.random.default_rng(7)
        q, k, v = _qkv(rng, s=32, d=8)

        def loss_oracle(q, k, v):
            return jnp.sum(jnp.square(dot_product_attention(
                q, k, v, causal=True, window=8)))

        def loss_chunked(q, k, v):
            return jnp.sum(jnp.square(local_attention_chunked(
                q, k, v, window=8)))

        go = jax.grad(loss_oracle, argnums=(0, 1, 2))(q, k, v)
        gc = jax.grad(loss_chunked, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(go, gc):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)

    def test_first_window_matches_plain_causal(self):
        """Queries before the window fills see plain causal attention."""
        rng = np.random.default_rng(9)
        q, k, v = _qkv(rng, s=64)
        full = dot_product_attention(q, k, v, causal=True)
        win = local_attention_chunked(q, k, v, window=32)
        np.testing.assert_allclose(np.asarray(win)[..., :32, :],
                                   np.asarray(full)[..., :32, :],
                                   rtol=2e-5, atol=2e-5)
        # ...and later queries genuinely differ (the window binds).
        assert not np.allclose(np.asarray(win)[..., 32:, :],
                               np.asarray(full)[..., 32:, :], atol=1e-3)

    def test_rejects_indivisible(self):
        rng = np.random.default_rng(11)
        q, k, v = _qkv(rng, s=60)
        with pytest.raises(ValueError, match="divisible"):
            local_attention_chunked(q, k, v, window=16)


class TestDispatcher:
    def test_window_requires_causal(self):
        rng = np.random.default_rng(13)
        q, k, v = _qkv(rng, s=32)
        with pytest.raises(ValueError, match="causal"):
            multihead_attention_kernel(q, k, v, window=8)

    def test_window_with_packing_composes_masks(self):
        """Packed segments + window stay on the O(S·w) chunked path
        (segment ids ride the shift-concat) and match the dense-mask
        oracle composition exactly."""
        rng = np.random.default_rng(15)
        q, k, v = _qkv(rng, b=1, s=32)
        seg = jnp.asarray(
            np.repeat([1, 2], 16)[None, :])  # two 16-token documents
        got = multihead_attention_kernel(
            q, k, v, causal=True, segment_ids=seg, window=8)
        segmask = (seg[:, None, :, None] == seg[:, None, None, :])
        want = dot_product_attention(q, k, v, causal=True, mask=segmask,
                                     window=8)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        # And it really is the chunked path (identical, not just close).
        direct = local_attention_chunked(q, k, v, window=8,
                                         segment_ids=seg)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(direct))

    def test_uneven_doc_boundaries_in_chunked_path(self):
        """Doc boundaries that do NOT align with window chunks still
        mask exactly (ids shift-concat like the keys)."""
        rng = np.random.default_rng(16)
        q, k, v = _qkv(rng, b=2, s=64)
        lens = [(11, 29, 24), (5, 3, 56)]
        seg = jnp.asarray(np.stack([
            np.repeat(np.arange(1, len(l) + 1), l) for l in lens]))
        got = multihead_attention_kernel(
            q, k, v, causal=True, segment_ids=seg, window=16)
        segmask = (seg[:, None, :, None] == seg[:, None, None, :])
        want = dot_product_attention(q, k, v, causal=True, mask=segmask,
                                     window=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_window_zero_rejected(self):
        rng = np.random.default_rng(18)
        q, k, v = _qkv(rng, s=32)
        with pytest.raises(ValueError, match=">= 1"):
            multihead_attention_kernel(q, k, v, causal=True, window=0)

    def test_dense_fallback_warns_at_long_context(self):
        rng = np.random.default_rng(19)
        q, k, v = _qkv(rng, s=60)  # 60 % 14 != 0, 60 >= 4*14
        with pytest.warns(UserWarning, match="DENSE"):
            multihead_attention_kernel(q, k, v, causal=True, window=14)

    def test_kernel_window_routes_to_chunked(self):
        rng = np.random.default_rng(17)
        q, k, v = _qkv(rng, s=64)
        got = multihead_attention_kernel(q, k, v, causal=True, window=16)
        want = local_attention_chunked(q, k, v, window=16)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestLlamaSlidingWindow:
    def _cfgs(self):
        import dataclasses

        from tensorflow_train_distributed_tpu.models import llama

        base = llama.LLAMA_PRESETS["llama_tiny"]
        return base, dataclasses.replace(base, sliding_window=32)

    def test_short_sequences_match_full_attention(self):
        """S <= window: sliding window is vacuous, logits identical."""
        from tensorflow_train_distributed_tpu.models import llama

        full_cfg, win_cfg = self._cfgs()
        toks = jnp.asarray(
            np.random.default_rng(0).integers(0, 256, (2, 32)), jnp.int32)
        params = llama.LlamaModel(full_cfg).init(jax.random.key(0), toks)
        a = llama.LlamaModel(full_cfg).apply(params, toks)
        b = llama.LlamaModel(win_cfg).apply(params, toks)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)

    def test_long_sequences_differ_and_train(self):
        import optax

        from tensorflow_train_distributed_tpu.models import llama

        full_cfg, win_cfg = self._cfgs()
        rng = np.random.default_rng(1)
        toks = jnp.asarray(rng.integers(0, 256, (2, 96)), jnp.int32)
        params = llama.LlamaModel(full_cfg).init(jax.random.key(0), toks)
        a = np.asarray(llama.LlamaModel(full_cfg).apply(params, toks))
        b = np.asarray(llama.LlamaModel(win_cfg).apply(params, toks))
        # The window binds beyond position 32 → different logits there.
        assert not np.allclose(a[:, 40:], b[:, 40:], atol=1e-3)
        # And a grad step is finite.
        task = llama.CausalLmTask(win_cfg)
        batch = {"tokens": np.asarray(toks),
                 "targets": rng.integers(0, 256, (2, 96)).astype(np.int32)}
        variables = task.init_variables(jax.random.key(0), batch)

        def loss(p):
            l, _ = task.loss_fn(p, {}, batch, jax.random.key(1), True)
            return l

        grads = jax.grad(loss)(variables["params"])
        assert all(np.isfinite(np.asarray(g)).all()
                   for g in jax.tree.leaves(grads))

    def test_decode_matches_teacher_forcing(self):
        """Greedy decode through the windowed KV cache reproduces the
        windowed model's full-forward argmax tokens."""
        from tensorflow_train_distributed_tpu.models import generate, llama

        _, win_cfg = self._cfgs()
        rng = np.random.default_rng(2)
        prompt = rng.integers(2, 256, (1, 48)).astype(np.int32)
        params = llama.LlamaModel(win_cfg).init(
            jax.random.key(0), jnp.asarray(prompt))["params"]
        out = generate.generate(win_cfg, params, prompt,
                                max_new_tokens=8)
        # Teacher-forced check: feeding the generated prefix reproduces
        # each next token via the full windowed forward.
        model = llama.LlamaModel(win_cfg)
        seq = np.asarray(out)
        for t in range(prompt.shape[1], seq.shape[1]):
            logits = model.apply({"params": params},
                                 jnp.asarray(seq[:, :t]))
            np.testing.assert_array_equal(
                np.argmax(np.asarray(logits)[:, -1], -1), seq[:, t])

    def test_rolling_cache_window_sized_and_wrap_exact(self):
        """cache_len > window → ring buffer of WINDOW rows per layer
        (the serving-memory win), and generation deep past several slot
        wraps still reproduces the windowed model's teacher-forced
        argmax stream."""
        import dataclasses

        import flax

        from tensorflow_train_distributed_tpu.models import generate, llama

        base = llama.LLAMA_PRESETS["llama_tiny"]
        cfg = dataclasses.replace(base, sliding_window=16)
        rng = np.random.default_rng(4)
        prompt = rng.integers(2, 256, (1, 20)).astype(np.int32)
        params = llama.LlamaModel(cfg).init(
            jax.random.key(0), jnp.asarray(prompt))["params"]
        # Cache buffers are window-sized, not request-sized.
        model = llama.LlamaModel(cfg, decode=True, cache_len=60)
        _, variables = model.apply({"params": params},
                                   jnp.asarray(prompt), mutable=["cache"])
        for path, leaf in flax.traverse_util.flatten_dict(
                dict(variables["cache"])).items():
            if path[-1] in ("key_cache", "value_cache"):
                assert leaf.shape[1] == 16, (path, leaf.shape)
        # 40 new tokens → positions to 59: slots wrap ~3.7 times.  One
        # causal forward teacher-forces every step at once: logits at
        # t-1 must argmax to the generated token t.
        out = np.asarray(generate.generate(cfg, params, prompt,
                                           max_new_tokens=40))
        logits = np.asarray(llama.LlamaModel(cfg).apply(
            {"params": params}, jnp.asarray(out)))
        p = prompt.shape[1]
        np.testing.assert_array_equal(
            np.argmax(logits[:, p - 1:-1], -1), out[:, p:])

    def test_rolling_chunked_prefill_matches_one_shot(self):
        """Multi-token calls at cur > 0 (chunked prefill) are exact under
        the rolling cache: feeding the prompt in two chunks produces the
        same logits and the same subsequent step logits as one prefill."""
        import dataclasses

        from tensorflow_train_distributed_tpu.models import llama

        cfg = dataclasses.replace(llama.LLAMA_PRESETS["llama_tiny"],
                                  sliding_window=8)
        rng = np.random.default_rng(5)
        prompt = jnp.asarray(rng.integers(2, 256, (1, 26)), jnp.int32)
        params = llama.LlamaModel(cfg).init(jax.random.key(0),
                                            prompt)["params"]
        model = llama.LlamaModel(cfg, decode=True, cache_len=40)
        one, v_one = model.apply({"params": params}, prompt,
                                 mutable=["cache"])
        a, va = model.apply({"params": params}, prompt[:, :11],
                            mutable=["cache"])
        b, vb = model.apply({"params": params, "cache": va["cache"]},
                            prompt[:, 11:], mutable=["cache"])
        np.testing.assert_allclose(
            np.asarray(one), np.concatenate([np.asarray(a),
                                             np.asarray(b)], axis=1),
            rtol=1e-5, atol=1e-5)
        # And the cache states agree for the NEXT step.
        tok = jnp.asarray([[7]], jnp.int32)
        s1, _ = model.apply({"params": params, "cache": v_one["cache"]},
                            tok, mutable=["cache"])
        s2, _ = model.apply({"params": params, "cache": vb["cache"]},
                            tok, mutable=["cache"])
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   rtol=1e-5, atol=1e-5)

    def test_window_composes_with_seq_parallel(self):
        """Windowed llama trains under ring AND Ulysses SP with the
        SAME first-step loss as the unsharded windowed model — and the
        ring additionally skips out-of-window hops (if it skipped a
        NEEDED one, the losses would differ)."""
        import dataclasses

        import optax

        from tensorflow_train_distributed_tpu.models import llama
        from tensorflow_train_distributed_tpu.parallel.sharding import (
            shard_batch,
        )
        from tensorflow_train_distributed_tpu.runtime.mesh import (
            MeshConfig, build_mesh,
        )
        from tensorflow_train_distributed_tpu.training import (
            Trainer, TrainerConfig,
        )

        rng = np.random.default_rng(3)
        batch = {"tokens": rng.integers(0, 256, (4, 64)).astype(np.int32),
                 "targets": rng.integers(0, 256,
                                         (4, 64)).astype(np.int32)}

        def first_loss(seq_parallel, mesh_cfg):
            import math

            cfg = dataclasses.replace(
                llama.LLAMA_PRESETS["llama_tiny"], sliding_window=16,
                seq_parallel=seq_parallel)
            n = math.prod(mesh_cfg.axis_sizes().values())
            mesh = build_mesh(mesh_cfg, devices=jax.devices()[:n])
            trainer = Trainer(llama.CausalLmTask(cfg), optax.adam(1e-3),
                              mesh, config=TrainerConfig(log_every=1))
            state = trainer.create_state(batch)
            step = trainer._compiled_train_step()
            _, metrics = step(state, shard_batch(mesh, batch))
            return float(metrics["loss"])

        base = first_loss(None, MeshConfig(data=2))
        ring = first_loss("ring", MeshConfig(data=2, seq=4))
        uly = first_loss("ulysses", MeshConfig(data=2, seq=2))
        assert base == pytest.approx(ring, rel=1e-4)
        assert base == pytest.approx(uly, rel=1e-4)

    def test_ring_window_parity_at_shard_boundaries(self):
        """shard_mapped ring attention with a window spanning shard
        boundaries matches the full windowed oracle (the skipped-hops
        optimization must keep every in-window key)."""
        from tensorflow_train_distributed_tpu.parallel.ring_attention \
            import shard_mapped_attention
        from tensorflow_train_distributed_tpu.runtime.mesh import (
            MeshConfig, build_mesh,
        )

        mesh = build_mesh(MeshConfig(data=2, seq=4),
                          devices=jax.devices()[:8])
        rng = np.random.default_rng(6)
        q, k, v = _qkv(rng, b=2, h=4, s=64, d=8)
        for w in (8, 16, 24, 40):  # shard span 16: below/at/cross/2-hop
            out = shard_mapped_attention(mesh, q, k, v, method="ring",
                                         causal=True, window=w)
            ref = dot_product_attention(q, k, v, causal=True, window=w)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4,
                err_msg=f"window={w}")


class TestAttentionSinks:
    """StreamingLLM attention sinks: first-N positions stay attendable
    past the window — oracle, chunked path, decode sink buffers."""

    @pytest.mark.parametrize("s,w,sk", [(64, 16, 4), (64, 16, 16),
                                        (96, 32, 2)])
    def test_chunked_matches_oracle(self, s, w, sk):
        rng = np.random.default_rng(s + w + sk)
        q, k, v = _qkv(rng, s=s)
        want = dot_product_attention(q, k, v, causal=True, window=w,
                                     sinks=sk)
        got = local_attention_chunked(q, k, v, window=w, sinks=sk)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=2e-5)

    def test_sinks_actually_extend_reach(self):
        """Beyond the window, sink keys change the output vs plain SWA."""
        rng = np.random.default_rng(31)
        q, k, v = _qkv(rng, s=64)
        plain = local_attention_chunked(q, k, v, window=16)
        sunk = local_attention_chunked(q, k, v, window=16, sinks=4)
        # Early queries (window covers everything incl. sinks): equal.
        np.testing.assert_allclose(np.asarray(plain)[..., :16, :],
                                   np.asarray(sunk)[..., :16, :],
                                   rtol=1e-5, atol=1e-5)
        assert not np.allclose(np.asarray(plain)[..., 32:, :],
                               np.asarray(sunk)[..., 32:, :], atol=1e-3)

    def test_sinks_require_window(self):
        rng = np.random.default_rng(33)
        q, k, v = _qkv(rng, s=32)
        with pytest.raises(ValueError, match="sliding window"):
            multihead_attention_kernel(q, k, v, causal=True, sinks=2)

    def test_packed_sinks_compose(self):
        rng = np.random.default_rng(35)
        q, k, v = _qkv(rng, b=1, s=64)
        seg = jnp.asarray(np.repeat([1, 2], 32)[None, :])
        got = multihead_attention_kernel(
            q, k, v, causal=True, window=16, sinks=4, segment_ids=seg)
        segmask = (seg[:, None, :, None] == seg[:, None, None, :])
        want = dot_product_attention(q, k, v, causal=True, window=16,
                                     sinks=4, mask=segmask)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=2e-5)

    def test_streaming_decode_teacher_forcing_exact(self):
        """Generation deep past the window with sink buffers + rolling
        ring reproduces the full-forward argmax stream (several slot
        wraps; the sink buffer carries positions the ring evicted)."""
        import dataclasses

        from tensorflow_train_distributed_tpu.models import generate, llama

        cfg = dataclasses.replace(llama.LLAMA_PRESETS["llama_tiny"],
                                  sliding_window=16, attention_sinks=4)
        rng = np.random.default_rng(37)
        prompt = rng.integers(2, 256, (1, 24)).astype(np.int32)
        params = llama.LlamaModel(cfg).init(
            jax.random.key(0), jnp.asarray(prompt))["params"]
        out = np.asarray(generate.generate(cfg, params, prompt,
                                           max_new_tokens=40))
        logits = np.asarray(llama.LlamaModel(cfg).apply(
            {"params": params}, jnp.asarray(out)))
        p = prompt.shape[1]
        np.testing.assert_array_equal(
            np.argmax(logits[:, p - 1:-1], -1), out[:, p:])

    def test_chunked_prefill_with_sinks_matches_one_shot(self):
        import dataclasses

        from tensorflow_train_distributed_tpu.models import llama

        cfg = dataclasses.replace(llama.LLAMA_PRESETS["llama_tiny"],
                                  sliding_window=8, attention_sinks=3)
        rng = np.random.default_rng(39)
        prompt = jnp.asarray(rng.integers(2, 256, (1, 26)), jnp.int32)
        params = llama.LlamaModel(cfg).init(jax.random.key(0),
                                            prompt)["params"]
        model = llama.LlamaModel(cfg, decode=True, cache_len=40)
        one, v_one = model.apply({"params": params}, prompt,
                                 mutable=["cache"])
        a, va = model.apply({"params": params}, prompt[:, :11],
                            mutable=["cache"])
        b, vb = model.apply({"params": params, "cache": va["cache"]},
                            prompt[:, 11:], mutable=["cache"])
        np.testing.assert_allclose(
            np.asarray(one),
            np.concatenate([np.asarray(a), np.asarray(b)], axis=1),
            rtol=1e-5, atol=1e-5)
        tok = jnp.asarray([[9]], jnp.int32)
        s1, _ = model.apply({"params": params, "cache": v_one["cache"]},
                            tok, mutable=["cache"])
        s2, _ = model.apply({"params": params, "cache": vb["cache"]},
                            tok, mutable=["cache"])
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   rtol=1e-5, atol=1e-5)

    def test_streaming_from_single_token_prompt(self):
        """Degenerate-but-legal: prompt SHORTER than the sink count.
        The sink buffer fills incrementally as positions decode (masked
        merge), exclusivity holds at every cur, and the stream still
        teacher-forces exactly."""
        import dataclasses

        from tensorflow_train_distributed_tpu.models import generate, llama

        cfg = dataclasses.replace(llama.LLAMA_PRESETS["llama_tiny"],
                                  sliding_window=8, attention_sinks=4)
        prompt = np.asarray([[5]], np.int32)
        params = llama.LlamaModel(cfg).init(
            jax.random.key(0), jnp.asarray(prompt))["params"]
        out = np.asarray(generate.generate(cfg, params, prompt,
                                           max_new_tokens=30))
        logits = np.asarray(llama.LlamaModel(cfg).apply(
            {"params": params}, jnp.asarray(out)))
        np.testing.assert_array_equal(
            np.argmax(logits[:, :-1], -1), out[:, 1:])

    @pytest.mark.parametrize("sk", [2, 8, 16])
    def test_ring_sp_sinks_match_oracle(self, sk):
        """Ring SP + sinks: shard 0's sink block broadcasts (tiny psum)
        and every shard folds it into the online softmax — matches the
        full windowed+sinks oracle at sink counts below/at the shard
        span (span 16 on a 4-way seq axis over S=64)."""
        from tensorflow_train_distributed_tpu.parallel.ring_attention \
            import shard_mapped_attention
        from tensorflow_train_distributed_tpu.runtime.mesh import (
            MeshConfig, build_mesh,
        )

        mesh = build_mesh(MeshConfig(data=2, seq=4),
                          devices=jax.devices()[:8])
        rng = np.random.default_rng(43 + sk)
        q, k, v = _qkv(rng, b=2, h=4, s=64, d=8)
        out = shard_mapped_attention(mesh, q, k, v, method="ring",
                                     causal=True, window=24, sinks=sk)
        ref = dot_product_attention(q, k, v, causal=True, window=24,
                                    sinks=sk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)

    def test_ring_sp_sinks_with_packing(self):
        from tensorflow_train_distributed_tpu.parallel.ring_attention \
            import shard_mapped_attention
        from tensorflow_train_distributed_tpu.runtime.mesh import (
            MeshConfig, build_mesh,
        )

        mesh = build_mesh(MeshConfig(data=2, seq=4),
                          devices=jax.devices()[:8])
        rng = np.random.default_rng(47)
        q, k, v = _qkv(rng, b=2, h=4, s=64, d=8)
        seg = jnp.asarray(np.stack([
            np.repeat([1, 2], [30, 34]), np.repeat([1, 2], [10, 54])]))
        out = shard_mapped_attention(mesh, q, k, v, method="ring",
                                     causal=True, window=24, sinks=4,
                                     segment_ids=seg)
        segmask = (seg[:, None, :, None] == seg[:, None, None, :])
        ref = dot_product_attention(q, k, v, causal=True, window=24,
                                    sinks=4, mask=segmask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)

    def test_ring_sp_sinks_exceeding_shard_rejected(self):
        from tensorflow_train_distributed_tpu.parallel.ring_attention \
            import shard_mapped_attention
        from tensorflow_train_distributed_tpu.runtime.mesh import (
            MeshConfig, build_mesh,
        )

        mesh = build_mesh(MeshConfig(data=2, seq=4),
                          devices=jax.devices()[:8])
        rng = np.random.default_rng(49)
        q, k, v = _qkv(rng, b=2, h=4, s=64, d=8)
        with pytest.raises(ValueError, match="shard"):
            shard_mapped_attention(mesh, q, k, v, method="ring",
                                   causal=True, window=24, sinks=20)


def test_cli_trains_windowed_family():
    """The registered mistral-shaped config (sliding window + sinks)
    trains through the real CLI."""
    from tensorflow_train_distributed_tpu import launch

    result = launch.run(launch.build_parser().parse_args([
        "--config", "mistral_tiny_lm", "--steps", "3",
        "--global-batch-size", "8", "--platform", "cpu",
        "--log-every", "1"]))
    assert np.isfinite(result.history["loss"]).all()


class TestSplashWindow:
    """The TPU splash-kernel route for sliding windows, validated in
    pallas interpret mode on CPU against the exact masked oracle."""

    def test_forward_parity_interpret(self):
        from tensorflow_train_distributed_tpu.ops.attention import (
            dot_product_attention,
            splash_window_attention,
        )

        rng = np.random.default_rng(0)
        b, h, s, d, w = 1, 2, 256, 64, 64
        q, k, v = (jnp.asarray(rng.normal(0, 1, (b, h, s, d)),
                               jnp.float32) for _ in range(3))
        want = dot_product_attention(q, k, v, causal=True, window=w)
        got = splash_window_attention(q, k, v, window=w, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    def test_segment_ids_parity_interpret(self):
        from tensorflow_train_distributed_tpu.ops.attention import (
            multihead_attention_kernel,
            splash_window_attention,
        )

        rng = np.random.default_rng(1)
        b, h, s, d, w = 1, 2, 256, 64, 64
        q, k, v = (jnp.asarray(rng.normal(0, 1, (b, h, s, d)),
                               jnp.float32) for _ in range(3))
        seg = jnp.asarray(
            np.repeat([1, 1, 2, 2], s // 4)[None, :], jnp.int32)
        # Oracle: the exactly-masked reference path (force_reference).
        want = multihead_attention_kernel(
            q, k, v, causal=True, window=w, segment_ids=seg,
            force_reference=True)
        got = splash_window_attention(q, k, v, window=w,
                                      segment_ids=seg, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    def test_gradient_parity_interpret(self):
        from tensorflow_train_distributed_tpu.ops.attention import (
            dot_product_attention,
            splash_window_attention,
        )

        rng = np.random.default_rng(2)
        b, h, s, d, w = 1, 1, 256, 64, 64
        q, k, v = (jnp.asarray(rng.normal(0, 1, (b, h, s, d)),
                               jnp.float32) for _ in range(3))

        def loss_ref(q, k, v):
            return jnp.sum(dot_product_attention(
                q, k, v, causal=True, window=w) ** 2)

        def loss_splash(q, k, v):
            return jnp.sum(splash_window_attention(
                q, k, v, window=w, interpret=True) ** 2)

        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        g_spl = jax.grad(loss_splash, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g_spl, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=5e-3, atol=5e-3)

    def test_splash_opt_in_and_kill_switch(self, monkeypatch):
        """Splash is OPT-IN (TTD_SPLASH=1): chunked beat it on silicon
        at the measured shape (last_tpu_result.json, 2026-07-31), so the measured
        winner is the default.  On CPU the splash route never fires;
        TTD_NO_SPLASH still wins over TTD_SPLASH (kill switch); and
        0/false/empty mean OFF for both flags (the TTD_NO_PALLAS
        lesson)."""
        from tensorflow_train_distributed_tpu.ops import attention

        monkeypatch.delenv("TTD_NO_SPLASH", raising=False)  # dev shells
        monkeypatch.delenv("TTD_SPLASH", raising=False)
        q = jnp.zeros((1, 2, 256, 64))
        args = dict(sinks=0, mask=None, force_reference=False)
        assert not attention._splash_window_friendly(q, q, **args)  # cpu
        # Fake a TPU backend: the shape/dtype gates pass, so the env
        # flags are what the next assertions exercise.
        monkeypatch.setattr(attention.jax, "default_backend",
                            lambda: "tpu")
        assert not attention._splash_window_friendly(q, q, **args)  # opt-in
        monkeypatch.setenv("TTD_SPLASH", "1")
        assert attention._splash_window_friendly(q, q, **args)
        monkeypatch.setenv("TTD_NO_SPLASH", "1")  # kill switch wins
        assert not attention._splash_window_friendly(q, q, **args)
        monkeypatch.setenv("TTD_NO_SPLASH", "0")
        assert attention._splash_window_friendly(q, q, **args)
        monkeypatch.setenv("TTD_SPLASH", "false")
        assert not attention._splash_window_friendly(q, q, **args)
