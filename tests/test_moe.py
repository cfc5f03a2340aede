"""MoE / expert-parallelism tests: routing math, sharding, training.

Routing ground truths: with generous capacity every token is dispatched
exactly top_k times and its combine weights sum to 1; with capacity
squeezed, drops show up as combine mass < 1 (those tokens ride the
residual).  Expert-sharded and unsharded execution must agree numerically.
"""

import pytest

pytestmark = pytest.mark.slow  # compile/fit-heavy: full-suite tier

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tensorflow_train_distributed_tpu.runtime import compat
from tensorflow_train_distributed_tpu.models import moe
from tensorflow_train_distributed_tpu.runtime.mesh import (
    MeshConfig, build_mesh,
)


def _probs(tokens=32, experts=4, seed=0, peaked=False):
    rng = np.random.default_rng(seed)
    logits = rng.normal(0, 1, (tokens, experts)).astype(np.float32)
    if peaked:  # everyone wants expert 0 → forces capacity drops
        logits[:, 0] += 10.0
    return jax.nn.softmax(jnp.asarray(logits), axis=-1)


def test_router_dispatches_topk_with_ample_capacity():
    p = _probs()
    top_k = 2
    dispatch, combine, routed = moe._router_one_hot(p, top_k, capacity=32)
    # Every token lands in exactly top_k expert slots.
    np.testing.assert_array_equal(
        np.asarray(dispatch.sum(axis=(1, 2))), np.full(32, top_k))
    # Combine weights normalize to 1 per token.
    np.testing.assert_allclose(
        np.asarray(combine.sum(axis=(1, 2))), np.ones(32), rtol=1e-5)
    # Each expert slot holds at most one token.
    assert np.asarray(dispatch.sum(axis=0)).max() <= 1.0 + 1e-6
    assert np.asarray(routed.sum(axis=1)).max() == top_k


def test_router_respects_capacity():
    p = _probs(peaked=True)  # all 32 tokens pick expert 0 first
    capacity = 4
    dispatch, combine, _ = moe._router_one_hot(p, 1, capacity)
    per_expert = np.asarray(dispatch.sum(axis=(0, 2)))
    assert per_expert[0] == capacity  # full, not overfull
    # Dropped tokens have zero combine mass (residual path).
    mass = np.asarray(combine.sum(axis=(1, 2)))
    assert (mass == 0).sum() == 32 - capacity


def test_router_slots_unique():
    p = _probs(tokens=16, experts=2, seed=3)
    dispatch, _, _ = moe._router_one_hot(p, 2, capacity=16)
    # No two tokens share an (expert, slot) cell.
    cell = np.asarray(dispatch.sum(axis=0))
    assert cell.max() <= 1.0 + 1e-6


@pytest.fixture(scope="module")
def tiny():
    return moe.MOE_PRESETS["moe_tiny"]


def test_forward_shapes_and_aux(tiny):
    task = moe.MoeLmTask(tiny)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (2, 16)), jnp.int32)
    variables = task.model.init(jax.random.key(0), tokens)
    logits, cols = task.model.apply(
        {"params": variables["params"]}, tokens, mutable=["aux_loss"])
    assert logits.shape == (2, 16, 256)
    leaves = jax.tree.leaves(cols["aux_loss"])
    assert leaves and all(np.isfinite(np.asarray(l)).all() for l in leaves)


def test_loss_includes_aux(tiny):
    task = moe.MoeLmTask(tiny)
    rng = np.random.default_rng(1)
    batch = {
        "tokens": rng.integers(0, 256, (2, 16)).astype(np.int32),
        "targets": rng.integers(0, 256, (2, 16)).astype(np.int32),
    }
    variables = task.init_variables(jax.random.key(0), batch)
    loss, (metrics, _) = task.loss_fn(
        variables["params"], {}, batch, jax.random.key(1), True)
    assert float(metrics["aux_loss"]) > 0
    np.testing.assert_allclose(
        float(loss), float(metrics["ce_loss"]) + float(metrics["aux_loss"]),
        rtol=1e-5)


def test_routing_health_metrics_ample_capacity(tiny):
    """Generous capacity: nothing dropped, per-expert load is a
    distribution over kept tokens."""
    import dataclasses

    cfg = dataclasses.replace(tiny, capacity_factor=8.0)
    task = moe.MoeLmTask(cfg)
    rng = np.random.default_rng(7)
    batch = {
        "tokens": rng.integers(0, 256, (2, 16)).astype(np.int32),
        "targets": rng.integers(0, 256, (2, 16)).astype(np.int32),
    }
    variables = task.init_variables(jax.random.key(0), batch)
    _, (metrics, _) = task.loss_fn(
        variables["params"], {}, batch, jax.random.key(1), True)
    assert float(metrics["dropped_frac"]) == 0.0
    lo, hi = float(metrics["expert_load_min"]), float(
        metrics["expert_load_max"])
    assert 0.0 <= lo <= 1.0 / cfg.num_experts <= hi <= 1.0


def test_routing_health_metrics_binding_capacity(tiny):
    """A binding capacity_factor surfaces as dropped_frac > 0 in train
    metrics — the silent residual fallthrough is no longer silent."""
    import dataclasses

    cfg = dataclasses.replace(tiny, capacity_factor=0.25)
    task = moe.MoeLmTask(cfg)
    rng = np.random.default_rng(8)
    batch = {
        "tokens": rng.integers(0, 256, (2, 16)).astype(np.int32),
        "targets": rng.integers(0, 256, (2, 16)).astype(np.int32),
    }
    variables = task.init_variables(jax.random.key(0), batch)
    _, (metrics, _) = task.loss_fn(
        variables["params"], {}, batch, jax.random.key(1), True)
    assert 0.0 < float(metrics["dropped_frac"]) < 1.0
    assert np.isfinite(float(metrics["expert_load_max"]))


def test_grads_reach_all_experts(tiny):
    task = moe.MoeLmTask(tiny)
    rng = np.random.default_rng(2)
    batch = {
        "tokens": rng.integers(0, 256, (4, 32)).astype(np.int32),
        "targets": rng.integers(0, 256, (4, 32)).astype(np.int32),
    }
    variables = nn.unbox(task.init_variables(jax.random.key(0), batch))

    def loss(p):
        return task.loss_fn(p, {}, batch, jax.random.key(1), True)[0]

    grads = jax.grad(loss)(variables["params"])
    # Expert FFN kernels carry a leading [num_experts] axis; with 128
    # tokens and balanced-ish routing every expert sees gradient signal.
    wo = grads["layer_0"]["moe"]["experts"]["wo"]["kernel"]
    per_expert = np.asarray(jnp.abs(wo).sum(axis=(1, 2)))
    assert (per_expert > 0).all(), per_expert


def test_sharded_matches_unsharded(tiny):
    """dp_ep-sharded forward == single-device forward (the GSPMD contract)."""
    from tensorflow_train_distributed_tpu.parallel import (
        sharding as sharding_lib,
    )

    task = moe.MoeLmTask(tiny)
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, 256, (8, 16)), jnp.int32)
    variables = task.model.init(jax.random.key(0), tokens)
    want = task.model.apply({"params": variables["params"]}, tokens)

    mesh = build_mesh(MeshConfig(data=2, expert=4))
    with sharding_lib.with_logical_rules(mesh), compat.set_mesh(mesh):
        got = jax.jit(
            lambda p, t: task.model.apply({"params": p}, t)
        )(variables["params"], tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_moe_trains_under_expert_mesh(tiny):
    """Full Trainer step on a data×expert mesh; loss decreases."""
    from tensorflow_train_distributed_tpu.data.datasets import get_dataset
    from tensorflow_train_distributed_tpu.data.pipeline import (
        DataConfig, HostDataLoader,
    )
    from tensorflow_train_distributed_tpu.training import (
        History, Trainer, TrainerConfig,
    )

    mesh = build_mesh(MeshConfig(data=2, expert=4))
    hist = History()
    trainer = Trainer(
        moe.MoeLmTask(tiny),
        optax.adam(3e-3),
        mesh,
        config=TrainerConfig(log_every=5),
        callbacks=[hist],
    )
    loader = HostDataLoader(
        get_dataset("lm", vocab_size=256, seq_len=32, num_examples=512),
        DataConfig(global_batch_size=16, seed=0),
        process_index=0, process_count=1,
    )
    trainer.fit(loader, steps=30)
    losses = hist.history["loss"]
    assert losses[-1] < losses[0], losses


class TestMoeDecode:
    """KV-cache generation for the MoE family (the Mixtral serving path):
    cached greedy decode must match naive full re-forward per token."""

    def _naive_greedy(self, cfg, params, prompt, n_new):
        import jax.numpy as jnp

        model = moe.MoeLmModel(cfg)
        toks = jnp.asarray(prompt)
        for _ in range(n_new):
            logits = model.apply({"params": params}, toks)
            nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
            toks = jnp.concatenate(
                [toks, nxt[:, None].astype(toks.dtype)], axis=1)
        return np.asarray(toks)

    def test_cached_greedy_matches_naive(self):
        import dataclasses

        import jax

        from tensorflow_train_distributed_tpu.models.generate import (
            generate,
        )

        # Parity needs a NON-BINDING capacity (E/k: no token can ever
        # drop): decode routes groups of one token (capacity never
        # binds), while the naive full-sequence forward drops tokens
        # under a binding capacity_factor — the same semantic caveat as
        # packed segments (MoeLmModel docstring).
        cfg = dataclasses.replace(moe.MOE_PRESETS["moe_tiny"],
                                  capacity_factor=2.0)
        rng = np.random.default_rng(0)
        prompt = rng.integers(0, cfg.vocab_size, (2, 7)).astype(np.int32)
        params = moe.MoeLmModel(cfg).init(
            jax.random.key(0), prompt)["params"]
        want = self._naive_greedy(cfg, params, prompt, 6)
        got = np.asarray(generate(cfg, params, jnp.asarray(prompt), 6))
        np.testing.assert_array_equal(got, want)

    def test_sampling_smoke(self):
        import jax

        from tensorflow_train_distributed_tpu.models.generate import (
            generate,
        )

        cfg = moe.MOE_PRESETS["moe_tiny"]
        prompt = np.zeros((1, 4), np.int32)
        params = moe.MoeLmModel(cfg).init(
            jax.random.key(1), prompt)["params"]
        out = generate(cfg, params, jnp.asarray(prompt), 5,
                       temperature=0.7, top_k=20, rng=jax.random.key(2))
        assert out.shape == (1, 9)


class TestSharedExpert:
    """DeepSeek/Qwen-MoE-style shared expert: an always-on SwiGLU
    beside the routed experts (MoeConfig.shared_expert_size)."""

    def _params(self, cfg):
        import jax.numpy as jnp

        return moe.MoeLmModel(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]

    def test_param_tree_and_forward(self):
        import jax
        import jax.numpy as jnp
        import numpy as np

        cfg = moe.MOE_PRESETS["moe_tiny_shared"]
        params = self._params(cfg)
        paths = [jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(params)[0]]
        assert any("shared_mlp" in p for p in paths)
        # Plain config: NO shared branch in the tree.
        base = self._params(moe.MOE_PRESETS["moe_tiny"])
        bpaths = [jax.tree_util.keystr(p) for p, _ in
                  jax.tree_util.tree_flatten_with_path(base)[0]]
        assert not any("shared_mlp" in p for p in bpaths)
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)),
                           jnp.int32)
        out = moe.MoeLmModel(cfg).apply({"params": params}, toks)
        assert out.shape == (2, 16, cfg.vocab_size)
        assert bool(jnp.isfinite(out).all())

    def test_decode_matches_train_path_and_engine_serves(self):
        import dataclasses

        import jax
        import jax.numpy as jnp
        import numpy as np

        from tensorflow_train_distributed_tpu.models.generate import (
            generate,
        )
        from tensorflow_train_distributed_tpu.serving import ServingEngine

        cfg = moe.MOE_PRESETS["moe_tiny_shared"]
        params = self._params(cfg)
        rng = np.random.default_rng(1)
        prompt = rng.integers(1, cfg.vocab_size, (1, 5)).astype(np.int32)
        # Train-path oracle is only valid for DROPLESS dispatch: dense
        # capacity at S=11 can drop assignments the per-token decode
        # never drops (documented decode-vs-train caveat).  gmm is
        # exact, so it pins the shared branch through the decode cache.
        gcfg = dataclasses.replace(cfg, dispatch="gmm")
        model = moe.MoeLmModel(gcfg)
        toks = jnp.asarray(prompt)
        for _ in range(6):
            logits = model.apply({"params": params}, toks)
            nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
            toks = jnp.concatenate(
                [toks, nxt[:, None].astype(toks.dtype)], axis=1)
        want = np.asarray(toks)[0].tolist()
        ggot = np.asarray(generate(gcfg, params, jnp.asarray(prompt),
                                   6))[0].tolist()
        assert ggot == want
        # Dense dispatch: engine serving must match generate() (the
        # decode-vs-decode contract every MoE family pins).
        dref = np.asarray(generate(cfg, params, jnp.asarray(prompt),
                                   6))[0].tolist()
        eng = ServingEngine(cfg, params, slots=2, cache_len=32, chunk=3)
        rid = eng.submit(list(prompt[0]), 6)
        assert eng.run()[rid] == dref
