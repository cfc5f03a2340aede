"""Pipeline-parallelism tests: schedule correctness, grads, DP composition.

Ground truth is sequential stage application — the pipeline is an
execution schedule, not a math change, so outputs and gradients must match
exactly (fp32 on CPU).
"""

import pytest

pytestmark = pytest.mark.slow  # compile/fit-heavy: full-suite tier

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflow_train_distributed_tpu.parallel import pipeline
from tensorflow_train_distributed_tpu.runtime.mesh import (
    MeshConfig, build_mesh,
)


def _stage_fn(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


def _init_stage(rng, dim=8):
    kw, kb = jax.random.split(rng)
    return {"w": jax.random.normal(kw, (dim, dim)) * 0.3,
            "b": jax.random.normal(kb, (dim,)) * 0.1}


def _sequential(stacked, x):
    num_stages = jax.tree.leaves(stacked)[0].shape[0]
    for s in range(num_stages):
        p = jax.tree.map(lambda a: a[s], stacked)
        x = _stage_fn(p, x)
    return x


@pytest.fixture(scope="module")
def mesh_pp4():
    return build_mesh(MeshConfig(pipeline=4, data=2))


@pytest.fixture(scope="module")
def stacked4():
    return pipeline.init_stage_params(_init_stage, jax.random.key(0), 4)


def test_matches_sequential(mesh_pp4, stacked4):
    x = jax.random.normal(jax.random.key(1), (16, 8))
    want = _sequential(stacked4, x)
    got = pipeline.gpipe(_stage_fn, stacked4, x, mesh=mesh_pp4,
                         num_microbatches=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_microbatch_counts(mesh_pp4, stacked4):
    x = jax.random.normal(jax.random.key(2), (16, 8))
    want = _sequential(stacked4, x)
    for m in (1, 2, 8, 16):
        got = pipeline.gpipe(_stage_fn, stacked4, x, mesh=mesh_pp4,
                             num_microbatches=m)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


def test_indivisible_microbatches_rejected(mesh_pp4, stacked4):
    x = jnp.ones((10, 8))
    with pytest.raises(ValueError, match="not divisible"):
        pipeline.gpipe(_stage_fn, stacked4, x, mesh=mesh_pp4,
                       num_microbatches=3)


def test_gradients_match_sequential(mesh_pp4, stacked4):
    x = jax.random.normal(jax.random.key(3), (8, 8))

    def loss_pp(params):
        y = pipeline.gpipe(_stage_fn, params, x, mesh=mesh_pp4,
                           num_microbatches=4)
        return jnp.mean(y ** 2)

    def loss_seq(params):
        return jnp.mean(_sequential(params, x) ** 2)

    g_pp = jax.grad(loss_pp)(stacked4)
    g_seq = jax.grad(loss_seq)(stacked4)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        g_pp, g_seq)


def test_composes_with_data_parallel(mesh_pp4, stacked4):
    """PP × DP in one program: microbatch dim sharded over `data`."""
    x = jax.random.normal(jax.random.key(4), (16, 8))
    want = _sequential(stacked4, x)
    got = pipeline.gpipe(_stage_fn, stacked4, x, mesh=mesh_pp4,
                         num_microbatches=2, batch_axes=("data",))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_jit_and_sharded_params(mesh_pp4, stacked4):
    """Params placed stage-per-device; whole pipeline under jit."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharded = jax.device_put(
        stacked4, NamedSharding(mesh_pp4, P("pipeline")))

    @jax.jit
    def run(params, x):
        return pipeline.gpipe(_stage_fn, params, x, mesh=mesh_pp4,
                              num_microbatches=4)

    x = jax.random.normal(jax.random.key(5), (16, 8))
    got = run(sharded, x)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_sequential(stacked4, x)),
                               rtol=1e-6, atol=1e-6)


def test_two_stage_minimal():
    mesh = build_mesh(MeshConfig(pipeline=2, data=4))
    stacked = pipeline.init_stage_params(_init_stage, jax.random.key(7), 2)
    x = jax.random.normal(jax.random.key(8), (4, 8))
    got = pipeline.gpipe(_stage_fn, stacked, x, mesh=mesh,
                         num_microbatches=2)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_sequential(stacked, x)),
                               rtol=1e-6, atol=1e-6)


def test_gpipe_layers_groups_match_sequential(mesh_pp4):
    """8 layers over 4 stages: each stage scans its 2-layer group."""
    stacked8 = pipeline.init_stage_params(_init_stage, jax.random.key(9), 8)
    x = jax.random.normal(jax.random.key(10), (8, 8))
    want = _sequential(stacked8, x)
    got = pipeline.gpipe_layers(_stage_fn, stacked8, x, mesh=mesh_pp4,
                                num_microbatches=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="not divisible"):
        stacked6 = pipeline.init_stage_params(
            _init_stage, jax.random.key(9), 6)
        pipeline.gpipe_layers(_stage_fn, stacked6, x, mesh=mesh_pp4,
                              num_microbatches=2)


def test_gpipe_layers_gradients_match(mesh_pp4):
    stacked8 = pipeline.init_stage_params(_init_stage, jax.random.key(11), 8)
    x = jax.random.normal(jax.random.key(12), (8, 8))

    def loss_pp(params):
        y = pipeline.gpipe_layers(_stage_fn, params, x, mesh=mesh_pp4,
                                  num_microbatches=4)
        return jnp.mean(y ** 2)

    def loss_seq(params):
        return jnp.mean(_sequential(params, x) ** 2)

    g_pp = jax.grad(loss_pp)(stacked8)
    g_seq = jax.grad(loss_seq)(stacked8)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        g_pp, g_seq)


class TestLlamaPipelineEndToEnd:
    """--strategy=dp_pp drives the GPipe schedule
    through the full Trainer/launch path, with loss matching dp exactly
    (the pipeline is an execution schedule, not a math change)."""

    def _run(self, strategy):
        from tensorflow_train_distributed_tpu import launch

        return launch.run(launch.build_parser().parse_args([
            "--config", "llama_tiny_pp", "--steps", "20",
            "--global-batch-size", "16", "--strategy", strategy,
            "--precision", "float32", "--log-every", "1",
            "--optimizer", "adam", "--learning-rate", "1e-3",
        ]))

    def test_dp_pp_trains_and_matches_dp(self):
        r_pp = self._run("dp_pp")
        assert dict(r_pp.mesh.shape)["pipeline"] == 2
        r_dp = self._run("dp")
        assert dict(r_dp.mesh.shape)["pipeline"] == 1
        np.testing.assert_allclose(
            r_pp.history["loss"], r_dp.history["loss"],
            rtol=2e-4, atol=1e-5)
        # And it actually learns.
        assert r_pp.history["loss"][-1] < r_pp.history["loss"][0]
