"""Dropless (megablox grouped-matmul) MoE dispatch tests.

Ground truths: with ample capacity the gmm path reproduces the dense
GShard dispatch exactly (same router, same gate normalization, same
SwiGLU — only the data movement differs); with a BINDING capacity the
dense path drops tokens but gmm still equals the no-drop oracle
(dropless by construction, ``dropped_frac`` pinned to 0).  The two
formulations share one parameter tree, so checkpoints transfer.

Kernels run in pallas interpret mode on the CPU test mesh
(``tests/conftest.py`` sets ``moe.GMM_INTERPRET``) — slow, so
shapes here are tiny.
"""

import dataclasses

import pytest

# Interpret-mode pallas through whole blocks and models: full-suite
# tier.  The routed rows against the per-pair loop (the last test) are
# small enough for the fast tier.
slow = pytest.mark.slow

import jax
import jax.numpy as jnp
import numpy as np
import optax

from tensorflow_train_distributed_tpu.runtime import compat
from tensorflow_train_distributed_tpu.models import moe


@pytest.fixture(scope="module")
def tiny_pair():
    """(dense_cfg, gmm_cfg, params, x): ample capacity, shared params."""
    cfg_d = dataclasses.replace(moe.MOE_PRESETS["moe_tiny"],
                                capacity_factor=100.0)
    cfg_g = dataclasses.replace(cfg_d, dispatch="gmm")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, cfg_d.d_model),
                          jnp.float32)
    params = moe.MoEMlpBlock(cfg_d).init(jax.random.PRNGKey(1), x)["params"]
    return cfg_d, cfg_g, params, x


def _apply(cfg, params, x):
    return moe.MoEMlpBlock(cfg).apply(
        {"params": params}, x, mutable=["aux_loss", "router_stats"])


@slow
def test_same_param_tree(tiny_pair):
    cfg_d, cfg_g, params, x = tiny_pair
    params_g = moe.MoEMlpBlock(cfg_g).init(
        jax.random.PRNGKey(1), x)["params"]
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(params_g))
    shapes_d = jax.tree.map(lambda a: a.shape, params)
    shapes_g = jax.tree.map(lambda a: a.shape, params_g)
    assert shapes_d == shapes_g


@slow
def test_forward_matches_dense_with_ample_capacity(tiny_pair):
    cfg_d, cfg_g, params, x = tiny_pair
    yd, _ = _apply(cfg_d, params, x)
    yg, _ = _apply(cfg_g, params, x)
    np.testing.assert_allclose(np.asarray(yd), np.asarray(yg),
                               atol=1e-5, rtol=1e-5)


@slow
def test_aux_losses_match_dense(tiny_pair):
    cfg_d, cfg_g, params, x = tiny_pair
    _, sd = _apply(cfg_d, params, x)
    _, sg = _apply(cfg_g, params, x)
    for name in ("load_balance", "router_z"):
        np.testing.assert_allclose(
            float(sd["aux_loss"][name][0]), float(sg["aux_loss"][name][0]),
            rtol=1e-5)


@slow
def test_grads_match_dense(tiny_pair):
    cfg_d, cfg_g, params, x = tiny_pair

    def loss(p, cfg):
        return jnp.sum(_apply(cfg, p, x)[0] ** 2)

    gd = jax.grad(loss)(params, cfg_d)
    gg = jax.grad(loss)(params, cfg_g)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-3),
        gd, gg)


@slow
def test_dropless_under_binding_capacity(tiny_pair):
    cfg_d, cfg_g, params, x = tiny_pair
    cfg_bind = dataclasses.replace(cfg_d, capacity_factor=0.5)
    yb, sb = _apply(cfg_bind, params, x)
    yg, sg = _apply(cfg_g, params, x)
    yd_ample, _ = _apply(cfg_d, params, x)
    # Dense with binding capacity really drops...
    assert float(sb["router_stats"]["dropped_frac"][0]) > 0.1
    # ...gmm never does, and still equals the no-drop oracle.
    assert float(sg["router_stats"]["dropped_frac"][0]) == 0.0
    np.testing.assert_allclose(np.asarray(yg), np.asarray(yd_ample),
                               atol=1e-5, rtol=1e-5)
    assert float(jnp.max(jnp.abs(yb - yg))) > 1e-2


@slow
def test_expert_load_sums_to_one(tiny_pair):
    _, cfg_g, params, x = tiny_pair
    _, sg = _apply(cfg_g, params, x)
    load = np.asarray(sg["router_stats"]["expert_load"][0])
    np.testing.assert_allclose(load.sum(), 1.0, atol=1e-5)
    assert (load >= 0).all()


@slow
def test_unknown_dispatch_rejected(tiny_pair):
    cfg_d, _, params, x = tiny_pair
    bad = dataclasses.replace(cfg_d, dispatch="scatter")
    with pytest.raises(ValueError, match="dispatch"):
        _apply(bad, params, x)


@slow
def test_gmm_rejects_quantized_serving(tiny_pair):
    """int8 serving scales present → loud refusal, not silent garbage
    (the quant interceptor only rewrites nn.Dense call sites, which the
    gmm path bypasses)."""
    _, cfg_g, params, x = tiny_pair
    scales = {"experts": {"wi_gate": {"scale": jnp.ones((4, 128))}}}
    with pytest.raises(NotImplementedError, match="gmm"):
        moe.MoEMlpBlock(cfg_g).apply(
            {"params": params, "quant": scales}, x,
            mutable=["aux_loss", "router_stats"])


@slow
def test_gmm_expert_sharded_matches_unsharded(tiny_pair):
    """Expert-parallel gmm (shard_map: local sort + group_offset gmm +
    one psum) == unsharded gmm on a data×expert mesh — every row is
    computed by exactly one expert shard."""
    from tensorflow_train_distributed_tpu.parallel import (
        sharding as sharding_lib,
    )
    from tensorflow_train_distributed_tpu.runtime.mesh import (
        MeshConfig, build_mesh,
    )

    _, cfg_g, params, _ = tiny_pair
    x = jax.random.normal(jax.random.PRNGKey(7), (8, 16, cfg_g.d_model),
                          jnp.float32)
    want, _ = _apply(cfg_g, params, x)
    mesh = build_mesh(MeshConfig(data=2, expert=4))
    with sharding_lib.with_logical_rules(mesh), compat.set_mesh(mesh):
        got = jax.jit(lambda p, t: moe.MoEMlpBlock(cfg_g).apply(
            {"params": p}, t,
            mutable=["aux_loss", "router_stats"])[0])(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)

    def loss(p):
        y = moe.MoEMlpBlock(cfg_g).apply(
            {"params": p}, x, mutable=["aux_loss", "router_stats"])[0]
        return jnp.sum(y ** 2)

    with sharding_lib.with_logical_rules(mesh), compat.set_mesh(mesh):
        g_sharded = jax.jit(jax.grad(loss))(params)
    g_unsharded = jax.grad(loss)(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-3, rtol=1e-3),
        g_sharded, g_unsharded)


@slow
def test_gmm_trains_under_expert_mesh():
    """Full Trainer step: gmm dispatch on a data×expert mesh, loss
    decreases (the dropless EP training path end-to-end)."""
    import optax

    from tensorflow_train_distributed_tpu.data.datasets import get_dataset
    from tensorflow_train_distributed_tpu.data.pipeline import (
        DataConfig, HostDataLoader,
    )
    from tensorflow_train_distributed_tpu.runtime.mesh import (
        MeshConfig, build_mesh,
    )
    from tensorflow_train_distributed_tpu.training import (
        History, Trainer, TrainerConfig,
    )

    cfg = dataclasses.replace(moe.MOE_PRESETS["moe_tiny"], dispatch="gmm")
    mesh = build_mesh(MeshConfig(data=2, expert=4))
    hist = History()
    trainer = Trainer(moe.MoeLmTask(cfg), optax.adam(3e-3), mesh,
                      config=TrainerConfig(log_every=5), callbacks=[hist])
    loader = HostDataLoader(
        get_dataset("lm", vocab_size=256, seq_len=32, num_examples=512),
        DataConfig(global_batch_size=16, seed=0),
        process_index=0, process_count=1,
    )
    trainer.fit(loader, steps=30)
    losses = hist.history["loss"]
    assert losses[-1] < losses[0], losses


@slow
def test_gmm_rejects_expert_tensor_mesh(tiny_pair):
    """expert×tensor meshes must refuse gmm loudly: the shard_map would
    silently replicate expert kernels over tensor (undoing TP)."""
    from tensorflow_train_distributed_tpu.parallel import (
        sharding as sharding_lib,
    )
    from tensorflow_train_distributed_tpu.runtime.mesh import (
        MeshConfig, build_mesh,
    )

    _, cfg_g, params, _ = tiny_pair
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 16, cfg_g.d_model))
    mesh = build_mesh(MeshConfig(data=2, expert=2, tensor=2))
    with sharding_lib.with_logical_rules(mesh), compat.set_mesh(mesh):
        with pytest.raises(ValueError, match="dense"):
            jax.jit(lambda p, t: moe.MoEMlpBlock(cfg_g).apply(
                {"params": p}, t,
                mutable=["aux_loss", "router_stats"]))(params, x)


@slow
def test_gmm_rejects_indivisible_expert_axis(tiny_pair):
    from tensorflow_train_distributed_tpu.parallel import (
        sharding as sharding_lib,
    )
    from tensorflow_train_distributed_tpu.runtime.mesh import (
        MeshConfig, build_mesh,
    )

    _, cfg_g, params, _ = tiny_pair  # 4 experts
    bad = dataclasses.replace(cfg_g, num_experts=6)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 16, cfg_g.d_model))
    params6 = moe.MoEMlpBlock(bad).init(jax.random.PRNGKey(1), x)["params"]
    mesh = build_mesh(MeshConfig(data=2, expert=4))
    with sharding_lib.with_logical_rules(mesh), compat.set_mesh(mesh):
        with pytest.raises(ValueError, match="divisible"):
            jax.jit(lambda p, t: moe.MoEMlpBlock(bad).apply(
                {"params": p}, t,
                mutable=["aux_loss", "router_stats"]))(params6, x)


@slow
def test_full_task_trains_with_gmm():
    """One gradient step through MoeLmTask(dispatch='gmm') under remat:
    finite loss, finite grads touching every expert kernel."""
    cfg = dataclasses.replace(moe.MOE_PRESETS["moe_tiny"], dispatch="gmm",
                              remat=True)
    task = moe.MoeLmTask(cfg)
    rng = np.random.default_rng(0)
    batch = {
        "tokens": rng.integers(0, cfg.vocab_size, (4, 32)).astype(np.int32),
        "targets": rng.integers(0, cfg.vocab_size, (4, 32)).astype(np.int32),
    }
    variables = task.init_variables(jax.random.PRNGKey(0), batch)
    loss, (metrics, _) = task.loss_fn(variables["params"], {}, batch,
                                      jax.random.PRNGKey(0), True)
    assert np.isfinite(float(loss))
    assert float(metrics["dropped_frac"]) == 0.0
    grads = jax.grad(lambda p: task.loss_fn(p, {}, batch,
                                            jax.random.PRNGKey(0), True)[0])(
        variables["params"])
    for leaf in jax.tree.leaves(grads):
        assert bool(jnp.isfinite(leaf).all())
    # Every expert's kernels get gradient signal (routing reaches all
    # experts on this random batch; a broken group_sizes mapping or a
    # collapsed router would zero some expert's slice).
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    expert_leaves = [leaf for path, leaf in flat
                     if any(getattr(p, "key", "") == "experts"
                            for p in path)]
    assert expert_leaves
    for leaf in expert_leaves:  # [E, ...] stacked: per-expert norms
        norms = jnp.sqrt(jnp.sum(leaf ** 2, axis=tuple(
            range(1, leaf.ndim))))
        assert bool((norms > 0).all()), norms


@slow
def test_decode_smoke_with_gmm():
    """The decode path (one-token groups) routes through gmm too."""
    cfg = dataclasses.replace(moe.MOE_PRESETS["moe_tiny"], dispatch="gmm",
                              remat=False)
    model = moe.MoeLmModel(cfg)
    tokens = jnp.zeros((2, 8), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    logits = model.apply({"params": variables["params"]}, tokens,
                         mutable=["aux_loss", "router_stats"])[0]
    assert logits.shape == (2, 8, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())


def _per_pair(flat, top_e, gate_w, wi_gate, wi_up, wo, offset):
    """Every (token, choice) pair through its expert's SwiGLU, one
    choice at a time, float32; a pair whose expert is not among the
    kernels held adds nothing."""
    held = wi_gate.shape[0]
    y = jnp.zeros(flat.shape, jnp.float32)
    for c in range(top_e.shape[1]):
        local = top_e[:, c] - offset
        here = (local >= 0) & (local < held)
        e = jnp.clip(local, 0, held - 1)
        h = (jax.nn.silu(jnp.einsum("td,tdf->tf", flat, wi_gate[e]))
             * jnp.einsum("td,tdf->tf", flat, wi_up[e]))
        pair = jnp.einsum("tf,tfd->td", h, wo[e])
        y = y + jnp.where(here[:, None], pair, 0.0) * gate_w[:, c:c + 1]
    return y


@pytest.mark.parametrize("top_k,share,tokens", [
    (4, False, 16), (4, True, 16), (8, False, 16), (8, True, 16),
    (10, False, 16), (10, True, 16), (10, True, 12)],
    ids=lambda v: str(v))
def test_routed_rows_equal_a_per_pair_loop(top_k, share, tokens):
    """The sort to expert order, the grouped matmuls and the un-sort
    and gate-combine against the plain loop, in value and in the
    gradient of the tokens, the gates and the three kernels: at every
    k, with all experts and with a held share behind an offset, and at
    a token count that is no multiple of 8 (the pairs then pad and the
    sums' rows do; no value may change)."""
    experts, d, f = 16, 128, 128
    held, offset = (6, 5) if share else (experts, 0)
    keys = jax.random.split(jax.random.PRNGKey(top_k + tokens), 7)
    flat = jax.random.normal(keys[0], (tokens, d), jnp.float32)
    _, top_e = jax.lax.top_k(
        jax.random.normal(keys[1], (tokens, experts)), top_k)
    gate_w = jax.nn.softmax(jax.random.normal(keys[2], (tokens, top_k)))
    wi_gate, wi_up, wo = (
        jax.random.normal(key, shape, jnp.float32) * 0.1
        for key, shape in zip(keys[3:6], [(held, d, f), (held, d, f),
                                          (held, f, d)]))
    cot = jax.random.normal(keys[6], (tokens, d), jnp.float32)

    def routed(flat, gate_w, wi_gate, wi_up, wo):
        return moe._routed_ffn_rows(
            flat, top_e, gate_w, experts, wi_gate, wi_up, wo,
            dtype=jnp.float32, interpret=True,
            group_offset=offset if share else None)

    def plain(flat, gate_w, wi_gate, wi_up, wo):
        return _per_pair(flat, top_e, gate_w, wi_gate, wi_up, wo, offset)

    args = (flat, gate_w, wi_gate, wi_up, wo)
    np.testing.assert_allclose(routed(*args), plain(*args),
                               rtol=1e-5, atol=1e-5)
    got, want = (jax.grad(lambda *a: jnp.sum(fn(*a) * cot),
                          argnums=tuple(range(5)))(*args)
                 for fn in (routed, plain))
    for g, w, name in zip(got, want,
                          ["flat", "gate_w", "wi_gate", "wi_up", "wo"]):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5,
                                   err_msg=name)


# (k, n) of the gate / up product of the benchmark's four expert
# configurations, with the router's groups, a decode step's pairs
# (slots x top_k) and a 1024-token piece's; the down product and the
# backward's transposed products have k and n in each other's place.
_BENCH_SHAPES = {
    "glm": (2048, 1536, 64, 32 * 4, 1024 * 4),
    "deepseek": (7168, 2048, 256, 16 * 8, 1024 * 8),
    "laguna": (3072, 1024, 256, 32 * 10, 1024 * 10),
    "ling": (2560, 768, 512, 64 * 8, 1024 * 8),
}
_TODAY = {(2048, 1536): (128, 2048, 512), (1536, 2048): (128, 1536, 512)}


@pytest.mark.parametrize("rows_of", ["step", "piece"])
@pytest.mark.parametrize("transposed", [False, True], ids=["k_n", "n_k"])
@pytest.mark.parametrize("config", sorted(_BENCH_SHAPES))
def test_serving_tiles_divide_an_experts_kernel(config, transposed, rows_of):
    """No remainder tile in ``k`` (megablox masks one on the vector
    unit at every visit) nor in ``n``, ``k`` whole (one k tile: the
    rows' block is fetched once a row tile), and a slice within what
    the docstring states; GLM's tuples are what they were."""
    k, n, groups, step, piece = _BENCH_SHAPES[config]
    if transposed:
        k, n = n, k
    rows = -(-(step if rows_of == "step" else piece) // 128) * 128
    tm, tk, tn = moe._gmm_tiling(rows, groups, k, n)
    assert tm == 128 and tk % 128 == 0 and tn % 128 == 0
    assert k % tk == 0 and n % tn == 0
    assert tk == k                          # every benchmark k fits whole
    assert tk * tn <= moe._GMM_SLICE        # 2 MB in bf16, 4 double buffered
    # ... and no larger divisor of n would have fitted.
    assert not [d for d in range(tn + 128, n + 1, 128)
                if n % d == 0 and tk * d <= moe._GMM_SLICE]
    if (k, n) in _TODAY:
        assert (tm, tk, tn) == _TODAY[(k, n)]


@pytest.mark.parametrize("args,want", [
    # a k that is no multiple of 128 is one rounded-up (masked) tile,
    # an n that is none a whole number of tiles of its rounded width
    ((128, 4, 64, 64), (128, 128, 128)),
    ((128, 8, 200, 72), (128, 256, 128)),
    ((256, 16, 100, 300), (128, 128, 384)),
    # a k of which 128 columns outgrow a slice is split exactly
    ((128, 8, 16384, 256), (128, 8192, 128)),
    ((128, 8, 3 * 8192, 256), (128, 8192, 128)),
    # more than four row tiles an expert: megablox's own
    ((4 * 128 * 8 + 128, 8, 2560, 768), (128, 128, 128)),
    ((4 * 128 * 8, 8, 2560, 768), (128, 2560, 384)),
], ids=str)
def test_tiling_outside_the_benchmark_shapes(args, want):
    assert moe._gmm_tiling(*args) == want


@pytest.mark.parametrize("case", ["all_groups", "held_share", "k_split"])
def test_gmm_with_awkward_tile_ratios_equals_a_per_group_einsum(
        case, monkeypatch):
    """``k`` = 5 x 128 and ``n`` = 3 x 128 (2560 x 768 scaled down):
    one k tile of five lane tiles and one n tile of three, over all
    groups and over a held share behind ``group_offset`` (rows of
    groups not held come back zero); and with the slice shrunk so that
    ``k`` splits into exact tiles that accumulate."""
    k, n, groups, rows = 640, 384, 6, 256
    if case == "k_split":
        monkeypatch.setattr(moe, "_GMM_SLICE", 2 ** 16)
        assert moe._gmm_tiling(rows, groups, k, n) == (128, 128, 384)
    else:
        assert moe._gmm_tiling(rows, groups, k, n) == (128, k, n)
    first, held = (2, 3) if case == "held_share" else (0, groups)
    keys = jax.random.split(jax.random.PRNGKey(40), 2)
    lhs = jax.random.normal(keys[0], (rows, k), jnp.float32)
    rhs = jax.random.normal(keys[1], (held, k, n), jnp.float32) * 0.1
    sizes = jnp.asarray([40, 0, 70, 1, 17, 128], jnp.int32)
    got = moe._gmm(lhs, rhs, sizes, True,
                   None if case != "held_share" else first)
    ends = np.cumsum(np.asarray(sizes))
    want = np.zeros((rows, n), np.float32)
    for g in range(first, first + held):
        lo, hi = ends[g] - int(sizes[g]), ends[g]
        want[lo:hi] = np.einsum("rk,kn->rn", np.asarray(lhs[lo:hi]),
                                np.asarray(rhs[g - first]))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
