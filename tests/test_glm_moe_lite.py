"""GLM-4.7-Flash's block (latent attention, sigmoid-routed experts with
a shared one, a leading dense layer) at test size on the CPU, held to
the benchmark's plain reference (``benchmark/references/glm_moe_lite.py``,
which imports nothing of the program).

Tolerance of the logit comparisons: both sides are float32 here (the
CPU's matmuls are exact float32 products), so what differs is the order
of accumulation and, at decode, the association of ``Wkv_b`` (absorbed
into query and output against up-projected keys and values).  Logits
are O(1); 2e-4 is ~100 x the float32 noise seen (2e-6) and ~100 x below
what a wrong row, position, gate or scaling factor moves them by
(>= 2e-2 at these widths).
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import weights  # noqa: E402
from benchmark.references import glm_moe_lite as reference  # noqa: E402
from tensorflow_train_distributed_tpu.models import layers as L  # noqa: E402
from tensorflow_train_distributed_tpu.models import moe  # noqa: E402
from tensorflow_train_distributed_tpu.ops import (  # noqa: E402
    pallas_kernels as pk,
)
from tensorflow_train_distributed_tpu.serving import (  # noqa: E402
    ServingEngine,
)

TOL = 2e-4
CFG = moe.MOE_PRESETS["glm_lite_tiny"]
#: ``glm_lite_tiny`` in the source's key names, as a configuration file
#: states a model (what the reference reads).
FILE_CFG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "intermediate_size": 160,
    "moe_intermediate_size": 48, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "norm_topk_prob": True,
    "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 12,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-5, "vocab_size": 256}


@pytest.fixture(scope="module")
def params():
    model = moe.MoeLmModel(CFG)
    boxed = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    return weights.make_params(weights.plain_shapes(boxed)["params"],
                               2 ** 33 + 11, jnp.float32)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(2, 256, n).astype(np.int32)


def test_full_forward_matches_the_reference(params):
    toks = _tokens(40)
    got = moe.MoeLmModel(CFG).apply({"params": params}, toks[None])[0]
    want = reference.logits_at(params, FILE_CFG, toks, np.arange(40))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=TOL, rtol=0)


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["gathered", "kernel-interpreted"])
def test_prefill_then_paged_decode_matches_the_reference(
        params, kernel, monkeypatch):
    """Chunked prefill on the batch-1 linear latent cache (two pieces,
    the second ragged), the engine's insert into the paged pool, then
    teacher-forced decode steps through the block table, absorbed: the
    logits of every position against the reference's ONE full forward
    pass over the whole sequence."""
    if kernel:
        monkeypatch.setenv("TTD_FUSED_ATTN_INTERPRET", "1")
    toks = _tokens(30, seed=1)
    n_prompt, piece = 24, 16
    eng = ServingEngine(CFG, params, slots=2, chunk=2, cache_len=64,
                        kv_block_size=8, prefill_chunk=piece)
    assert eng.fused_attn() == kernel
    variables = {"params": params}
    cache_1 = eng._fresh_cache(1)
    got = []
    for start in range(0, n_prompt, piece):
        part = np.zeros((1, piece), np.int32)       # pad rows after
        real = min(piece, n_prompt - start)
        part[0, :real] = toks[start:start + real]
        logits, upd = eng._prefill_model.apply(
            dict(variables, cache=cache_1), jnp.asarray(part),
            mutable=["cache"])
        cache_1 = upd["cache"]
        got.append(np.asarray(logits[0, :real]))
    grid = eng._fresh_cache(eng.slots, grid=True)
    table_row = jnp.arange(1, eng._kv_nblk_lane + 1, dtype=jnp.int32)
    grid = eng._paged_insert(grid, cache_1, jnp.int32(1), table_row,
                             jnp.int32(0), jnp.int32(n_prompt))
    for t in toks[n_prompt:]:
        logits, upd = eng._model.apply(
            dict(variables, cache=grid),
            jnp.asarray([[0], [t]], jnp.int32), mutable=["cache"])
        grid = upd["cache"]
        got.append(np.asarray(logits[1]))
    want = reference.logits_at(params, FILE_CFG, toks, np.arange(30))
    np.testing.assert_allclose(np.concatenate(got), np.asarray(want),
                               atol=TOL, rtol=0)


def test_absorbed_decode_equals_up_projected_attention():
    """One ``LatentAttention`` layer: every position decoded one token
    at a time through the paged pool (absorbed: scores against the
    latent rows, ``Wkv_b`` folded into query and output) against the
    same weights' full-sequence forward (up-projected keys and values
    of every head)."""
    kw = dict(num_heads=4, q_lora_rank=24, kv_lora_rank=32, qk_nope_dim=12,
              qk_rope_dim=8, v_head_dim=16)
    x = jax.random.normal(jax.random.key(1), (2, 20, 64))
    full = L.LatentAttention(**kw)
    variables = full.init(jax.random.key(2), x)
    want = full.apply(variables, x)
    paged = L.LatentAttention(**kw, decode=True, cache_len=32,
                              slot_decode=True, paged_kv_blocks=9,
                              kv_block_size=8)
    cache = jax.eval_shape(
        lambda: paged.apply(variables, x[:, :1], mutable=["cache"]))[1]
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         cache["cache"])
    cache["block_table"] = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]],
                                       jnp.int32)
    got = []
    for i in range(x.shape[1]):
        y, upd = paged.apply(dict(variables, cache=cache), x[:, i:i + 1],
                             mutable=["cache"])
        cache = upd["cache"]
        got.append(y)
    assert cache["latent_pool"].shape == (9, 8, 128)   # 40 values in 128
    np.testing.assert_allclose(np.concatenate(got, axis=1),
                               np.asarray(want), atol=2e-5, rtol=0)


# logits 2, 1, 0, -1 -> scores .8808 .7311 .5 .2689; the bias lifts
# expert 3 over expert 0 in the CHOICE (.7689 > .7311 > .5 > -.1192), so
# experts 3 and 1 are taken, and the gates are their SCORES over their
# sum, x 1.8: (.2689, .7311) / 1.0 x 1.8.
HAND_LOGITS = [2.0, 1.0, 0.0, -1.0]
HAND_BIAS = [-1.0, 0.0, 0.0, 0.5]
HAND_GATES = [0.0, 1.8 * 0.731059, 0.0, 1.8 * 0.268941]


def test_router_bias_takes_part_in_the_choice_and_not_in_the_gate():
    """A hand-worked token through ``MoEMlpBlock``: expert i is rigged
    to return the unit vector e_i whatever comes in, so the layer's
    output IS the gate of every expert."""
    d, e, f, big = 8, 4, 8, 20.0
    cfg = dataclasses.replace(
        CFG, d_model=d, num_experts=e, ffn_size=f, top_k=2,
        shared_expert_size=None)
    x = np.zeros((1, 1, d), np.float32)
    x[0, 0, :e] = HAND_LOGITS
    x[0, 0, e] = 1.0                                  # the constant input
    router = np.zeros((d, e), np.float32)
    router[:e] = np.eye(e)
    wg = np.zeros((e, d, f), np.float32)
    wu = np.zeros((e, d, f), np.float32)
    wo = np.zeros((e, f, d), np.float32)
    wg[:, e, 0] = big                                 # silu(20) = 20
    wu[:, e, 0] = 1.0 / big
    wo[np.arange(e), 0, np.arange(e)] = 1.0
    params = {"router": {"kernel": router}, "bias": np.asarray(HAND_BIAS),
              "experts": {"wi_gate": {"kernel": wg},
                          "wi_up": {"kernel": wu}, "wo": {"kernel": wo}}}
    y = moe.MoEMlpBlock(cfg).apply({"params": params}, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y)[0, 0, :e], HAND_GATES,
                               atol=1e-5)
    # and the reference's router on the same numbers
    ref = reference.gates(
        jnp.asarray(x[0]), {"router": {"kernel": router},
                            "bias": jnp.asarray(HAND_BIAS)},
        {"num_experts_per_tok": 2, "routed_scaling_factor": 1.8})
    np.testing.assert_allclose(np.asarray(ref)[0], HAND_GATES, atol=1e-5)


def test_sigmoid_router_refuses_the_capacity_bounded_dispatch(params):
    cfg = dataclasses.replace(CFG, dispatch="dense")
    with pytest.raises(ValueError, match="dispatch='gmm' only"):
        moe.MoeLmModel(cfg).apply({"params": params},
                                  jnp.zeros((1, 4), jnp.int32))


def _serve(params, prompt, n_new, **kw):
    eng = ServingEngine(CFG, params, slots=2, chunk=4, cache_len=64,
                        kv_block_size=8, **kw)
    rid = eng.submit([int(t) for t in prompt], n_new)
    return eng.run()[rid][len(prompt):], eng


@pytest.mark.parametrize("tile", [None, 8], ids=["one-tile", "tile-8"])
def test_chunked_prefill_equals_whole_prefill_under_gmm(
        params, walk_in_tiles, tile):
    """Dropless routing takes every token by itself, so a prompt in
    8-token pieces serves the tokens of the prompt in one piece.
    ``tile-8``: the latent cache of 64 rows is walked in tiles of a
    piece, so the three pieces up-project and attend over one, two and
    three tiles of its eight (the whole-prompt engine before it walks
    the cache as one tile: the whole-cache expression)."""
    prompt = _tokens(21, seed=3)
    whole, _ = _serve(params, prompt, 10, prompt_buckets=(32,))
    walks = walk_in_tiles(tile)
    pieces, eng = _serve(params, prompt, 10, prefill_chunk=8)
    assert eng.prefill_stats["installments"] >= 3
    assert pieces == whole
    # The pieces' walks (and the one-token trace that sizes a cache).
    assert set(walks) == ({(8, 8, 64), (1, 8, 64)} if tile else set())


@pytest.mark.parametrize("rows, want", [
    # 32 lanes x top 4 over 64 experts, a 1024-token piece's rows and a
    # prefill call's of four pieces: a few row tiles or less an expert,
    # whole slices of an expert's kernel
    (128, (128, 2048, 512)), (4096, (128, 2048, 512)),
    (16384, (128, 2048, 512)),
    # a training batch's rows, more than four row tiles an expert:
    # megablox's own tiles, as before
    (4 * 8192 + 128, (128, 128, 128))])
def test_grouped_matmul_tiles_follow_the_rows_an_expert_gets(
        monkeypatch, rows, want):
    from jax.experimental.pallas.ops.tpu.megablox import ops as mb

    seen = []
    monkeypatch.setattr(
        mb, "gmm", lambda lhs, rhs, sizes, **kw: seen.append(kw["tiling"]))
    moe._gmm(jax.ShapeDtypeStruct((rows, 2048), jnp.bfloat16),
             jax.ShapeDtypeStruct((64, 2048, 1536), jnp.bfloat16),
             jnp.zeros((64,), jnp.int32), False)
    assert seen == [want]


def test_engine_counts_the_experts_a_chunk_hits(params):
    """``_decode_chunk`` returns the rows every expert took, every step
    and expert layer (what the layers sowed, by name), and the harvest
    folds them into the step's counts (``engine/step``'s
    ``experts_hit``, ``expert_load_cv``)."""
    _, eng = _serve(params, _tokens(9, seed=4), 6)
    counts = eng._step_counts
    # 2 lanes x top-2 rows a step: between 2 and 4 distinct experts.
    assert 2.0 <= counts["experts_hit"] <= 4.0
    assert counts["expert_load_cv"] > 0
    rows = np.asarray(eng._decode_chunk(
        eng._variables, eng._fresh_cache(2, grid=True),
        jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.uint32),
        jnp.zeros((2,), jnp.int32))[4]["expert_rows"])
    assert rows.shape == (eng.chunk, 2, CFG.num_experts)
    assert (rows.sum(axis=-1) == 2 * CFG.top_k).all()


def test_latent_cache_is_one_row_a_token(params):
    """The paged pool of a latent layer is ONE [blocks, block, row]
    leaf, counted into ``kv_pool_bytes``; int8 weight-only serving of
    its raw-read kernels is refused, loudly."""
    _, eng = _serve(params, _tokens(9, seed=5), 2)
    pools = [(p[-1].key, leaf.shape) for p, leaf in
             jax.tree_util.tree_flatten_with_path(eng._cache)[0]
             if p[-1].key.endswith("_pool")]
    assert pools == [("latent_pool", (17, 8, 128))] * CFG.num_layers
    assert eng.kv_pool_bytes() == CFG.num_layers * 17 * 8 * 128 * 4
    from tensorflow_train_distributed_tpu.models.quant import (
        quantize_params,
    )

    q, scales = quantize_params(params)
    with pytest.raises(NotImplementedError, match="kernels read as arrays"):
        ServingEngine(CFG, q, quant_scales=scales, slots=2, chunk=4,
                      cache_len=64, kv_block_size=8)


class TestPagedLatentKernel:
    BS, N_BLK, FOLD, ROW, RANK = 4, 8, 2, 128, 64

    def _case(self, q_len, seed=0):
        rng = np.random.default_rng(seed)
        lanes, heads = 3, 5
        nb = 1 + lanes * self.N_BLK
        pool = jnp.asarray(rng.normal(size=(nb, self.BS, self.ROW)),
                           jnp.float32)
        table = jnp.asarray(
            1 + rng.permutation(nb - 1).reshape(lanes, self.N_BLK),
            jnp.int32)
        lengths = jnp.asarray([0, 9, self.BS * self.N_BLK - q_len],
                              jnp.int32)
        q = jnp.asarray(rng.normal(size=(lanes, q_len, heads, self.ROW)),
                        jnp.float32)
        return q, pool, table, lengths

    def _run(self, q, pool, table, lengths, **kw):
        return pk.paged_latent_attention(
            q, pool, table, lengths, value_dim=self.RANK, scale=0.125, **kw)

    @pytest.mark.parametrize("q_len", [1, 3])
    def test_kernel_matches_the_gathered_reference(self, q_len,
                                                   monkeypatch):
        monkeypatch.setattr(pk, "_paged_fold", lambda *shape: self.FOLD)
        case = self._case(q_len)
        want = self._run(*case, use_pallas=False)
        got = self._run(*case, use_pallas=True, interpret=True)
        assert got.shape == (3, q_len, 5, self.RANK)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("q_len", [1, 3])
    def test_walk_stops_at_the_lanes_length(self, q_len, monkeypatch):
        """PR 25's poison test for the latent pool: every block a
        lane's length does not reach is NaN and the output does not
        move."""
        monkeypatch.setattr(pk, "_paged_fold", lambda *shape: self.FOLD)
        q, pool, table, lengths = self._case(q_len, seed=5)
        clean = self._run(q, pool, table, lengths, use_pallas=True,
                          interpret=True)
        reach = np.asarray(pk.paged_blocks_walked(
            np.asarray(lengths), q_len, self.BS, self.N_BLK))
        dead = np.concatenate([[0]] + [
            np.asarray(table[lane, n:]) for lane, n in enumerate(reach)])
        assert 0 < len(dead) < pool.shape[0]
        dirty = self._run(q, pool.at[dead].set(jnp.nan), table, lengths,
                          use_pallas=True, interpret=True)
        assert np.all(np.isfinite(np.asarray(clean)))
        np.testing.assert_array_equal(np.asarray(dirty), np.asarray(clean))
