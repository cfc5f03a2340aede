"""DeepSeek-V3.2-Exp's block (latent attention over the rows a learned
indexer chooses, YaRN, group-limited routing, a share of the experts)
at test size on the CPU, held to the benchmark's plain reference
(``benchmark/references/deepseek_v32.py``, which imports nothing of the
program).

Tolerance of the logit comparisons: both sides are float32 here (the
CPU's matmuls are exact float32 products), so what differs is the order
of accumulation and, at decode, the association of ``Wkv_b``.  Logits
are O(1); 2e-4 is ~50 x the float32 noise seen (4e-6) and ~100 x below
what a wrong row, a row chosen wrongly (a bf16 index score moves the
edge of the choice), a wrong group, gate, frequency or scale moves them
by (>= 2e-2 at these widths: ``test_a_dropped_stage_fails`` shows it
for each stage).
"""

import dataclasses
import math
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import weights  # noqa: E402
from benchmark.references import deepseek_v32 as reference  # noqa: E402
from tensorflow_train_distributed_tpu.models import layers as L  # noqa: E402
from tensorflow_train_distributed_tpu.models import moe  # noqa: E402
from tensorflow_train_distributed_tpu.ops import (  # noqa: E402
    attention as attention_ops, pallas_kernels as pk,
)
from tensorflow_train_distributed_tpu.runtime import compat  # noqa: E402
from tensorflow_train_distributed_tpu.serving import (  # noqa: E402
    ServingEngine,
)

TOL = 2e-4
CFG = moe.MOE_PRESETS["deepseek_v32_tiny"]
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 16,
        "type": "yarn"}
#: ``deepseek_v32_tiny`` in the source's key names, as a configuration
#: file states a model (what the reference reads).
FILE_CFG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "intermediate_size": 160,
    "moe_intermediate_size": 48, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "n_shared_experts": 1, "n_group": 4,
    "topk_group": 2, "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 12,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 10000.0,
    "rope_scaling": YARN, "rms_norm_eps": 1e-6, "vocab_size": 256,
    "index_n_heads": 4, "index_head_dim": 16, "index_topk": 16}


def _params(cfg, seed=2 ** 33 + 11):
    model = moe.MoeLmModel(cfg)
    boxed = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    return weights.make_params(weights.plain_shapes(boxed)["params"],
                               seed, jnp.float32)


@pytest.fixture(scope="module")
def params():
    return _params(CFG)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(2, 256, n).astype(np.int32)


def _pieces_then_decode(cfg, params, toks, n_prompt, piece=16,
                        compiled=False, **kw):
    """Logits of every position: chunked prefill on the batch-1 linear
    caches (``compiled``: the piece as one jitted program, traced
    once), the engine's insert into the paged pools, teacher-forced
    decode steps through the block table."""
    eng = ServingEngine(cfg, params, slots=2, chunk=2, cache_len=128,
                        kv_block_size=8, prefill_chunk=piece, **kw)
    variables = {"params": params}
    cache_1 = eng._fresh_cache(1)
    got = []

    def a_piece(cache, part):
        return eng._prefill_model.apply(
            dict(variables, cache=cache), part, mutable=["cache"])

    if compiled:
        a_piece = jax.jit(a_piece)
    for start in range(0, n_prompt, piece):
        part = np.zeros((1, piece), np.int32)       # pad rows after
        real = min(piece, n_prompt - start)
        part[0, :real] = toks[start:start + real]
        logits, upd = a_piece(cache_1, jnp.asarray(part))
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
    return np.concatenate(got), eng


# -- (a) prefill in pieces, then paged decode, against the full forward ----

def test_full_forward_matches_the_reference(params):
    toks = _tokens(100)                 # six times index_topk
    got = moe.MoeLmModel(CFG).apply({"params": params}, toks[None])[0]
    want = reference.logits_at(params, FILE_CFG, toks, np.arange(100))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=TOL, rtol=0)


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["gathered", "kernels-interpreted"])
def test_prefill_then_paged_decode_matches_the_reference(
        params, kernel, monkeypatch, walk_in_tiles):
    """Five pieces of 16 (the last ragged) at contexts of up to 4 x
    ``index_topk``, the linear caches walked in tiles of a piece, then
    20 decode steps at 4-5 x ``index_topk``: every position's logits
    against the reference's ONE full forward pass."""
    if kernel:
        monkeypatch.setenv("TTD_FUSED_ATTN_INTERPRET", "1")
    walks = walk_in_tiles(16)
    toks = _tokens(90, seed=1)
    got, eng = _pieces_then_decode(CFG, params, toks, 70)
    assert eng.fused_attn() == kernel
    assert (16, 16, 128) in set(walks)
    want = reference.logits_at(params, FILE_CFG, toks, np.arange(90))
    np.testing.assert_allclose(got, np.asarray(want), atol=TOL, rtol=0)


def test_the_pieces_walk_as_one_kernel_matches_the_reference(
        params, latent_interpreted, walk_in_tiles):
    """The same five pieces with their attention through
    ``prefix_flash_latent`` (interpreted, tiles of a piece): the rows
    up-projected in the kernel and the learned choice handed in as
    ``keep`` give the reference's logits, and the engine counts every
    layer's walk as the kernel's."""
    walk_in_tiles(16)
    calls = latent_interpreted(16, 16)
    toks = _tokens(71, seed=1)          # (and one step, on its table)
    got, eng = _pieces_then_decode(CFG, params, toks, 70, compiled=True)
    assert calls == [(16, True)] * CFG.num_layers      # traced once
    assert eng._flash_layers(False, 16) == CFG.num_layers
    want = reference.logits_at(params, FILE_CFG, toks, np.arange(71))
    np.testing.assert_allclose(got, np.asarray(want), atol=TOL, rtol=0)


@pytest.mark.parametrize("stage, change", [
    ("index score in bf16", "bf16"),
    ("no selection", dict(index_topk=0)),
    ("one group", dict(n_group=1, topk_group=1)),
    ("no yarn", dict(rope_scaling=None)),
    ("unscaled gates", dict(routed_scaling=1.0)),
])
def test_a_dropped_stage_fails(params, stage, change, monkeypatch):
    """Each stage this block adds moves the logits by >= 100 x ``TOL``
    when it is left out or computed in bf16: the comparisons above are
    tight enough to catch it."""
    cfg = CFG
    if change == "bf16":
        real = attention_ops.prefix_index_scores
        monkeypatch.setattr(
            attention_ops, "prefix_index_scores",
            lambda q, w, k, s, **kw: real(
                q.astype(jnp.bfloat16), w, k.astype(jnp.bfloat16), s, **kw))
    else:
        cfg = dataclasses.replace(CFG, **change)
    toks = _tokens(100)
    got = moe.MoeLmModel(cfg).apply({"params": params}, toks[None])[0]
    want = reference.logits_at(params, FILE_CFG, toks, np.arange(100))
    assert float(jnp.abs(got - want).max()) > 100 * TOL, stage


# -- (b) no more rows than index_topk: dense latent attention --------------

def test_up_to_index_topk_rows_the_block_is_dense_latent_attention(params):
    """With ``index_topk`` at or above every context the choice is all
    rows: the engine's logits are those of the same weights without an
    indexer (the selected path of a paged step gathers every row, in the
    order of their scores)."""
    toks = _tokens(60, seed=2)
    dense = dataclasses.replace(CFG, index_topk=0)
    want, _ = _pieces_then_decode(dense, params, toks, 40)
    # Every lane holds <= 64 rows of a cache of 128: the indexer runs
    # and chooses all of them.
    wide = dataclasses.replace(CFG, index_topk=64)
    got, _ = _pieces_then_decode(wide, params, toks, 40)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    # A cache no longer than index_topk has no index cache at all.
    eng = ServingEngine(dataclasses.replace(CFG, index_topk=128), params,
                        slots=2, chunk=2, cache_len=128, kv_block_size=8)
    names = {p[-1].key for p, _ in jax.tree_util.tree_flatten_with_path(
        eng._fresh_cache(2, grid=True))[0]}
    assert "latent_pool" in names and "index_pool" not in names


# -- (c) the choice ---------------------------------------------------------

def _layer_inputs(params, n=100):
    """A layer's normed input, its attention weights and the query
    latent, as the reference makes them."""
    x = reference._f32(jnp.take(params["token_embed"]["embedding"],
                                _tokens(n, seed=3), axis=0))
    w = params["layer_1"]
    normed = reference.rms_norm(x, w["attn_norm"]["scale"], 1e-6)
    a = w["attention"]
    c_q = reference.rms_norm(reference._mm(normed, a["q_a"]["kernel"]),
                             a["q_norm"]["scale"], 1e-6)
    return normed, a, c_q


@pytest.mark.parametrize("tile", [None, 16], ids=["one-tile", "tile-16"])
def test_the_programs_choice_is_the_references(params, tile):
    """``S_t`` of every query, program against reference, in float32:
    the same set, row for row."""
    normed, a, c_q = _layer_inputs(params)
    positions = jnp.arange(100)
    want = np.asarray(reference.unpack_rows(reference.chosen_rows(
        c_q, normed, a, FILE_CFG, positions), 100))

    class Chooser(L.LatentAttention):
        @nn.compact
        def __call__(self, c_q, x):
            q_i, w_i = self._index_queries(c_q, x, positions[None])
            k_i = self._index_keys(x, positions[None])
            scores = attention_ops.prefix_index_scores(
                q_i, w_i, k_i, jnp.zeros((1,), jnp.int32), tile=tile)
            return attention_ops.select_top_rows(
                scores, 16, jnp.zeros((1,), jnp.int32), tile=tile)

    attn = Chooser(
        num_heads=4, q_lora_rank=24, kv_lora_rank=32, qk_nope_dim=12,
        qk_rope_dim=8, v_head_dim=16, rope_scaling=CFG.rope_scaling,
        rms_epsilon=1e-6, index_heads=4, index_dim=16, index_topk=16)
    got = np.asarray(jax.jit(lambda: attn.apply(
        {"params": a}, c_q[None], normed[None]))())[0]
    assert want.sum(axis=-1).tolist() == [min(16, t + 1)
                                          for t in range(100)]
    np.testing.assert_array_equal(got, want)


def _top_k_set(scores, k):
    """``lax.top_k``'s rows as a mask, the ``-inf`` ones left out."""
    _, idx = jax.lax.top_k(jnp.asarray(scores), k)
    want = np.zeros(scores.shape, bool)
    np.put_along_axis(want, np.asarray(idx), True, axis=-1)
    return want & np.isfinite(scores)


def _lane_scores(start, q_len, cache_len, ties, seed=0):
    """Scores as ``prefix_index_scores`` leaves them: lane ``b``'s
    queries at ``start[b] + arange(q_len)``, a row past a query
    ``-inf``; small integers (many exact ties) or normal draws."""
    rng = np.random.default_rng(seed)
    shape = (len(start), q_len, cache_len)
    scores = (rng.integers(-3, 4, shape) if ties
              else rng.normal(size=shape)).astype(np.float32)
    seen = np.asarray(start)[:, None] + np.arange(q_len)
    scores[np.arange(cache_len) > seen[..., None]] = -np.inf
    return scores


#: name: (each lane's start, queries, cache_len, tile), with k = 8.
_WALKS = {
    "planted": None,
    "fewer-than-k": ([0, 2], 5, 40, 8),
    "exactly-k": ([3, 0], 5, 40, 8),
    "k-plus-1": ([4, 1], 5, 40, 8),
    "ends-mid-tile": ([17, 2], 4, 48, 8),
    "ragged-cache": ([30, 7], 9, 43, 8),
    "whole-ragged-cache": ([34, 0], 9, 43, 8),
    "one-tile": ([12, 0], 9, 40, 64),
}


@pytest.mark.parametrize("ties", [True, False], ids=["ties", "random"])
@pytest.mark.parametrize("walk", list(_WALKS))
def test_select_top_rows_breaks_ties_as_top_k_does(walk, ties):
    """The mask is ``lax.top_k``'s set among the visible rows: on
    scores with many exact ties, ``-inf`` rows and rows of fewer than k
    visible entries (``planted``), and on lanes whose ``start`` differs
    with fewer than, exactly and one more than k rows seen, a walk that
    ends mid-tile, a cache that is no multiple of the tile, one tile.
    The rows past the tiles walked are never read (NaN there moves
    nothing), and no pass runs exactly where no query sees more than k
    rows."""
    k = 8
    if _WALKS[walk] is None:
        start, tile = [0, 0], None
        scores = _lane_scores([40, 40], 9, 40, ties)      # nothing hidden
        scores[0, 0, 5:] = -np.inf        # five visible rows
        scores[0, 1] = 0.0                # all equal: the first 8
        scores[1, 2, ::2] = -np.inf
    else:
        start, q_len, cache_len, tile = _WALKS[walk]
        scores = _lane_scores(start, q_len, cache_len, ties)
    q_len, cache_len = scores.shape[1:]
    want = _top_k_set(scores, k)
    dirty = scores.copy()
    if tile is not None and cache_len > tile:
        walked = int(attention_ops.prefix_tiles_walked(
            np.asarray(start), q_len, tile, cache_len))
        dirty[..., walked * tile:] = np.nan
        assert walk != "ends-mid-tile" or np.isnan(dirty).any()
    got = np.asarray(jax.jit(lambda s, at: attention_ops.select_top_rows(
        s, k, at, tile=tile))(jnp.asarray(dirty), jnp.asarray(start)))
    np.testing.assert_array_equal(got, want)
    counted = int(attention_ops.select_tiles_counted(
        np.asarray(start), q_len, k,
        tile or attention_ops.PREFIX_TILE, cache_len))
    if max(start) + q_len <= k:
        assert counted == 0
        np.testing.assert_array_equal(got, np.isfinite(scores))
    else:
        assert counted == int(attention_ops.prefix_tiles_walked(
            np.asarray(start), q_len, tile or attention_ops.PREFIX_TILE,
            cache_len))
    if walk == "planted":
        assert got[0, 0].sum() == 5 and got[0, 1, :8].all()


@pytest.mark.parametrize("bits", [1, 2, 8])
def test_select_top_rows_is_the_same_mask_at_any_bits_a_pass(
        bits, monkeypatch):
    """``SELECT_BITS`` is a cost, not a result."""
    monkeypatch.setattr(attention_ops, "SELECT_BITS", bits)
    scores = _lane_scores([30, 7], 9, 43, ties=bits == 1, seed=bits)
    got = attention_ops.select_top_rows(
        jnp.asarray(scores), 8, jnp.asarray([30, 7]), tile=8)
    np.testing.assert_array_equal(np.asarray(got), _top_k_set(scores, 8))


def test_no_pass_runs_where_nothing_is_to_choose():
    """The counting passes lie in one branch of a ``cond`` on the
    traced ``start``, and the other branch holds no loop."""
    jaxpr = jax.make_jaxpr(lambda s, at: attention_ops.select_top_rows(
        s, 8, at, tile=8))(jnp.zeros((2, 5, 40)), jnp.zeros((2,), jnp.int32))
    conds = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    loops = sorted(sum(q.primitive.name in ("while", "scan")
                       for q in branch.jaxpr.eqns)
                   for branch in conds[0].params["branches"])
    assert loops == [0, 1]


class TestPagedIndexKernel:
    BS, N_BLK, FOLD, DIM, HEADS = 4, 8, 2, 16, 4

    def _case(self, q_len, seed=0):
        rng = np.random.default_rng(seed)
        lanes = 3
        nb = 1 + lanes * self.N_BLK
        pool = jnp.asarray(rng.normal(size=(nb, self.BS, self.DIM)),
                           jnp.float32)
        table = jnp.asarray(
            1 + rng.permutation(nb - 1).reshape(lanes, self.N_BLK),
            jnp.int32)
        lengths = jnp.asarray([0, 9, self.BS * self.N_BLK - q_len],
                              jnp.int32)
        q = jnp.asarray(rng.normal(
            size=(lanes, q_len, self.HEADS, self.DIM)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(lanes, q_len, self.HEADS)),
                        jnp.float32)
        return q, w, pool, table, lengths

    @pytest.mark.parametrize("q_len", [1, 3])
    def test_kernel_matches_the_gathered_reference(self, q_len,
                                                   monkeypatch):
        monkeypatch.setattr(pk, "_paged_fold", lambda *shape: self.FOLD)
        case = self._case(q_len)
        want = pk.paged_index_scores(*case, use_pallas=False)
        got = pk.paged_index_scores(*case, use_pallas=True, interpret=True)
        assert got.shape == (3, q_len, self.BS * self.N_BLK)
        seen = np.isfinite(np.asarray(want))
        assert seen.sum(axis=-1)[:, 0].tolist() == [1, 10, 33 - q_len]
        np.testing.assert_array_equal(np.isfinite(np.asarray(got)), seen)
        np.testing.assert_allclose(np.asarray(got)[seen],
                                   np.asarray(want)[seen], atol=1e-5)

    def test_walk_stops_at_the_lanes_length(self, monkeypatch):
        """Every block a lane's length does not reach is NaN and the
        scores do not move."""
        monkeypatch.setattr(pk, "_paged_fold", lambda *shape: self.FOLD)
        q, w, pool, table, lengths = self._case(1, seed=5)
        clean = pk.paged_index_scores(q, w, pool, table, lengths,
                                      use_pallas=True, interpret=True)
        reach = np.asarray(pk.paged_blocks_walked(
            np.asarray(lengths), 1, self.BS, self.N_BLK))
        dead = np.concatenate([[0]] + [
            np.asarray(table[lane, n:]) for lane, n in enumerate(reach)])
        dirty = pk.paged_index_scores(
            q, w, pool.at[dead].set(jnp.nan), table, lengths,
            use_pallas=True, interpret=True)
        assert not np.isnan(np.asarray(clean)).any()
        np.testing.assert_array_equal(np.asarray(dirty), np.asarray(clean))


# -- (d) the group-limited router -------------------------------------------

def test_group_limited_router_matches_the_reference(params):
    """The layer's routed output with rigged experts IS the gate of
    every expert: program against the reference's ``gates``, on tokens
    whose plain top-2 would leave the best groups."""
    d, e = 64, 8
    cfg = dataclasses.replace(CFG, shared_expert_size=None, ffn_size=8)
    x = np.array(jax.random.normal(jax.random.key(4), (1, 50, d)))
    x[..., e] = 1.0                                   # the constant input
    m = params["layer_1"]["moe"]
    big = 20.0
    wg = np.zeros((e, d, 8), np.float32)
    wu = np.zeros((e, d, 8), np.float32)
    wo = np.zeros((e, 8, d), np.float32)
    wg[:, e, 0] = big                                 # silu(20) = 20
    wu[:, e, 0] = 1.0 / big
    wo[np.arange(e), 0, np.arange(e)] = 1.0
    rigged = {"router": m["router"], "bias": m["bias"],
              "experts": {"wi_gate": {"kernel": wg},
                          "wi_up": {"kernel": wu}, "wo": {"kernel": wo}}}
    got = np.asarray(moe.MoEMlpBlock(cfg).apply(
        {"params": rigged}, jnp.asarray(x)))[0, :, :e]
    want = np.asarray(reference.gates(jnp.asarray(x[0]), m, FILE_CFG))
    np.testing.assert_allclose(got, want, atol=1e-5)
    plain = np.asarray(reference.gates(
        jnp.asarray(x[0]), m, dict(FILE_CFG, n_group=1)))
    assert ((want > 0) != (plain > 0)).any(), "no token left its groups"
    # every chosen expert lies in one of the token's two best groups
    assert ((want.reshape(50, 4, 2) > 0).any(-1).sum(-1) <= 2).all()


def test_one_group_is_todays_plain_top_k(params):
    """``n_group`` 1 (every file until now) and all groups staying
    choose as the ungrouped router does."""
    x = jax.random.normal(jax.random.key(5), (2, 20, 64))
    m = {"params": params["layer_1"]["moe"]}
    outs = [np.asarray(moe.MoEMlpBlock(dataclasses.replace(
        CFG, n_group=g, topk_group=g)).apply(m, x)) for g in (1, 4)]
    np.testing.assert_array_equal(outs[0], outs[1])
    with pytest.raises(ValueError, match="groups of two or more"):
        moe.MoEMlpBlock(dataclasses.replace(
            CFG, n_group=8, topk_group=4)).apply(m, x)


# -- (e) YaRN ----------------------------------------------------------------

def test_yarn_frequencies_and_scale_follow_the_formula():
    """DeepSeek-V3.2's numbers: 64 rotary dims, base 10000, factor 40
    over 4096, beta 32 / 1."""
    dim, base, factor, old = 64, 10000.0, 40.0, 4096
    freqs = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    got = np.asarray(L.scaled_freqs(
        jnp.asarray(freqs, jnp.float32), ("yarn", factor, 32.0, 1.0, old),
        base))

    def pair(turns):
        return dim * math.log(old / (turns * 2 * math.pi)) / (
            2 * math.log(base))

    low, high = math.floor(pair(32)), math.ceil(pair(1))
    assert (low, high) == (10, 23)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    np.testing.assert_allclose(
        got, freqs / factor * ramp + freqs * (1 - ramp), rtol=1e-6)
    np.testing.assert_allclose(got[:11], freqs[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], freqs[23:] / 40, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(reference.yarn_inv_freq(dim, base, dict(
            YARN, original_max_position_embeddings=old))), got, rtol=1e-6)
    attn = L.LatentAttention(
        num_heads=128, q_lora_rank=1536, kv_lora_rank=512, qk_nope_dim=128,
        qk_rope_dim=64, v_head_dim=128,
        rope_scaling=("yarn", factor, 32.0, 1.0, old))
    want = 192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2
    assert attn.softmax_scale == pytest.approx(want, rel=1e-12)
    assert dataclasses.replace(
        attn, rope_scaling=None).softmax_scale == 192 ** -0.5
    assert reference.softmax_scale(dict(
        FILE_CFG, qk_nope_head_dim=128, qk_rope_head_dim=64)) == \
        pytest.approx(want, rel=1e-12)


def test_llama3_scaling_is_unchanged_and_tags_are_checked():
    x = jax.random.normal(jax.random.key(6), (2, 9, 3, 16))
    pos = jnp.arange(9)[None] * 700 + jnp.asarray([[0], [5]])
    bare = (8.0, 1.0, 4.0, 8192)
    freqs = 1.0 / 10000.0 ** (jnp.arange(0, 16, 2, dtype=jnp.float32) / 16)
    np.testing.assert_array_equal(
        np.asarray(L.scaled_freqs(freqs, bare, 10000.0)),
        np.asarray(L.llama3_scaled_freqs(freqs, bare)))
    assert not np.array_equal(np.asarray(L.apply_rope(x, pos)),
                              np.asarray(L.apply_rope(x, pos, scaling=bare)))
    with pytest.raises(ValueError, match="unknown rope scaling"):
        L.apply_rope(x, pos, scaling=("ntk", 2.0))


# -- (f) the shares add up ---------------------------------------------------

def _share(params_moe, offset, held):
    cut = jax.tree.map(lambda k: k[offset:offset + held],
                       params_moe["experts"])
    return dict(params_moe, experts=cut)


def test_the_shares_add_up_to_the_uncut_layer(params):
    """Four chips hold two experts each of a layer of eight: the routed
    parts of all four shares, with the shared expert counted once, are
    the uncut reference layer; each share is the reference's part for
    that share."""
    n = jax.random.normal(jax.random.key(7), (3, 24, 64))
    flat = n.reshape(-1, 64)
    m = params["layer_1"]["moe"]
    want = np.asarray(reference.expert_layer(flat, m, FILE_CFG))
    routed = dataclasses.replace(CFG, shared_expert_size=None)
    total = np.asarray(reference.swiglu(flat, m["shared_mlp"]))
    here = []
    for offset in range(0, 8, 2):
        cfg = dataclasses.replace(routed, experts_held=2,
                                  experts_offset=offset)
        share = {k: v for k, v in _share(m, offset, 2).items()
                 if k != "shared_mlp"}
        part, sown = moe.MoEMlpBlock(cfg).apply(
            {"params": share}, n, mutable=["moe_stats"])
        part = np.asarray(part).reshape(-1, 64)
        ref_part = np.asarray(reference.routed_part(
            flat, _share(m, offset, 2),
            dict(FILE_CFG, experts_offset=offset)))
        np.testing.assert_allclose(part, ref_part, atol=2e-5)
        total = total + part
        stats = {p[-2].key: np.asarray(v) for p, v in
                 jax.tree_util.tree_flatten_with_path(sown["moe_stats"])[0]}
        assert stats["expert_rows"].shape == (2,)
        here.append(float(stats["routed_here"]))
        assert stats["expert_rows"].sum() == round(here[-1] * 72 * 2)
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert sum(here) == pytest.approx(1.0)
    with pytest.raises(ValueError, match="are not among the router's 8"):
        moe.MoEMlpBlock(dataclasses.replace(
            routed, experts_held=2, experts_offset=7)).apply(
                {"params": _share(m, 6, 2)}, n)


def test_the_expert_mesh_computes_what_the_shares_compute(params):
    """The ``expert``-mesh path (four shards of two experts, one psum)
    and the held-experts path are one body: the mesh's layer equals the
    sum of the four shares (and the whole layer on one device)."""
    from tensorflow_train_distributed_tpu.parallel import (
        sharding as sharding_lib,
    )
    from tensorflow_train_distributed_tpu.runtime.mesh import (
        MeshConfig, build_mesh,
    )

    n = jax.random.normal(jax.random.key(8), (8, 16, 64))
    m = {k: v for k, v in params["layer_1"]["moe"].items()
         if k != "shared_mlp"}
    routed = dataclasses.replace(CFG, shared_expert_size=None)
    whole = np.asarray(moe.MoEMlpBlock(routed).apply({"params": m}, n))
    mesh = build_mesh(MeshConfig(data=2, expert=4))
    with sharding_lib.with_logical_rules(mesh), compat.set_mesh(mesh):
        meshed = np.asarray(jax.jit(lambda p, t: moe.MoEMlpBlock(
            routed).apply({"params": p}, t))(m, n))
    shares = sum(np.asarray(moe.MoEMlpBlock(dataclasses.replace(
        routed, experts_held=2, experts_offset=o)).apply(
            {"params": _share(m, o, 2)}, n)) for o in range(0, 8, 2))
    np.testing.assert_allclose(meshed, whole, atol=2e-5)
    np.testing.assert_allclose(shares, meshed, atol=2e-5)


@pytest.mark.parametrize("tile", [None, 16], ids=["one-tile", "tile-16"])
def test_engine_counts_the_share_and_the_selection(params, tile,
                                                   walk_in_tiles):
    """``engine/step``'s counters and ``prefill/piece``'s
    ``select_rows``, through a served request on a model that holds
    experts [2, 4) of 8."""
    from tensorflow_train_distributed_tpu.runtime import events

    walk_in_tiles(tile)
    cfg = dataclasses.replace(CFG, experts_held=2, experts_offset=2)
    eng = ServingEngine(cfg, _params(cfg), slots=2, chunk=4, cache_len=128,
                        kv_block_size=8, prefill_chunk=16)
    prompt = [int(t) for t in _tokens(40, seed=9)]
    rec = events.get_recorder()
    seq0 = rec.events_after(0)[0]
    rid = eng.submit(prompt, 9)
    assert len(eng.run()[rid]) == 49
    counts = eng._step_counts
    assert counts["experts_held"] == 2
    assert 0.0 <= counts["experts_hit"] <= 2.0
    assert 0.0 <= counts["routed_here"] <= 1.0
    # one live lane at 41-48 rows, 16 of them attended
    assert counts["rows_selected"] == 16.0
    assert 41.0 <= counts["rows_scored"] <= 49.0
    assert eng.kv_pool_bytes() == cfg.num_layers * 33 * 8 * (128 + 16) * 4
    # Three pieces of 16: the first sees no more than index_topk rows
    # and counts nothing, the others count over the tiles they walk.
    pieces = [e[5] for e in rec.events_after(seq0)[1]
              if e[0] == "prefill/piece"]
    walked = [128, 128, 128] if tile is None else [16, 32, 48]
    assert [p["rows"] for p in pieces] == walked
    assert [p["select_rows"] for p in pieces] == [0] + walked[1:]


# -- (g) the index rows travel with the latent rows -------------------------

_KW = dict(slots=2, chunk=4, cache_len=128, kv_block_size=8,
           prefill_chunk=16)


@pytest.fixture(scope="module", params=["glm_lite_tiny",
                                        "deepseek_v32_tiny"])
def latent_model(request):
    """A latent-attention model with one kind of cached row a token
    (the latent row) and one with two (and the index key)."""
    cfg = moe.MOE_PRESETS[request.param]
    pools = {"latent_pool"} | ({"index_pool"} if cfg.index_topk else set())
    return cfg, _params(cfg), pools


def _pool_names(eng):
    return {p[-1].key for p, _ in jax.tree_util.tree_flatten_with_path(
        eng._cache)[0] if p[-1].key.endswith("_pool")}


def test_radix_prefix_reuse_carries_every_row_leaf(latent_model):
    """A second request that shares 48 tokens with the first gathers
    them out of the pools (``_gather_prefix``, every row leaf) and
    serves what a cold engine serves."""
    cfg, params, pools = latent_model
    rng = np.random.default_rng(5)
    pre = [int(t) for t in rng.integers(2, 200, 48)]
    a = pre + [int(t) for t in rng.integers(2, 200, 7)]
    b = pre + [int(t) for t in rng.integers(2, 200, 9)]
    cold = ServingEngine(cfg, params, **_KW)
    want = cold.run() if cold.submit(b, 10) is None else cold.run()
    eng = ServingEngine(cfg, params, **_KW)
    eng.submit(a, 6)
    eng.run()
    rid = eng.submit(b, 10)
    got = eng.run()[rid]
    assert _pool_names(eng) == pools
    assert eng.kv_stats["prefix_hit_tokens"] >= 48
    assert got == list(want.values())[0]


def test_export_and_install_carry_every_row_leaf(latent_model):
    """A lane exported mid-stream and installed into a second engine
    resumes there without re-prefilling and serves the unmigrated
    run's tokens."""
    cfg, params, pools = latent_model
    prompt = [int(t) for t in _tokens(37, seed=3)]
    ref_eng = ServingEngine(cfg, params, **_KW)
    rid = ref_eng.submit(list(prompt), 24, seed=7)
    ref = ref_eng.run()[rid]
    src = ServingEngine(cfg, params, **_KW)
    rid = src.submit(list(prompt), 24, seed=7)
    for _ in range(200):
        src.serve_step()
        meta, blob = src.export_lane(rid)
        if (meta["kind"] == "lane"
                and len(meta["tokens"]) >= len(prompt) + 10):
            break
    assert meta["kind"] == "lane" and meta["kv"]["n"] >= 40 and blob
    dst = ServingEngine(cfg, params, **_KW)
    assert dst.install_lane(meta, blob) == meta["kv"]["n"]
    rid2 = dst.submit(list(meta["tokens"]), meta["remaining"], seed=7,
                      resume_from=len(meta["tokens"]) - len(prompt))
    assert dst.run()[rid2] == ref
    assert dst.kv_stats["prefix_hit_tokens"] >= meta["kv"]["n"]
    assert _pool_names(dst) == pools


def test_a_reset_lane_serves_as_a_fresh_one(latent_model):
    """Three requests through one slot: each is served in a lane the
    one before left (table reset to the scratch block, rows of every
    pool overwritten before they are read) and equals a fresh engine's
    answer."""
    cfg, params, _ = latent_model
    kw = dict(_KW, slots=1)
    prompts = [[int(t) for t in _tokens(n, seed=s)]
               for n, s in ((45, 11), (23, 12), (60, 13))]
    eng = ServingEngine(cfg, params, **kw)
    ids = [eng.submit(p, 8) for p in prompts]
    out = eng.run()
    for rid, p in zip(ids, prompts):
        fresh = ServingEngine(cfg, params, **kw)
        fid = fresh.submit(p, 8)
        assert out[rid] == fresh.run()[fid]
