"""Full and window layers whose KV heads differ by kind, keys wider
than values and a learned sink in the window layers' softmax
(``mimo_v2``) at test size: ``MOE_PRESETS["mimo_v25_tiny"]`` through
the serving engine's own programs (prefill in pieces on the batch-1
cache, the insert into both kinds of pool at each kind's own row,
paged decode past the window and past three ring turns, by the gather
leg and by the fused kernel) against the plain reference
``benchmark/references/mimo_v2.py``; each planted fault (the sink left
out, the value scale left out, the window off by one, the full layers'
KV heads on a window layer) is seen; the paged kernel with a value head
of its own size and sinks against its ``jnp`` reference over
``block0``, ragged lengths and a window, at test size and at a key head
of 192 (one and a half lane tiles); a sink of -inf is no sink, to the
bit; the sixteen shares of an expert layer add up to the uncut layer;
a pattern said with a lead is the pattern said without one, to the bit;
the engine's refusals tell sink rows from a sink logit."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (REPO, os.path.dirname(os.path.abspath(__file__))):
    if path not in sys.path:
        sys.path.insert(0, path)

from benchmark.harness import serve_family, weights  # noqa: E402
from benchmark.references import mimo_v2 as reference  # noqa: E402
from tensorflow_train_distributed_tpu.models import moe  # noqa: E402
from tensorflow_train_distributed_tpu.ops import attention  # noqa: E402
from tensorflow_train_distributed_tpu.ops import (  # noqa: E402
    pallas_kernels as pk,
)
from tensorflow_train_distributed_tpu.runtime import events  # noqa: E402
from tensorflow_train_distributed_tpu.serving import (  # noqa: E402
    ServingEngine,
)

import test_laguna  # noqa: E402  (program_logits: the engine's programs)

TINY = moe.MOE_PRESETS["mimo_v25_tiny"]
SEED = 2 ** 31 + 41
WINDOW_LAYERS = (1, 2, 3, 4, 6)


def cfg_file_of(cfg, **over):
    """The configuration-file keys the reference reads, for a program
    config of this family (the source's own names)."""
    kinds = [cfg.attn_kind(i) for i in range(cfg.num_layers)]
    full = next(k for k in kinds if k.window is None)
    window = next(k for k in kinds if k.window is not None)
    out = {
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": full.num_heads,
        "num_key_value_heads": full.num_kv_heads or cfg.num_kv_heads,
        "swa_num_key_value_heads": window.num_kv_heads,
        "head_dim": cfg.head_dim, "swa_head_dim": cfg.head_dim,
        "v_head_dim": cfg.v_head_dim, "swa_v_head_dim": cfg.v_head_dim,
        "attention_value_scale": cfg.value_scale,
        "add_swa_attention_sink_bias": window.sink,
        "add_full_attention_sink_bias": full.sink,
        "partial_rotary_factor": full.rotary_share,
        "rope_theta": full.rope_base, "swa_rope_theta": window.rope_base,
        "sliding_window": window.window,
        "layernorm_epsilon": cfg.rms_epsilon,
        "num_experts_per_tok": cfg.top_k, "norm_topk_prob": True,
        "routed_scaling_factor": None,
        "experts_offset": cfg.experts_offset,
        "hybrid_layer_pattern": [int(k.window is not None) for k in kinds],
        "moe_layer_freq": [int(i >= cfg.dense_layers)
                           for i in range(cfg.num_layers)],
    }
    out.update(over)
    return out


@pytest.fixture(scope="module")
def params():
    """Seeded float32 weights; the sinks, which the benchmark's rule
    draws near zero (a ``bias`` leaf), drawn about 1 +- 1 here so that
    a sink left out is a large fault."""
    tree = weights.make_params(serve_family.moe_param_shapes(TINY), SEED,
                               jnp.float32)
    for i in WINDOW_LAYERS:
        tree[f"layer_{i}"]["attention"]["sink"]["bias"] = jnp.asarray(
            1.0 + np.random.default_rng(i).standard_normal(4), jnp.float32)
    return tree


def reference_logits(params, cfg_file, seq):
    return np.asarray(reference.logits_at(
        params, cfg_file, [int(t) for t in seq], list(range(len(seq)))))


@pytest.fixture(scope="module")
def sequence():
    return np.random.default_rng(41).integers(3, 256, 61).astype(np.int32)


@pytest.fixture(scope="module")
def reference_run(params, sequence):
    return reference_logits(params, cfg_file_of(TINY), sequence)


# float32 on both sides, logits of a few units: what is left is the
# order of float32 sums: a few 1e-6.  Any stage dropped or bent moves a
# logit by 1e-2 or more.
TOL = 2e-5


def test_the_pattern_and_the_rows_are_the_published_ones_at_test_size():
    kinds = [TINY.attn_kind(i) for i in range(TINY.num_layers)]
    assert [k.window for k in kinds] == [None, 8, 8, 8, 8, None, 8]
    assert [k.num_kv_heads or TINY.num_kv_heads for k in kinds] == [
        1, 2, 2, 2, 2, 1, 2]
    assert [k.sink for k in kinds] == [False] + [True] * 4 + [False, True]
    big = moe.MOE_PRESETS["mimo_v25"]
    published = [0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 7 + [0]
    assert [int(big.attn_kind(i).window is not None)
            for i in range(48)] == published
    assert big.attn_window == 128 and TINY.attn_window == 8
    assert int(192 * big.attn_kind(0).rotary_share) == 64


@pytest.mark.parametrize("fused", [False, True], ids=["gather", "kernel"])
def test_pieces_then_paged_decode_agree_with_the_reference(
        fused, params, sequence, reference_run, monkeypatch):
    """21 prompt tokens in three pieces of 8, then 40 paged decode
    steps: the window is 8 rows and the ring 3 blocks of 4 = 12 rows,
    so decode runs 32 rows past the window and past three ring turns;
    a full layer's row is 24 + 16 values of one KV head, a window
    layer's 2 x (24 + 16).  By the gathered view and by the fused
    kernel (interpreted), from the sink."""
    if fused:
        monkeypatch.setenv("TTD_FUSED_ATTN_INTERPRET", "1")
    eng, pre, dec = test_laguna.program_logits(TINY, params, sequence, 21)
    assert (eng._window, eng._ring_blocks) == (8, 3)
    assert bool(eng.fused_attn()) == fused
    ours = np.concatenate([pre, dec])
    assert ours.shape == reference_run.shape == (61, 256)
    np.testing.assert_allclose(ours, reference_run, atol=TOL, rtol=0)
    assert (ours.argmax(-1) == reference_run.argmax(-1)).all()
    # pools by each kind's own row: keys 24 wide and values 16 a KV
    # head, 1 head in a full layer's blocks and 2 in a window's rings
    flat = {eng._path_key(p): leaf.shape for p, leaf
            in jax.tree_util.tree_flatten_with_path(
                eng._cache_struct(2, grid=True))[0]}
    rows = {k[0]: (flat[k][-1], flat[k[:-1] + ("value_pool",)][-1])
            for k in flat if k[-1] == "key_pool"}
    assert rows == {f"layer_{i}": ((48, 32) if i in WINDOW_LAYERS
                                   else (24, 16)) for i in range(7)}
    ring = 5 * (1 + 2 * 3) * 4 * (48 + 32) * 4
    assert eng._kv_ring_bytes == ring
    assert eng.kv_pool_bytes() == ring + 2 * (1 + 2 * 24) * 4 * 40 * 4
    assert eng._kv_pool.bytes_per_block == 2 * 4 * 40 * 4


def _kinds(cfg, **changes):
    """``cfg`` with every WINDOW kind of its lead and period changed."""
    def bent(kinds):
        return tuple(dataclasses.replace(k, **changes)
                     if k.window is not None else k for k in kinds)

    return dataclasses.replace(cfg, attn_lead=bent(cfg.attn_lead),
                               attn_period=bent(cfg.attn_period))


#: The issue's three planted faults and one more: a program with the
#: fault against the sound reference.
BENT = {
    "no-sink": lambda cfg: _kinds(cfg, sink=False),
    "no-value-scale": lambda cfg: dataclasses.replace(cfg, value_scale=1.0),
    "window-off-by-one": lambda cfg: _kinds(cfg, window=7),
    "whole-head-rotary": lambda cfg: dataclasses.replace(
        cfg, attn_lead=tuple(dataclasses.replace(k, rotary_share=1.0)
                             for k in cfg.attn_lead),
        attn_period=tuple(dataclasses.replace(k, rotary_share=1.0)
                          for k in cfg.attn_period)),
}


@pytest.mark.parametrize("bend", sorted(BENT))
def test_a_planted_fault_in_the_program_is_seen(
        bend, params, sequence, reference_run):
    """The same weights through a program with one fault planted: logits
    leave the reference by far more than rounding, in decode (past the
    window for the mask) if not before."""
    tree = params
    if bend == "no-sink":       # a program without the parameter
        tree = jax.tree.map(lambda x: x, params)
        for i in WINDOW_LAYERS:
            del tree[f"layer_{i}"]["attention"]["sink"]
    _, pre, dec = test_laguna.program_logits(BENT[bend](TINY), tree,
                                             sequence, 21)
    off = np.abs(np.concatenate([pre, dec]) - reference_run)
    assert off.max() > 500 * TOL, (bend, off.max())
    if bend == "window-off-by-one":
        # the first 7 rows see the same keys either way
        assert off[:7].max() < TOL < off[7:].max()


def test_the_full_layers_kv_heads_on_a_window_layer_are_seen(
        params, sequence, reference_run):
    """A program that gave the window layers the full layers' one KV
    head (the first of their two: the key and value kernels cut to it)
    is another model."""
    wrong = jax.tree.map(lambda x: x, params)
    for i in WINDOW_LAYERS:
        a = wrong[f"layer_{i}"]["attention"]
        a["key"] = {"kernel": a["key"]["kernel"][:, :24]}
        a["value"] = {"kernel": a["value"]["kernel"][:, :16]}
    _, pre, dec = test_laguna.program_logits(
        _kinds(TINY, num_kv_heads=None), wrong, sequence, 21)
    off = np.abs(np.concatenate([pre, dec]) - reference_run)
    assert off.max() > 500 * TOL


def test_the_training_forward_is_the_reference_too(params, sequence,
                                                   reference_run):
    """``MoeLmModel.apply`` without a cache: the exactly-masked oracle
    with the sink and the narrower value head."""
    logits = moe.MoeLmModel(TINY).apply(
        {"params": params}, jnp.asarray(sequence)[None])[0]
    np.testing.assert_allclose(np.asarray(logits), reference_run,
                               atol=TOL, rtol=0)


# -- the paged kernel ---------------------------------------------------------

def _paged_case(heads, kvh, hd, vd, bs, *, window=None, q_len=1, block0=0,
                sink=True, seed=0, lanes=3):
    """Pools of ``kvh`` heads of ``hd`` keys beside ``vd`` values in
    blocks of ``bs``, ragged lengths (one lane empty, one long), a
    table of its own blocks a lane (a ring of 3 blocks past the
    window's under ``window``), ``block0`` blocks of another layer in
    front."""
    rng = np.random.default_rng(seed)
    n_blk = (-(-(window + q_len - 1) // bs) + 1) if window else 10
    cache_len = 40 * bs if window else n_blk * bs
    nb = block0 + 1 + lanes * n_blk
    k_pool = jnp.asarray(rng.standard_normal((nb, bs, kvh * hd)),
                         jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((nb, bs, kvh * vd)),
                         jnp.float32)
    table = jnp.asarray(1 + np.arange(lanes * n_blk).reshape(lanes, n_blk),
                        jnp.int32)
    lengths = jnp.asarray(
        [0, cache_len - q_len] + rng.integers(
            1, cache_len - q_len, lanes - 2).tolist(), jnp.int32)
    q = jnp.asarray(rng.standard_normal((lanes, q_len, heads, hd)),
                    jnp.float32)
    sinks = (jnp.asarray(rng.standard_normal(heads) + 1.0, jnp.float32)
             if sink else None)
    return (q, k_pool, v_pool, table, lengths), dict(
        cache_len=cache_len, window=window, block0=block0,
        sink_logits=sinks)


PAGED = {
    # test size: rows narrower than a lane tile, a head's own columns
    "tiny-full": dict(heads=4, kvh=1, hd=24, vd=16, bs=4),
    "tiny-window": dict(heads=4, kvh=2, hd=24, vd=16, bs=4, window=8),
    "tiny-window-q3": dict(heads=4, kvh=2, hd=24, vd=16, bs=4, window=8,
                           q_len=3),
    "tiny-block0": dict(heads=4, kvh=2, hd=24, vd=16, bs=4, block0=31),
    # a key head of 192 in a row of whole lane tiles: the tiles that
    # cover a head, queries padded over the neighbour's columns
    "k192-full": dict(heads=8, kvh=4, hd=192, vd=128, bs=16, sink=False),
    "k192-window": dict(heads=8, kvh=2, hd=192, vd=128, bs=16, window=20),
    "k192-block0-q2": dict(heads=4, kvh=2, hd=192, vd=128, bs=16,
                           block0=7, q_len=2),
}


@pytest.mark.parametrize("case", sorted(PAGED))
def test_paged_kernel_with_its_own_value_width_and_sinks_equals_its_reference(
        case):
    args, kw = _paged_case(**PAGED[case], seed=len(case))
    want = pk.paged_attention_reference(*args, **kw)
    got = pk.paged_attention(*args, interpret=True, **kw)
    spec = PAGED[case]
    assert got.shape == want.shape == (
        3, spec.get("q_len", 1), spec["heads"], spec["vd"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-6, rtol=0)
    if kw["sink_logits"] is not None:
        # the sink takes mass: without it the rows are others
        bare = pk.paged_attention_reference(
            *args, **dict(kw, sink_logits=None))
        assert np.abs(np.asarray(bare) - np.asarray(want)).max() > 1e-2


def test_key_spans_are_a_heads_own_columns_or_the_tiles_that_cover_them():
    # whole tiles a head, or a test-size row: a head's own columns
    assert pk._key_spans(4, 128) == tuple((g * 128, 128) for g in range(4))
    assert pk._key_spans(2, 24) == ((0, 24), (24, 24))
    assert pk._key_spans(16, 64) == tuple((g * 64, 64) for g in range(16))
    # 192: heads begin at 0, 192, 384, 576: tiles 0-1, 1-2, 3-4, 4-5
    assert pk._key_spans(4, 192) == ((0, 256), (128, 256), (384, 256),
                                     (512, 256))
    for kvh in (4, 8):
        for g, (col0, width) in enumerate(pk._key_spans(kvh, 192)):
            assert col0 % 128 == 0 and width % 128 == 0
            assert col0 <= g * 192 and (g + 1) * 192 <= col0 + width
            assert col0 + width <= kvh * 192


# -- a sink of -inf is no sink ------------------------------------------------

def test_a_sink_of_minus_infinity_is_no_sink_to_the_bit():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((2, 4, 5, 24)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 4, 40, 24)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 4, 40, 16)), jnp.float32)
    none = jnp.full((4,), -jnp.inf, jnp.float32)
    mask = jnp.asarray(rng.random((2, 1, 5, 40)) < 0.7).at[..., 0].set(True)
    plain = attention.dot_product_attention(q, k, v, mask=mask)
    sunk = attention.dot_product_attention(q, k, v, mask=mask,
                                           sink_logits=none)
    assert np.array_equal(np.asarray(plain), np.asarray(sunk))
    # the tile walk (five tiles of 8 rows), with and without a window
    cache = (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
    start = jnp.asarray([30, 11], jnp.int32)

    def heads(rows):
        return [c.transpose(0, 2, 1, 3) for c in rows]

    for window in (None, 6):
        walked = [attention.prefix_attention(
            q, cache, start, heads, tile=8, window=window, sink_logits=s)
            for s in (None, none)]
        assert np.array_equal(*map(np.asarray, walked))
    # the paged kernel and its reference
    for name in ("tiny-window", "k192-full"):
        args, kw = _paged_case(**dict(PAGED[name], sink=False))
        inf = jnp.full((PAGED[name]["heads"],), -jnp.inf, jnp.float32)
        for fn, extra in ((pk.paged_attention_reference, {}),
                          (pk.paged_attention, {"interpret": True})):
            pair = [fn(*args, **dict(kw, sink_logits=s), **extra)
                    for s in (None, inf)]
            assert np.array_equal(*map(np.asarray, pair)), (name, fn)


def test_a_sink_takes_its_share_of_a_rows_mass():
    """One key with score 0 and a sink of 0: half the mass each, so half
    the value comes out; a sink far below the scores takes nothing."""
    q = jnp.zeros((1, 1, 1, 8), jnp.float32)
    k = jnp.ones((1, 1, 1, 8), jnp.float32)
    v = jnp.full((1, 1, 1, 4), 3.0, jnp.float32)
    half = attention.dot_product_attention(
        q, k, v, sink_logits=jnp.zeros((1,), jnp.float32))
    np.testing.assert_allclose(np.asarray(half), 1.5, rtol=1e-6)
    low = attention.dot_product_attention(
        q, k, v, sink_logits=jnp.full((1,), -80.0, jnp.float32))
    np.testing.assert_allclose(np.asarray(low), 3.0, rtol=1e-6)


@pytest.mark.parametrize("case", ["sink", "value-width"])
def test_the_dense_fallback_of_a_whole_forward_says_so(case):
    """A forward outside the engine with a sink logit or a value head
    of its own size takes the S x S oracle; from ``DENSE_WARN_ROWS``
    queries on it warns, below it does not, and a plain call never
    does."""
    import warnings

    rows = attention.DENSE_WARN_ROWS
    q = jnp.ones((1, 1, rows, 8), jnp.float32)
    v = jnp.ones((1, 1, rows, 8 if case == "sink" else 4), jnp.float32)
    kw = ({"sink_logits": jnp.zeros((1,), jnp.float32)}
          if case == "sink" else {})
    with pytest.warns(UserWarning, match="DENSE"):
        out = attention.multihead_attention_kernel(q, q, v, causal=True,
                                                   **kw)
    assert out.shape == v.shape
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        attention.multihead_attention_kernel(
            q[:, :, :rows // 2], q[:, :, :rows // 2], v[:, :, :rows // 2],
            causal=True, **kw)
        attention.multihead_attention_kernel(q, q, q, causal=True,
                                             force_reference=True)


# -- the share -----------------------------------------------------------------

def test_sixteen_shares_of_an_expert_layer_add_up_to_the_uncut_layer(
        params):
    """Ranks 0-15 of sixteen chips hold one expert each of a router of
    16 (the tiny layer's 8 kernels twice over, a router twice as wide).
    What the program's layer gives for each share adds up to the
    reference's uncut layer: no shared expert is counted, there is
    none."""
    layer = params["layer_2"]["moe"]
    rng = np.random.default_rng(5)
    wide = {
        "router": {"kernel": jnp.asarray(
            rng.standard_normal((64, 16)) / 8.0, jnp.float32)},
        "bias": jnp.asarray(0.02 * rng.standard_normal(16), jnp.float32),
        "experts": jax.tree.map(
            lambda kernel: jnp.concatenate([kernel, -kernel[::-1]]),
            layer["experts"])}
    x = jnp.asarray(rng.standard_normal((1, 24, 64)), jnp.float32)
    cfg_file = cfg_file_of(TINY)
    whole = np.asarray(reference.expert_layer(x[0], wide, cfg_file))
    total = np.zeros_like(whole)
    for rank in range(16):
        cfg = dataclasses.replace(TINY, num_experts=16, experts_held=1,
                                  experts_offset=rank)
        mine = dict(wide, experts=jax.tree.map(
            lambda kernel: kernel[rank:rank + 1], wide["experts"]))
        y = np.asarray(moe.MoEMlpBlock(cfg).apply({"params": mine}, x)[0])
        want = np.asarray(reference.expert_layer(
            x[0], mine, dict(cfg_file, experts_offset=rank)))
        np.testing.assert_allclose(y, want, atol=2e-5, rtol=0)
        total += y
    np.testing.assert_allclose(total, whole, atol=5e-5, rtol=0)
    # and no share is the whole: the experts elsewhere add something
    assert np.abs(total - y - whole).max() > 1e-3


# -- one way to say a pattern --------------------------------------------------

def _lead_of_one(cfg):
    """``cfg``'s period said as a lead of its first kind and the period
    rotated by one: the same kind for every layer."""
    return dataclasses.replace(
        cfg, attn_lead=cfg.attn_period[:1],
        attn_period=cfg.attn_period[1:] + cfg.attn_period[:1])


@pytest.mark.parametrize("preset", ["laguna_tiny", "ling_tiny"])
def test_a_pattern_with_a_lead_is_the_pattern_without_to_the_bit(preset):
    """The presets that were there say their patterns as before (no
    lead), and ``attn_kind`` is theirs layer by layer; said with a lead
    the same layers give the same logits, to the last bit."""
    cfg = moe.MOE_PRESETS[preset]
    assert cfg.attn_lead == () and cfg.attn_period
    period = cfg.attn_period
    assert [cfg.attn_kind(i) for i in range(48)] == [
        period[i % len(period)] for i in range(48)]
    led = _lead_of_one(cfg)
    assert [led.attn_kind(i) for i in range(48)] == [
        cfg.attn_kind(i) for i in range(48)]
    assert led.attn_window == cfg.attn_window
    assert led.recurrent_layers == cfg.recurrent_layers
    tree = weights.make_params(serve_family.moe_param_shapes(cfg), SEED,
                               jnp.float32)
    toks = jnp.asarray(np.random.default_rng(2).integers(3, 256, (1, 24)))
    ours, theirs = (np.asarray(moe.MoeLmModel(c).apply({"params": tree},
                                                       toks))
                    for c in (led, cfg))
    assert np.array_equal(ours, theirs)


def test_the_kinds_that_were_there_keep_their_five_fields():
    """``serve_pattern.kind_of`` compares ``dataclasses.astuple`` of a
    Laguna layer's kind with five values; the KV heads and the sink of
    a kind are ``KvKind``'s, a subclass."""
    for preset in ("laguna_s21", "laguna_tiny", "ling3_flash", "ling_tiny"):
        for kind in moe.MOE_PRESETS[preset].attn_period:
            assert len(dataclasses.astuple(kind)) == 5
            assert not isinstance(kind, moe.KvKind)
    kind = TINY.attn_kind(1)
    assert dataclasses.astuple(kind) == (4, 8, 10_000.0, 0.334, None, 2,
                                         True)
    assert kind.kind == "softmax" and isinstance(kind, moe.AttnKind)
    with pytest.raises(ValueError, match="attn_lead"):
        moe.MoeLmModel(dataclasses.replace(
            moe.MOE_PRESETS["moe_tiny"],
            attn_lead=(moe.AttnKind(num_heads=4),))).init(
                jax.random.key(0), jnp.zeros((1, 4), jnp.int32))


# -- the engine ----------------------------------------------------------------

@pytest.fixture(scope="module")
def served(params):
    """An engine that served six requests on two slots, with what it
    recorded; its outputs."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 256, n).tolist() for n in (5, 23, 37, 12,
                                                          30, 9)]
    eng = ServingEngine(TINY, params, slots=2, cache_len=96, chunk=4,
                        prefill_chunk=8, kv_block_size=4)
    seq0 = events.get_recorder().events_after(0)[0]
    rids = [eng.submit(p, 24) for p in prompts]
    out = eng.run()
    recorded = events.get_recorder().events_after(seq0)[1]
    return eng, prompts, [out[r] for r in rids], recorded


def test_the_engine_serves_the_references_greedy_tokens(params, served):
    """Every served token is the reference's first choice at its
    position (float32 on both sides: a near-tie apart)."""
    _, prompts, outs, _ = served
    cfg_file = cfg_file_of(TINY)
    for prompt, got in zip(prompts, outs):
        gaps = reference.served_gaps(params, cfg_file, prompt,
                                     got[len(prompt):])
        assert len(gaps) == 24 and gaps.max() < 1e-4


def test_steps_count_both_kinds_of_walk_at_a_ring_of_three(served):
    """``engine/step`` states ``kv_blocks`` and ``kv_window_blocks`` by
    the kernel's own walk rule, the window's bounded by the ring
    whatever the lanes hold; a retired lane leaves both kinds of pool
    as it found them."""
    eng, prompts, outs, recorded = served
    for name, _, _, _, _, attrs in recorded:
        assert events.in_contract(name), name
        assert set(attrs or ()) <= events.contract_attrs(name), (name, attrs)
    steps = [e[5] for e in recorded
             if e[0] == "engine/step" and e[5].get("lanes")]
    assert steps
    per_lane = -(-(8 + 1) // 4) + 1           # window 8, blocks of 4
    for s in steps:
        assert 0 < s["kv_window_blocks"] <= per_lane * eng.slots
        assert s["kv_window_blocks"] <= s["kv_blocks"]
        assert s["kv_bytes"] == s["kv_blocks"] * 2 * 4 * 40 * 4
    assert any(s["kv_window_blocks"] < s["kv_blocks"] for s in steps)
    assert {e[5]["pool"] for e in recorded
            if e[0] == "kv/alloc"} == {"full", "window"}
    eng._flush_stale_lanes()
    assert eng._kv_pool.free_blocks() == eng._kv_pool.n_blocks
    rids = [eng.submit(p, 24) for p in prompts]
    again = eng.run()
    assert [again[r] for r in rids] == outs


def test_the_engine_serves_the_same_tokens_with_the_walk_as_the_kernel(
        params, served, flash_interpreted):
    """``prefix_flash_attention`` (interpreted; blocks of half a piece
    against tiles of a piece) under the pieces of both kinds of layer,
    keys of 24 beside values of 16, a window of 8 from a sink: the
    served tokens are the XLA walk's, and every ``prefill/piece`` says
    that all seven layers ran it (none of them before)."""
    _, prompts, outs, recorded = served
    assert {e[5]["flash_layers"] for e in recorded
            if e[0] == "prefill/piece"} == {0}
    traced = flash_interpreted(4, 8)
    eng = ServingEngine(TINY, params, slots=2, cache_len=96, chunk=4,
                        prefill_chunk=8, kv_block_size=4)
    seq0 = events.get_recorder().events_after(0)[0]
    rids = [eng.submit(p, 24) for p in prompts]
    out = eng.run()
    assert [out[r] for r in rids] == outs
    assert traced and set(traced) <= {8, 16, 32}
    pieces = [e[5] for e in events.get_recorder().events_after(seq0)[1]
              if e[0] == "prefill/piece"]
    assert pieces and {p["flash_layers"] for p in pieces} == {
        TINY.num_layers}


def test_refusals_tell_sink_rows_from_a_sink_logit(params):
    """StreamingLLM sinks (rows kept past the window) and a
    LlamaConfig's one global window stay with ``generate()``, and the
    message says that a learned sink logit is another thing; what an
    engine with window layers refused it still refuses."""
    from tensorflow_train_distributed_tpu.models import llama

    tiny = llama.LLAMA_PRESETS["llama_tiny"]
    for bent in (dataclasses.replace(tiny, sliding_window=8),
                 dataclasses.replace(tiny, sliding_window=8,
                                     attention_sinks=2)):
        with pytest.raises(ValueError, match="sink LOGIT") as err:
            ServingEngine(bent, {}, slots=1)
        assert "StreamingLLM" in str(err.value)
        assert "attn_period" in str(err.value)
    with pytest.raises(ValueError, match="window layers"):
        ServingEngine(TINY, params, slots=1, cache_len=64,
                      draft_config=TINY, draft_params=params,
                      speculative_k=2)
    eng = ServingEngine(TINY, params, slots=2, cache_len=96, chunk=4,
                        prefill_chunk=8, kv_block_size=4)
    with pytest.raises(ValueError, match="behind the window"):
        eng.preload_prefix(list(range(3, 19)))
    assert eng.export_prefix_kv(list(range(3, 40))) is None
    # the rolling cache of ``generate()`` takes no sink logit, and says so
    from tensorflow_train_distributed_tpu.models.generate import generate

    with pytest.raises(ValueError, match="rolling window cache"):
        generate(TINY, params, jnp.asarray([list(range(3, 15))]), 8)
