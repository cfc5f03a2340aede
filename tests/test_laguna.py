"""Window and full attention layers side by side (``laguna``) at test
size: ``MOE_PRESETS["laguna_tiny"]`` through the serving engine's own
programs (prefill in pieces on the batch-1 cache, the insert into both
kinds of pool, paged decode past the window and past a ring turn)
against the plain reference ``benchmark/references/laguna.py``; each
stage of the block dropped or bent is seen; the paged kernel with a
first block against its ``jnp`` reference at 6 and 9 queries a KV head;
the tile walk with a first tile; the four shares of an expert layer add
up to the uncut layer; a lane that retires leaves both kinds of pool as
it found them; nothing is shared, exported or preloaded beside window
layers; the engine equals ``generate()``."""

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

from benchmark.harness import serve_family, weights  # noqa: E402
from benchmark.references import laguna as reference  # noqa: E402
from tensorflow_train_distributed_tpu.models import moe  # noqa: E402
from tensorflow_train_distributed_tpu.models.generate import (  # noqa: E402
    generate,
)
from tensorflow_train_distributed_tpu.ops import attention  # noqa: E402
from tensorflow_train_distributed_tpu.ops import (  # noqa: E402
    pallas_kernels as pk,
)
from tensorflow_train_distributed_tpu.runtime import events  # noqa: E402
from tensorflow_train_distributed_tpu.serving import (  # noqa: E402
    ServingEngine,
)

TINY = moe.MOE_PRESETS["laguna_tiny"]
SEED = 2 ** 31 + 34


def cfg_file_of(cfg, **over):
    """The configuration-file keys the reference reads, for a program
    config of this family (the source's own names)."""
    full, window = cfg.attn_period[0], cfg.attn_period[1]
    _, factor, fast, slow, old, att = full.rope_scaling
    kinds = [cfg.attn_kind(i) for i in range(cfg.num_layers)]
    out = {
        "num_hidden_layers": cfg.num_layers, "head_dim": cfg.head_dim,
        "num_key_value_heads": cfg.num_kv_heads,
        "rms_norm_eps": cfg.rms_epsilon,
        "num_experts_per_tok": cfg.top_k, "norm_topk_prob": True,
        "moe_routed_scaling_factor": cfg.routed_scaling,
        "sliding_window": window.window,
        "experts_offset": cfg.experts_offset,
        "layer_types": ["sliding_attention" if k.window else
                        "full_attention" for k in kinds],
        "mlp_layer_types": ["dense" if i < cfg.dense_layers else "sparse"
                            for i in range(cfg.num_layers)],
        "gating_types": ["per_head"] * cfg.num_layers,
        "num_attention_heads_per_layer": [k.num_heads for k in kinds],
        "rope_parameters": {
            "full_attention": {
                "rope_theta": full.rope_base, "rope_type": "yarn",
                "factor": factor, "beta_fast": fast, "beta_slow": slow,
                "original_max_position_embeddings": old,
                "attention_factor": att,
                "partial_rotary_factor": full.rotary_share},
            "sliding_attention": {
                "rope_theta": window.rope_base, "rope_type": "default",
                "partial_rotary_factor": window.rotary_share}},
    }
    out.update(over)
    return out


@pytest.fixture(scope="module")
def params():
    return weights.make_params(serve_family.moe_param_shapes(TINY), SEED,
                               jnp.float32)


def program_logits(cfg, params, seq, n_prompt, *, piece=8, cache_len=96,
                   block=4):
    """Float32 logits [len(seq), V] of the ENGINE's programs over
    ``seq``: the prompt in ``piece``-token pieces on the batch-1 linear
    cache, ``_paged_insert`` into lane 1 of a two-lane grid (lane 0
    idles), then one paged decode step a token, teacher-forced."""
    eng = ServingEngine(cfg, params, slots=2, chunk=4, cache_len=cache_len,
                        kv_block_size=block, prefill_chunk=piece)
    variables = eng._variables
    cache_1 = eng._fresh_cache(1)
    padded = np.zeros(-(-n_prompt // piece) * piece, np.int32)
    padded[:n_prompt] = seq[:n_prompt]
    got = []
    for i in range(len(padded) // piece):
        logits, vs = eng._prefill_model.apply(
            dict(variables, cache=cache_1),
            jnp.asarray(padded[None, i * piece:(i + 1) * piece]),
            mutable=["cache"])
        cache_1 = vs["cache"]
        got.append(np.asarray(logits[0]))
    kv = eng._kv_claim(0, [int(t) for t in seq[:n_prompt]],
                       len(seq) - n_prompt)
    cache = eng._paged_insert(
        eng._fresh_cache(2, grid=True), cache_1, jnp.int32(1),
        eng._kv_table(kv), jnp.int32(0), jnp.int32(n_prompt))

    @jax.jit
    def decode(cache, toks):
        def step(cache, t):
            logits, upd = eng._model.apply(
                dict(variables, cache=cache),
                jnp.stack([jnp.int32(3), t])[:, None],
                mutable=["cache", "moe_stats", "attn_stats"])
            return upd["cache"], logits[1, -1]
        return jax.lax.scan(step, cache, toks)

    _, dec = decode(cache, jnp.asarray(seq[n_prompt:]))
    return eng, np.concatenate(got)[:n_prompt], np.asarray(dec)


def reference_logits(params, cfg_file, seq):
    return np.asarray(reference.logits_at(
        params, cfg_file, [int(t) for t in seq], list(range(len(seq)))))


@pytest.fixture(scope="module")
def sequence():
    return np.random.default_rng(34).integers(3, 256, 61).astype(np.int32)


@pytest.fixture(scope="module")
def reference_run(params, sequence):
    return reference_logits(params, cfg_file_of(TINY), sequence)


# float32 on both sides, logits of a few units: what is left is the
# order of float32 sums (blocks of queries and tiles of rows against
# one softmax over every key): a few 1e-6.  Any stage dropped or bent
# moves a logit by 1e-2 or more.
TOL = 2e-5


def test_pieces_then_paged_decode_agree_with_the_reference(
        params, sequence, reference_run):
    """21 prompt tokens in three pieces of 8, then 40 paged decode
    steps: the window is 8 rows and the ring 3 blocks of 4 = 12 rows
    (the window's two blocks and one for a window that starts
    mid-block), so decode runs 32 rows past the window and past three
    ring turns."""
    eng, pre, dec = program_logits(TINY, params, sequence, 21)
    assert (eng._window, eng._ring_blocks) == (8, 3)
    ours = np.concatenate([pre, dec])
    assert ours.shape == reference_run.shape == (61, 256)
    np.testing.assert_allclose(ours, reference_run, atol=TOL, rtol=0)
    assert (ours.argmax(-1) == reference_run.argmax(-1)).all()


def _all_full(cfg):
    return dataclasses.replace(cfg, attn_period=tuple(
        dataclasses.replace(k, window=None) for k in cfg.attn_period))


def _whole_rotary(cfg):
    return dataclasses.replace(cfg, attn_period=tuple(
        dataclasses.replace(k, rotary_share=1.0) for k in cfg.attn_period))


def _unscaled_rotary(cfg):
    return dataclasses.replace(cfg, attn_period=tuple(
        dataclasses.replace(k, rope_scaling=k.rope_scaling
                            and k.rope_scaling[:5])
        for k in cfg.attn_period))


BENT = {
    "no-window-mask": _all_full,
    "no-gate": lambda cfg: dataclasses.replace(cfg, attn_gate=False),
    "whole-head-rotary-on-a-full-layer": _whole_rotary,
    "no-attention-factor": _unscaled_rotary,
    "unscaled-gates": lambda cfg: dataclasses.replace(
        cfg, routed_scaling=1.0),
}


@pytest.mark.parametrize("bend", sorted(BENT))
def test_a_stage_dropped_from_the_program_is_seen(
        bend, params, sequence, reference_run):
    """The same weights through a program with one stage bent: logits
    leave the reference by far more than rounding, in decode (past the
    window for the mask) if not before."""
    _, pre, dec = program_logits(BENT[bend](TINY), params, sequence, 21)
    off = np.abs(np.concatenate([pre, dec]) - reference_run)
    assert off.max() > 500 * TOL, (bend, off.max())
    if bend == "no-window-mask":
        # the first 8 rows see the same keys either way
        assert off[:8].max() < TOL < off[8:].max()


def test_the_full_layers_head_count_on_a_window_layer_is_seen(
        params, sequence, reference_run):
    """A program that gave the window layers the full layers' 4 query
    heads (the first 4 of their 6: the query, gate and out kernels cut
    to them) is another model."""
    def cut(tree, layer):
        a = dict(tree[layer]["attention"])
        a["query"] = {"kernel": a["query"]["kernel"][:, :4 * 16]}
        a["gate"] = {"kernel": a["gate"]["kernel"][:, :4]}
        a["out"] = {"kernel": a["out"]["kernel"][:4 * 16]}
        return dict(tree[layer], attention=a)

    wrong = dict(params)
    for i in (1, 2, 3):
        wrong[f"layer_{i}"] = cut(params, f"layer_{i}")
    cfg = dataclasses.replace(TINY, attn_period=tuple(
        dataclasses.replace(k, num_heads=4) for k in TINY.attn_period))
    _, pre, dec = program_logits(cfg, wrong, sequence, 21)
    off = np.abs(np.concatenate([pre, dec]) - reference_run)
    assert off.max() > 500 * TOL


def _ring_case(heads, kvh, q_len, seed):
    """Lanes of a ring of 4 blocks of 4 rows (window 10: the engine's
    own size, ``ceil((window + q_len - 1) / 4) + 1``, no block to
    spare) holding 0 (a reset lane), fewer rows than the window, just
    over a ring, and several ring turns; the pools random, so a row
    read from the wrong place is seen."""
    hd, bs, ring, window, cache_len = 16, 4, 4, 10, 200
    assert ring == -(-(window + q_len - 1) // bs) + 1
    lengths = np.array([0, 5, 23, 97, 150], np.int32)
    lanes = len(lengths)
    rng = np.random.default_rng(seed)
    nb = 1 + lanes * ring
    k_pool = rng.standard_normal((nb, bs, kvh * hd)).astype(np.float32)
    v_pool = rng.standard_normal((nb, bs, kvh * hd)).astype(np.float32)
    table = (1 + np.arange(lanes)[:, None] * ring
             + np.arange(ring)[None]).astype(np.int32)
    table[0] = 0                                # points at scratch
    q = rng.standard_normal((lanes, q_len, heads, hd)).astype(np.float32)
    return (q, k_pool, v_pool, table, lengths), dict(
        window=window, cache_len=cache_len), (hd, bs, ring)


@pytest.mark.parametrize("q_len", [1, 3])
@pytest.mark.parametrize("heads, kvh", [(12, 2), (18, 2)],
                         ids=["6-queries-a-kv-head", "9-queries-a-kv-head"])
def test_paged_kernel_with_a_first_block_equals_its_reference(
        heads, kvh, q_len):
    """The kernel (interpreted) over rings against the ``jnp``
    reference, which gathers every ring whole and masks by the position
    each row holds; and against plain attention over the lane's own
    window of rows, laid out by hand."""
    args, kw, (hd, bs, ring) = _ring_case(heads, kvh, q_len, 7 + q_len)
    want = pk.paged_attention_reference(*map(jnp.asarray, args), **kw)
    got = pk.paged_attention(*map(jnp.asarray, args), **kw,
                             use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=0)
    # By hand, lane 3 (97 rows held, several ring turns): query i at
    # position 97 + i sees positions (97 + i - 10, 97 + i].
    q, k_pool, v_pool, table, lengths = args
    lane, cur = 3, int(lengths[3])
    rows = ring * bs

    def row_of(pool, p):
        return pool[table[lane, (p // bs) % ring], p % bs].reshape(kvh, hd)

    for i in range(q_len):
        seen = [p for p in range(cur + i - 9, cur + i + 1) if p >= 0]
        assert len(seen) == 10 and cur + i - min(seen) < rows
        k = np.stack([row_of(k_pool, p) for p in seen])      # [10, kvh, hd]
        v = np.stack([row_of(v_pool, p) for p in seen])
        for h in range(heads):
            g = h // (heads // kvh)
            s = k[:, g] @ q[lane, i, h] / np.sqrt(hd)
            p = np.exp(s - s.max())
            np.testing.assert_allclose(
                np.asarray(want)[lane, i, h], (p / p.sum()) @ v[:, g],
                atol=2e-5)


def test_the_walk_rule_starts_at_the_windows_first_block():
    """``paged_blocks_walked`` under a window counts from
    ``paged_first_block``: bounded by the window whatever a lane holds,
    the same numbers for a numpy vector (the host's counter) and for a
    traced scalar (the kernel); without a window it is what it was."""
    lengths = np.array([0, 1, 15, 16, 511, 512, 513, 5000, 17000], np.int64)
    plain = pk.paged_blocks_walked(lengths, 1, 16, 1088)
    np.testing.assert_array_equal(plain, (lengths + 16) // 16)
    first = pk.paged_first_block(lengths, 16, 512)
    walked = pk.paged_blocks_walked(lengths, 1, 16, 1088, 512)
    np.testing.assert_array_equal(
        first, [0, 0, 0, 0, 0, 0, 0, (5000 - 511) // 16, (17000 - 511) // 16])
    np.testing.assert_array_equal(walked, plain - first)
    # 512 rows from mid-block reach into 33 blocks; never more.
    assert walked.max() == 33
    assert all(int(pk.paged_blocks_walked(jnp.int32(n), 1, 16, 1088, 512))
               == w for n, w in zip(lengths, walked))
    assert pk.paged_first_block(lengths, 16, None) == 0
    # an overrun lane still reads one block
    assert int(pk.paged_blocks_walked(np.int64(10 ** 6), 1, 16, 1088,
                                      512)) == 1


@pytest.mark.parametrize("start", [0, 5, 16, 37, 100])
def test_the_tile_walk_with_a_first_tile_equals_masked_attention(start):
    """``prefix_attention(window=)`` over a linear cache in tiles of 16
    rows against one softmax over every key under the same mask; the
    tiles before ``prefix_first_tile`` are not read (filled with NaN
    here: a read would poison the sum)."""
    b, h, q_len, hd, cache_len, tile, window = 1, 3, 8, 16, 128, 16, 20
    rng = np.random.default_rng(start)
    q = jnp.asarray(rng.standard_normal((b, h, q_len, hd)), jnp.float32)
    k = rng.standard_normal((b, cache_len, h, hd)).astype(np.float32)
    v = rng.standard_normal((b, cache_len, h, hd)).astype(np.float32)
    first = int(attention.prefix_first_tile(np.int64(start), tile, window))
    last = int(attention.prefix_tiles_walked(
        np.array([start]), q_len, tile, cache_len))
    assert first == max(start - window + 1, 0) // tile
    assert last - first <= -(-(window + q_len) // tile) + 1
    k[:, :first * tile] = np.nan
    v[:, :first * tile] = np.nan
    k[:, last * tile:] = np.nan
    v[:, last * tile:] = np.nan

    def heads(rows):
        return [r.transpose(0, 2, 1, 3) for r in rows[:2]]

    got = attention.prefix_attention(
        q, (jnp.asarray(k), jnp.asarray(v), None), jnp.array([start]),
        heads, tile=tile, window=window)
    pos = start + np.arange(q_len)
    kv_pos = np.arange(cache_len)
    ok = ((kv_pos[None] <= pos[:, None])
          & (pos[:, None] - kv_pos[None] < window))
    want = attention.dot_product_attention(
        q, *heads((jnp.nan_to_num(k), jnp.nan_to_num(v))),
        mask=jnp.asarray(ok)[None, None])
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=0)


def _ling_share():
    """The same test for the family whose router chooses within groups
    (``ling_tiny``: 8 experts in 4 groups of 2, 2 groups stay): its
    preset, seeded weights, reference and file keys."""
    import test_ling

    plain = weights.make_params(
        serve_family.moe_param_shapes(test_ling.TINY), SEED, jnp.float32)
    return test_ling.TINY, plain, test_ling.reference, test_ling.cfg_file_of


SHARES = {"laguna": lambda params: (TINY, params, reference, cfg_file_of),
          "ling": lambda params: _ling_share()}


@pytest.mark.parametrize("family", sorted(SHARES))
def test_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer(
        params, family):
    """Ranks 0-3 of four chips hold experts [0,2) [2,4) [4,6) [6,8) of
    the router's 8.  What the program's layer gives for each share,
    with the shared expert (which every chip computes alike) counted
    once, adds up to the reference's uncut layer."""
    tiny, tree, ref, file_of = SHARES[family](params)
    layer = tree["layer_2"]["moe"]
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((1, 24, 64)), jnp.float32)
    cfg_file = file_of(tiny)
    whole = np.asarray(ref.expert_layer(x[0], layer, cfg_file))
    shared = np.asarray(ref.swiglu(x[0], layer["shared_mlp"]))
    total = np.zeros_like(whole)
    for rank in range(4):
        cfg = dataclasses.replace(tiny, experts_held=2,
                                  experts_offset=2 * rank)
        mine = dict(layer, experts=jax.tree.map(
            lambda kernel: kernel[2 * rank:2 * rank + 2], layer["experts"]))
        y = np.asarray(moe.MoEMlpBlock(cfg).apply({"params": mine}, x)[0])
        want = np.asarray(ref.expert_layer(
            x[0], mine, dict(cfg_file, experts_offset=2 * rank)))
        np.testing.assert_allclose(y, want, atol=2e-5, rtol=0)
        total += y - shared
    np.testing.assert_allclose(total + shared, whole, atol=5e-5, rtol=0)
    # and no share is the whole: the experts elsewhere add something
    assert np.abs(total + shared - (y - shared) - whole).max() > 1e-3


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


def test_engine_equals_generate(params, served):
    _, prompts, outs, _ = served
    for prompt, got in zip(prompts, outs):
        want = np.asarray(generate(TINY, params, jnp.asarray([prompt]),
                                   24))[0].tolist()
        assert got == want


def test_a_lane_that_retires_leaves_both_kinds_of_pool_as_it_found_them(
        params, served):
    """Every block back on the free list, both kinds of table pointing
    at the scratch block, every index zero; the engine then serves the
    same requests to the same tokens out of the same rings."""
    eng, prompts, outs, _ = served
    eng._flush_stale_lanes()
    assert eng._kv_pool.free_blocks() == eng._kv_pool.n_blocks
    assert eng.kv_blocks_in_use() == 0
    flat = {eng._path_key(p): np.asarray(leaf) for p, leaf
            in jax.tree_util.tree_flatten_with_path(eng._cache)[0]}
    tables = {k: v for k, v in flat.items()
              if k[-1] in ("block_table", "window_table")}
    assert sorted(k[-1] for k in tables).count("window_table") == 3
    assert sorted(k[-1] for k in tables).count("block_table") == 2
    assert all((t == 0).all() for t in tables.values())
    assert all((v == 0).all() for k, v in flat.items() if k[-1] == "index")
    # A window layer's pool is its rings and the scratch block, bounded
    # by the window; a full layer's the allocator's blocks.
    pools = {k: v.shape for k, v in flat.items() if k[-1] == "key_pool"}
    assert sorted(s[0] for s in pools.values()) == [
        1 + 2 * 3] * 3 + [1 + 2 * 24] * 2
    # layers x (keys, values) x blocks x rows x a row's float32 values
    assert eng._kv_ring_bytes == 3 * 2 * 7 * 4 * 32 * 4
    assert eng.kv_pool_bytes() == (eng._kv_ring_bytes
                                   + 2 * 2 * 49 * 4 * 32 * 4)
    rids = [eng.submit(p, 24) for p in prompts]
    again = eng.run()
    assert [again[r] for r in rids] == outs


def test_steps_and_pieces_count_the_window_layers_walk(served):
    """``engine/step`` states ``kv_window_blocks`` by the kernel's rule
    from the window's first block on, bounded by the window whatever
    the lanes hold; ``prefill/piece`` states ``window_rows``; both are
    the contract's, as is ``kv/alloc``'s kind of pool."""
    eng, _, _, recorded = served
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
    assert any(s["kv_window_blocks"] < s["kv_blocks"] for s in steps)
    pieces = [e[5] for e in recorded if e[0] == "prefill/piece"]
    assert pieces and all(
        0 < p["window_rows"] <= p["rows"] <= p["cache_rows"]
        for p in pieces)
    kinds = {e[5]["pool"] for e in recorded if e[0] == "kv/alloc"}
    assert kinds == {"full", "window"}


def test_nothing_is_shared_exported_or_preloaded_beside_window_layers(
        params):
    """Rows behind a window are gone, so the engine takes no radix
    match, ships no KV (the receiver prefills), installs none, and
    ``preload_prefix`` raises with the reason; reset, insert and the
    pairing of pools with the batch-1 cache cover every row leaf of
    both kinds."""
    eng = ServingEngine(TINY, params, slots=2, cache_len=96, chunk=4,
                        prefill_chunk=8, kv_block_size=4)
    prompt = list(range(3, 40))
    with pytest.raises(ValueError, match="behind the window"):
        eng.preload_prefix(prompt[:16])
    first = eng.submit(prompt, 6)
    done = eng.run()
    # the same prompt again: nothing matched, everything prefilled
    second = eng.submit(prompt, 6)
    while eng.pending():
        done.update(eng.serve_step())
        live = [s for s in eng._slot_states if s is not None]
        if live:
            meta, blob = eng.export_lane(live[0].request_id)
            assert meta["kind"] == "lane" and meta["kv"] is None
            assert blob == b""
    assert eng.kv_stats["prefix_hits"] == 0
    assert done[first] == done[second]
    assert eng.export_prefix_kv(prompt) is None
    assert eng.install_prefix_kv({"tokens": prompt[:16], "n": 16,
                                  "leaves": []}, b"") == 0
    grid = eng._cache_struct(2, grid=True)
    pairs = eng._paired_leaves(grid, eng._cache_struct(1))
    assert len(pairs) == 2 * 5              # a key and a value pool a layer
    assert len(eng._ringed_modules(grid)) == 3
    stale = eng._reset_lanes(jax.tree.map(jnp.ones_like, eng._cache),
                             jnp.asarray([True, False]))
    for p, leaf in jax.tree_util.tree_flatten_with_path(stale)[0]:
        if eng._path_key(p)[-1] in ("block_table", "window_table", "index"):
            leaf = np.asarray(leaf)
            assert (leaf[0] == 0).all() and (leaf[1] == 1).all()


def test_refusals_say_what_is_served(params):
    """A LlamaConfig's one global window and attention sinks stay with
    ``generate()``; a draft beside window layers is not served yet."""
    from tensorflow_train_distributed_tpu.models import llama

    dense = dataclasses.replace(llama.LLAMA_PRESETS["llama_tiny"],
                                sliding_window=8)
    with pytest.raises(ValueError, match="attn_period"):
        ServingEngine(dense, {}, slots=1)
    with pytest.raises(ValueError, match="window layers"):
        ServingEngine(TINY, params, slots=1, cache_len=64,
                      draft_config=TINY, draft_params=params,
                      speculative_k=2)
    two = dataclasses.replace(TINY, attn_period=(
        TINY.attn_period[0], TINY.attn_period[1],
        dataclasses.replace(TINY.attn_period[1], window=12)))
    with pytest.raises(ValueError, match="several sizes"):
        two.attn_window
