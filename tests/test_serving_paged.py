"""Paged KV cache: block pool, radix sharing, and engine parity.

North star: the engine's outputs are ``generate()``'s token for token
(greedy; seeded sampling where the two draw from the same stream), for
plain and speculative serving, through refills, mid-stream cancel and
staged-prefill interleave; and the pool holds, to the bit, the rows the
batch-1 prefill cache held.  The host allocator (``serving_kv``) is
pinned separately: radix insert/match/evict invariants, copy-on-write
divergence after a shared prefix, and eviction-under-pressure REFUSING
admission rather than corrupting a live lane.

Fast tier: the host-only allocator/radix tests (no device work), one
tiny engine run against ``generate()`` and the pool's programs one by
one.  The full matrix (sampling, speculative, cancel, interleave,
pressure) is slow-tier.
"""

import dataclasses

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from tensorflow_train_distributed_tpu import serving_kv
from tensorflow_train_distributed_tpu.models.llama import (
    LLAMA_PRESETS,
    LlamaModel,
)
from tensorflow_train_distributed_tpu.serving import (
    _LINEAR_ROW_DIMS,
    ServingEngine,
)

CFG = LLAMA_PRESETS["llama_tiny"]


@pytest.fixture(scope="module")
def params():
    return LlamaModel(CFG).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]


# ── fast tier: host-only pool + radix invariants ───────────────────────


def test_pool_alloc_ref_free_cycle():
    pool = serving_kv.KVBlockPool(4, 8)
    assert pool.free_blocks() == 4 and pool.blocks_in_use() == 0
    a = pool.alloc(3)
    assert sorted(a) == [1, 2, 3]          # block 0 is scratch: never
    assert pool.alloc(2) is None           # all-or-nothing
    pool.ref(a[0])
    pool.deref(a[0])
    assert pool.blocks_in_use() == 3       # still lane-held
    for b in a:
        pool.deref(b)
    assert pool.free_blocks() == 4
    with pytest.raises(ValueError, match="free block"):
        pool.deref(a[0])


def test_radix_insert_match_evict_invariants():
    pool = serving_kv.KVBlockPool(8, 2)
    idx = serving_kv.RadixPrefixIndex(pool)
    toks = [1, 2, 3, 4, 5, 6]
    blocks = pool.alloc(3)
    idx.insert(toks, lambda j: blocks[j])
    idx.check_invariants()
    # Match is block-aligned and must leave >= 1 suffix token.
    assert idx.match(toks + [9]) == (6, blocks)
    assert idx.match(toks) == (4, blocks[:2])       # strict-extension cap
    assert idx.match([1, 2, 9, 9, 9])[0] == 2
    assert idx.match([9, 9, 9])[0] == 0
    # Lane releases its refs: blocks become tree-held (cached).
    for b in blocks:
        pool.deref(b)
    assert pool.blocks_in_use() == 3
    # A matching lane re-refs the shared blocks; they are then pinned
    # against eviction.
    m, shared = idx.match(toks + [7])
    for b in shared:
        pool.ref(b)
    assert idx.evict_for(8) < 8            # cannot evict pinned chain
    idx.check_invariants()
    for b in shared:
        pool.deref(b)
    # Fully retired: eviction drains the whole subtree, leaves first.
    evicted = idx.evict_for(8)
    assert evicted == 3 and pool.free_blocks() == 8 and len(idx) == 0
    idx.check_invariants()


def test_radix_eviction_walks_the_tree_once_a_call(monkeypatch):
    """Long retired chains (a prompt of thousands of tokens is hundreds
    of blocks) drain in ONE walk of the tree a call, least recent chain
    first and each chain from its leaf up, stopping at a pinned block
    and as soon as enough is free."""
    pool = serving_kv.KVBlockPool(40, 2)
    idx = serving_kv.RadixPrefixIndex(pool)
    chains = {}
    for first in (1, 2, 3):                     # three 10-block prompts
        toks = [first] * 20
        chains[first] = pool.alloc(10)
        idx.insert(toks, lambda j, b=chains[first]: b[j])
    for blocks in chains.values():
        for blk in blocks:
            pool.deref(blk)
    idx.match([2] * 20 + [9])                   # chain 2 is most recent
    pool.ref(chains[3][4])                      # a lane still reads 3's
    walks = []                                  # first five blocks
    walk = idx._evictable
    monkeypatch.setattr(idx, "_evictable",
                        lambda: walks.append(1) or walk())
    assert pool.free_blocks() == 10
    assert idx.evict_for(10) == 0 and not walks   # enough free already
    assert idx.evict_for(23) == 13 and len(walks) == 1
    # all of chain 1 (least recent), then chain 3 from its leaf up
    assert idx.match([1] * 20 + [9])[0] == 0
    assert idx.match([3] * 20 + [9])[0] == 14
    assert idx.match([2] * 20 + [9])[0] == 20
    # chain 3 stops at its pinned block; chain 2 goes next
    assert idx.evict_for(40) == 12 and pool.free_blocks() == 35
    assert idx.match([3] * 20 + [9])[0] == 10
    idx.check_invariants()


def test_radix_lru_evicts_least_recent_leaf():
    pool = serving_kv.KVBlockPool(4, 2)
    idx = serving_kv.RadixPrefixIndex(pool)
    a = pool.alloc(1)
    idx.insert([1, 1], lambda j: a[j])
    b = pool.alloc(1)
    idx.insert([2, 2], lambda j: b[j])
    for blk in a + b:
        pool.deref(blk)
    idx.match([1, 1, 9])                   # refresh [1, 1]'s recency
    assert idx.evict_for(pool.free_blocks() + 1) == 1
    assert idx.match([2, 2, 9])[0] == 0    # LRU victim was [2, 2]
    assert idx.match([1, 1, 9])[0] == 2
    idx.check_invariants()


def test_radix_dedup_keeps_canonical_block():
    pool = serving_kv.KVBlockPool(4, 2)
    idx = serving_kv.RadixPrefixIndex(pool)
    a = pool.alloc(1)
    assert idx.insert([5, 6], lambda j: a[j]) == 1
    dup = pool.alloc(1)
    # Same chunk from a second lane: existing node stays canonical,
    # nothing new is cached, the duplicate stays lane-owned only.
    assert idx.insert([5, 6], lambda j: dup[j]) == 0
    assert idx.match([5, 6, 7]) == (2, a)
    pool.deref(dup[0])
    assert pool.free_blocks() == 3         # dup freed, a still 2-held
    idx.check_invariants()


def test_lane_kv_table_padding():
    kv = serving_kv.LaneKV(request_id=1, matched=4, shared=[3, 7],
                           owned=[5])
    assert kv.table(5) == [3, 7, 5, 0, 0]
    assert kv.blocks() == [3, 7, 5]


def _ref(params, prompt, max_new, **kw):
    from tensorflow_train_distributed_tpu.models.generate import generate

    return np.asarray(generate(
        CFG, params, jnp.asarray([prompt], jnp.int32), max_new,
        **kw))[0].tolist()


def _serve(params, reqs, *, seeds=None, **kw):
    eng = ServingEngine(CFG, params, **kw)
    seeds = seeds or [None] * len(reqs)
    ids = [eng.submit(p, m, seed=s) for (p, m), s in zip(reqs, seeds)]
    out = eng.run()
    return [out[i] for i in ids], eng


def test_paged_engine_smoke_matches_generate(params):
    """Fast-tier canary: tiny paged engine run, token-identical to
    generate(), with the pool drained back to the radix cache
    afterwards."""
    rng = np.random.default_rng(0)
    reqs = [(list(rng.integers(1, 200, 5)), 4),
            (list(rng.integers(1, 200, 3)), 5)]
    out, eng = _serve(params, reqs, slots=2, cache_len=32, chunk=2,
                      prompt_buckets=(8,), kv_block_size=4)
    for o, (p, m) in zip(out, reqs):
        assert o == _ref(params, p, m)
    # Lanes released; what's in use is exactly the radix-cached blocks.
    assert eng.kv_blocks_in_use() == eng._radix.cached_blocks()
    eng._radix.check_invariants()


def test_step_counts_the_blocks_the_kernel_walks(params):
    """``engine/step``'s ``kv_blocks`` is the attention kernel's rule
    (``ceil((length + queries) / block)`` blocks of a lane's table,
    never none and never more than it has, and one for an idle slot)
    over the host's own lane lengths: for a known set, and as the
    engine records it while it serves."""
    from tensorflow_train_distributed_tpu.runtime import events

    eng = ServingEngine(CFG, params, slots=6, cache_len=32, chunk=2,
                        prompt_buckets=(8,), kv_block_size=4)
    assert eng._kv_nblk_lane == 8

    def counted(held, spec_k=0):
        eng._count_dispatch(held, spec_k)
        return (eng._step_counts["kv_blocks"],
                eng._step_counts["kv_table_blocks"])

    #          blocks: 1  1  1  2  2   8
    assert counted([0, 1, 3, 4, 7, 31]) == (15, 48)
    assert counted([40, 3]) == (8 + 1 + 4, 48)    # overrun: its 8, no more
    assert counted([0, 3, 4]) == (1 + 1 + 2 + 3, 48)      # 3 idle slots
    assert counted([0, 1, 4], spec_k=3) == (1 + 2 + 2 + 3, 48)
    assert counted([]) == (6, 48)

    rec = events.get_recorder()
    seq0 = rec.events_after(0)[0]
    eng.submit([5, 6, 7, 8, 9], 6)
    eng.run()
    steps = [e[5] for e in rec.events_after(seq0)[1]
             if e[0] == "engine/step" and e[5]["lanes"]]
    assert steps
    for attrs in steps:     # one lane of `positions` rows, five idle
        assert attrs["kv_table_blocks"] == 48
        assert attrs["kv_blocks"] == 5 + -(-(attrs["positions"] + 1) // 4)
    assert "kv_blocks" in events.contract_attrs("engine/step")


def test_fused_kill_switch_bitwise_and_attrs(params, monkeypatch):
    """Fast-tier canary for the fused paged-attention plumbing: on CPU
    the fused kernel never engages (``fused_attn()`` False), so the
    default engine and the ``TTD_NO_PALLAS=1`` engine must be BITWISE
    identical — the switch changes dispatch, never math; and
    ``kv_pool_bytes`` reports the pool's device footprint."""
    rng = np.random.default_rng(7)
    reqs = [(list(rng.integers(1, 200, 5)), 4),
            (list(rng.integers(1, 200, 3)), 5)]
    out, eng = _serve(params, reqs, slots=2, cache_len=32, chunk=2,
                      prompt_buckets=(8,), kv_block_size=4)
    assert eng.fused_attn() is False          # CPU: gather path
    assert eng.kv_pool_bytes() > 0
    monkeypatch.setenv("TTD_NO_PALLAS", "1")
    killed, eng_k = _serve(params, reqs, slots=2, cache_len=32, chunk=2,
                           prompt_buckets=(8,), kv_block_size=4)
    assert eng_k.fused_attn() is False
    assert killed == out


ICFG = dataclasses.replace(CFG, kv_cache_int8=True)


def _ref_seeded(cfg, params, prompt, max_new, seed, **sampling):
    """``generate()`` for one prompt; where the engine samples,
    ``generate()``'s own decode model (the shared-index cache) stepped
    here on the engine's stream: token ``i`` of a request is drawn with
    ``fold_in(key(seed), i)`` wherever the request lands, which
    ``generate()``, that splits one key a batch, does not offer."""
    if not sampling:
        return _ref_cfg(cfg, params, prompt, max_new)
    from tensorflow_train_distributed_tpu.models.generate import (
        _decode_model,
        filter_logits,
    )

    model = _decode_model(cfg, cache_len=len(prompt) + max_new)
    tokens, variables = list(prompt), {"params": params}
    feed = jnp.asarray([prompt], jnp.int32)
    for i in range(max_new):
        logits, upd = model.apply(variables, feed, mutable=["cache"])
        variables = dict(variables, cache=upd["cache"])
        tokens.append(int(jax.random.categorical(
            jax.random.fold_in(jax.random.key(seed), i),
            filter_logits(logits[0, -1].astype(jnp.float32),
                          **sampling))))
        feed = jnp.asarray([tokens[-1:]], jnp.int32)
    return tokens


def _serve_cfg(cfg, params, reqs, *, seeds=None, **kw):
    eng = ServingEngine(cfg, params, **kw)
    seeds = seeds or [None] * len(reqs)
    ids = [eng.submit(p, m, seed=s) for (p, m), s in zip(reqs, seeds)]
    out = eng.run()
    return [out[i] for i in ids], eng


def _ref_cfg(cfg, params, prompt, max_new, **kw):
    from tensorflow_train_distributed_tpu.models.generate import generate

    return np.asarray(generate(
        cfg, params, jnp.asarray([prompt], jnp.int32), max_new,
        **kw))[0].tolist()


def test_int8_paged_engine_smoke_matches_generate(params):
    """Fast-tier canary for the int8 paged pool: a kv_cache_int8
    config SERVES through the engine (the old rejection is lifted),
    the pool stores int8 rows + a parallel f32 scale pool, and greedy
    outputs are token-identical to generate() with the same config
    (the linear-cache int8 recipe applied block-wise — same quantized
    bytes, different layout)."""
    rng = np.random.default_rng(1)
    reqs = [(list(rng.integers(1, 200, 5)), 4),
            (list(rng.integers(1, 200, 3)), 5)]
    out, eng = _serve_cfg(ICFG, params, reqs, slots=2, cache_len=32,
                          chunk=2, prompt_buckets=(8,), kv_block_size=4)
    assert eng.kv_cache_int8
    for o, (p, m) in zip(out, reqs):
        assert o == _ref_cfg(ICFG, params, p, m)
    kinds = {p[-1].key: leaf.dtype for p, leaf in
             jax.tree_util.tree_flatten_with_path(eng._cache)[0]}
    assert kinds["key_pool"] == jnp.int8
    assert kinds["value_pool"] == jnp.int8
    assert kinds["kv_pool_scales"] == jnp.float32
    # int8 pool + f32 scales < the fp32 pool it replaces.
    _, eng_fp = _serve_cfg(CFG, params, reqs, slots=2, cache_len=32,
                           chunk=2, prompt_buckets=(8,),
                           kv_block_size=4)
    assert eng.kv_pool_bytes() < eng_fp.kv_pool_bytes()


# ── the depth scan's carried pools against a pool a layer, the ───────
# ── batch-1 cache and generate(), program by program ─────────────────

SCFG = dataclasses.replace(CFG, scan_layers=True)


def _stacked(params):
    """Unscanned parameters in the depth scan's layout: the same
    weights, so the two programs must agree to the bit."""
    import flax.linen as nn

    params = nn.meta.unbox(params)
    layers = [params[f"layer_{i}"] for i in range(CFG.num_layers)]
    rest = {k: v for k, v in params.items() if not k.startswith("layer_")}
    return dict(rest, layers={"stack": {"block": jax.tree.map(
        lambda *a: jnp.stack(a), *layers)}})


def _row_leaves(cache, scanned):
    """name -> [layers, ...] array of a cache tree's row-holding
    leaves, whichever module holds them: the depth scan's one leaf a
    pool, or a leaf a layer stacked here."""
    flat = {ServingEngine._path_key(p): np.asarray(leaf) for p, leaf
            in jax.tree_util.tree_flatten_with_path(cache)[0]}
    names = {k[-1] for k in flat} - {"index", "block_table"}
    if scanned:
        return {n: next(v for k, v in flat.items() if k[-1] == n)
                for n in names}
    return {n: np.stack([flat[(f"layer_{i}", "attention", n)]
                         for i in range(CFG.num_layers)]) for n in names}


def _rows(leaves, name, lo, hi):
    """Rows [lo, hi) of leaf ``name`` of a batch-1 cache's
    ``_row_leaves`` ([layers, (2,) 1, C, *row])."""
    axis = leaves[name].ndim - (1 + _LINEAR_ROW_DIMS[name])
    return np.take(leaves[name], range(lo, hi), axis=axis)


def _dequantised(leaves, lo, hi):
    """K and V rows [lo, hi) of a batch-1 cache's ``_row_leaves`` as
    floats, each with its quantisation step a (row, KV head): 0 where
    the rows are floats already."""
    out = {}
    for i, name in enumerate(("key_cache", "value_cache")):
        rows = _rows(leaves, name, lo, hi).astype(np.float32)
        step = np.zeros(rows.shape[:-1], np.float32)
        if "kv_scales" in leaves:     # [layers, 2, 1, rows, kv_heads]
            step = np.take(_rows(leaves, "kv_scales", lo, hi), i, axis=1)
            rows = rows * step[..., None]
        out[name] = (rows, step)
    return out


class _Lanes:
    """Four lanes set up by hand through the engine's own programs
    (prefill pieces -> ``_paged_insert``), so that every rule of the
    pool's write is on a lane of its own:

    0. an ordinary lane (11 rows in blocks 1..8);
    1. a lane whose first two blocks are lane 0's (a radix-shared
       prefix: inserted from row 8 on, and the rows below it in its
       batch-1 cache POISONED, so a write there would show);
    2. a lane never inserted: table all scratch, index 0;
    3. a lane two rows short of its table's end, which overruns it in
       the steps that follow (rows dropped).

    ``prefilled[which][slot]``: the batch-1 cache the lane's own
    prefill wrote (target, then draft), as it was before the poison.
    """

    C, BS = 32, 4
    TABLES = {0: list(range(1, 9)), 1: [1, 2] + list(range(9, 15)),
              3: list(range(15, 23))}
    START = {0: 0, 1: 8, 3: 0}

    def __init__(self, cfg, params, *, q_len):
        rng = np.random.default_rng(11)
        a = list(rng.integers(1, 200, 11))
        self.prompts = {0: a, 1: a[:8] + list(rng.integers(1, 200, 3)),
                        3: list(rng.integers(1, 200, 30))}
        kw = dict(slots=4, cache_len=self.C, chunk=2,
                  prompt_buckets=(16,), kv_block_size=self.BS)
        self.q_len, self.spec = q_len, q_len > 1
        if self.spec:
            kw.update(draft_config=cfg, draft_params=params,
                      speculative_k=q_len - 1)
        self.eng = eng = ServingEngine(cfg, params, **kw)
        self.caches = [eng._fresh_cache(4, grid=True)]
        if self.spec:
            self.caches.append(eng._fresh_cache(4, draft=True, grid=True))
        self.prefilled = [{} for _ in self.caches]
        tok = np.zeros(4, np.int32)
        for slot, prompt in self.prompts.items():
            for which, draft in enumerate([False, True][:len(self.caches)]):
                cache_1, first = eng._prefill_tokens(
                    prompt, seed=0,
                    cache_1=eng._fresh_cache(1, draft=draft), draft=draft)
                if not draft:
                    tok[slot] = int(first)
                self.prefilled[which][slot] = cache_1
                self.caches[which] = eng._paged_insert(
                    self.caches[which],
                    self._poisoned(cache_1, self.START[slot]),
                    jnp.int32(slot),
                    jnp.asarray(self.TABLES[slot], jnp.int32),
                    jnp.int32(self.START[slot]), jnp.int32(len(prompt)))
        self.first = tok
        self.tok = jnp.asarray(tok)
        self.emitted = []

    @staticmethod
    def _poisoned(cache_1, start):
        """Rows below ``start`` overwritten: they are another lane's
        (shared) blocks, and an insert that wrote them would be seen."""
        def poison(path, leaf):
            name = getattr(path[-1], "key", "")
            if name == "index" or not start:
                return leaf
            axis = leaf.ndim - (1 + _LINEAR_ROW_DIMS[name])
            idx = [slice(None)] * leaf.ndim
            idx[axis] = slice(0, start)
            return leaf.at[tuple(idx)].set(77)

        return jax.tree_util.tree_map_with_path(poison, cache_1)

    def step(self):
        """One decode chunk (two steps of one row a lane) or one
        speculative round (a block of ``q_len`` rows a lane)."""
        eng = self.eng
        seeds = jnp.zeros(4, jnp.uint32)
        counts = jnp.zeros(4, jnp.int32)
        if self.spec:
            (self.caches[0], self.caches[1], emit, emitted, self.tok, _,
             _) = eng._spec_round(
                eng._variables, eng._draft_variables, self.caches[0],
                self.caches[1], self.tok, seeds, counts,
                self.q_len - 1)
            self.emitted.append((np.asarray(emit), np.asarray(emitted)))
        else:
            self.caches[0], toks, self.tok, _, _ = eng._decode_chunk(
                eng._variables, self.caches[0], self.tok, seeds, counts)
            self.emitted.append((np.asarray(toks),))

    def lengths(self):
        return np.asarray(next(
            leaf for p, leaf in jax.tree_util.tree_flatten_with_path(
                self.caches[0])[0]
            if p[-1].key == "index")).reshape(-1, 4)[0]

    def generated(self, slot):
        """The tokens lane ``slot`` has produced: its prefill's pick,
        then what each chunk or round emitted for it."""
        out = [int(self.first[slot])]
        for step in self.emitted:
            n = int(step[1][slot]) if self.spec else step[0].shape[1]
            out += [int(t) for t in step[0][slot, :n]]
        return out


@pytest.mark.parametrize("leg", ["gather", "kernel"])
@pytest.mark.parametrize("q_len", [1, 4], ids=["q1", "q4"])
@pytest.mark.parametrize("cfg", [CFG, ICFG], ids=["f32", "int8"])
def test_carried_pools_match_a_pool_a_layer_and_the_batch1_cache(
        params, cfg, q_len, leg, monkeypatch):
    """The depth scan CARRIES the pools (one [layers, blocks, ...] leaf
    a pool, written in place); an unrolled model holds a pool a layer.
    With the same weights the two serve the same tokens and leave the
    same bytes in every block, through ``_paged_insert`` -> decode
    chunks (``q_len`` 1) or speculative rounds (``q_len`` 4, target and
    draft) -> ``_gather_prefix``.  Lanes: ``_Lanes``.  Both are held to
    three references that know nothing of the pool: the prompt rows
    ``_gather_prefix`` reads back are, to the bit, the rows of the
    batch-1 cache the lane's own prefill wrote; the rows the chunks or
    rounds wrote agree with the rows a batch-1 prefill of prompt +
    generated tokens writes, to float32 rounding (int8: to one
    quantisation step); and the tokens of lanes 0 and 1 are
    ``generate()``'s (a speculative round's the plain greedy ones).
    Lane 3 overruns its table and is compared between the two paged
    layouts alone.  ``kernel``: the same under the fused attention
    kernel, interpreted, which reads the carried pool through
    ``block0`` (its online softmax is not the gather leg's to the bit,
    so the rows it wrote are compared between the paged two only)."""
    if leg == "kernel":
        monkeypatch.setenv("TTD_FUSED_ATTN_INTERPRET", "1")
    scfg = dataclasses.replace(cfg, scan_layers=True)
    carried = _Lanes(scfg, _stacked(params), q_len=q_len)
    layered = _Lanes(cfg, params, q_len=q_len)
    assert carried.eng.fused_attn() is (leg == "kernel")
    shared = {}
    for which, cache in enumerate(carried.caches):
        pools = _row_leaves(cache, scanned=True)
        assert pools["key_pool"].shape == (
            CFG.num_layers, 33, 4, CFG.num_kv_heads * 16)
        shared[which] = {n: v[..., 1:3, :, :].copy()
                         for n, v in pools.items()}
    np.testing.assert_array_equal(carried.tok, layered.tok)
    for _ in range(2):
        for lanes in (carried, layered):
            lanes.step()
    for got, want in zip(carried.emitted, layered.emitted):
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
    lengths = carried.lengths()
    np.testing.assert_array_equal(lengths, layered.lengths())
    assert lengths[3] > _Lanes.C                      # lane 3 overran
    for slot in (0, 1):
        prompt = carried.prompts[slot]
        tokens = carried.generated(slot)
        assert lengths[slot] == len(prompt) + len(tokens) - 1
        assert prompt + tokens == _ref_cfg(cfg, params, prompt,
                                           len(tokens))
    for which, (c_cache, l_cache) in enumerate(zip(
            carried.caches, layered.caches)):
        c_pools = _row_leaves(c_cache, scanned=True)
        l_pools = _row_leaves(l_cache, scanned=False)
        assert set(c_pools) == set(l_pools) == (
            {"key_pool", "value_pool"}
            | ({"kv_pool_scales"} if cfg.kv_cache_int8 else set()))
        for name, pool in c_pools.items():
            # every block but the scratch block, which takes whatever
            # the idle and the reset lanes write
            np.testing.assert_array_equal(pool[..., 1:, :, :],
                                          l_pools[name][..., 1:, :, :])
            # the shared blocks: lane 1's insert wrote nothing there
            np.testing.assert_array_equal(pool[..., 1:3, :, :],
                                          shared[which][name])
        for lanes, cache, scanned in ((carried, c_cache, True),
                                      (layered, l_cache, False)):
            for slot, prompt in lanes.prompts.items():
                n = min(int(lengths[slot]), _Lanes.C)
                got = _row_leaves(lanes.eng._gather_prefix(
                    cache, jnp.asarray(_Lanes.TABLES[slot], jnp.int32),
                    bool(which), jnp.int32(n)), scanned)
                wrote = _row_leaves(lanes.prefilled[which][slot], scanned)
                for name in wrote:
                    np.testing.assert_array_equal(
                        _rows(got, name, _Lanes.START[slot], len(prompt)),
                        _rows(wrote, name, _Lanes.START[slot],
                              len(prompt)))
                if slot == 3 or leg == "kernel":
                    continue
                # Positions [len(prompt), n) hold the lane's generated
                # tokens but the last, which was never fed back.
                again, _ = lanes.eng._prefill_tokens(
                    prompt + lanes.generated(slot)[:-1], seed=0,
                    cache_1=lanes.eng._fresh_cache(1, draft=bool(which)),
                    draft=bool(which))
                want = _dequantised(_row_leaves(again, scanned),
                                    len(prompt), n)
                for name, (rows, step) in _dequantised(
                        got, len(prompt), n).items():
                    ref, ref_step = want[name]
                    tol = (1e-5 + 1e-5 * np.abs(ref) + 1.001 * np.maximum(
                        step, ref_step)[..., None])
                    worst = (np.abs(rows - ref) / tol).max()
                    assert worst <= 1, (name, slot, which, worst)


# ── slow tier: the full parity matrix ──────────────────────────────────

pytestmark_slow = pytest.mark.slow


@pytest.mark.slow
@pytest.mark.parametrize("sampling", [
    dict(),
    dict(temperature=0.9, top_k=16),
    dict(temperature=0.7, top_p=0.9),
])
def test_paged_matches_generate_with_refills(params, sampling):
    """Six mixed requests through two slots (every lane refills):
    each request's tokens are ``generate()``'s for its prompt alone,
    greedy and seeded sampling (a request's stream is keyed by its
    seed and the tokens it has drawn, as a batch of one's is)."""
    rng = np.random.default_rng(1)
    reqs = [(list(rng.integers(1, 200, n)), m)
            for n, m in [(5, 6), (3, 9), (7, 4), (4, 12), (6, 1),
                         (2, 0)]]
    seeds = [11, 22, 33, 44, 55, 66]
    kw = dict(slots=2, cache_len=64, chunk=4, prompt_buckets=(8, 16),
              kv_block_size=4, **sampling)
    out, _ = _serve(params, reqs, seeds=seeds, **kw)
    for o, (p, m), seed in zip(out, reqs, seeds):
        assert o == _ref_seeded(CFG, params, p, m, seed, **sampling)


@pytest.mark.slow
def test_paged_matches_generate_speculative(params):
    """Speculative serving (self-draft, full acceptance) and a
    DISAGREEING draft, through two slots that refill.  Greedy: the
    tokens are plain greedy ``generate()``'s.  Sampled: a speculative
    round's draws are not plain sampling's, so no plain reference has
    its tokens; each request's are those it gets ALONE on a one-slot
    engine (its stream is its own wherever it lands)."""
    dcfg = LLAMA_PRESETS["llama_tiny_scan"]
    dparams = LlamaModel(dcfg).init(
        jax.random.PRNGKey(9), jnp.zeros((1, 4), jnp.int32))["params"]
    rng = np.random.default_rng(2)
    reqs = [(list(rng.integers(1, 200, n)), m)
            for n, m in [(5, 8), (7, 6), (3, 9)]]
    for draft_cfg, draft_params in ((CFG, params), (dcfg, dparams)):
        for sampling in (dict(), dict(temperature=0.8, top_k=20)):
            kw = dict(slots=2, cache_len=48, chunk=3,
                      prompt_buckets=(8,), kv_block_size=4,
                      draft_config=draft_cfg, draft_params=draft_params,
                      speculative_k=3, **sampling)
            out, eng = _serve(params, reqs, seeds=[1, 2, 3], **kw)
            for o, req, seed in zip(out, reqs, [1, 2, 3]):
                if sampling:
                    alone, _ = _serve(params, [req], seeds=[seed],
                                      **dict(kw, slots=1))
                    assert o == alone[0]
                else:
                    assert o == _ref(params, *req)
            assert eng.spec_stats["rounds"] >= 1


@pytest.mark.slow
def test_paged_matches_generate_mid_stream_cancel(params):
    """Cancel mid-decode and mid-staged-prefill: the surviving
    requests' outputs stay ``generate()``'s, and the cancelled lanes'
    blocks return to the pool."""
    rng = np.random.default_rng(3)
    long_prompt = list(rng.integers(1, 200, 24))
    short = [list(rng.integers(1, 200, 5)) for _ in range(3)]

    eng = ServingEngine(CFG, params, slots=2, cache_len=64, chunk=3,
                        prompt_buckets=(8,), prefill_chunk=8,
                        kv_block_size=4)
    a = eng.submit(short[0], 10)
    b = eng.submit(short[1], 10)
    eng.serve_step()
    c = eng.submit(long_prompt, 8)     # stages behind the decode
    d = eng.submit(short[2], 6)
    eng.serve_step()
    assert eng.cancel(c)               # mid-staged-prefill
    eng.serve_step()
    assert eng.cancel(a)               # mid-decode
    out = {}
    while eng.pending():
        out.update(eng.serve_step())
    assert out.get(b) == _ref(params, short[1], 10)
    assert out.get(d) == _ref(params, short[2], 6)
    assert all(kv is None for kv in eng._lane_kv)
    eng._radix.check_invariants()


@pytest.mark.slow
@pytest.mark.parametrize("sampling", [dict(),
                                      dict(temperature=0.8, top_k=12)])
def test_paged_matches_generate_staged_interleave(params, sampling):
    """A long prompt admitted mid-stream under the interleaved prefill
    scheduler (several budget installments): the long request AND the
    active lanes around it produce ``generate()``'s tokens, greedy and
    on their own seeded streams."""
    rng = np.random.default_rng(4)
    active = [(list(rng.integers(1, 200, 6)), 14) for _ in range(2)]
    long_req = (list(rng.integers(1, 200, 30)), 6)

    eng = ServingEngine(CFG, params, slots=3, cache_len=64, chunk=3,
                        prompt_buckets=(8,), prefill_chunk=8,
                        kv_block_size=4, **sampling)
    ids = [eng.submit(p, m, seed=7 + i)
           for i, (p, m) in enumerate(active)]
    eng.serve_step()
    ids.append(eng.submit(*long_req, seed=99))
    out = {}
    while eng.pending():
        out.update(eng.serve_step())
    for rid, (p, m), seed in zip(ids, active + [long_req], [7, 8, 99]):
        assert out[rid] == _ref_seeded(CFG, params, p, m, seed,
                                       **sampling)


@pytest.mark.slow
def test_copy_on_write_divergence_after_shared_prefix(params):
    """Two requests share a block-aligned prefix then diverge: each
    decodes its own continuation (bitwise = generate()), and the
    SHARED physical blocks' bytes are untouched by either lane — the
    allocation-time copy-on-write contract."""
    rng = np.random.default_rng(5)
    pre = list(rng.integers(1, 200, 8))     # 2 full blocks at bs=4
    a = pre + list(rng.integers(1, 200, 3))
    b = pre + list(rng.integers(1, 200, 3))
    eng = ServingEngine(CFG, params, slots=2, cache_len=48, chunk=3,
                        prompt_buckets=(16,), kv_block_size=4)
    ia = eng.submit(a, 6)
    out1 = eng.run()
    # The first request seeded the radix; snapshot the shared blocks'
    # bytes before the second (sharing) request runs.
    matched, shared = eng._radix.match(b)
    assert matched == 8 and len(shared) == 2

    def pool_rows(blocks):
        idx = jnp.asarray(blocks)
        rows = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                eng._cache)[0]:
            name = getattr(path[-1], "key", "")
            if name in ("key_pool", "value_pool"):
                rows[ServingEngine._path_key(path)] = np.asarray(
                    jnp.take(leaf, idx, axis=leaf.ndim - 3))
        return rows

    before = pool_rows(shared)
    ib = eng.submit(b, 6)
    out2 = eng.run()
    after = pool_rows(shared)
    assert out1[ia] == _ref(params, a, 6)
    assert out2[ib] == _ref(params, b, 6)
    assert eng.kv_stats["prefix_hit_tokens"] >= 8
    for k in before:
        assert np.array_equal(before[k], after[k]), f"shared {k} written"


@pytest.mark.slow
def test_eviction_under_pressure_refuses_admission(params):
    """A pool too small for two lanes: the second request is REFUSED
    admission (queued, counted) until the first retires — outputs stay
    exactly ``generate()``'s, and no live lane is ever corrupted.
    Retired prefixes evict LRU to make room."""
    rng = np.random.default_rng(6)
    reqs = [(list(rng.integers(1, 200, 6)), 8) for _ in range(3)]
    eng = ServingEngine(CFG, params, slots=2, cache_len=32, chunk=3,
                        prompt_buckets=(8,), kv_block_size=4,
                        kv_pool_blocks=4)    # one lane's worth
    ids = [eng.submit(p, m) for p, m in reqs]
    out = eng.run()
    assert [out[i] for i in ids] == [_ref(params, p, m)
                                     for p, m in reqs]
    assert eng.kv_stats["alloc_refusals"] >= 1
    assert eng.kv_stats["evictions"] >= 1
    assert eng.kv_blocks_in_use() <= eng.kv_blocks_total()
    eng._radix.check_invariants()
    # A request that could NEVER fit is rejected at submit, not queued
    # forever.
    with pytest.raises(ValueError, match="KV blocks"):
        eng.submit(list(rng.integers(1, 200, 8)), 12)


@pytest.mark.slow
def test_stored_prefix_pairs_are_lru_bounded(params):
    """The stored batch-1 pairs (``_prefix_caches``) do not leak:
    preloads past ``prefix_cache_limit`` evict the least recently
    matched."""
    eng = ServingEngine(CFG, params, slots=1, cache_len=32, chunk=2,
                        prompt_buckets=(8,), prefix_cache_limit=2)
    eng.preload_prefix([1, 1])
    eng.preload_prefix([2, 2])
    eng._match_prefix([1, 1, 9], touch=True)   # refresh [1, 1]
    eng.preload_prefix([3, 3])                 # evicts [2, 2]
    assert len(eng._prefix_caches) == 2
    assert eng._match_prefix([2, 2, 9])[0] == 0
    assert eng._match_prefix([1, 1, 9])[0] == 2
    assert eng._match_prefix([3, 3, 9])[0] == 2


@pytest.mark.slow
def test_block_knobs_and_window_configs_are_screened(params):
    """Engine-level guards: the pool's block knobs, and the configs
    whose cache has no per-slot form."""
    with pytest.raises(ValueError, match="kv_block_size"):
        ServingEngine(CFG, params, slots=1, cache_len=16,
                      prompt_buckets=(8,), kv_block_size=0)
    with pytest.raises(ValueError, match="kv_pool_blocks"):
        ServingEngine(CFG, params, slots=1, cache_len=16,
                      prompt_buckets=(8,), kv_pool_blocks=0)
    wcfg = dataclasses.replace(CFG, sliding_window=8)
    with pytest.raises(ValueError, match="sliding_window"):
        ServingEngine(wcfg, params)


@pytest.mark.slow
@pytest.mark.parametrize("sampling", [
    dict(),
    dict(temperature=0.9, top_k=16),
])
def test_int8_paged_matches_generate_with_refills(params, sampling):
    """kv_cache_int8 through two slots with every lane refilling: each
    request's tokens are those of ``generate()``'s shared-index int8
    cache (same quantized rows, same scales, different physical
    layout), greedy and on its own seeded stream."""
    rng = np.random.default_rng(11)
    reqs = [(list(rng.integers(1, 200, n)), m)
            for n, m in [(5, 6), (3, 9), (7, 4), (4, 8), (6, 1)]]
    seeds = [11, 22, 33, 44, 55]
    kw = dict(slots=2, cache_len=64, chunk=4, prompt_buckets=(8, 16),
              kv_block_size=4, **sampling)
    out, _ = _serve_cfg(ICFG, params, reqs, seeds=seeds, **kw)
    for o, (p, m), seed in zip(out, reqs, seeds):
        assert o == _ref_seeded(ICFG, params, p, m, seed, **sampling)


@pytest.mark.slow
def test_int8_paged_speculative_and_prefix(params):
    """int8 composes with the rest of the paged feature set:
    speculative serving (int8 target AND int8 draft — shared block
    tables, both pools quantized) and radix prefix sharing (the
    ``_gather_prefix`` copy carries the scale rows, so a prefix hit
    reads the exact bytes the original prefill quantized)."""
    rng = np.random.default_rng(12)
    reqs = [(list(rng.integers(1, 200, n)), m)
            for n, m in [(5, 8), (7, 6), (3, 9)]]
    kw = dict(slots=2, cache_len=48, chunk=3, prompt_buckets=(8,),
              kv_block_size=4, draft_config=ICFG, draft_params=params,
              speculative_k=3)
    out, eng = _serve_cfg(ICFG, params, reqs, seeds=[1, 2, 3], **kw)
    for o, (p, m) in zip(out, reqs):
        assert o == _ref_cfg(ICFG, params, p, m)
    assert eng.spec_stats["rounds"] >= 1
    # Prefix sharing: a block-aligned shared prefix hits warm int8 KV
    # and the continuation still equals generate().
    pre = list(rng.integers(1, 200, 8))
    a = pre + list(rng.integers(1, 200, 3))
    b = pre + list(rng.integers(1, 200, 3))
    eng2 = ServingEngine(ICFG, params, slots=2, cache_len=48, chunk=3,
                         prompt_buckets=(16,), kv_block_size=4)
    ia = eng2.submit(a, 6)
    o1 = eng2.run()
    ib = eng2.submit(b, 6)
    o2 = eng2.run()
    assert o1[ia] == _ref_cfg(ICFG, params, a, 6)
    assert o2[ib] == _ref_cfg(ICFG, params, b, 6)
    assert eng2.kv_stats["prefix_hit_tokens"] >= 8
    # preload_prefix seeds the same int8 pool.
    eng3 = ServingEngine(ICFG, params, slots=2, cache_len=48, chunk=3,
                         prompt_buckets=(16,), kv_block_size=4)
    eng3.preload_prefix(pre)
    ic = eng3.submit(a, 6)
    assert eng3.run()[ic] == _ref_cfg(ICFG, params, a, 6)


@pytest.mark.slow
def test_fused_interpret_parity_matrix(params, monkeypatch):
    """The fused-kernel serving parity bar, exercised FOR REAL on CPU:
    ``TTD_FUSED_ATTN_INTERPRET=1`` compiles the decode programs with
    the interpret-mode fused kernel, and every scenario — greedy,
    seeded sampling, speculative, staged-prefill interleave,
    prefix-hit admission, mid-stream cancel, int8 pool — must produce
    the SAME TOKENS as the ``TTD_NO_PALLAS=1`` XLA block-gather
    leg.  Both legs are deterministic functions of the same inputs, so
    token equality here is a stable pin, not a flaky race."""
    rng = np.random.default_rng(13)
    pre = list(rng.integers(1, 200, 8))
    reqs = [(list(rng.integers(1, 200, 5)), 8),
            (pre + list(rng.integers(1, 200, 3)), 6),
            (pre + list(rng.integers(1, 200, 4)), 5)]
    long_req = (list(rng.integers(1, 200, 24)), 6)

    def scenario(cfg, **kw):
        eng = ServingEngine(cfg, params, slots=2, cache_len=64, chunk=3,
                            prompt_buckets=(8,), prefill_chunk=8,
                            kv_block_size=4, **kw)
        ids = [eng.submit(p, m, seed=5 + i)
               for i, (p, m) in enumerate(reqs)]
        eng.serve_step()
        ids.append(eng.submit(*long_req, seed=99))  # staged interleave
        victim = eng.submit(list(rng.integers(1, 200, 5)), 9, seed=42)
        eng.serve_step()
        assert eng.cancel(victim)                   # mid-stream cancel
        out = {}
        while eng.pending():
            out.update(eng.serve_step())
        return [out[i] for i in ids], eng

    def legs(cfg, **kw):
        monkeypatch.setenv("TTD_FUSED_ATTN_INTERPRET", "1")
        fused, eng_f = scenario(cfg, **kw)
        assert eng_f.fused_attn() is True
        monkeypatch.delenv("TTD_FUSED_ATTN_INTERPRET")
        monkeypatch.setenv("TTD_NO_PALLAS", "1")
        gather, eng_g = scenario(cfg, **kw)
        assert eng_g.fused_attn() is False
        monkeypatch.delenv("TTD_NO_PALLAS")
        return fused, gather

    for cfg in (CFG, ICFG):
        fused, gather = legs(cfg)                       # greedy
        assert fused == gather
        fused, gather = legs(cfg, temperature=0.8, top_k=16)  # sampled
        assert fused == gather
    fused, gather = legs(CFG, draft_config=CFG, draft_params=params,
                         speculative_k=3)               # speculative
    assert fused == gather


@pytest.mark.slow
def test_engine_accepts_int8_rejects_windows(params):
    """The PR-11 screen shape: kv_cache_int8 configs construct and
    serve (the stale 'serves through models.generate' claim is gone);
    rolling-window/sink configs still fail loudly, without blaming
    int8."""
    eng = ServingEngine(ICFG, params, slots=1, cache_len=16, chunk=2,
                        prompt_buckets=(8,))
    assert eng.kv_cache_int8
    wcfg = dataclasses.replace(CFG, sliding_window=8)
    with pytest.raises(ValueError, match="sliding_window") as ei:
        ServingEngine(wcfg, params)
    assert "kv_cache_int8 is supported" in str(ei.value)


@pytest.mark.slow
def test_paged_metrics_accessors_track_pool(params):
    """kv_blocks_in_use/total + hit/eviction counters feed /metrics;
    check they move with real traffic."""
    rng = np.random.default_rng(8)
    pre = list(rng.integers(1, 200, 8))
    eng = ServingEngine(CFG, params, slots=2, cache_len=48, chunk=3,
                        prompt_buckets=(16,), kv_block_size=4)
    assert eng.kv_blocks_total() == 2 * (48 // 4)
    r1 = eng.submit(pre + [5, 6], 4)
    eng.run()
    hits0 = eng.kv_prefix_hit_tokens()
    r2 = eng.submit(pre + [7, 8, 9], 4)
    out = eng.run()
    assert out[r2][:len(pre)] == pre
    assert eng.kv_prefix_hit_tokens() - hits0 >= 8
    assert 0 < eng.kv_blocks_in_use() <= eng.kv_blocks_total()
    assert r1 != r2
