"""Attention: pure-jax reference + pallas flash-attention TPU fast path.

The reference framework has no attention kernel of its own (BERT/Transformer
configs ride stock Keras layers → cuDNN).  TPU-first, attention is the one
op worth a hand kernel: the pallas flash attention
(``jax/experimental/pallas/ops/tpu/flash_attention.py``) streams KV blocks
through VMEM without materializing the S×S score matrix, which is what makes
long-context training feasible at all (SURVEY.md §5.7 — a capability the
reference lacks).

Dispatch contract: ``multihead_attention_kernel`` takes [B, H, S, D] q/k/v
and routes to pallas on TPU when shapes are kernel-friendly, else to the
reference einsum path (always used on CPU test meshes — it is also the
numerics oracle the kernel is tested against).

Decode-mode calls on a linear KV cache (the engine's prefill pieces,
``generate()``) go through ``prefix_attention``: plain jax on every
backend, it walks the row tiles the call's lanes hold with a running
softmax, so a piece at the head of a long cache pays for its own rows.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    mask: Optional[jax.Array] = None,
    window: Optional[int] = None,
    sinks: int = 0,
    softmax_scale: Optional[float] = None,
    sink_logits: Optional[jax.Array] = None,
) -> jax.Array:
    """Reference attention. q/k/v: [B, H, S, D] (q may have different S;
    v may have a head size of its own, which is the output's).

    ``window`` (requires ``causal``): sliding-window attention — each
    query sees only the last ``window`` keys including itself (the
    Mistral convention), masked here exactly; this is the numerics
    oracle for ``local_attention_chunked``.  ``sinks`` (StreamingLLM):
    the first ``sinks`` absolute positions stay attendable past the
    window — the attention-sink trick that keeps streaming decode
    stable.  ``sink_logits`` [H] float32 is another thing: a learned
    logit a head that joins every row's softmax denominator and
    carries no value (``softmax_with_sink``).
    """
    *_, q_len, head_dim = q.shape
    kv_len = k.shape[-2]
    scale = softmax_scale if softmax_scale is not None else head_dim**-0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    logits = logits.astype(jnp.float32)
    # Large finite negative, not -inf: a fully-masked query row must produce
    # ~zeros after softmax, not NaN (all--inf rows NaN out the whole batch).
    mask_value = jnp.finfo(jnp.float32).min / 2
    if window is not None and not causal:
        raise ValueError("window (sliding-window attention) requires "
                         "causal=True")
    if sinks and window is None:
        raise ValueError("sinks (attention sinks) only apply with a "
                         "sliding window")
    if causal:
        # Bottom-right aligned causal mask (supports q_len != kv_len).
        q_pos = jnp.arange(q_len)[:, None] + (kv_len - q_len)
        k_pos = jnp.arange(kv_len)[None, :]
        keep = q_pos >= k_pos
        if window is not None:
            band = q_pos - k_pos < window
            if sinks:
                band = jnp.logical_or(band, k_pos < sinks)
            keep = jnp.logical_and(keep, band)
        logits = jnp.where(keep, logits, mask_value)
    if mask is not None:
        logits = jnp.where(mask, logits, mask_value)
    if sink_logits is None:
        weights = jax.nn.softmax(logits, axis=-1)
    else:
        weights = softmax_with_sink(logits, sink_logits[:, None, None])
    return jnp.einsum("bhqk,bhkd->bhqd", weights.astype(v.dtype), v)


def softmax_with_sink(logits, sink):
    """``exp(s_j - m) / (exp(b - m) + sum_i exp(s_i - m))`` over the last
    axis, ``m = max(b, max_i s_i)``: a softmax whose denominator holds
    one more logit ``b`` (``sink``, broadcast against ``logits`` with a
    last axis of 1) that has no column of its own.  A sink of ``-inf``
    is ``jax.nn.softmax``, to the bit."""
    m = jnp.maximum(jnp.max(logits, axis=-1, keepdims=True), sink)
    m = jax.lax.stop_gradient(m)
    unnormalized = jnp.exp(logits - m)
    return unnormalized / (jnp.sum(unnormalized, axis=-1, keepdims=True)
                           + jnp.exp(sink - m))


#: Rows of a linear KV cache that ``prefix_attention`` folds into its
#: running softmax a step (chosen on the chip: PERF.md section 6, PR 31).
PREFIX_TILE = 512


def prefix_tiles_walked(start, q_len: int, tile: int, cache_len: int):
    """Tiles of ``tile`` rows that ``prefix_attention`` walks for lanes
    whose ``q_len`` queries sit at positions ``start .. start + q_len -
    1`` of a cache of ``cache_len`` rows: the queries of the longest
    lane see rows ``0 .. max(start) + q_len - 1``, so ``ceil((max(start)
    + q_len) / tile)`` tiles, never more than the cache has and never
    fewer than one (row 0 is visible to every query, which keeps the
    running softmax off an all-masked start).  One rule for the device's
    trip count (a traced vector) and for the host's ``prefill/piece``
    ``rows`` (a numpy integer): ``start`` needs ``max``, ``+``, ``//``,
    ``clip``."""
    return ((start.max() + (q_len + tile - 1)) // tile).clip(
        1, -(-cache_len // tile))


def prefix_first_tile(start, tile: int, window: Optional[int]):
    """The first tile ``prefix_attention`` walks under a sliding
    ``window``: the shortest lane's first query, at ``min(start)``, sees
    no row before ``min(start) - window + 1``, so the tiles before that
    row's are not read (0 without a window).  With
    ``prefix_tiles_walked`` for the end, one rule for the device's trip
    range and for the host's ``prefill/piece`` ``window_rows``:
    ``start`` needs ``min``, ``-``, ``//``, ``clip``."""
    if window is None:
        return 0
    return (start.min() - (window - 1)).clip(0) // tile


def _by_query_blocks(op, start, q_len: int, block: Optional[int], axis: int,
                     *per_query):
    """``op(start, *per_query)`` over queries ``q_len`` long, ``block``
    at a time: block ``j`` is the slice ``[j * block, (j + 1) * block)``
    of every ``per_query`` array along its query axis (``per_query``
    holds ``(array, axis)`` pairs), at ``start + j * block``, and the
    results are joined along ``axis``.  Each block walks its own
    tile range (its own ``prefix_tiles_walked``, ``prefix_first_tile``
    and ``select_tiles_counted``), so a call over the pieces of one
    prompt reads the tiles, in the order and with the arithmetic, of the
    pieces run apart.  The whole blocks are one loop (traced and
    compiled once, however many there are) that slices its block out
    of each array and writes its result into place: no array is laid
    out again by block.  A last, shorter block is a call of its own.
    ``None`` when one call covers every query (no ``block``, or no more
    queries than one): the caller's expression stands as it is."""
    if not block or q_len <= block:
        return None
    start = jnp.asarray(start, jnp.int32)

    def at(row0, size):
        return op(start + row0, *(
            jax.lax.dynamic_slice_in_dim(a, row0, size, axis=ax)
            for a, ax in per_query))

    like = jax.eval_shape(lambda: at(0, block))
    out = jnp.zeros((*like.shape[:axis], q_len, *like.shape[axis + 1:]),
                    like.dtype)
    out = jax.lax.fori_loop(
        0, q_len // block,
        lambda j, out: jax.lax.dynamic_update_slice_in_dim(
            out, at(j * block, block), j * block, axis=axis), out)
    rest = q_len % block
    if rest:
        out = jax.lax.dynamic_update_slice_in_dim(
            out, at(q_len - rest, rest), q_len - rest, axis=axis)
    return out


def prefix_attention(
    q: jax.Array,
    cache,
    start: jax.Array,
    kv_of,
    *,
    tile: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    keep: Optional[jax.Array] = None,
    window: Optional[int] = None,
    block: Optional[int] = None,
    sink_logits: Optional[jax.Array] = None,
) -> jax.Array:
    """Attention of ``q`` [B, H, Q, D] over the prefix of a linear KV
    cache that its lanes hold, tile by tile with a running softmax.

    ``cache`` is a tree of arrays with the cache's rows on axis 1 ([B,
    C, ...]: keys and values, their int8 scales, or latent rows);
    ``kv_of(rows)`` makes the keys and values of every head, [B, H, T,
    D] and [B, H, T, Dv], from a tree of T rows (repeat grouped heads,
    dequantize, up-project: whatever the caller's cache needs, paid for
    the rows walked and no others).  Lane ``b``'s queries sit at
    positions ``start[b] + arange(Q)`` and see ``kv_pos <= position``,
    this call's own rows (already in the cache) included.

    Only ``prefix_tiles_walked`` tiles are read, a traced trip count:
    one compiled program whatever the lanes hold, and its float32
    scores are [B, H, Q, tile] and not [B, H, Q, C].  The arithmetic is
    ``dot_product_attention``'s (scores scaled in the inputs' type,
    softmax in float32, probabilities cast to the values' type before
    the product) with the maximum, the sum and the accumulator carried
    in float32 across tiles.  A cache of one tile IS that expression
    over the whole cache under the mask, to the bit.  ``tile`` is
    ``PREFIX_TILE`` unless a test makes a small cache walk several.

    ``keep`` [B, Q, C] bool (``select_top_rows``, which counts and
    marks inside the same ``prefix_tiles_walked`` tiles and leaves every
    row past them unmarked and unread) further restricts each query to
    the rows it marks: the walk and its cost stay those of the rows
    held, the rows left out are masked in each tile.

    ``window``: a query at position p sees ``p - window < kv_pos <= p``
    and no other row, and the walk starts at ``prefix_first_tile``: a
    piece of a window layer reads the tiles its window and its own rows
    reach, whatever the lane holds behind them.  A tile may then hold
    no row that some query sees, so a masked entry's probability is
    set to zero and not left to the running maximum.

    ``block``: more queries than that are walked ``block`` at a time,
    each block over its own tiles (``_by_query_blocks``): the cost of a
    call over k pieces of a prompt is the k pieces', not k times the
    last one's.

    ``sink_logits`` [H] float32: a learned sink a head in every row's
    denominator (``softmax_with_sink``): the running maximum starts at
    it, the running sum at 1 and the accumulator at 0, which is that
    expression and costs no tile.
    """
    tile = PREFIX_TILE if tile is None else tile
    cache_len = jax.tree.leaves(cache)[0].shape[1]
    q_len = q.shape[-2]
    blocks = _by_query_blocks(
        lambda s, q, keep=None: prefix_attention(
            q, cache, s, kv_of, tile=tile, softmax_scale=softmax_scale,
            keep=keep, window=window, sink_logits=sink_logits),
        start, q_len, block, q.ndim - 2, (q, q.ndim - 2),
        *(() if keep is None else ((keep, 1),)))
    if blocks is not None:
        return blocks
    start = jnp.asarray(start, jnp.int32).reshape(-1)     # [B] or [1]
    positions = start[:, None, None] + jnp.arange(q_len)[:, None]

    def seen(kv_pos, row0=None):            # [B | 1, 1, Q, rows]
        ok = kv_pos <= positions
        if window is not None:
            ok &= positions - kv_pos < window
        if keep is not None:
            ok &= keep if row0 is None else jax.lax.dynamic_slice_in_dim(
                keep, row0, kv_pos.shape[0], axis=2)
        return ok[:, None]

    if cache_len <= tile:
        k, v = kv_of(cache)
        return dot_product_attention(
            q, k, v, mask=seen(jnp.arange(cache_len)),
            softmax_scale=softmax_scale, sink_logits=sink_logits)

    scale = (softmax_scale if softmax_scale is not None
             else q.shape[-1] ** -0.5)
    mask_value = jnp.finfo(jnp.float32).min / 2

    def tile_kv(first):
        # The last tile of a cache that is no multiple of ``tile``
        # starts early enough to fit; the rows it shares with the tile
        # before are masked below.
        row0 = jnp.minimum(first, cache_len - tile)
        return row0, kv_of(jax.tree.map(
            lambda c: jax.lax.dynamic_slice_in_dim(c, row0, tile, axis=1),
            cache))

    def fold(t, carry):
        m, l, acc = carry
        row0, (k, v) = tile_kv(t * tile)
        kv_pos = row0 + jnp.arange(tile)
        s = (jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale).astype(
            jnp.float32)
        ok = seen(kv_pos, row0) & (kv_pos >= t * tile)
        s = jnp.where(ok, s, mask_value)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if window is not None:
            p = jnp.where(ok, p, 0.0)
        alpha = jnp.exp(m - m_new)
        return (m_new, l * alpha + p.sum(axis=-1, keepdims=True),
                acc * alpha + jnp.einsum(
                    "bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32))

    v_like = jax.eval_shape(lambda: tile_kv(0)[1][1])
    stat = jnp.full((*q.shape[:-1], 1), mask_value, jnp.float32)
    if sink_logits is None:
        m0, l0 = stat, jnp.zeros_like(stat)
    else:
        m0 = jnp.broadcast_to(
            sink_logits.astype(jnp.float32)[:, None, None], stat.shape)
        l0 = jnp.ones_like(stat)
    _, l, acc = jax.lax.fori_loop(
        prefix_first_tile(start, tile, window),
        prefix_tiles_walked(start, q_len, tile, cache_len), fold,
        (m0, l0,
         jnp.zeros((*q.shape[:-1], v_like.shape[-1]), jnp.float32)))
    return (acc / l).astype(v_like.dtype)


def prefix_index_scores(q, w, keys, start, *, tile: Optional[int] = None,
                        block: Optional[int] = None):
    """The learned selection's score of every cached row for every
    query (DeepSeek-V3.2's indexer)::

        I[b, t, s] = sum_h w[b, t, h] * relu(q[b, t, h] . keys[b, s])

    ``q`` [B, Q, H, D] and ``keys`` [B, C, D] (a linear cache of one
    key a row) in their own type, products accumulated in float32;
    ``w`` [B, Q, H] float32.  Lane ``b``'s queries sit at ``start[b] +
    arange(Q)``; a row past a query's position scores ``-inf``.
    Returns float32 [B, Q, C].  Walked as ``prefix_attention`` walks:
    the tiles ``prefix_tiles_walked`` gives and no others (the rest
    stay ``-inf``), so the per-head scores are [B, Q, H, tile] at a
    time and never [B, Q, H, C].  ``block``: as ``prefix_attention``'s,
    a block's rows past its own tiles ``-inf``."""
    tile = PREFIX_TILE if tile is None else tile
    b, q_len = q.shape[:2]
    cache_len = keys.shape[1]
    blocks = _by_query_blocks(
        lambda s, q, w: prefix_index_scores(q, w, keys, s, tile=tile),
        start, q_len, block, 1, (q, 1), (w, 1))
    if blocks is not None:
        return blocks
    start = jnp.asarray(start, jnp.int32).reshape(-1)
    positions = start[:, None] + jnp.arange(q_len)             # [B|1, Q]

    def score(k, kv_pos):
        s = jnp.einsum("bqhd,bkd->bqhk", q, k,
                       preferred_element_type=jnp.float32)
        s = jnp.einsum("bqhk,bqh->bqk", jax.nn.relu(s), w,
                       precision=jax.lax.Precision.HIGHEST)
        return jnp.where(kv_pos <= positions[..., None], s, -jnp.inf)

    if cache_len <= tile:
        return score(keys, jnp.arange(cache_len))

    def fold(t, out):
        row0 = jnp.minimum(t * tile, cache_len - tile)
        s = score(jax.lax.dynamic_slice_in_dim(keys, row0, tile, axis=1),
                  row0 + jnp.arange(tile))
        return jax.lax.dynamic_update_slice_in_dim(out, s, row0, axis=2)

    return jax.lax.fori_loop(
        0, prefix_tiles_walked(start, q_len, tile, cache_len), fold,
        jnp.full((b, q_len, cache_len), -jnp.inf, jnp.float32))


def _ordered_bits(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))


#: Bits of a score's pattern that one counting pass of
#: ``select_top_rows`` settles: ``32 / SELECT_BITS`` passes of ``2 **
#: SELECT_BITS - 1`` thresholds each (chosen on the chip: PERF.md
#: section 6, PR 33).
SELECT_BITS = 4


def select_tiles_counted(start, q_len: int, k: int, tile: int,
                         cache_len: int):
    """Tiles of ``tile`` rows that ``select_top_rows`` counts over: the
    ``prefix_tiles_walked`` tiles the scores were written to, or none
    where the last query of the longest lane sees no more than ``k``
    rows (``max(start) + q_len <= k``: nothing to choose, every row
    seen is kept).  One rule for the device (a traced vector) and for
    the host's ``prefill/piece`` ``select_rows`` (a numpy integer), as
    ``prefix_tiles_walked`` is."""
    return (start.max() + q_len > k) * prefix_tiles_walked(
        start, q_len, tile, cache_len)


def select_top_rows(scores, k: int, start, *, tile: Optional[int] = None,
                    block: Optional[int] = None):
    """Bool [B, Q, C]: for each query the ``k`` rows of largest score,
    ties to the lower position (``lax.top_k``'s rule), and only rows
    whose score is not ``-inf`` (a query that sees fewer than ``k``
    rows keeps them all).

    ``scores`` [B, Q, C] float32 are ``prefix_index_scores``'s: lane
    ``b``'s queries sit at ``start[b] + arange(Q)`` and every row past
    the ``prefix_tiles_walked`` tiles is ``-inf`` by construction.
    Those rows are not read: the k-th largest score is found by
    counting over the tiles walked alone (a traced trip count, one
    compiled program whatever the lanes hold), on the scores' bit
    patterns, ``SELECT_BITS`` bits a pass from the top (each pass reads
    a tile once and counts the rows at or above every candidate for the
    next digit: no sort of [queries, C]); then the rows above it and
    the first of those equal to it are kept, tile by tile with the
    count of equals carried.  Where ``select_tiles_counted`` is 0 no
    pass runs and every row seen is kept.  A cache of one tile is the
    same count and the same tie rule as one expression over all of it.
    ``block``: as ``prefix_index_scores``'s, whose blocks these are;
    each counts over its own tiles, or none.
    """
    tile = PREFIX_TILE if tile is None else tile
    b, q_len, cache_len = scores.shape
    blocks = _by_query_blocks(
        lambda s, scores: select_top_rows(scores, k, s, tile=tile),
        start, q_len, block, 1, (scores, 1))
    if blocks is not None:
        return blocks
    scores = jax.lax.stop_gradient(scores)   # a choice has no gradient
    start = jnp.asarray(start, jnp.int32).reshape(-1)
    one_tile = cache_len <= tile
    walked = 1 if one_tile else prefix_tiles_walked(
        start, q_len, tile, cache_len)

    def rows_of(t):
        """Tile ``t``: its first row, its scores, and which of its rows
        are its own (the last tile of a cache that is no multiple of
        ``tile`` starts early enough to fit)."""
        if one_tile:
            return 0, scores, True
        row0 = jnp.minimum(t * tile, cache_len - tile)
        return (row0,
                jax.lax.dynamic_slice_in_dim(scores, row0, tile, axis=2),
                row0 + jnp.arange(tile) >= t * tile)

    def over_tiles(fold, init):
        return (fold(0, init) if one_tile
                else jax.lax.fori_loop(0, walked, fold, init))

    digits = jnp.arange(1, 1 << SELECT_BITS, dtype=jnp.uint32)

    def settle(i, carry):
        """One pass: the next ``SELECT_BITS`` bits of the largest
        threshold that ``k`` scores reach, and the rows above what is
        settled so far."""
        thr, above = carry
        shift = (32 - SELECT_BITS * (i + 1)).astype(jnp.uint32)
        cands = (thr | (digits << shift))[..., None]       # [B, Q, D, 1]

        def count(t, n):
            _, s, own = rows_of(t)
            return n + jnp.sum(
                (_ordered_bits(s)[:, :, None] >= cands) & own, axis=-1,
                dtype=jnp.int32)

        n = over_tiles(count, jnp.zeros(cands.shape[:3], jnp.int32))
        # n falls as the digit grows: the digits k rows reach are the
        # first ``d``, and the rows above the new prefix are those at
        # or above digit d + 1 (or, past the last digit, those above
        # the old prefix).
        d = jnp.sum(n >= k, axis=-1, keepdims=True)
        above = jnp.sum(
            jnp.where(jnp.arange(digits.size + 1) == d,
                      jnp.concatenate([n, above], axis=-1), 0),
            axis=-1, keepdims=True)
        return thr | (d.astype(jnp.uint32) << shift), above

    # The largest threshold that k scores reach: the k-th largest score
    # (0, below every float, where fewer than k rows exist or nothing
    # is counted), and how many scores lie above it.
    nothing = (jnp.zeros((b, q_len, 1), jnp.uint32),
               jnp.zeros((b, q_len, 1), jnp.int32))
    thr, above = jax.lax.cond(
        select_tiles_counted(start, q_len, k, tile, cache_len) == 0,
        lambda: nothing,
        lambda: jax.lax.fori_loop(0, 32 // SELECT_BITS, settle, nothing))
    room = k - above

    def mark(t, carry):
        keep, equal = carry
        row0, s, own = rows_of(t)
        bits = _ordered_bits(s)
        level = (bits == thr) & own
        run = equal + jnp.cumsum(level, axis=-1, dtype=jnp.int32)
        kept = ((bits > thr) | (level & (run <= room))) & (s > -jnp.inf)
        if one_tile:
            return kept, run[..., -1:]
        old = jax.lax.dynamic_slice_in_dim(keep, row0, tile, axis=2)
        return (jax.lax.dynamic_update_slice_in_dim(
            keep, jnp.where(own, kept, old), row0, axis=2), run[..., -1:])

    return over_tiles(mark, (jnp.zeros(scores.shape, bool),
                             jnp.zeros((b, q_len, 1), jnp.int32)))[0]


def local_attention_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    window: int,
    segment_ids: Optional[jax.Array] = None,
    sinks: int = 0,
    softmax_scale: Optional[float] = None,
) -> jax.Array:
    """Sliding-window causal self-attention in O(S·window), TPU-native.

    Chunks the sequence into ``window``-sized blocks; each query block
    attends to (previous block, own block) — exactly the keys its
    sliding window can reach — so scores are [.., nc, w, 2w] instead of
    [.., S, S]: no quadratic materialization, static shapes, plain
    einsums XLA tiles onto the MXU.  Numerically matches
    ``dot_product_attention(causal=True, window=w)`` (oracle-tested).

    ``segment_ids`` [B, S] (sequence packing) stays structured: ids ride
    the same shift-concat as the keys, so packing composes WITHOUT the
    dense S×S mask.  ``sinks`` prepends the sequence's first ``sinks``
    keys to every chunk's key set (StreamingLLM attention sinks) — cost
    grows to O(S·(window+sinks)), still linear.  Requires q_len ==
    kv_len and q_len % window == 0 (the dispatcher falls back to the
    masked oracle otherwise).
    """
    *lead, s, d = q.shape
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if not 0 <= sinks <= window:
        raise ValueError(
            f"sinks must be in [0, window], got sinks={sinks} "
            f"window={window}")
    if s % window or k.shape[-2] != s:
        raise ValueError(
            f"local_attention_chunked wants self-attention with seq "
            f"divisible by window, got seq={s} window={window}")
    w = window
    nc = s // w
    scale = softmax_scale if softmax_scale is not None else d**-0.5

    def chunk(t):  # [..., S, D] → [..., nc, w, D]
        return t.reshape(*lead, nc, w, d)

    def shift_concat(tc, pad_axes):
        """(chunk i-1, chunk i) along the chunk axis; chunk -1 is zeros
        (masked by pad_slot below)."""
        prev = jnp.pad(tc[..., :-1, :, :] if tc.ndim > 3
                       else tc[:, :-1, :], pad_axes)
        return jnp.concatenate([prev, tc], axis=-2 if tc.ndim > 3 else -1)

    qc = chunk(q)
    pad4 = [(0, 0)] * len(lead) + [(1, 0), (0, 0), (0, 0)]
    kwin = shift_concat(chunk(k), pad4)                  # [.., nc, 2w, D]
    vwin = shift_concat(chunk(v), pad4)
    kv = 2 * w
    if sinks:
        # Every chunk also sees the sequence's first `sinks` keys —
        # broadcast along the chunk axis (zero-copy under XLA).
        def with_sinks(twin, t):
            sink = jnp.broadcast_to(
                t[..., None, :sinks, :],
                (*lead, nc, sinks, d))
            return jnp.concatenate([sink, twin], axis=-2)

        kwin = with_sinks(kwin, k)
        vwin = with_sinks(vwin, v)
        kv += sinks
    logits = jnp.einsum("...cqd,...ckd->...cqk", qc, kwin) * scale
    logits = logits.astype(jnp.float32)
    mask_value = jnp.finfo(jnp.float32).min / 2
    qi = jnp.arange(w)[:, None]          # query pos within chunk
    kj = jnp.arange(2 * w)[None, :]      # key pos within (prev, own)
    # Window band: key global = base + kj - w, query global = base + qi;
    # keep 0 <= qi - (kj - w) < w  ⇔  qi < kj <= qi + w.
    band = jnp.logical_and(kj > qi, kj <= qi + w)        # [w, 2w]
    # Chunk 0 has no previous block: its first w key slots are padding.
    first = (jnp.arange(nc) == 0)[:, None, None]         # [nc, 1, 1]
    pad_slot = (kj < w)[None, :, :] & first              # [nc, w, 2w]
    keep = band[None, :, :] & ~pad_slot                  # [nc, w, 2w]
    if sinks:
        # Sink columns: key global = si (< sinks), query global =
        # base + qi.  Keep when causal (si <= base+qi) and NOT already
        # a band key of this chunk (the band covers globals
        # > base+qi-w >= base-w; sinks overlap only for chunks 0/1 where
        # base - w < sinks is possible) — dedupe by excluding sink
        # columns the band already reaches: si > base + qi - w.
        base = (jnp.arange(nc) * w)[:, None, None]       # [nc, 1, 1]
        si = jnp.arange(sinks)[None, None, :]            # [1, 1, sinks]
        qg = base + qi[None]                             # [nc, w, 1]
        sink_keep = (si <= qg) & (si <= qg - w)          # causal & not-in-band
        keep = jnp.concatenate(
            [jnp.broadcast_to(sink_keep, (nc, w, sinks)),
             jnp.broadcast_to(keep, (nc, w, 2 * w))], axis=-1)
    if segment_ids is not None:
        b = segment_ids.shape[0]
        segc = segment_ids.reshape(b, nc, w)
        seg_win = shift_concat(segc, [(0, 0), (1, 0), (0, 0)])
        if sinks:
            sink_seg = jnp.broadcast_to(
                segment_ids[:, None, :sinks], (b, nc, sinks))
            seg_win = jnp.concatenate([sink_seg, seg_win], axis=-1)
        seg_keep = segc[..., :, None] == seg_win[..., None, :]
        # [B, nc, w, kv] → broadcast over the head axis.
        keep = keep[None, None] & seg_keep[:, None]
    logits = jnp.where(keep, logits, mask_value)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("...cqk,...ckd->...cqd", weights.astype(vwin.dtype),
                     vwin)
    return out.reshape(*lead, s, d)


def _pallas_friendly(q, k, v) -> bool:
    """Pallas flash kernel wants seq multiples of 128 and head_dim >= 128-
    lane tiling; fall back cleanly otherwise."""
    if jax.default_backend() != "tpu":
        return False
    q_len, kv_len = q.shape[-2], k.shape[-2]
    # q_len == kv_len: the pallas kernel's causal mask is top-left aligned;
    # our reference semantics are bottom-right — they only coincide for
    # equal lengths, so unequal lengths take the reference path.
    return (
        q_len == kv_len
        and q_len % 128 == 0
        and q.shape[-1] in (64, 128, 256)
        and q.dtype in (jnp.float32, jnp.bfloat16)
    )


def _splash_window_friendly(q, k, sinks, mask, force_reference) -> bool:
    """Whether the splash local-attention kernel takes this call.

    OPT-IN (``TTD_SPLASH=1``), not the default: on silicon the chunked
    jnp path beat splash at the measured shape — llama_125m b8×s2048
    w512: chunked 58.1k tok/s (full remat) vs splash 43.8k (full remat)
    / 53.7k (+no_ffn, which splash alone enables) —
    profiles/bench/last_tpu_result.json, 2026-07-31.
    Splash's remat freedom did not make up the kernel gap there; until a
    shape is measured where it wins, the measured winner stays default.
    """
    from tensorflow_train_distributed_tpu.ops.pallas_kernels import (
        env_flag,
    )

    # env_flag is the one shared parser ("0"/"false"/empty mean OFF —
    # the TTD_NO_PALLAS lesson).  TTD_NO_SPLASH still forces it off even
    # if TTD_SPLASH is set (kill switch wins).
    if env_flag("TTD_NO_SPLASH") or not env_flag("TTD_SPLASH"):
        return False
    if force_reference or mask is not None or sinks:
        return False
    # Same kernel-friendliness rules as the flash path (one source).
    return _pallas_friendly(q, k, q)


def splash_window_attention(q, k, v, *, window: int,
                            segment_ids=None,
                            softmax_scale: Optional[float] = None,
                            interpret: bool = False) -> jax.Array:
    """Sliding-window causal attention via the library SPLASH kernel.

    Splash supports local masks NATIVELY (``LocalMask``), streaming KV
    blocks through VMEM and SKIPPING fully-masked blocks — so unlike the
    jnp chunked path nothing [B,H,chunks,c,c+w]-shaped ever
    materializes, which removes the full-remat pairing constraint the
    chunked path has (measured: its saved f32 score stacks OOM a 16
    GiB chip under no-remat/no_ffn).  q/k/v: [B, H, S, D] with KV
    already repeated to full heads (the caller's GQA contract).

    ``interpret=True`` runs the kernel in pallas interpret mode — the
    CPU parity-test path (slow; tiny shapes only).
    """
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as _sk,
        splash_attention_mask as _sm,
    )

    b, h, s, d = q.shape
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    # LocalMask window_size is (left, right) EXCLUSIVE of self; our
    # ``window`` counts the query itself (Mistral), hence window - 1.
    mask = _sm.MultiHeadMask(
        [_sm.LocalMask((s, s), (window - 1, 0), 0) for _ in range(h)])
    kernel = _sk.make_splash_mha(
        mask, head_shards=1, q_seq_shards=1, interpret=interpret)
    qs = (q * scale).astype(q.dtype)  # splash does not scale internally

    if segment_ids is None:
        def one(qi, ki, vi):
            return kernel(qi, ki, vi)

        return jax.vmap(one)(qs, k, v)

    def one_seg(qi, ki, vi, si):
        return kernel(qi, ki, vi,
                      segment_ids=_sk.SegmentIds(q=si, kv=si))

    return jax.vmap(one_seg)(qs, k, v, segment_ids)


#: Queries from which the dense fallback of a sink or an unequal value
#: head warns: 64 heads' float32 scores are 1 GiB a sequence there.
DENSE_WARN_ROWS = 2048


def multihead_attention_kernel(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    mask: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    window: Optional[int] = None,
    sinks: int = 0,
    softmax_scale: Optional[float] = None,
    force_reference: bool = False,
    sink_logits: Optional[jax.Array] = None,
) -> jax.Array:
    """Flash attention on TPU, reference path elsewhere.

    ``segment_ids`` [B, S]: restrict attention to same-segment pairs (the
    sequence-packing mask) — structured, so the pallas kernel handles it
    natively (``SegmentIds``); an arbitrary dense ``mask`` forces the
    reference path instead.

    ``window``: sliding-window causal attention (Mistral convention —
    each query sees the last ``window`` keys including itself).  Plain
    long self-attention takes the O(S·window) chunked path
    (``local_attention_chunked``); combinations with packing/masks/
    cross-length fall back to the exactly-masked oracle.  ``sinks``
    (StreamingLLM attention sinks, needs ``window``): the first
    ``sinks`` positions stay attendable past the window.

    ``sink_logits`` [H] (a learned sink in the denominator) or a value
    head of its own size takes the exactly-masked oracle: no kernel
    here computes either, so a whole forward (training, evaluation, a
    prompt in one call) holds [B, H, S, S] float32 scores, and says so
    from ``DENSE_WARN_ROWS`` queries on.  The engine's pieces and decode
    steps do not come here (``prefix_attention``, ``paged_attention``).
    """
    def _fold_segments(mask):
        """Dense same-segment mask (the packing restriction) — only for
        the S×S fallback paths; the chunked path keeps ids structured."""
        if segment_ids is None:
            return mask
        seg = (segment_ids[:, None, :, None]
               == segment_ids[:, None, None, :])  # [B, 1, Sq, Skv]
        return seg if mask is None else jnp.logical_and(mask, seg)

    if sinks and window is None:
        raise ValueError("sinks (attention sinks) only apply with a "
                         "sliding window")
    if sink_logits is not None or v.shape[-1] != q.shape[-1]:
        if q.shape[-2] >= DENSE_WARN_ROWS and not force_reference:
            import warnings

            warnings.warn(
                f"attention with a learned sink logit or a value head "
                f"of its own size ({v.shape[-1]} beside {q.shape[-1]}) "
                f"runs the DENSE S×S path at seq={q.shape[-2]}: "
                f"{q.shape[-3]} heads hold "
                f"{q.shape[-3] * q.shape[-2] * k.shape[-2] * 4 / 2**30:.1f}"
                f" GiB of float32 scores a sequence; no tiled kernel "
                f"computes either outside the serving engine",
                stacklevel=2)
        return dot_product_attention(
            q, k, v, causal=causal, mask=_fold_segments(mask),
            window=window, sinks=sinks, softmax_scale=softmax_scale,
            sink_logits=sink_logits)
    if window is not None:
        if not causal:
            raise ValueError("window (sliding-window attention) requires "
                             "causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if (_splash_window_friendly(q, k, sinks, mask, force_reference)
                and q.shape[-2] > window):
            # TPU: the splash kernel handles the local mask natively —
            # no score materialization, no remat pairing constraint.
            return splash_window_attention(
                q, k, v, window=window, segment_ids=segment_ids,
                softmax_scale=softmax_scale)
        chunkable = (mask is None and not force_reference
                     and q.shape[-2] == k.shape[-2]
                     and q.shape[-2] % window == 0
                     and q.shape[-2] > window
                     and sinks <= window)
        if chunkable:
            return local_attention_chunked(
                q, k, v, window=window, segment_ids=segment_ids,
                sinks=sinks, softmax_scale=softmax_scale)
        if q.shape[-2] >= 4 * window and not force_reference:
            import warnings

            warnings.warn(
                f"sliding-window attention fell back to the DENSE "
                f"S×S path (seq={q.shape[-2]}, window={window}: "
                f"seq not divisible by window, a dense mask, or "
                f"cross-length) — the O(S·window) chunked path "
                f"needs seq %% window == 0; at long context this "
                f"fallback can OOM", stacklevel=2)
        return dot_product_attention(
            q, k, v, causal=True, mask=_fold_segments(mask), window=window,
            sinks=sinks, softmax_scale=softmax_scale)
    if force_reference or mask is not None or not _pallas_friendly(q, k, v):
        return dot_product_attention(
            q, k, v, causal=causal, mask=_fold_segments(mask),
            softmax_scale=softmax_scale
        )
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        SegmentIds, flash_attention,
    )

    from jax.sharding import PartitionSpec as P

    from tensorflow_train_distributed_tpu.ops.pallas_kernels import (
        activation_spec, per_shard,
    )

    scale = (softmax_scale if softmax_scale is not None
             else q.shape[-1] ** -0.5)

    def kernel(q, k, v, seg=None):
        return flash_attention(
            q, k, v,
            segment_ids=(None if seg is None
                         else SegmentIds(q=seg, kv=seg)),
            causal=causal, sm_scale=scale)

    # [B, H, S, D]: batches and heads are independent, the sequence is
    # whole here (the sequence-parallel path is ring_attention's).
    def qkv_spec(mesh):
        return activation_spec(mesh, q.shape, heads_dim=1)

    def in_specs(mesh):
        spec = qkv_spec(mesh)
        seg = () if segment_ids is None else (P(spec[0], None),)
        return (spec, spec, spec) + seg

    args = (q, k, v) if segment_ids is None else (q, k, v, segment_ids)
    return per_shard(kernel, in_specs, qkv_spec)(*args)


# ---------------------------------------------------------------------------
# Gated delta rule (models.layers.DeltaAttention): the chunked scan
# ---------------------------------------------------------------------------

#: Rows of one chunk of ``delta_rule_scan``.  A row's decay is a value a
#: key channel, so the rows of a chunk meet through products of
#: ``exp(+L)`` and ``exp(-L)`` of the chunk's running log decay ``L``.
#: With the log of one step's decay bounded below by -5 (the layer's
#: bounded gate), 16 rows keep ``|L|`` under 80, inside float32's (and
#: bfloat16's) range, whose ends are e^-87 and e^88; taken about the
#: chunk's middle row the factors stay within e^-40 .. e^40.
DELTA_CHUNK = 16


def _unit_lower_inverse(m):
    """``(I + m)^-1`` of strictly lower triangular ``m`` [..., n, n]:
    ``m`` is nilpotent, so the inverse is the finite series ``sum_k
    (-m)^k = (I - m)(I + m^2)(I + m^4)...``, a few small products."""
    n = m.shape[-1]
    eye = jnp.eye(n, dtype=m.dtype)
    hi = jax.lax.Precision.HIGHEST
    inv, power, span = eye - m, m, 2
    while span < n:
        power = jnp.matmul(power, power, precision=hi)
        inv = jnp.matmul(inv, eye + power, precision=hi)
        span *= 2
    return inv


def delta_rule_scan(q, k, v, g, beta, state, *, chunk: int = DELTA_CHUNK,
                    dtype=None):
    """The gated delta rule over a call's rows, a chunk at a time.

    For each batch row and head, with ``S`` [dk, dv] starting at
    ``state``::

        S_ = diag(exp g_t) S;   S = S_ + beta_t k_t (v_t - S_^T k_t)^T
        o_t = S^T q_t

    ``q``, ``k`` [B, T, H, dk] (normalised and scaled by the caller),
    ``v`` [B, T, H, dv], ``g`` [B, T, H, dk] float32 (log decay, in
    ``(-88 / chunk, 0]``), ``beta`` [B, T, H], ``state`` [B, H, dk, dv]
    float32.  A row with ``g = 0`` and ``beta = 0`` is the identity on
    the state (a call's padding).  Returns ``(o [B, T, H, dv] float32,
    state')``.

    Within a chunk of C rows, with ``L_t`` the running sum of ``g`` and
    ``u_t = beta_t (v_t - S_^T k_t)`` the row's correction, the
    corrections solve a unit lower triangular system (the chunk's own
    rows see each other through ``A_sr = sum_c k_sc k_rc exp(L_sc -
    L_rc)``, r < s)::

        (I + diag(beta) A) U = diag(beta) (V - (K e^L) S_0)
        O = (Q e^L) S_0 + tril(Q e^L (K e^-L)^T) U
        S_C = diag(e^{L_C}) S_0 + (K e^{L_C - L})^T U

    Everything that does not read the state (``A``, the inverse, ``T
    V``, ``T K e^L``) is made for all chunks at once; the scan over
    chunks is four small products a chunk.  State and decays float32;
    matmul operands in ``dtype`` (None: ``q``'s) into float32.
    """
    f32 = jnp.float32
    b, t, h, dk = q.shape
    mm = jnp.dtype(dtype or q.dtype)
    n = -(-t // chunk)

    def chunks(x):                       # [B, T, H, d] -> [n, B, H, C, d]
        x = jnp.pad(x, ((0, 0), (0, n * chunk - t)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, n, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 2, 3), 1, 0)

    ein = functools.partial(jnp.einsum, preferred_element_type=f32,
                            precision=(jax.lax.Precision.HIGHEST
                                       if mm == f32 else None))
    qc, kc, vc = chunks(q.astype(f32)), chunks(k.astype(f32)), chunks(v)
    run = jnp.cumsum(chunks(g.astype(f32)), axis=-2)     # L, inclusive
    bc = chunks(beta.astype(f32)[..., None])[..., 0]     # [n, B, H, C]
    last = run[..., -1:, :]
    # Rows meet each other through exp(L_s - L_r), formed as two
    # factors about the chunk's middle row (|L - L_mid| <= 40: no
    # factor near the range's ends, where a small component of q or k
    # would flush to zero); they meet S_0 through exp(L) itself.
    mid = run - run[..., chunk // 2:chunk // 2 + 1, :]
    k_in = (kc * jnp.exp(run)).astype(mm)                # meets S_0
    q_in = (qc * jnp.exp(run)).astype(mm)
    k_end = (kc * jnp.exp(last - run)).astype(mm)        # to the chunk's end
    k_out = (kc * jnp.exp(-mid)).astype(mm)              # met by later rows
    rows = jnp.arange(chunk)
    among = ein("...sc,...rc->...sr", (kc * jnp.exp(mid)).astype(mm), k_out)
    reads = jnp.where(
        rows[:, None] >= rows[None, :],
        ein("...sc,...rc->...sr", (qc * jnp.exp(mid)).astype(mm), k_out),
        0.0)
    solve = _unit_lower_inverse(
        jnp.where(rows[:, None] > rows[None, :], among, 0.0)
        * bc[..., None]) * bc[..., None, :]              # (I + bA)^-1 b
    solve_m = solve.astype(mm)
    u_free = ein("...sr,...rv->...sv", solve_m, vc.astype(mm))
    u_state = ein("...sr,...rc->...sc", solve_m, k_in).astype(mm)

    def step(s, xs):
        u_free, u_state, q_in, reads, k_end, last = xs
        sm = s.astype(mm)
        u = (u_free - ein("bhsc,bhcv->bhsv", u_state, sm)).astype(mm)
        o = (ein("bhsc,bhcv->bhsv", q_in, sm)
             + ein("bhsr,bhrv->bhsv", reads, u))
        s = (jnp.exp(last)[..., 0, :, None] * s
             + ein("bhsc,bhsv->bhcv", k_end, u))
        return s, o

    state, o = jax.lax.scan(
        step, state.astype(f32),
        (u_free, u_state, q_in, reads.astype(mm), k_end, last))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)        # [B, n, C, H, dv]
    return o.reshape(b, n * chunk, h, -1)[:, :t], state


def delta_rule_recurrence(q, k, v, g, beta, state):
    """``delta_rule_scan``'s definition, a token at a time (the oracle
    of its tests and of the decode step's kernel)."""
    from tensorflow_train_distributed_tpu.ops.pallas_kernels import (
        delta_state_step_reference,
    )

    def step(s, xs):
        return delta_state_step_reference(s, *xs)

    state, o = jax.lax.scan(
        step, state.astype(jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state
