"""Hand-rolled pallas TPU kernels: fused RMSNorm and fused softmax-CE.

The reference's hot ops live in cuDNN/cuBLAS; its framework code never hand-
writes kernels.  TPU-first, the two ops worth owning beyond attention are:

- **RMSNorm** (Llama-family norm, run 2×/layer): fusing square-mean,
  rsqrt and the scale multiply into one VMEM pass removes two HBM round
  trips of the [tokens, d_model] activation that unfused XLA sometimes
  leaves behind around the f32 upcast.  Custom VJP keeps the backward to
  one kernel + one einsum (dscale), saving the re-normalization recompute.
- **Softmax cross-entropy** over large vocab (the LM loss): the jnp path
  materializes an f32 [tokens, vocab] log-softmax (and its transpose flow
  in backward) in HBM — at Llama scale (8k tokens × 32k vocab × 4B ≈ 1 GB)
  that dwarfs the model's activations.  The fused kernel streams vocab
  blocks through VMEM with an online (max, sumexp) accumulator — flash
  attention's trick applied to the loss — and the backward recomputes
  softmax blockwise from the saved logsumexp, so HBM cost is the logits
  themselves and [tokens]-sized residuals.

Two serving-side kernels back the engine's paged KV cache:

- **Paged KV gather** (``paged_kv_gather``): the decode step reads each
  lane's KV through a block table (physical blocks of ``block_size``
  rows in one fixed pool — serving.ServingEngine's paged cache).  The
  jnp reference materializes the gather through XLA's generic scatter/
  gather lowering; the kernel is a block-copy loop whose source block
  index comes from a SCALAR-PREFETCHED table (``PrefetchScalarGridSpec``
  — the index map reads ``table[lane, slot]`` before the body runs), so
  each grid step is one contiguous [block_size, kv_heads·head_dim] VMEM
  copy at the natural tile shape, no per-row index math on the vector
  units.
- **Fused paged attention** (``paged_attention``): the gather above
  still MATERIALIZES a dense [lanes, cache_len, kv_heads, head_dim]
  KV view in HBM before attention ever runs — doubling HBM traffic on
  the one resource decode is bound by (the paged_kv_ab residual).
  This kernel computes flash-style decode attention DIRECTLY through
  the block table: one grid step a lane, which walks the blocks its
  prefetched length reaches and no others (``paged_blocks_walked``;
  the pools stay in HBM and the scalar-prefetched table steers
  hand-issued, double-buffered copies of up to ``PAGED_FOLD_ROWS``
  rows' worth of blocks a step), an online (max, sumexp, acc) accumulator
  per (head, query row) carried across steps in VMEM scratch, per-lane
  causal masking from the same length, GQA per kv-head group in-kernel, and
  optional int8-pool dequant fused into the walk (per-row symmetric
  scales ride in a parallel scale pool) — the dense per-lane view is
  never materialized, and the kernel's time follows what the lanes
  hold, not slots x blocks a lane.  ``TTD_NO_PALLAS=1`` keeps the
  gather-then-attend path (the byte-comparable A/B leg);
  ``TTD_FUSED_ATTN_INTERPRET=1`` forces the kernel in interpret mode
  off-TPU (the CPU parity-test path).

A prefill piece's attention over a LINEAR cache of plain bf16 rows is
``prefix_flash_attention``: ``ops.attention.prefix_attention``'s walk
(its oracle, and the path of every other cache) as one kernel, each
query block over its own tiles, a KV head's tile met by all its query
heads once, the scores never out of fast memory.

``prefix_flash_latent`` is the same walk over LATENT rows
(``LatentAttention``'s prefill piece): a tile of rows up-projected in
fast memory once a head for all the call's queries, the learned
choice handed in as a tile of ``keep``, one fold (``_fold_head``) for
both kernels.

All have pure-jax references (the CPU path and the numerics oracle) and
run in interpreter mode in tests (``interpret=True``); kernel layout
follows ``/opt/skills/guides/pallas_guide.md`` (f32 accumulation, 128-lane
blocks, grid innermost over the reduction axis).
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from tensorflow_train_distributed_tpu.runtime import compat

_NEG = -1e30  # big finite negative: avoids -inf − -inf = NaN in masking


def env_flag(name: str) -> bool:
    """True when the A/B kill-switch env var ``name`` is SET (on).

    "", "0", and "false" (any case) mean OFF — a raw truthiness check
    would make NAME=0 silently flip the A/B (the TTD_NO_PALLAS lesson).
    One parser for every switch so the semantics cannot diverge.
    """
    return os.environ.get(name, "").lower() not in ("", "0", "false")


def _use_pallas(override: Optional[bool]) -> bool:
    if override is not None:
        return override
    # Kill switch for on-chip A/B: the custom-VJP
    # kernels block XLA fusion around them, so their win must be measured,
    # not assumed — TTD_NO_PALLAS=1 falls back to the pure-jax path.
    # ("0"/"false"/empty mean OFF — a raw truthiness check would make
    # TTD_NO_PALLAS=0 silently disable the kernels and corrupt the A/B.)
    if env_flag("TTD_NO_PALLAS"):
        return False
    return jax.default_backend() == "tpu"


def _unpartitioned_axes(mesh) -> dict:
    """{axis: size} of the mesh axes GSPMD still partitions over: more
    than one device wide and not already manual (inside an enclosing
    shard_map, e.g. the grad-quant step's per-data-shard program)."""
    return {a: n for a, n in mesh.shape.items()
            if n > 1 and a not in mesh.manual_axes}


def activation_spec(mesh, shape, *, heads_dim: Optional[int] = None):
    """Where a kernel operand's independent dims ride the mesh, by the
    framework's activation layout (``parallel.sharding.DEFAULT_RULES``):
    dim 0 is the batch, over (data, fsdp); ``heads_dim`` over tensor;
    dim 1 of a headless >= 3-D operand is the sequence, over seq.  A dim
    whose size the axes do not divide, and every other dim, stays
    whole."""
    sizes = _unpartitioned_axes(mesh)
    dims = [None] * len(shape)
    batch = tuple(a for a in ("data", "fsdp") if a in sizes)
    if batch and shape[0] % math.prod(sizes[a] for a in batch) == 0:
        dims[0] = batch
    if heads_dim is not None:
        if "tensor" in sizes and shape[heads_dim] % sizes["tensor"] == 0:
            dims[heads_dim] = "tensor"
    elif (len(shape) >= 3 and "seq" in sizes
          and shape[1] % sizes["seq"] == 0):
        dims[1] = "seq"
    return P(*dims)


def per_shard(kernel, in_specs, out_specs):
    """``kernel`` run on each device's shard under the ambient mesh.

    GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    shard_map" is the TPU compiler's own error), so under any mesh of
    more than one device the kernels whose rows/heads are independent
    run inside one; the specs say which dims those are
    (``activation_spec``).  ``in_specs``/``out_specs`` are callables of
    the mesh, evaluated only when there is one.  No mesh (one chip, the
    serving engine's default): the kernel is called as is."""
    mesh = compat.get_abstract_mesh()
    if mesh is None or mesh.empty or not _unpartitioned_axes(mesh):
        return kernel
    # Every axis not manual yet, the one-device ones too: Mosaic lowers
    # only where the whole mesh is manual.
    return compat.shard_map(
        kernel, mesh=mesh, in_specs=in_specs(mesh),
        out_specs=out_specs(mesh), check_vma=False,
        axis_names=frozenset(mesh.axis_names) - set(mesh.manual_axes))


# ---------------------------------------------------------------------------
# Paged KV gather (serving.ServingEngine paged cache)
# ---------------------------------------------------------------------------


def paged_kv_gather_reference(pool, table, cache_len: int):
    """Pure-jax oracle: gather each lane's logical KV rows.

    ``pool``: [num_blocks, block_size, row] physical rows, as the cache
    stores them (a K or V row is its ``kv_heads * head_dim`` values side
    by side); ``table``: [lanes, n_blk] int32 physical block per logical
    block.  Returns [lanes, cache_len, row] — lane b's logical row p is
    ``pool[table[b, p // bs], p % bs]``.
    """
    nb, bs, row = pool.shape
    lanes = table.shape[0]
    # Gather whole BLOCKS (lanes * n_blk indices, contiguous [bs, row]
    # slices each) rather than per-row (lanes * cache_len indices):
    # same bytes, far less index math — XLA lowers this to slice
    # copies, which keeps the paged read from taxing decode.
    blocks = jnp.take(pool, table, axis=0)     # [lanes, n_blk, bs, row]
    return blocks.reshape(lanes, -1, row)[:, :cache_len]


def _paged_gather_kernel(tbl_ref, pool_ref, out_ref):
    # The index map already steered the DMA to the right physical
    # block (scalar-prefetched table); the body is a straight copy.
    del tbl_ref
    out_ref[:] = pool_ref[:]


def paged_kv_gather(pool, table, cache_len: int, *,
                    use_pallas: Optional[bool] = None,
                    interpret: bool = False):
    """Block-table KV gather: [num_blocks, bs, row] pool + [lanes,
    n_blk] table → [lanes, cache_len, row] per-lane linear view
    (bit-identical to the reference: a gather moves bytes, no math).
    The pool is taken as it lies, with no view of it: a pool that holds
    several layers' blocks is read through a table of ids into the
    whole of it."""
    if not _use_pallas(use_pallas) and not interpret:
        return paged_kv_gather_reference(pool, table, cache_len)
    from jax.experimental.pallas import tpu as pltpu

    nb, bs, row = pool.shape
    lanes, n_blk = table.shape
    out = pl.pallas_call(
        _paged_gather_kernel,
        name="paged_kv_gather",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(lanes, n_blk),
            in_specs=[
                pl.BlockSpec((1, bs, row),
                             lambda i, j, tbl: (tbl[i, j], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, bs, row),
                                   lambda i, j, tbl: (i, j, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((lanes, n_blk * bs, row),
                                       pool.dtype),
        interpret=interpret,
    )(table, pool)
    return out[:, :cache_len]


# ---------------------------------------------------------------------------
# Fused paged attention (serving.ServingEngine paged decode)
# ---------------------------------------------------------------------------


def use_fused_paged_attention() -> bool:
    """Whether the paged decode step should run the FUSED kernel
    (``paged_attention``) instead of gather-then-attend.

    ``TTD_NO_PALLAS=1`` wins over everything (the XLA block-gather
    path, byte-comparable as the A/B leg);
    ``TTD_FUSED_ATTN_INTERPRET=1`` forces the kernel ON in interpret
    mode off-TPU (the CPU parity-test path — slow, tiny shapes only);
    otherwise the decision is the standard pallas one (TPU backend).
    Read at TRACE time — set before the engine compiles its decode
    programs."""
    if env_flag("TTD_NO_PALLAS"):
        return False
    return env_flag("TTD_FUSED_ATTN_INTERPRET") or _use_pallas(None)


def fused_attn_interpret() -> bool:
    """True when the fused kernel should run INTERPRETED (the
    TTD_FUSED_ATTN_INTERPRET CPU test path; on a real TPU the flag is
    ignored — the compiled kernel is the thing being shipped)."""
    return (env_flag("TTD_FUSED_ATTN_INTERPRET")
            and jax.default_backend() != "tpu")


def ring_row_positions(lengths, q_len: int, rows: int):
    """The position each row of a lane's RING holds once a call's
    ``q_len`` rows are in it: a ring of ``rows`` rows keeps position
    ``p`` at row ``p % rows``, so after rows up to ``last = lengths +
    q_len - 1`` row ``i`` holds ``last - ((last - i) mod rows)``, the
    newest position of its residue (negative: never written).
    ``lengths`` [lanes] -> int32 [lanes, rows]."""
    last = lengths.astype(jnp.int32)[:, None] + (q_len - 1)
    return last - jnp.mod(last - jnp.arange(rows, dtype=jnp.int32), rows)


def ring_mask(lengths, q_len: int, rows: int, window: int):
    """bool [lanes, q_len, rows]: the rows of a lane's ring that each of
    a call's queries sees (query ``i`` sits at ``lengths + i``): written
    at all, not after the query, and inside its window.  The mask of
    every read that gathers a ring whole (``paged_attention_reference``,
    the engine's gather leg)."""
    held = ring_row_positions(lengths, q_len, rows)[:, None, :]
    at = (lengths[:, None] + jnp.arange(q_len))[:, :, None]
    return (held >= 0) & (held <= at) & (at - held < window)


def paged_attention_reference(q, k_pool, v_pool, table, lengths, *,
                              k_scales=None, v_scales=None,
                              cache_len: Optional[int] = None,
                              block0=0, window: Optional[int] = None,
                              sink_logits=None):
    """Pure-jax oracle: gather-then-attend, the exact math of the
    engine's XLA block-gather leg (``models.layers`` ``_cache_attend``
    minus the sharding constraints, which are numerically no-ops).

    ``q``: [lanes, q_len, heads, head_dim] (RoPE already applied);
    ``k_pool``/``v_pool``: [blocks, block_size, kv_heads * head_dim],
    the layout the cache stores and the kernel copies (int8 when
    ``k_scales``/``v_scales`` [num_blocks, block_size, kv_heads] are
    given — per-row symmetric dequant, the linear-cache kv8 recipe);
    ``table``: [lanes, n_blk] int32; ``lengths``: [lanes] int32, each
    lane's pre-call row count (query i sits at position ``lengths[lane]
    + i`` and sees rows ``<=`` it).  ``block0``: where the table's
    block 0 lies in the pools, for pools that hold several layers'
    blocks one after the other (the scales are one layer's, numbered by
    the table itself).  ``window``: a sliding-window layer, whose table
    is a RING (``table[lane, (p // block_size) % n_blk]`` holds
    position ``p``: ``ring_row_positions``) of at least ``window +
    q_len - 1`` rows, and whose query at ``p`` sees ``p - window <
    row <= p``.  The value pool's rows may be narrower or wider than
    the key pool's (a value head of its own size: ``v_pool.shape[2] //
    kv_heads``, the output's head size).  ``sink_logits`` [heads]
    float32: a learned sink a head in every row's softmax denominator
    (``ops.attention.softmax_with_sink``).  Returns [lanes, q_len,
    heads, the value head's size]."""
    from tensorflow_train_distributed_tpu.ops.attention import (
        dot_product_attention,
    )

    bs = k_pool.shape[1]
    lanes, q_len, heads, hd = q.shape
    kvh = k_pool.shape[2] // hd
    c = cache_len if cache_len is not None else table.shape[1] * bs
    if window is not None:
        c = table.shape[1] * bs             # the ring, whole
    kc = paged_kv_gather_reference(k_pool, table + block0, c)
    vc = paged_kv_gather_reference(v_pool, table + block0, c)
    kc, vc = (t.reshape(lanes, c, kvh, -1) for t in (kc, vc))
    if k_scales is not None:
        ks = paged_kv_gather_reference(k_scales, table, c)[..., None]
        vs = paged_kv_gather_reference(v_scales, table, c)[..., None]
        kc = kc.astype(q.dtype) * ks.astype(q.dtype)
        vc = vc.astype(q.dtype) * vs.astype(q.dtype)
    if kvh != heads:
        rep = heads // kvh
        kc = jnp.repeat(kc, rep, axis=2)
        vc = jnp.repeat(vc, rep, axis=2)
    positions = lengths[:, None] + jnp.arange(q_len)        # [B, q]
    if window is None:
        mask = jnp.arange(c)[None, None, :] <= positions[:, :, None]
    else:
        mask = ring_mask(lengths, q_len, c, window)
    out = dot_product_attention(
        q.transpose(0, 2, 1, 3), kc.transpose(0, 2, 1, 3),
        vc.transpose(0, 2, 1, 3), mask=mask[:, None],
        sink_logits=sink_logits)
    return out.transpose(0, 2, 1, 3)


def paged_first_block(lengths, bs: int, window: Optional[int]):
    """The first block of a lane's rows that ``paged_attention`` reads
    under a sliding ``window``: the call's first query, at position
    ``lengths``, sees no row before ``lengths - window + 1`` (block 0
    without a window).  As ``paged_blocks_walked``, one rule for the
    kernel and for the host: ``lengths`` needs ``-``, ``//``, ``clip``.
    """
    if window is None:
        return 0
    return (lengths - (window - 1)).clip(0) // bs


def paged_blocks_walked(lengths, q_len: int, bs: int, n_blk: int,
                        window: Optional[int] = None):
    """Blocks of its table that ``paged_attention`` reads for a lane
    holding ``lengths`` rows before the call: the ``q_len`` queries see
    rows ``0 .. lengths + q_len - 1``, so ``ceil((lengths + q_len) /
    bs)`` blocks, never more than the table has and never fewer than
    one (block 0 always holds a visible row, which keeps an empty or
    reset lane's accumulator off an all-masked zero).  One rule for the
    kernel (a scalar out of SMEM) and for the host's ``kv_blocks``
    counter (a numpy vector): ``lengths`` needs ``+``, ``//``, ``clip``.
    Under a sliding ``window`` the walk starts at ``paged_first_block``
    and the count is of the blocks from there (``n_blk``: the blocks
    the lane's whole context has, not its ring's).
    """
    last = ((lengths + (q_len + bs - 1)) // bs).clip(1, n_blk)
    if window is None:
        return last
    return last - paged_first_block(lengths, bs, window).clip(0, last - 1)


#: Most cached rows that one copy-and-fold step of the three paged
#: decode walks holds (``_paged_fold``; chosen on the chip from a sweep:
#: PERF.md section 6, PR 50).
PAGED_FOLD_ROWS = 512
#: Fast memory that a step's rows may take by ``_paged_fold``'s sum, of
#: the 16 MiB of scoped VMEM that the three calls are compiled with (the
#: sum counts a step's float32 copies whole, which the compiler does
#: not hold at once: PERF.md section 6, PR 50).
_PAGED_STEP_VMEM = 12 << 20


def _paged_fold(bs: int, n_blk: int, row_bytes: int) -> int:
    """Table entries one step of a paged walk folds into the
    accumulators: up to ``PAGED_FOLD_ROWS`` rows' worth, so the running
    maximum, sum and rescale run once for that many rows and a step
    waits once on that many copies; a table or ring shorter than that
    is one step.  ``row_bytes`` is what one cached row costs a step in
    fast memory (``_step_row_bytes``): a step is as many whole lane
    tiles of rows as ``_PAGED_STEP_VMEM`` holds at that cost, and never
    under one tile (the 128 rows every width has compiled with), so the
    rows of a wide cache (an MHA model's 8,192 columns of keys and
    values) are folded fewer to a step and the constant is the most."""
    rows = max(_LANES, _PAGED_STEP_VMEM // row_bytes // _LANES * _LANES)
    return min(n_blk, max(1, min(PAGED_FOLD_ROWS, rows) // bs))


def _step_row_bytes(pools, q_rows: int, widened: bool = False) -> int:
    """Fast memory one cached row costs a step of a paged walk over
    ``pools`` ([blocks, block_size, columns] each): its place in both
    double buffers, its float32 copy where the kernel widens a step's
    rows before the products (``widened``), and its column of the six
    float32-sized [``q_rows``, step] arrays the scores pass through
    (positions, mask, logits, probabilities)."""
    return sum(p.shape[-1] * (2 * p.dtype.itemsize + 4 * widened)
               for p in pools) + 24 * q_rows


#: Entries of a step that one turn of ``_walk_copies``' loop handles:
#: their starts written out, their wait ONE wait for the bytes of all
#: of them (chosen on the chip: PERF.md section 6, PR 50).
_COPY_GROUP = 8


def _walk_copies(tbl_ref, pairs, sems, fold, live, first=0, ring=None):
    """``copies(step, slot, wait=False)`` of a paged walk's kernel: start
    the copies of the lane's step ``step`` into slot ``slot`` of the
    double buffers, or await them.  Entry ``p`` of the step is entry
    ``step * fold + p`` of the lane's walk, which the table holds there
    or, over a ``ring``, at ``(first + entry) % ring``; its block of
    each ``pool`` goes to ``buf.at[slot, p]``, for every ``(pool, buf)``
    of ``pairs`` (``buf`` [2, fold, block_size, row]), all signalling
    ``sems.at[slot]``.  A step holds ``min(fold, live - step * fold)``
    entries, what the lane holds of it, and a wait awaits as many: no
    block is read that the lane does not hold, and none twice.  (A wait
    needs only shapes, and a semaphore counts bytes: one wait for a
    group's bytes stands for the group's waits.)  The entries are a
    ROLLED loop over groups of ``_COPY_GROUP`` and a second over the
    entries left, so the kernel's traced size, and with it what every
    process pays to trace and lower it, does not grow with the step's
    width."""
    from jax.experimental.pallas import tpu as pltpu

    lane = pl.program_id(0)
    group = min(_COPY_GROUP, fold)

    def copies(step, slot, wait=False):
        sem = sems.at[slot]

        def start(p):
            entry = step * fold + p
            if ring is not None:
                entry = jax.lax.rem(first + entry, ring)
            blk = tbl_ref[lane, entry]
            for pool, buf in pairs:
                pltpu.make_async_copy(pool.at[blk], buf.at[slot, p],
                                      sem).start()

        def await_(entries):
            for pool, buf in pairs:
                pltpu.make_async_copy(
                    pool.at[pl.ds(0, entries)],
                    buf.at[slot, pl.ds(0, entries)], sem).wait()

        def a_group(g, _):
            if wait:
                await_(group)
            else:
                for u in range(group):
                    start(g * group + u)

        def an_entry(p, _):
            await_(1) if wait else start(p)

        count = jnp.minimum(fold, live - step * fold)
        groups = count // group
        jax.lax.fori_loop(0, groups, a_group, None)
        jax.lax.fori_loop(groups * group, count, an_entry, None)

    return copies


def _walk_params():
    """The three paged walks' compiler parameters: the lanes of a call
    run IN ORDER on one core.  The kernels that clear their value
    buffers do so once a call, at lane 0 (``_paged_attn_kernel``), which
    holds only while no lane can run before it or on another core's
    scratch: the lane axis must never be marked parallel."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=("arbitrary",))


_LANES = 128        # the lane width of a vector tile


def _key_spans(kvh: int, hd: int) -> tuple:
    """``(first column, width)`` of the slice of a key row that the
    kernel contracts KV head ``g``'s queries with.  A head's own
    columns, ``[g * hd, (g + 1) * hd)``, where no head straddles the
    edge of a lane tile (``hd`` a multiple of 128 or a whole fraction
    of it) or the row is no whole tiles anyway (a test-size row).  Else
    (a head of 192 in a row of whole tiles) the whole tiles that cover
    them, one width for every head: the queries come in padded with
    zeros over the neighbours' columns (``paged_attention``), so the
    product is the head's own and no slice of the block is shifted
    across lanes."""
    row = kvh * hd
    if hd % _LANES == 0 or _LANES % hd == 0 or row % _LANES:
        return tuple((g * hd, hd) for g in range(kvh))
    first = [g * hd // _LANES * _LANES for g in range(kvh)]
    width = max(-(-(g + 1) * hd // _LANES) * _LANES - f
                for g, f in enumerate(first))
    return tuple((min(f, row - width), width) for f in first)


def _paged_attn_kernel(tbl_ref, len_ref, q_ref, k_hbm, v_hbm, *rest,
                       bs, fold, last_row, kvh, rep, q_len, hd, scale,
                       int8, vd, spans, sink, window=None, ring=0):
    """Grid (lane,): the lane walks the blocks it holds
    (``paged_blocks_walked``), ``fold`` table entries to a step, and
    stops there.  The pools stay in HBM; each step's blocks come in by
    hand-issued copies steered by the scalar-prefetched table
    (``_walk_copies``: as many as the lane holds in the step), double
    buffered so step s + 1 is in flight while step s is folded into
    each query row's online (max, sumexp, acc) accumulator: one
    [rows, fold * bs] product a KV head, one maximum, sum and rescale a
    step.  Row layout is [heads*q_len, hd] with row = head*q_len + qi,
    so each GQA group's rows are one contiguous slice and the per-row
    query position is ``row % q_len``.  A sliding ``window`` (static)
    starts the walk at ``paged_first_block``, finds a block in the
    lane's ``ring`` table entries by its number modulo ``ring``, and
    drops the rows behind each query's window.  ``vd``: the value
    head's size (a value row is ``kvh * vd`` wide, as are the
    accumulator's and the output's heads); ``spans``: ``_key_spans``;
    ``sink``: a [heads*q_len, 1] input holds each row's sink logit,
    where its running maximum starts, with a running sum of 1
    (``softmax_with_sink``).

    The rows of a lane's last step that no copy filled lie past the
    lane's length, so the mask drops their scores whatever the key
    buffer holds there; their VALUES meet a probability of 0 in a
    product, where 0 times a NaN is NaN, so the value buffers are set
    to zero once a call, by its first lane (the grid runs in order on
    one core: afterwards a buffer holds zeros or rows that some lane of
    the call held)."""
    if int8:
        ks_ref, vs_ref = rest[:2]
    if sink:
        sink_ref = rest[2 * int8]
    o_ref, k_buf, v_buf, sem, m_ref, l_ref, acc_ref = rest[-7:]
    i = pl.program_id(0)
    cur = len_ref[i]
    live = paged_blocks_walked(cur, q_len, bs, last_row // bs + 1, window)
    steps = pl.cdiv(live, fold)
    first = paged_first_block(cur, bs, window)

    copies = _walk_copies(tbl_ref, ((k_hbm, k_buf), (v_hbm, v_buf)), sem,
                          fold, live, first, ring or None)

    @pl.when(i == 0)
    def _():
        v_buf[...] = jnp.zeros_like(v_buf)

    if sink:
        m_ref[:] = sink_ref[:]
        l_ref[:] = jnp.ones_like(l_ref)
    else:
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)
    copies(0, 0)
    qf = q_ref[0].astype(jnp.float32)        # [heads*q_len, span width]
    r = rep * q_len                          # rows per kv-head group
    n = fold * bs                            # cache rows a step folds
    # Causal through the table: row p visible to query qi iff
    # p <= cur + qi, and the cache has it (an overrun lane sees the
    # whole cache and no further).  Rows past the lane's length mask
    # out; block 0 always has a visible row for every query (p=0), so
    # the accumulator never divides by an all-masked zero.
    col = jax.lax.broadcasted_iota(jnp.int32, (r, n), 1)
    qi = jax.lax.broadcasted_iota(jnp.int32, (r, n), 0) % q_len
    last_seen = jnp.minimum(cur + qi, last_row)

    def fold_step(step, _):
        slot = jax.lax.rem(step, 2)

        @pl.when(step + 1 < steps)
        def _():
            copies(step + 1, 1 - slot)

        copies(step, slot, wait=True)
        kf = k_buf[slot].astype(jnp.float32).reshape(n, kvh * hd)
        vf = v_buf[slot].astype(jnp.float32).reshape(n, kvh * vd)
        if window is None:
            seen = step * n + col <= last_seen
        else:
            pos = first * bs + step * n + col
            seen = (pos <= last_seen) & (cur + qi - pos < window)
        if int8:
            cols = pl.ds(pl.multiple_of(step * n, n), n)
            ksf, vsf = ks_ref[0, :, cols], vs_ref[0, :, cols]  # [kvh, n]
        for g in range(kvh):                 # static: tiny head count
            rows = slice(g * r, (g + 1) * r)
            col0, width = spans[g]
            logits = jax.lax.dot_general(
                qf[rows], kf[:, col0:col0 + width],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale      # [r, n]
            if int8:
                # Per-row symmetric dequant of the int8 bytes that came
                # off HBM: a cache row's scale is a factor of its whole
                # logit column, so it multiplies after the product.
                logits = logits * ksf[g:g + 1]
            logits = jnp.where(seen, logits, _NEG)
            m_prev = m_ref[rows]
            m_new = jnp.maximum(m_prev,
                                jnp.max(logits, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(logits - m_new)
            if window is not None:
                # A step may hold no row some query sees (its window
                # starts further on): such entries add nothing.
                p = jnp.where(seen, p, 0.0)
            l_ref[rows] = (l_ref[rows] * alpha
                           + jnp.sum(p, axis=-1, keepdims=True))
            if int8:                         # and of its value row
                p = jnp.where(seen, p * vsf[g:g + 1], 0.0)
            acc_ref[rows] = acc_ref[rows] * alpha + jax.lax.dot_general(
                p, vf[:, g * vd:(g + 1) * vd], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[rows] = m_new

    jax.lax.fori_loop(0, steps, fold_step, None)
    o_ref[0] = (acc_ref[:] / l_ref[:]).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, table, lengths, *,
                    k_scales=None, v_scales=None,
                    cache_len: Optional[int] = None, block0=0,
                    window: Optional[int] = None,
                    sink_logits=None,
                    use_pallas: Optional[bool] = None,
                    interpret: bool = False):
    """Flash-style decode attention DIRECTLY through the block table —
    the dense per-lane KV view ``paged_kv_gather`` materializes never
    exists.  Arguments as ``paged_attention_reference`` (the pure-jax
    oracle this is tested against; also the CPU path).  The pools come
    as the cache stores them, [blocks, block_size, kv_heads *
    head_dim], and go to the kernel as they lie: no view is taken, so
    no copy of a pool is made on the way.  A pool may hold the blocks
    of several layers one after the other (the depth scan's carried
    pool); ``block0`` (a traced scalar there) is added to the table
    the kernel's copies are steered by.  One grid step is one lane,
    which reads the blocks its length reaches (``paged_blocks_walked``)
    and no others, ``_paged_fold`` of them to a copy-and-fold step: HBM
    reads are the lane's own rows once, and the kernel's time follows
    what the lanes hold, not the table's width.  An int8 pool's scales
    (4 bytes a row and KV head against ``head_dim`` of them) are too
    narrow for a copy of their own: they come in as one gathered
    [kv_heads, rows] strip a lane.  ``window``: the same kernel over a
    sliding-window layer's RING (``paged_attention_reference``): the
    walk starts at the window's first block (``paged_first_block``), so
    a lane reads the blocks its window spans whatever its context, and
    ``cache_len`` is the context's, not the ring's.  A value pool of
    another width than the key pool's (a value head of its own size)
    is copied as it lies too; a key head that is no whole number of
    lane tiles wide is contracted over the whole tiles that cover it
    (``_key_spans``).  ``sink_logits`` [heads]: each row's running
    softmax starts from its head's sink, which costs no block."""
    if window is not None:
        if k_scales is not None:
            raise ValueError("a window layer's ring holds no int8 rows")
        if cache_len is None:
            raise ValueError("a ring says nothing of the context's "
                             "length: window needs cache_len")
    if not _use_pallas(use_pallas) and not interpret:
        return paged_attention_reference(
            q, k_pool, v_pool, table, lengths, k_scales=k_scales,
            v_scales=v_scales, cache_len=cache_len, block0=block0,
            window=window, sink_logits=sink_logits)
    from jax.experimental.pallas import tpu as pltpu

    bs = k_pool.shape[1]
    lanes, q_len, heads, hd = q.shape
    kvh = k_pool.shape[2] // hd
    vd = v_pool.shape[2] // kvh
    n_blk = table.shape[1]
    if heads % kvh:
        raise ValueError(f"heads {heads} not a multiple of kv_heads "
                         f"{kvh}")
    rep = heads // kvh
    spans = _key_spans(kvh, hd)
    if spans[0][1] != hd:
        # Each head's queries at their place in the span of its KV
        # head, zeros over the columns that are a neighbour's.
        width = spans[0][1]
        groups = q.reshape(lanes, q_len, kvh, rep, hd)
        q = jnp.stack(
            [jnp.pad(groups[:, :, g], ((0, 0),) * 3 + (
                (g * hd - col0, col0 + width - (g + 1) * hd),))
             for g, (col0, _) in enumerate(spans)],
            axis=2).reshape(lanes, q_len, heads, width)
    int8 = k_scales is not None
    fold = _paged_fold(bs, n_blk, _step_row_bytes(
        (k_pool, v_pool), rep * q_len, widened=True))
    last_row = min(cache_len or n_blk * bs, n_blk * bs) - 1
    walk = {}
    if window is not None:
        last_row = cache_len - 1
        walk = dict(window=window, ring=n_blk)
    # [lanes, q_len, H, hd] → [lanes, H*q_len, hd]: row = h*q_len + qi,
    # so each kv-head group's rows are contiguous in the kernel.
    qt = q.transpose(0, 2, 1, 3).reshape(lanes, heads * q_len,
                                         q.shape[-1])
    rows = pl.BlockSpec((1, heads * q_len, q.shape[-1]),
                        lambda i, tbl, lens: (i, 0, 0))
    out_rows = pl.BlockSpec((1, heads * q_len, vd),
                            lambda i, tbl, lens: (i, 0, 0))
    in_specs = [rows] + [pl.BlockSpec(memory_space=pltpu.HBM)] * 2
    args = [table + block0, lengths.astype(jnp.int32), qt, k_pool, v_pool]
    if int8:
        # The strip is as wide as whole steps of the walk, so a step's
        # slice of it never runs off the end.
        wide = jnp.pad(table, ((0, 0), (0, -n_blk % fold)))
        in_specs += [pl.BlockSpec((1, kvh, wide.shape[1] * bs),
                                  lambda i, tbl, lens: (i, 0, 0))] * 2
        args += [jnp.take(s, wide, axis=0, mode="clip")
                 .reshape(lanes, -1, kvh).transpose(0, 2, 1)
                 for s in (k_scales, v_scales)]
    if sink_logits is not None:
        in_specs += [pl.BlockSpec((heads * q_len, 1),
                                  lambda i, tbl, lens: (0, 0))]
        args += [jnp.repeat(sink_logits.astype(jnp.float32), q_len)
                 [:, None]]
    out = pl.pallas_call(
        functools.partial(
            _paged_attn_kernel, bs=bs, fold=fold, last_row=last_row,
            kvh=kvh, rep=rep, q_len=q_len, hd=hd, scale=hd ** -0.5,
            int8=int8, vd=vd, spans=spans, sink=sink_logits is not None,
            **walk),
        # No name= here: a name becomes the HLO instruction's, and the
        # benchmark finds this kernel's device events by the name the
        # calling method gives it (``attention._paged_decode_step``).
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(lanes,),
            in_specs=in_specs,
            out_specs=out_rows,
            scratch_shapes=[
                pltpu.VMEM((2, fold, bs, kvh * hd), k_pool.dtype),
                pltpu.VMEM((2, fold, bs, kvh * vd), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((heads * q_len, 1), jnp.float32),
                pltpu.VMEM((heads * q_len, 1), jnp.float32),
                pltpu.VMEM((heads * q_len, vd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((lanes, heads * q_len, vd),
                                       q.dtype),
        compiler_params=_walk_params(),
        interpret=interpret,
    )(*args)
    return out.reshape(lanes, heads, q_len, vd).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Prefix flash attention (a prefill piece over a linear cache of plain rows)
# ---------------------------------------------------------------------------

#: Queries of each head that one grid step of ``prefix_flash_attention``
#: holds, and cache rows it meets them with (chosen on the chip from a
#: sweep: PERF.md section 6, PR 42; a window layer's tiles are no
#: longer than its window, in steps of 256 rows).
PREFIX_FLASH_BLOCK_Q = 512
PREFIX_FLASH_TILE = 1024
#: Fast memory the kernel may hold: sixteen heads' query block, output
#: block and accumulator beside a tile's scores are past the default
#: 16 MB of scoped VMEM; a v5e core has 128 MiB.
_PREFIX_FLASH_VMEM = 64 << 20


def _prefix_key_blocks(kvh: int, hd: int) -> tuple:
    """``(width, cw)``: the columns of a key row [kvh * hd] that hold
    one KV head's keys (``_key_spans``' one width: the head's own, or
    the whole lane tiles that cover a head of one and a half) and the
    column block they are fetched in: a lane tile, ``width // cw`` of
    them side by side, or the head itself where a row is no whole
    tiles (a test-size row)."""
    width = _key_spans(kvh, hd)[0][1]
    return width, _LANES if width % _LANES == 0 else width


def prefix_flash_engages(q_len: int, k_cache, v_cache) -> bool:
    """Whether a walk of ``q_len`` queries a lane over these caches
    [B, C, kv_heads, D] / [B, C, kv_heads, Dv] runs
    ``prefix_flash_attention`` and not ``ops.attention.
    prefix_attention``, by what the call can see: bf16 rows, whole
    query blocks, a value head and a key head's span of whole lane
    tiles (what the compiled kernel's blocks need), and the backend
    decision every kernel here shares."""
    kvh, hd = k_cache.shape[-2:]
    return (k_cache.dtype == jnp.bfloat16 == v_cache.dtype
            and q_len >= PREFIX_FLASH_BLOCK_Q
            and q_len % PREFIX_FLASH_BLOCK_Q == 0
            and _prefix_key_blocks(kvh, hd)[1] == _LANES
            and v_cache.shape[-1] % _LANES == 0
            and _use_pallas(None))


def _prefix_block_tiles(p0, bq: int, tk: int, cache_len: int,
                        window: Optional[int]):
    """``(first, end)``: the tiles ``[first, end)`` of ``tk`` cache rows
    that the ``bq`` queries at positions ``p0 .. p0 + bq - 1`` of one
    lane walk: ``ops.attention``'s rule for a call (``prefix_first_
    tile``, ``prefix_tiles_walked``) at a query block's size, so a tile
    wholly above the block's diagonal, past the cache or behind its
    window is in no block's range."""
    from tensorflow_train_distributed_tpu.ops import attention

    return (attention.prefix_first_tile(p0, tk, window),
            attention.prefix_tiles_walked(p0, bq, tk, cache_len))


def _fold_head(s, stats, at, vt, bias=None):
    """One head's scores of one tile folded into its running softmax:
    ``s`` [tile, queries] float32, scaled, held TRANSPOSED (keys down
    the sublanes, queries along the lanes), so a query's maximum, sum
    and rescaling factor are lane-dense rows [1, queries]; ``stats``
    the refs of the running maximum, sum [.., 1, queries] and float32
    accumulator [.., value, queries], read and written at ``at``;
    ``vt`` and ``bias`` ``(ref, index)`` of the tile's values
    transposed [value, tile] and of the mask's bias [tile, queries]
    (0 or ``_NEG``), or no bias for a tile seen whole.  Softmax in
    float32, the probabilities cast to the values' type before the
    second product.  The fold of every prefix kernel here."""
    m_ref, l_ref, acc_ref = stats
    if bias is not None:
        s = s + bias[0][bias[1]]
    m_prev = m_ref[at]                                       # [1, bq]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[at] = l_ref[at] * alpha + jnp.sum(p, axis=0, keepdims=True)
    acc_ref[at] = acc_ref[at] * alpha + jax.lax.dot_general(
        vt[0][vt[1]], p.astype(vt[0].dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                  # [vd, bq]
    m_ref[at] = m_new


def _prefix_flash_kernel(start_ref, q_ref, *rest, bq, tk, n_kb, offsets,
                         last_col, cache_len, scale, window, sink):
    """Grid (lane, KV head, query block, key tile).  A step holds the
    ``bq`` queries of every query head of one KV head, [rep, bq, hd],
    and ONE copy of that head's tile of keys (``n_kb`` column blocks
    side by side, the head's own ``hd`` columns at one of ``offsets``
    in them) and values [tk, vd]; each head's running maximum, sum and
    float32 accumulator stay in scratch across the block's tiles, and
    its scores live and die inside the step.  They are held
    TRANSPOSED, [tk, bq] (keys down the sublanes, queries along the
    lanes), and so is the accumulator, [vd, bq]: a query's maximum,
    sum and rescaling factor are then whole lane-dense rows [1, bq]
    and a reduction over keys is elementwise across vector registers,
    where rows of queries would leave every statistic one lane wide
    (measured: PERF.md section 6, PR 42).  Step ``j`` of a
    block is tile ``first + j`` of its own range
    (``_prefix_block_tiles``); steps past the range compute nothing
    (and fetch nothing: the index maps repeat the last live tile).

    A masked entry's score is ``_NEG`` and a running maximum never
    below ``_NEG / 2``, so its probability is ``exp(<= _NEG / 2)``, 0,
    whether or not its tile holds a row its query sees; a tile every
    query of the block sees whole skips the mask.  The last tile of a
    cache that is no whole tiles runs past its rows: what lies there
    is set to zero before any product."""
    k_refs, v_ref = rest[:n_kb], rest[n_kb]
    sink_ref = rest[n_kb + 1] if sink else None
    (o_ref, m_ref, l_ref, acc_ref, bias_ref, k_tile, v_tile,
     vt_ref) = rest[-8:]
    b, g, iq, j = (pl.program_id(a) for a in range(4))
    rep, _, hd = q_ref.shape
    p0 = start_ref[b] + iq * bq
    first, end = _prefix_block_tiles(p0, bq, tk, cache_len, window)
    t = first + j
    ragged = bool(cache_len % tk)
    cw = k_refs[0].shape[-1]
    # Where the head's columns begin in the blocks fetched for it.
    off_g = g * hd - jnp.minimum(g * hd // cw, last_col) * cw

    @pl.when(j == 0)
    def _():
        if sink:
            m0 = jnp.maximum(sink_ref[:], _NEG / 2)
            m_ref[:] = jnp.broadcast_to(m0, m_ref.shape)
            l_ref[:] = jnp.broadcast_to(jnp.exp(sink_ref[:] - m0),
                                        l_ref.shape)
        else:
            m_ref[:] = jnp.full_like(m_ref, _NEG / 2)
            l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def fold(masked: bool):
        k_src, v_src = k_refs[0], v_ref
        zero_past = masked and ragged
        if zero_past:
            held = t * tk + jax.lax.broadcasted_iota(
                jnp.int32, (tk, 1), 0) < cache_len
            v_tile[:] = jnp.where(held, v_ref[:], jnp.zeros_like(v_tile))
            v_src = v_tile
        if zero_past or n_kb > 1 or offsets != (0,):
            kk = k_refs[0][:] if n_kb == 1 else jnp.concatenate(
                [r[:] for r in k_refs], axis=-1)
            for off in offsets:
                @pl.when(off_g == off)
                def _(off=off):
                    k = kk[:, off:off + hd]
                    if zero_past:
                        k = jnp.where(held, k, jnp.zeros_like(k))
                    k_tile[:] = k
            k_src = k_tile
        if masked:
            pos = p0 + jax.lax.broadcasted_iota(jnp.int32, (tk, bq), 1)
            kv_pos = t * tk + jax.lax.broadcasted_iota(
                jnp.int32, (tk, bq), 0)
            ok = kv_pos <= pos
            if window is not None:
                ok &= pos - kv_pos < window
            bias_ref[:] = jnp.where(ok, 0.0, _NEG)

        vt_ref[:] = v_src[:].T

        def head(h, _):
            s = jax.lax.dot_general(
                k_src[:], q_ref[h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [tk, bq]
            _fold_head(s, (m_ref, l_ref, acc_ref), h, (vt_ref, slice(None)),
                       (bias_ref, slice(None)) if masked else None)

        jax.lax.fori_loop(0, rep, head, None)

    # Whole: the tile's last row is no later than the block's first
    # query, and its first row inside the window of the block's last.
    whole = (t + 1) * tk - 1 <= p0
    if window is not None:
        whole &= p0 + bq - 1 - t * tk < window
    live = t < end
    pl.when(live & whole)(lambda: fold(False))
    pl.when(live & jnp.logical_not(whole))(lambda: fold(True))

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        def out(h, _):
            o_ref[h] = (acc_ref[h] / l_ref[h]).T.astype(o_ref.dtype)

        jax.lax.fori_loop(0, rep, out, None)


def prefix_flash_attention(q, k_cache, v_cache, start, *,
                           window: Optional[int] = None,
                           sink_logits=None,
                           softmax_scale: Optional[float] = None,
                           interpret: bool = False):
    """``ops.attention.prefix_attention`` (the numerics oracle this is
    tested against, and the path of every other cache) for a linear
    cache of plain rows, as ONE kernel: ``q`` [B, H, Q, D] over
    ``k_cache`` [B, C, kv_heads, D] and ``v_cache`` [B, C, kv_heads,
    Dv], lane ``b``'s queries at positions ``start[b] + arange(Q)``;
    [B, H, Q, Dv].

    The caches are read a (tile, one KV head's columns) block at a
    time, out of rows flattened to [C, kv_heads * D] (a copy: see
    below): nothing is repeated for grouped heads, and the ``H //
    kv_heads`` query heads of a KV head meet one copy of its tile.  A
    key head that is no whole number of lane tiles wide comes in as
    the whole tiles that cover it (``_key_spans``) and is cut out of
    them in fast memory.  Each
    block of ``PREFIX_FLASH_BLOCK_Q`` queries walks its own tiles
    (``_prefix_block_tiles``: the rule of a call of that many queries
    at the block's position), so a call over k pieces of a prompt costs
    the k pieces' tiles and gives the bits of the pieces run apart;
    ``start`` is scalar-prefetched and the grid is static: one compiled
    program whatever the lanes hold.  ``window`` and ``sink_logits``
    [H] as ``prefix_attention``'s.  The arithmetic is its too (products
    of the inputs' type accumulated in float32, softmax in float32,
    probabilities cast to the values' type before the second product),
    but for the scores, which are never rounded to the inputs' type on
    their way to the softmax."""
    from jax.experimental.pallas import tpu as pltpu

    lanes, heads, q_len, hd = q.shape
    cache_len, kvh = k_cache.shape[1:3]
    vd = v_cache.shape[-1]
    if heads % kvh:
        raise ValueError(f"heads {heads} not a multiple of kv_heads "
                         f"{kvh}")
    bq, tk = PREFIX_FLASH_BLOCK_Q, PREFIX_FLASH_TILE
    if window is not None:
        # A block's walk under a window spans few rows: tiles no longer
        # than the window (in steps of 256 rows) leave less of them
        # behind it.
        tk = min(tk, -(-window // 256) * 256)
    if q_len % bq:
        raise ValueError(f"{q_len} queries are no whole blocks of {bq}")
    rep = heads // kvh
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    width, cw = _prefix_key_blocks(kvh, hd)
    n_kb = width // cw
    offsets = tuple(sorted({g * hd - col0 for g, (col0, _) in
                            enumerate(_key_spans(kvh, hd))}))
    start = jnp.broadcast_to(
        jnp.asarray(start, jnp.int32).reshape(-1), (lanes,))
    if window is not None:
        # The kernel's blocks want a row's heads side by side, which a
        # cache [C, kv_heads, D] in the chip's tiled layout is not: the
        # flattening below is a copy.  A window layer's walk stays
        # inside the rows its window and the call's own reach, from
        # the first tile's first row on: the copy is of those.
        rows = q_len + -(-(window + tk - 2) // tk) * tk
        if rows < cache_len:
            row0 = jnp.minimum((start - (window - 1)).clip(0) // tk * tk,
                               cache_len - rows)
            k_cache, v_cache = (
                jax.vmap(lambda c, r: jax.lax.dynamic_slice_in_dim(
                    c, r, rows, 0))(c, row0) for c in (k_cache, v_cache))
            start, cache_len = start - row0, rows
    n_tiles = -(-cache_len // tk)
    # Tiles the longest walk of one block spans: the cache's, or what
    # a window and the block's own rows touch at the worst alignment.
    steps = n_tiles if window is None else min(
        n_tiles, (window + bq - 3) // tk + 2)
    last_col = kvh * hd // cw - n_kb

    def tile(b, iq, j, start_ref):
        first, end = _prefix_block_tiles(start_ref[b] + iq * bq, bq, tk,
                                         cache_len, window)
        return jnp.minimum(first + j, end - 1)

    def key_block(part):
        return pl.BlockSpec(
            (None, tk, cw),
            lambda b, g, iq, j, start_ref: (
                b, tile(b, iq, j, start_ref),
                jnp.minimum(g * hd // cw, last_col) + part))

    def query_rows(last):
        return pl.BlockSpec(
            (None, None, rep, bq, last),
            lambda b, g, iq, j, start_ref: (b, g, 0, iq, 0))

    in_specs = [query_rows(hd)] + [key_block(p) for p in range(n_kb)]
    in_specs.append(pl.BlockSpec(
        (None, tk, vd),
        lambda b, g, iq, j, start_ref: (b, tile(b, iq, j, start_ref), g)))
    args = [start, q.reshape(lanes, kvh, rep, q_len, hd)]
    args += [k_cache.reshape(lanes, cache_len, -1)] * n_kb
    args.append(v_cache.reshape(lanes, cache_len, -1))
    if sink_logits is not None:
        in_specs.append(pl.BlockSpec(
            (None, rep, 1, 1), lambda b, g, iq, j, start_ref: (g, 0, 0, 0)))
        args.append(sink_logits.astype(jnp.float32).reshape(
            kvh, rep, 1, 1))
    out = pl.pallas_call(
        functools.partial(
            _prefix_flash_kernel, bq=bq, tk=tk, n_kb=n_kb,
            offsets=offsets, last_col=last_col, cache_len=cache_len,
            scale=scale, window=window, sink=sink_logits is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(lanes, kvh, q_len // bq, steps),
            in_specs=in_specs,
            out_specs=query_rows(vd),
            scratch_shapes=[
                pltpu.VMEM((rep, 1, bq), jnp.float32),
                pltpu.VMEM((rep, 1, bq), jnp.float32),
                pltpu.VMEM((rep, vd, bq), jnp.float32),
                pltpu.VMEM((tk, bq), jnp.float32),
                pltpu.VMEM((tk, hd), k_cache.dtype),
                pltpu.VMEM((tk, vd), v_cache.dtype),
                pltpu.VMEM((vd, tk), v_cache.dtype),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((lanes, kvh, rep, q_len, vd),
                                       q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_PREFIX_FLASH_VMEM),
        interpret=interpret,
        name="prefix_flash_attention",
    )(*args)
    return out.reshape(lanes, heads, q_len, vd)


# ---------------------------------------------------------------------------
# Prefix flash attention over latent rows (LatentAttention's prefill piece)
# ---------------------------------------------------------------------------

#: Queries one fold of ``prefix_flash_latent`` meets a tile with, cache
#: rows a grid step up-projects and heads a step holds at most (chosen
#: on the chip from a sweep: PERF.md section 6, PR 44).
PREFIX_LATENT_BLOCK_Q = 1024
PREFIX_LATENT_TILE = 512
_PREFIX_LATENT_HEADS = 16
#: Fast memory the kernel may hold (a v5e core has 128 MiB).
_PREFIX_LATENT_VMEM = 96 << 20


def _latent_group(heads: int, q_len: int, bq: int, tk: int, nope: int,
                  tail: int, vd: int, rank: int, itemsize: int) -> int:
    """Heads a grid step of ``prefix_flash_latent`` holds: the largest
    divisor of ``heads`` (at most ``_PREFIX_LATENT_HEADS``) whose
    blocks fit three quarters of ``_PREFIX_LATENT_VMEM`` beside what a
    step holds whatever the group.  A head's: its queries and output
    (two buffers each), its float32 accumulator and statistics, its
    slices of ``kv_b`` (two buffers).  The step's: its rows (two
    buffers, a copy and the latent transposed), ``keep`` and the bias,
    one head's tile of keys and values, a fold's float32 scores and
    probabilities."""
    pad = lambda n: -(-n // _LANES) * _LANES                  # noqa: E731
    a_head = (2 * q_len * (pad(nope) + tail + pad(vd)) * itemsize
              + (vd + 16) * q_len * 4
              + 2 * rank * (pad(nope) + vd) * itemsize)
    step = (4 * tk * (rank + tail) * itemsize + 2 * tk * q_len
            + tk * q_len * 4 + tk * (pad(nope) + vd) * itemsize
            + 3 * tk * bq * 4)
    room = _PREFIX_LATENT_VMEM * 3 // 4 - step
    return max(g for g in range(1, min(heads, _PREFIX_LATENT_HEADS) + 1)
               if heads % g == 0 and (g == 1 or g * a_head <= room))


def prefix_flash_latent_engages(q_len: int, cache, *, rank: int, nope: int,
                                rope: int, vd: int) -> bool:
    """Whether a walk of ``q_len`` queries a lane over a linear cache
    of latent rows [B, C, row_store] runs ``prefix_flash_latent`` and
    not ``ops.attention.prefix_attention``, by what the call can see:
    bf16 rows, whole query blocks, the latent, both head sizes
    (``nope``, ``vd``) and the rotary key padded (the row's last lane
    tile, the key alone in it) in whole lane tiles, and the backend
    decision every kernel here shares.  A key head of one and a half
    tiles (GLM's 192 beside a rotary 64) computes, but costs three
    passes of the matrix unit where the XLA walk's concatenated 256
    costs two, and loses to it (PERF.md section 6, PR 44): left out."""
    return (cache.dtype == jnp.bfloat16
            and q_len >= _LANES and q_len % _LANES == 0
            and q_len % min(PREFIX_LATENT_BLOCK_Q, q_len) == 0
            and rank % _LANES == 0 and nope % _LANES == 0
            and vd % _LANES == 0
            and 0 < rope <= _LANES and cache.shape[-1] == rank + _LANES
            and _use_pallas(None))


def _prefix_latent_kernel(start_ref, qn_ref, qr_ref, rows_ref, wk_ref,
                          wvt_ref, *rest, bq, tk, rank, cache_len, scale,
                          keep):
    """Grid (lane, group of heads, key tile), every query of the call
    in the step.  A step fetches ONE tile of latent rows [tk,
    row_store]; each of the group's heads makes of it, in fast memory
    and once for all the call's queries, its keys ``c . W_uk[h]`` [tk,
    nope] and its values, already transposed, ``W_uv[h]^T . c^T`` [vd,
    tk] (rounded to the rows' type, as the XLA walk's up-projection
    gives them; the rotary key, the row's last lane tile, is every
    head's), and folds them into its running softmax for each block of
    ``bq`` queries (``_fold_head``: scores transposed, [tk, bq]) whose
    own range holds the tile (``_prefix_block_tiles``), so a call of k
    pieces computes the pieces' tiles and gives their bits.  Steps past
    the call's range compute nothing (and fetch nothing: the index maps
    repeat the last live tile).

    ``keep``: the learned choice's tile [tk, q_len] (int8, keys down
    the sublanes: one mask for every head) becomes the bias once a
    step; every tile is then masked.  Without it a tile every query of
    a block sees whole skips the mask (``_prefix_flash_kernel``'s
    split).  Rows past the cache are set to zero before any product."""
    keep_ref = rest[0] if keep else None
    (o_ref, m_ref, l_ref, acc_ref, bias_ref, c_ref, ct_ref, k_ref,
     vt_ref) = rest[-9:]
    b, _, j = (pl.program_id(a) for a in range(3))
    grp, q_len, _ = qn_ref.shape
    p0 = start_ref[b]
    blocks = [(q0, _prefix_block_tiles(p0 + q0, bq, tk, cache_len, None)[1])
              for q0 in range(0, q_len, bq)]

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG / 2)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def kv_pos(width):
        return j * tk + jax.lax.broadcasted_iota(jnp.int32, (tk, width), 0)

    def whole(q0):
        return (j + 1) * tk - 1 <= p0 + q0

    def causal_bias(q0):
        pos = p0 + q0 + jax.lax.broadcasted_iota(jnp.int32, (tk, bq), 1)
        bias_ref[:, pl.ds(q0, bq)] = jnp.where(kv_pos(bq) <= pos, 0.0, _NEG)

    def fold(h, q0, masked: bool):
        at = pl.ds(q0, bq)
        dims = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(
            k_ref[:], qn_ref[h, at, :], dims,
            preferred_element_type=jnp.float32)
            + jax.lax.dot_general(
                c_ref[:, rank:], qr_ref[h, at, :], dims,
                preferred_element_type=jnp.float32)) * scale  # [tk, bq]
        _fold_head(s, (m_ref, l_ref, acc_ref), (h, slice(None), at),
                   (vt_ref, slice(None)),
                   (bias_ref, (slice(None), at)) if masked else None)

    @pl.when(j < blocks[-1][1])         # the last block's end: the call's
    def _():
        rows = rows_ref[:]
        if cache_len % tk:
            rows = jnp.where(kv_pos(1) < cache_len, rows,
                             jnp.zeros_like(rows))
        c_ref[:] = rows
        ct_ref[:] = rows[:, :rank].T
        if keep:
            pos = p0 + jax.lax.broadcasted_iota(jnp.int32, (tk, q_len), 1)
            ok = (keep_ref[:].astype(jnp.float32) > 0) & (
                kv_pos(q_len) <= pos)
            bias_ref[:] = jnp.where(ok, 0.0, _NEG)
        else:
            for q0, end in blocks:
                pl.when((j < end) & jnp.logical_not(whole(q0)))(
                    functools.partial(causal_bias, q0))

        def head(h, _):
            k_ref[:] = jnp.dot(
                c_ref[:, :rank], wk_ref[h],
                preferred_element_type=jnp.float32).astype(k_ref.dtype)
            vt_ref[:] = jnp.dot(
                wvt_ref[h], ct_ref[:],
                preferred_element_type=jnp.float32).astype(vt_ref.dtype)
            for q0, end in blocks:
                if keep and len(blocks) == 1:
                    fold(h, q0, True)
                elif keep:
                    pl.when(j < end)(functools.partial(fold, h, q0, True))
                else:
                    pl.when((j < end) & whole(q0))(
                        functools.partial(fold, h, q0, False))
                    pl.when((j < end) & jnp.logical_not(whole(q0)))(
                        functools.partial(fold, h, q0, True))

        jax.lax.fori_loop(0, grp, head, None)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        def out(h, _):
            o_ref[h] = (acc_ref[h] / l_ref[h]).T.astype(o_ref.dtype)

        jax.lax.fori_loop(0, grp, out, None)


def prefix_flash_latent(q_nope, q_rope, cache, kv_b, start, *, keep=None,
                        softmax_scale: float, interpret: bool = False):
    """``ops.attention.prefix_attention`` over a linear cache of LATENT
    rows (``models.layers.LatentAttention._linear_step``'s walk: its
    oracle, and every other call's path) as ONE kernel: ``q_nope`` [B,
    H, Q, nope] and ``q_rope`` [B, H, Q, rope] (rotated) over ``cache``
    [B, C, row_store], a row ``[c_kv (rank) | k_r (rope) | zeros]``
    with the rotary key alone in its last lane tile; ``kv_b`` [rank, H,
    nope + Dv] up-projects a row to head ``h``'s key ``[c . W_uk[h] |
    k_r]`` and value ``c . W_uv[h]``; lane ``b``'s queries at positions
    ``start[b] + arange(Q)``; [B, H, Q, Dv].

    A tile of rows is fetched once a group of heads and up-projected
    once a head for ALL the call's queries, in fast memory; no head's
    keys, values or scores reach HBM.  ``keep`` [B, Q, C] bool
    (``select_top_rows``) restricts each query to the rows it marks,
    one mask for every head; the kernel reads it keys-major, ``int8 [B,
    C, Q]``: a transposed copy, made here.  Each block of
    ``PREFIX_LATENT_BLOCK_Q`` queries walks its own tiles
    (``_prefix_block_tiles``), ``start`` is scalar-prefetched and the
    grid is static.  The arithmetic is the walk's (keys and values
    rounded to the rows' type, products accumulated in float32, softmax
    in float32, probabilities cast to the values' type) but for the
    scores, which are never rounded on their way to the softmax."""
    from jax.experimental.pallas import tpu as pltpu

    lanes, heads, q_len, nope = q_nope.shape
    cache_len, store = cache.shape[1:]
    rank = kv_b.shape[0]
    vd = kv_b.shape[-1] - nope
    tail = store - rank
    bq, tk = min(PREFIX_LATENT_BLOCK_Q, q_len), PREFIX_LATENT_TILE
    if q_len % bq:
        raise ValueError(f"{q_len} queries are no whole blocks of {bq}")
    if not 0 < q_rope.shape[-1] <= tail:
        raise ValueError(f"a rotary key of {q_rope.shape[-1]} does not "
                         f"lie in the row's last {tail} columns")
    grp = _latent_group(heads, q_len, bq, tk, nope, tail, vd, rank,
                        cache.dtype.itemsize)
    start = jnp.broadcast_to(
        jnp.asarray(start, jnp.int32).reshape(-1), (lanes,))

    def of_heads(*last):
        return pl.BlockSpec((None, grp, *last),
                            lambda b, g, j, start_ref: (b, g, 0, 0))

    def of_group(*last):
        return pl.BlockSpec((grp, *last),
                            lambda b, g, j, start_ref: (g, 0, 0))

    def of_tile(width):
        return pl.BlockSpec(
            (None, tk, width),
            lambda b, g, j, start_ref: (b, jnp.minimum(
                j, _prefix_block_tiles(start_ref[b], q_len, tk, cache_len,
                                       None)[1] - 1), 0))

    in_specs = [of_heads(q_len, nope), of_heads(q_len, tail), of_tile(store),
                of_group(rank, nope), of_group(vd, rank)]
    args = [start, q_nope.astype(cache.dtype),
            jnp.pad(q_rope.astype(cache.dtype),
                    ((0, 0),) * 3 + ((0, tail - q_rope.shape[-1]),)),
            cache, kv_b[..., :nope].transpose(1, 0, 2),
            kv_b[..., nope:].transpose(1, 2, 0)]
    if keep is not None:
        in_specs.append(of_tile(q_len))
        args.append(keep.transpose(0, 2, 1).astype(jnp.int8))
    return pl.pallas_call(
        functools.partial(
            _prefix_latent_kernel, bq=bq, tk=tk, rank=rank,
            cache_len=cache_len, scale=softmax_scale,
            keep=keep is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(lanes, heads // grp, -(-cache_len // tk)),
            in_specs=in_specs,
            out_specs=of_heads(q_len, vd),
            scratch_shapes=[
                pltpu.VMEM((grp, 1, q_len), jnp.float32),
                pltpu.VMEM((grp, 1, q_len), jnp.float32),
                pltpu.VMEM((grp, vd, q_len), jnp.float32),
                pltpu.VMEM((tk, q_len), jnp.float32),
                pltpu.VMEM((tk, store), cache.dtype),
                pltpu.VMEM((rank, tk), cache.dtype),
                pltpu.VMEM((tk, nope), cache.dtype),
                pltpu.VMEM((vd, tk), cache.dtype),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((lanes, heads, q_len, vd),
                                       cache.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_PREFIX_LATENT_VMEM),
        interpret=interpret,
        name="prefix_flash_latent",
    )(*args)


# ---------------------------------------------------------------------------
# Paged latent attention (models.layers.LatentAttention paged decode)
# ---------------------------------------------------------------------------


def paged_latent_attention_reference(q, pool, table, lengths, *,
                                     value_dim: int, scale: float,
                                     cache_len: Optional[int] = None,
                                     window: Optional[int] = None):
    """Pure-jax oracle (and the CPU path) of ``paged_latent_attention``:
    gather each lane's rows, attend.  ``q`` [lanes, q_len, heads, row]
    (absorbed queries, RoPE applied); ``pool`` [num_blocks, block_size,
    row], every head's key; its leading ``value_dim`` columns are every
    head's value.  ``window``: the table is a RING, gathered whole
    under ``ring_mask`` (``paged_attention_reference``'s rule).
    Returns [lanes, q_len, heads, value_dim]."""
    nb, bs, row = pool.shape
    lanes, q_len = q.shape[:2]
    c = cache_len if cache_len is not None else table.shape[1] * bs
    if window is not None:
        c = table.shape[1] * bs             # the ring, whole
    rows = jnp.take(pool, table, axis=0).reshape(lanes, -1, row)[:, :c]
    logits = jnp.einsum("bqhr,bkr->bhqk", q, rows,
                        preferred_element_type=jnp.float32) * scale
    positions = lengths[:, None] + jnp.arange(q_len)        # [B, q]
    if window is None:
        mask = jnp.arange(c)[None, None, :] <= positions[:, :, None]
    else:
        mask = ring_mask(lengths, q_len, c, window)
    logits = jnp.where(mask[:, None], logits, _NEG)
    p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkc->bqhc", p, rows[..., :value_dim])


def _paged_latent_kernel(tbl_ref, len_ref, q_ref, pool_hbm, o_ref, buf,
                         sem, m_ref, l_ref, acc_ref, *, bs, fold,
                         last_row, q_len, value_dim, scale, window=None,
                         ring=0):
    """``_paged_attn_kernel``'s walk over ONE pool: grid (lane,), the
    lane's own blocks (``paged_blocks_walked``) ``fold`` to a
    double-buffered copy-and-fold step, of which the blocks the lane
    holds are copied (``_walk_copies``).  A row is the key of every head
    and, in its leading ``value_dim`` columns, the value of every head,
    so it is copied once and the query rows [heads*q_len, row] meet it
    in two products.  The products take the pool's own type (bf16 on
    the chip) and accumulate in float32; max, sum and softmax are
    float32.  A sliding ``window`` (static) is ``_paged_attn_kernel``'s:
    the walk starts at ``paged_first_block``, a block lies in the
    lane's ``ring`` table entries at its number modulo ``ring``, and
    the rows behind each query's window are dropped.  The buffer is the
    values' too, so it is set to zero once a call, by its first lane,
    as ``_paged_attn_kernel``'s value buffers are and for its reason: a
    row that no copy filled meets a probability of 0 in a product."""
    i = pl.program_id(0)
    cur = len_ref[i]
    live = paged_blocks_walked(cur, q_len, bs, last_row // bs + 1, window)
    steps = pl.cdiv(live, fold)
    first = paged_first_block(cur, bs, window)     # 0 without a window

    copies = _walk_copies(tbl_ref, ((pool_hbm, buf),), sem, fold, live,
                          first, ring or None)

    @pl.when(i == 0)
    def _():
        buf[...] = jnp.zeros_like(buf)

    m_ref[:] = jnp.full_like(m_ref, _NEG)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)
    copies(0, 0)
    q = q_ref[0]                             # [heads*q_len, row]
    r, n = q.shape[0], fold * bs
    col = jax.lax.broadcasted_iota(jnp.int32, (r, n), 1)
    qi = jax.lax.broadcasted_iota(jnp.int32, (r, n), 0) % q_len
    last_seen = jnp.minimum(cur + qi, last_row)

    def fold_step(step, _):
        slot = jax.lax.rem(step, 2)

        @pl.when(step + 1 < steps)
        def _():
            copies(step + 1, 1 - slot)

        copies(step, slot, wait=True)
        rows = buf[slot].reshape(n, buf.shape[-1])
        logits = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale          # [r, n]
        if window is None:
            seen = step * n + col <= last_seen
        else:
            pos = first * bs + step * n + col
            seen = (pos <= last_seen) & (cur + qi - pos < window)
        logits = jnp.where(seen, logits, _NEG)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev,
                            jnp.max(logits, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        if window is not None:
            # A step may hold no row some query sees (its window
            # starts further on): such entries add nothing.
            p = jnp.where(seen, p, 0.0)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :value_dim],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    jax.lax.fori_loop(0, steps, fold_step, None)
    o_ref[0] = (acc_ref[:] / l_ref[:]).astype(o_ref.dtype)


def paged_latent_attention(q, pool, table, lengths, *, value_dim: int,
                           scale: float, cache_len: Optional[int] = None,
                           window: Optional[int] = None,
                           use_pallas: Optional[bool] = None,
                           interpret: bool = False):
    """Absorbed latent-attention decode directly through the block
    table.  Arguments as ``paged_latent_attention_reference``.  One grid
    step a lane, which reads the blocks its length reaches
    (``paged_blocks_walked``) and no others, each row once: HBM reads
    are ``blocks x block_size x row`` values a call.  ``window``: the
    same kernel over a window layer's RING, as ``paged_attention``'s:
    the walk starts at the window's first block, so a lane reads the
    blocks its window spans whatever its context, and ``cache_len`` is
    the context's, not the ring's."""
    if window is not None and cache_len is None:
        raise ValueError("a ring says nothing of the context's length: "
                         "window needs cache_len")
    if not _use_pallas(use_pallas) and not interpret:
        return paged_latent_attention_reference(
            q, pool, table, lengths, value_dim=value_dim, scale=scale,
            cache_len=cache_len, window=window)
    from jax.experimental.pallas import tpu as pltpu

    nb, bs, row = pool.shape
    lanes, q_len, heads, _ = q.shape
    n_blk = table.shape[1]
    fold = _paged_fold(bs, n_blk, _step_row_bytes((pool,), heads * q_len))
    last_row = min(cache_len or n_blk * bs, n_blk * bs) - 1
    walk = {}
    if window is not None:
        last_row = cache_len - 1
        walk = dict(window=window, ring=n_blk)
    r = heads * q_len
    qt = q.transpose(0, 2, 1, 3).reshape(lanes, r, row).astype(pool.dtype)

    def lane_rows(width):
        return pl.BlockSpec((1, r, width), lambda i, tbl, lens: (i, 0, 0))

    out = pl.pallas_call(
        functools.partial(
            _paged_latent_kernel, bs=bs, fold=fold, last_row=last_row,
            q_len=q_len, value_dim=value_dim, scale=scale, **walk),
        # A name of its own over a ring, so that the device trace tells
        # the window layers' kernel from the full layers' (whose calls
        # over the chosen rows keep the name the accepted readers find).
        name=("paged_latent_attention" if window is None
              else "paged_latent_window"),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(lanes,),
            in_specs=[lane_rows(row), pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=lane_rows(value_dim),
            scratch_shapes=[
                pltpu.VMEM((2, fold, bs, row), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((r, 1), jnp.float32),
                pltpu.VMEM((r, 1), jnp.float32),
                pltpu.VMEM((r, value_dim), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((lanes, r, value_dim), q.dtype),
        compiler_params=_walk_params(),
        interpret=interpret,
    )(table, lengths.astype(jnp.int32), qt, pool)
    return out.reshape(lanes, heads, q_len, value_dim).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Paged index scores (models.layers.LatentAttention's learned selection)
# ---------------------------------------------------------------------------


def paged_index_scores_reference(q, w, pool, table, lengths, *,
                                 cache_len: Optional[int] = None):
    """Pure-jax oracle (and the CPU path) of ``paged_index_scores``:
    gather each lane's index keys, score.  ``q`` [lanes, q_len, heads,
    dim] (the indexer's queries, RoPE applied), ``w`` [lanes, q_len,
    heads] float32, ``pool`` [num_blocks, block_size, dim] one key a
    row.  Returns float32 [lanes, q_len, cache_len]: ``sum_h w_h *
    relu(q_h . key)``, ``-inf`` past a query's position."""
    nb, bs, dim = pool.shape
    lanes, q_len = q.shape[:2]
    c = cache_len if cache_len is not None else table.shape[1] * bs
    keys = jnp.take(pool, table, axis=0).reshape(lanes, -1, dim)[:, :c]
    s = jnp.einsum("bqhd,bkd->bqhk", q.astype(pool.dtype), keys,
                   preferred_element_type=jnp.float32)
    s = jnp.einsum("bqhk,bqh->bqk", jax.nn.relu(s), w,
                   precision=jax.lax.Precision.HIGHEST)
    positions = lengths[:, None] + jnp.arange(q_len)        # [B, q]
    return jnp.where(jnp.arange(c) <= positions[..., None], s, -jnp.inf)


def _paged_index_kernel(tbl_ref, len_ref, q_ref, w_ref, pool_hbm, o_ref,
                        buf, sem, *, bs, fold, last_row, q_len, heads):
    """``_paged_latent_kernel``'s walk over the index keys: grid
    (lane,), the lane's own blocks (``paged_blocks_walked``) ``fold``
    to a double-buffered copy-and-score step, of which the blocks the
    lane holds are copied (``_walk_copies``).  A step's keys [fold *
    bs, dim] meet the query rows [q_len * heads, dim] in one product
    (the pool's own type, float32 accumulation); ReLU, the heads'
    weights and their sum are float32 on the vector unit.  The scores
    of a step are one row ``o_ref[0, query, step]`` [fold * bs] a
    query; the rows the lane's walk does not reach stay ``-inf``.  A
    key that no copy filled lies past the lane's length, where the
    score is replaced and enters no product, so the buffer is never
    cleared."""
    i = pl.program_id(0)
    cur = len_ref[i]
    live = paged_blocks_walked(cur, q_len, bs, last_row // bs + 1)
    steps = pl.cdiv(live, fold)

    copies = _walk_copies(tbl_ref, ((pool_hbm, buf),), sem, fold, live)

    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)
    copies(0, 0)
    q = q_ref[0]                             # [q_len*heads, dim]
    w = w_ref[0]                             # [q_len*heads, 1] float32
    n = fold * bs
    col = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)

    def score_step(step, _):
        slot = jax.lax.rem(step, 2)

        @pl.when(step + 1 < steps)
        def _():
            copies(step + 1, 1 - slot)

        copies(step, slot, wait=True)
        keys = buf[slot].reshape(n, buf.shape[-1])
        s = jax.lax.dot_general(
            q, keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                  # [r, n]
        s = jnp.maximum(s, 0.0) * w
        for j in range(q_len):
            o_ref[0, j, pl.ds(step, 1), :] = jnp.where(
                step * n + col <= jnp.minimum(cur + j, last_row),
                jnp.sum(s[j * heads:(j + 1) * heads], axis=0,
                        keepdims=True), -jnp.inf)

    jax.lax.fori_loop(0, steps, score_step, None)


def paged_index_scores(q, w, pool, table, lengths, *,
                       cache_len: Optional[int] = None,
                       use_pallas: Optional[bool] = None,
                       interpret: bool = False):
    """The indexer's scores of a paged decode step directly through the
    block table.  Arguments as ``paged_index_scores_reference``.  One
    grid step a lane, which reads the blocks its length reaches
    (``paged_blocks_walked``) and no others, each key once: HBM reads
    are ``blocks x block_size x dim`` values a call."""
    if not _use_pallas(use_pallas) and not interpret:
        return paged_index_scores_reference(
            q, w, pool, table, lengths, cache_len=cache_len)
    from jax.experimental.pallas import tpu as pltpu

    nb, bs, dim = pool.shape
    lanes, q_len, heads, _ = q.shape
    n_blk = table.shape[1]
    fold = _paged_fold(bs, n_blk, _step_row_bytes((pool,), heads * q_len))
    c = min(cache_len or n_blk * bs, n_blk * bs)
    n = fold * bs
    slabs = -(-n_blk // fold)
    r = q_len * heads

    def lane_rows(*tail):
        return pl.BlockSpec((1, *tail), lambda i, tbl, lens: (
            i, *(0,) * len(tail)))

    out = pl.pallas_call(
        functools.partial(
            _paged_index_kernel, bs=bs, fold=fold, last_row=c - 1,
            q_len=q_len, heads=heads),
        name="paged_index_scores",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(lanes,),
            in_specs=[lane_rows(r, dim), lane_rows(r, 1),
                      pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=lane_rows(q_len, slabs, n),
            scratch_shapes=[
                pltpu.VMEM((2, fold, bs, dim), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((lanes, q_len, slabs, n),
                                       jnp.float32),
        compiler_params=_walk_params(),
        interpret=interpret,
    )(table, lengths.astype(jnp.int32),
      q.reshape(lanes, r, dim).astype(pool.dtype),
      w.reshape(lanes, r, 1).astype(jnp.float32), pool)
    return out.reshape(lanes, q_len, slabs * n)[..., :c]


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def rms_norm_reference(x, scale, *, epsilon=1e-5):
    """Pure-jax oracle (matches ``models.layers.RMSNorm`` numerics)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + epsilon)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rmsnorm_fwd_kernel(x_ref, s_ref, y_ref, r_ref, *, epsilon):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    r = jax.lax.rsqrt(var + epsilon)
    y_ref[:] = (x * r * s_ref[:].astype(jnp.float32)).astype(y_ref.dtype)
    r_ref[:] = r


def _rmsnorm_bwd_kernel(x_ref, s_ref, r_ref, g_ref, dx_ref):
    # y = x·r·s with r = rsqrt(mean x² + eps):
    #   dx = r·(g·s) − x · r³ · mean((g·s)·x)
    x = x_ref[:].astype(jnp.float32)
    gs = g_ref[:].astype(jnp.float32) * s_ref[:].astype(jnp.float32)
    r = r_ref[:]
    c = jnp.mean(gs * x, axis=-1, keepdims=True)
    dx = r * gs - x * (r * r * r) * c
    dx_ref[:] = dx.astype(dx_ref.dtype)


def _rmsnorm_rows(n_rows: int, d: int) -> int:
    """Rows a grid step normalizes: 256, or fewer where a row is wider
    than 4096 values, so that a block's float32 image stays within 4
    MiB (a power of two of rows, at least 8).  The kernel's fast memory
    is several such images and does not grow with ``n_rows``, but what
    the compiler leaves it does shrink as the program around it grows:
    256 rows of 7168 took 49 MiB, which a prefill call of 2048 rows no
    longer had (44)."""
    wide = max(8, 1 << (((4 << 20) // (4 * d)).bit_length() - 1))
    return min(256, wide, max(8, n_rows))


def _rmsnorm_fwd_call(x2, s2, *, epsilon, interpret):
    n, d = x2.shape
    bn = _rmsnorm_rows(n, d)
    return pl.pallas_call(
        functools.partial(_rmsnorm_fwd_kernel, epsilon=epsilon),
        name="rms_norm_fwd",
        grid=(pl.cdiv(n, bn),),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x2.dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x2, s2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rms_norm_pallas(x2, s2, epsilon, interpret):
    y, _ = _rmsnorm_fwd_call(x2, s2, epsilon=epsilon, interpret=interpret)
    return y


def _rms_norm_pallas_fwd(x2, s2, epsilon, interpret):
    y, r = _rmsnorm_fwd_call(x2, s2, epsilon=epsilon, interpret=interpret)
    return y, (x2, s2, r)


def _rms_norm_pallas_bwd(epsilon, interpret, res, g):
    x2, s2, r = res
    n, d = x2.shape
    bn = _rmsnorm_rows(n, d)
    dx = pl.pallas_call(
        _rmsnorm_bwd_kernel,
        name="rms_norm_bwd",
        grid=(pl.cdiv(n, bn),),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x2.dtype),
        interpret=interpret,
    )(x2, s2, r, g)
    # dscale_j = Σ_rows g_ij · x_ij · r_i — one dense reduction; XLA emits
    # the optimal column-sum, no kernel needed.
    ds = jnp.einsum(
        "nd,nd->d",
        g.astype(jnp.float32),
        x2.astype(jnp.float32) * r,
    ).astype(s2.dtype)
    return dx, ds[None, :]


_rms_norm_pallas.defvjp(_rms_norm_pallas_fwd, _rms_norm_pallas_bwd)


def rms_norm(x, scale, *, epsilon: float = 1e-5,
             use_pallas: Optional[bool] = None,
             interpret: bool = False):
    """Fused RMSNorm. ``x``: [..., D]; ``scale``: [D]."""
    if not _use_pallas(use_pallas):
        return rms_norm_reference(x, scale, epsilon=epsilon)

    def kernel(x, scale):
        d = x.shape[-1]
        y = _rms_norm_pallas(x.reshape(-1, d), scale.reshape(1, d),
                             epsilon, interpret)
        return y.reshape(x.shape)

    # Rows are independent: each device normalizes its own shard.
    return per_shard(
        kernel,
        lambda mesh: (activation_spec(mesh, x.shape), P(None)),
        lambda mesh: activation_spec(mesh, x.shape))(x, scale)


# ---------------------------------------------------------------------------
# Fused softmax cross-entropy (integer labels)
# ---------------------------------------------------------------------------


def cross_entropy_reference(logits, labels):
    """Per-example CE via the standard log-softmax (the memory-hungry path)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - ll


def _ce_block_cols(v: int) -> int:
    return min(2048, max(128, v))


def _ce_fwd_kernel(logits_ref, labels_ref, loss_ref, lse_ref,
                   m_ref, l_ref, ll_ref, *, vocab, block_v):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        ll_ref[:] = jnp.zeros_like(ll_ref)

    block = logits_ref[:].astype(jnp.float32)
    bn, bv = block.shape
    cols = j * block_v + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    block = jnp.where(cols < vocab, block, _NEG)

    m_prev = m_ref[:]
    m_new = jnp.maximum(m_prev, jnp.max(block, axis=-1, keepdims=True))
    l_ref[:] = (l_ref[:] * jnp.exp(m_prev - m_new)
                + jnp.sum(jnp.exp(block - m_new), axis=-1, keepdims=True))
    m_ref[:] = m_new
    hit = cols == labels_ref[:]
    ll_ref[:] += jnp.sum(jnp.where(hit, block, 0.0), axis=-1, keepdims=True)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        lse = m_ref[:] + jnp.log(l_ref[:])
        lse_ref[:] = lse
        loss_ref[:] = lse - ll_ref[:]


def _ce_bwd_kernel(logits_ref, labels_ref, lse_ref, g_ref, dlogits_ref,
                   *, vocab, block_v):
    j = pl.program_id(1)
    block = logits_ref[:].astype(jnp.float32)
    bn, bv = block.shape
    cols = j * block_v + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    p = jnp.exp(block - lse_ref[:])
    hit = (cols == labels_ref[:]).astype(jnp.float32)
    d = (p - hit) * g_ref[:]
    dlogits_ref[:] = jnp.where(
        cols < vocab, d, 0.0).astype(dlogits_ref.dtype)


def _ce_specs(n, v, bn, bv):
    return dict(
        grid=(pl.cdiv(n, bn), pl.cdiv(v, bv)),
        in_specs=[
            pl.BlockSpec((bn, bv), lambda i, j: (i, j)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
    )


def _ce_rows(n: int) -> int:
    return min(256, max(8, n))


def _ce_fwd(logits, labels2, *, interpret):
    from jax.experimental.pallas import tpu as pltpu

    n, v = logits.shape
    bn, bv = _ce_rows(n), _ce_block_cols(v)
    sp = _ce_specs(n, v, bn, bv)
    loss, lse = pl.pallas_call(
        functools.partial(_ce_fwd_kernel, vocab=v, block_v=bv),
        name="cross_entropy_fwd",
        grid=sp["grid"],
        in_specs=sp["in_specs"],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bn, 1), jnp.float32),
            pltpu.VMEM((bn, 1), jnp.float32),
            pltpu.VMEM((bn, 1), jnp.float32),
        ],
        interpret=interpret,
    )(logits, labels2)
    return loss, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _cross_entropy_pallas(logits, labels2, interpret):
    loss, _ = _ce_fwd(logits, labels2, interpret=interpret)
    return loss[:, 0]


def _cross_entropy_pallas_fwd(logits, labels2, interpret):
    loss, lse = _ce_fwd(logits, labels2, interpret=interpret)
    return loss[:, 0], (logits, labels2, lse)


def _cross_entropy_pallas_bwd(interpret, res, g):
    from jax.experimental.pallas import tpu as pltpu  # noqa: F401

    logits, labels2, lse = res
    n, v = logits.shape
    bn, bv = _ce_rows(n), _ce_block_cols(v)
    sp = _ce_specs(n, v, bn, bv)
    dlogits = pl.pallas_call(
        functools.partial(_ce_bwd_kernel, vocab=v, block_v=bv),
        name="cross_entropy_bwd",
        grid=sp["grid"],
        in_specs=sp["in_specs"] + [
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bv), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, v), logits.dtype),
        interpret=interpret,
    )(logits, labels2, lse, g[:, None].astype(jnp.float32))
    return dlogits, None


_cross_entropy_pallas.defvjp(_cross_entropy_pallas_fwd,
                             _cross_entropy_pallas_bwd)


def fused_cross_entropy(logits, labels, *,
                        use_pallas: Optional[bool] = None,
                        interpret: bool = False):
    """Per-example softmax CE with integer labels, never materializing
    softmax in HBM.  ``logits``: [..., V]; ``labels``: int [...]."""
    if not _use_pallas(use_pallas):
        return cross_entropy_reference(logits, labels)

    def kernel(logits, labels):
        out = _cross_entropy_pallas(
            logits.reshape(-1, logits.shape[-1]),
            labels.reshape(-1, 1).astype(jnp.int32), interpret)
        return out.reshape(labels.shape)

    # Examples are independent and each holds its whole vocab row (a
    # vocab-sharded head takes the GSPMD path — ops.losses).
    def label_spec(mesh):
        return P(*activation_spec(mesh, logits.shape)[:-1])

    return per_shard(
        kernel,
        lambda mesh: (activation_spec(mesh, logits.shape), label_spec(mesh)),
        label_spec)(logits, labels)


# ---------------------------------------------------------------------------
# Routed experts' un-sort and gate-combine (models.moe._routed_ffn_rows)
# ---------------------------------------------------------------------------
#
# The grouped matmuls return one float32 row a (token, choice) pair, in
# expert order.  Gathering them back to token order and summing is two
# to three passes over those rows in XLA; the kernel streams the rows
# once as they lie (only those of the experts held, which expert order
# keeps together) and adds each, times its gate, to its token's row of
# a float32 sum that stays in VMEM.  (At the end of the file: a line
# added above a kernel moves the source lines in its serialized body,
# and every program that holds it compiles anew.)


def moe_combine_reference(out, tok, gates, tokens: int, dtype):
    """``y[t]`` = the sum over the rows ``j`` with ``tok[j] == t`` of
    ``gates[j] * out[j]``, in float32, one cast at the end.  ``out``
    [rows, D] float32 in expert order; ``tok`` (int32) / ``gates``
    (float32) [pairs] the token and the gate of the pair in each of the
    first ``pairs`` rows."""
    pairs = tok.shape[0]
    y = jnp.zeros((tokens, out.shape[-1]), jnp.float32)
    return y.at[tok].add(out[:pairs] * gates[:, None],
                         mode="promise_in_bounds").astype(dtype)


#: Rows of ``out`` a grid step of ``moe_combine`` takes in.
_COMBINE_ROWS = 256
#: A one-dimensional int32 or float32 operand lies in tiles of this
#: many scalars, and a block of it in scalar memory is one of them.
_SMEM_TILE = 1024
#: Bytes of the float32 sums ``moe_combine`` keeps in fast memory: the
#: columns are walked in chunks that fit.
_COMBINE_ACC_BYTES = 16 << 20


def _combine_cols(t: int, d: int) -> int:
    """Columns ``moe_combine`` sums at a time: the widest multiple of
    128 that divides ``d`` and keeps ``t`` float32 rows inside
    ``_COMBINE_ACC_BYTES`` (128 where none does)."""
    fits = [c for c in range(128, d + 1, 128)
            if d % c == 0 and t * c * 4 <= _COMBINE_ACC_BYTES]
    return max(fits, default=128 if d % 128 == 0 else d)


def _moe_combine_kernel(span_ref, out_ref, tok_ref, gate_ref, o_ref, acc,
                        *, rows):
    """Grid (column chunk, row block).  ``span_ref`` [2]: the rows
    ``[lo, hi)`` of ``out`` that can be other than zero; blocks outside
    them are neither copied (the index map stays on a live block) nor
    read.  A live row is multiplied by its pair's gate and added to its
    token's sum, row by row in expert order."""
    i = pl.program_id(1)
    lo, hi = span_ref[0], span_ref[1]
    block = lo // rows + i

    @pl.when(i == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(block * rows < hi)
    def _():
        # The scalars come in XLA's own tiles of ``_SMEM_TILE``.
        base = jax.lax.rem(block * rows, _SMEM_TILE)

        def add(j, _):
            at = pl.ds(tok_ref[base + j], 1)
            acc[at, :] = (acc[at, :]
                          + gate_ref[base + j] * out_ref[pl.ds(j, 1), :])

        jax.lax.fori_loop(jnp.maximum(lo - block * rows, 0),
                          jnp.minimum(hi - block * rows, rows), add, None)

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc[...].astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def moe_combine(out, tok, gates, span, tokens: int, dtype,
                interpret=False):
    """``moe_combine_reference`` as one pass over ``out``: the rows
    ``span`` = ``[lo, hi)`` are streamed through fast memory once,
    block by block as they lie, each multiplied by its pair's gate and
    added in float32 to its token's row of a ``[tokens, columns]`` sum
    that stays there; ``[tokens, D]`` is written once, in ``dtype``.
    No row is moved to token order, and no row outside ``span`` is
    read: the caller promises those are zero (``gmm`` under
    ``group_offset`` masks the rows of experts not held, and expert
    order keeps the held ones together).  The backward is the same
    sum's: a row's gradient is its gate times its token's, a gate's
    the product of its row with its token's gradient."""
    from jax.experimental.pallas import tpu as pltpu

    n, d = out.shape
    pairs = tok.shape[0]
    rows = math.gcd(n, _COMBINE_ROWS)
    if rows % 8:
        raise ValueError(f"{n} expert rows are no multiple of 8")
    cols = _combine_cols(tokens, d)
    t_pad = -(-tokens // 16) * 16
    pad = (0, -(-n // _SMEM_TILE) * _SMEM_TILE - pairs)
    span = jnp.minimum(jnp.asarray(span, jnp.int32), pairs)

    def live(i, span):
        # The span's i-th block, or its last one where it has no i-th.
        last = jnp.maximum(span[1] - 1, span[0]) // rows
        return jnp.minimum(span[0] // rows + i, last)

    def scalars():
        return pl.BlockSpec(
            (_SMEM_TILE,),
            lambda j, i, span: (live(i, span) * rows // _SMEM_TILE,),
            memory_space=pltpu.SMEM)

    vmem = (t_pad * cols * (4 + 2 * jnp.dtype(dtype).itemsize)
            + 2 * rows * cols * 4)
    y = pl.pallas_call(
        functools.partial(_moe_combine_kernel, rows=rows),
        name="moe_combine",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(d // cols, n // rows),
            in_specs=[pl.BlockSpec((rows, cols),
                                   lambda j, i, span: (live(i, span), j)),
                      scalars(), scalars()],
            out_specs=pl.BlockSpec((t_pad, cols),
                                   lambda j, i, span: (0, j)),
            scratch_shapes=[pltpu.VMEM((t_pad, cols), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((t_pad, d), dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem + (8 << 20)),
        interpret=interpret,
    )(span, out, jnp.pad(tok, pad), jnp.pad(gates, pad))
    return y[:tokens]


def _moe_combine_fwd(out, tok, gates, span, tokens, dtype, interpret):
    return (moe_combine(out, tok, gates, span, tokens, dtype, interpret),
            (out, tok, gates))


def _moe_combine_bwd(tokens, dtype, interpret, res, g):
    out, tok, gates = res
    pairs = tok.shape[0]
    theirs = g.astype(jnp.float32).at[tok].get(mode="promise_in_bounds")
    d_out = jnp.pad(theirs * gates[:, None],
                    ((0, out.shape[0] - pairs), (0, 0)))
    d_gates = jnp.sum(out[:pairs] * theirs, axis=-1)
    return d_out.astype(out.dtype), None, d_gates, None


moe_combine.defvjp(_moe_combine_fwd, _moe_combine_bwd)


# ---------------------------------------------------------------------------
# Delta-rule state step (models.layers.DeltaAttention's decode step)
# ---------------------------------------------------------------------------
# (At the END of the file, as ``moe_combine`` above says of itself.)


def delta_state_step_reference(state, q, k, v, g, beta):
    """One token of the gated delta rule for every lane and head.
    ``state`` [B, H, dk, dv] float32; ``q``, ``k``, ``g`` [B, H, dk]
    (``g`` the log of the decay, a value a key channel, <= 0); ``v``
    [B, H, dv]; ``beta`` [B, H].  Returns ``(state', o [B, H, dv])``::

        S_ = diag(exp g) S;  S' = S_ + beta k (v - S_^T k)^T;  o = S'^T q
    """
    f32 = jnp.float32
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))
    decayed = jnp.exp(g)[..., None] * state
    seen = jnp.sum(k[..., None] * decayed, axis=-2)             # [B,H,dv]
    new = decayed + k[..., None] * (beta[..., None] * (v - seen))[..., None, :]
    return new, jnp.sum(q[..., None] * new, axis=-2)


def _delta_state_kernel(s_ref, cols_ref, v_ref, beta_ref, s_out, o_ref, *,
                        heads: int):
    # One lane: its heads one after another, each head's [dk, dv] state
    # read once and written once.  ``cols`` holds g | k | q of every
    # head as COLUMNS ([dk, 3 x heads]): a value a key channel scales a
    # row of the state, so it must lie along the sublanes.
    for h in range(heads):
        g = cols_ref[0, :, h:h + 1]                             # [dk, 1]
        k = cols_ref[0, :, heads + h:heads + h + 1]
        q = cols_ref[0, :, 2 * heads + h:2 * heads + h + 1]
        decayed = jnp.exp(g) * s_ref[0, h]                      # [dk, dv]
        seen = jnp.sum(k * decayed, axis=0, keepdims=True)      # [1, dv]
        write = beta_ref[0, h:h + 1, :] * (v_ref[0, h:h + 1, :] - seen)
        new = decayed + k * write
        s_out[0, h] = new
        o_ref[0, h:h + 1, :] = jnp.sum(q * new, axis=0, keepdims=True)


def delta_state_step(state, q, k, v, g, beta, *,
                     use_pallas: Optional[bool] = None,
                     interpret: bool = False):
    """``delta_state_step_reference`` as one kernel: a grid step a
    lane, the lane's state (heads x dk x dv float32) read once, decayed,
    corrected and written once IN PLACE (the output aliases ``state``),
    the step's rows beside it.  Bytes a call: 2 x state + the rows."""
    if not _use_pallas(use_pallas) and not interpret:
        return delta_state_step_reference(state, q, k, v, g, beta)
    from jax.experimental.pallas import tpu as pltpu

    lanes, heads, dk, dv = state.shape
    f32 = jnp.float32
    cols = jnp.concatenate(
        [t.astype(f32).transpose(0, 2, 1) for t in (g, k, q)],
        axis=-1)                                      # [B, dk, 3H]: g|k|q
    beta_rows = jnp.broadcast_to(beta.astype(f32)[..., None],
                                 (lanes, heads, dv))

    def lane(*block):
        return pl.BlockSpec((1,) + block,
                            lambda i: (i,) + (0,) * len(block))

    new, o = pl.pallas_call(
        functools.partial(_delta_state_kernel, heads=heads),
        name="delta_state_step",
        grid=(lanes,),
        in_specs=[lane(heads, dk, dv), lane(dk, 3 * heads),
                  lane(heads, dv), lane(heads, dv)],
        out_specs=[lane(heads, dk, dv), lane(heads, dv)],
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((lanes, heads, dv), f32)],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=4 * heads * dk * dv * 4 + (8 << 20)),
        interpret=interpret,
    )(state, cols, v.astype(f32), beta_rows)
    return new, o
