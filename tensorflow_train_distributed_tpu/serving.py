"""Continuous-batching serving engine (slot-refill decode).

``generate()`` (models/generate.py) serves one static batch: every
request waits for the slowest.  ``ServingEngine`` keeps ``slots``
requests in flight over ONE static-shaped decode program and refills a
lane the moment its request finishes (JetStream / Orca style).  Slots
change *when* work happens, never the math: per-slot positions give a
request the RoPE/mask view it would have alone, so greedy output equals
``generate()``'s token for token, and a sampled request draws from its
own rng stream (seeded at submit) wherever it lands.

**A lane's state.**  A slot is free, STAGED or DECODING.

- Staged (``_PrefillTask`` in ``_staging``): the slot is reserved
  while the request's batch-1 LINEAR cache is built piece by piece
  (``_prefill_piece``; bucketed lengths, or ``prefill_chunk``-token
  pieces of one program at any prompt length, the consecutive pieces
  of one prompt that ride one step as ONE call where the head request
  has as many left as the step's budget holds; a dense-dispatch MoE
  prefills whole at its exact length, since router capacity depends on
  it).  A speculative engine then builds the draft's cache over the
  same piece grid.  The last target call yields the first token, on
  the device.
- Decoding (``_SlotState`` in ``_slot_states``): the finished batch-1
  rows were inserted into the slot grid (``_paged_insert``); the host
  holds the request's tokens as far as it has read them, its remaining
  budget and its rng counter; the device holds the grid cache and the
  CARRY, each slot's next input token and rng counter, which never
  come to the host.  A new lane's first token is PENDING: spliced into
  the carry from where its prefill left it, and read by the host at a
  harvest a step or two later (``_read_picks``).

**A step** (``serve_step``, one ``engine/step`` span), in order:

1. *Dispatch* (``_dispatch_chunk``): when a lane is decoding, enqueue
   the next decode chunk (``_decode_chunk``: ``chunk`` steps for all
   slots; or one ``_spec_round``) from the device-resident carry,
   BEFORE the chunk already in flight is read.  JAX dispatch is
   asynchronous, so the successor queues behind its predecessor and the
   device stays busy through everything below.  Refilled slots have
   their first token (a device scalar) and their host-known counter
   spliced over the carry (``_carry_arrays``); stale lanes' tables are
   pointed at the scratch block first (``_flush_stale_lanes``).
   Skipped when every active lane certainly retires in the chunk in
   flight (``_skip_eager_dispatch``).
2. *Admit* (``_advance_prefills``): claim free slots for queued
   requests (``_stage_from_queue``: host bookkeeping only) and advance
   staged prefills in arrival order by at most ``prefill_budget``
   prompt tokens (default: one piece), enqueued BEHIND the chunk just
   dispatched: the gap admission adds to a decoding lane is bounded by
   the budget, and a long prompt spreads over steps.  With no lane
   decoding there is nobody to delay and the budget is waived.  A
   prompt's last piece leaves its first token on the device and the
   lane is inserted behind it: nothing here reads from the device.
3. *Harvest* (``_harvest_prev``): read the PREVIOUS chunk's tokens
   (this blocks until that chunk is done) and, in the same wait, the
   pending first tokens that have run by then: those the chunk was
   dispatched behind, and any other that is ready.  Append them to
   their requests, stop on budget or EOS, retire finished lanes.  This
   is the step's ONE read of the device, and while a lane decodes it
   waits for a program that has a successor queued behind it (the
   chunk step 1 dispatched), never for the newest.  Stop and
   refill decisions therefore lag one chunk: a slot whose request
   finished keeps decoding garbage through the successor; each chunk
   records which request held each slot at dispatch and the harvest
   drops what belongs to a previous tenant.
4. *Restage* freed lanes, and dispatch now if step 1 did not (first
   step of a session, a harvest-first step, restart after idle).
5. Hand back the requests that finished, so callers can ``submit()``
   between steps; while a lane decodes, one chunk stays in flight
   across EVERY return, also of a step that finished a prompt.  A
   client sees a request's first token after the first harvest that
   finds it run: with the first chunk that carried its lane at the
   latest.

**Paged KV cache with cross-request prefix sharing** (the slot grid's
one layout): KV rows live in one pool of
``kv_block_size``-row blocks per layer, and a lane maps its positions
through a block table (``serving_kv``: allocator, refcounts, a radix
tree over token ids at block granularity).  A lane holds
``ceil((prompt + max_new) / block_size)`` blocks, admission keys on
FREE BLOCKS (a request that cannot get them waits in the queue), and
requests whose prompts share a block-aligned prefix map their leading
table entries to the same blocks and prefill only the suffix (the
matched rows are gathered into the batch-1 cache).  The radix index is
fed at insert and retire; retired blocks stay cached until LRU eviction
reclaims them.  ``preload_prefix`` warms a shared prefix by hand.

Shapes are static everywhere (slots, cache rows, chunk, prompt buckets
or pieces, pool and tables); only cache contents and the per-slot index
vector change, so a handful of programs serve a whole session.

Scope: the decoder families ``generate()`` serves (Llama, Mixtral-style
and latent-attention MoE), greedy or sampled; int8 weight-only serving
via ``quant_scales``; ``kv_cache_int8`` configs (int8 rows + per-row
f32 scales, in the batch-1 cache and the pool alike); tensor-parallel
serving via ``mesh=``; speculative decoding with a draft model at a
fixed or adaptive depth.  LoRA-unmerged params, attention sinks and the
dense family's one global ``sliding_window`` keep the shared-index
``generate()`` path.

**Window and full attention layers side by side** (a ``MoeConfig`` with
``attn_period`` after ``attn_lead``): two kinds of cache in the one
slot grid, each kind with its own row (``KvKind.num_kv_heads``; a key
and a value head of different sizes make a key and a value pool of
different widths).  A full layer
holds a lane's whole context in the blocks of its table, as above.  A
window layer holds, in a pool of its own, a RING of ``ring_blocks``
blocks a lane (``ceil(window / kv_block_size) + 1``: a decode step adds
one row, and a window that starts mid-block reaches one block more;
position ``p`` in ring entry ``(p // block_size) % ring_blocks``): its memory and a decode step's reads are bounded by the
window whatever the context (``paged_blocks_walked`` with a first
block).  A LATENT layer with a window (``OwnLatentKind``) keeps its
latent rows in such a ring too, one ``latent_pool`` of ``1 + slots x
ring_blocks`` blocks at ITS kind's row width under a ``window_table``,
beside the full latent layers' pool (at theirs) and its index keys:
every pool, ring, batch-1 cache leaf and ring copy is sized by the
layer's own row, read off the cache tree (``_ringed_modules``,
``_paired_leaves``, ``kv_pool_parts``).  Slot ``s`` owns ring
``s``: admission keys on the full layers' free blocks and on a free slot, and a retired lane's ring table points
at the scratch block like its block table.  The batch-1 prefill cache
keeps every row in both kinds of layer (a piece of a window layer walks
the tiles its window reaches: ``prefix_first_tile``) and the insert
copies the last ``ring_blocks`` blocks into the ring.  Rows behind a
window are gone and cannot be rebuilt without running every layer below
over them, so such an engine shares no prefix: no radix match,
``preload_prefix`` raises, KV export ships nothing (the receiver
prefills).

**Recurrent layers beside layers of rows** (a ``MoeConfig`` whose
``attn_period`` names "linear" kinds: ``layers.DeltaAttention``): a
third kind of cache in the one slot grid, a leaf that holds no rows.  A
linear layer keeps, a lane, one float32 state ``[heads, d, d]`` and the
last rows its short convolution reaches back to (``_STATE_LEAVES``),
whatever the context: ``[slots, ...]`` in the grid, ``[1, ...]`` in the
batch-1 prefill cache, which carries them from call to call.
``_paged_insert`` writes a slot's state and tail WHOLE from the batch-1
cache, so nothing of the last occupant survives; no table maps them and
no length walks them.  A positional cache forgives a row that is run
twice or a pad row that is written; a state does not: ``_prefill_piece``
tells the layers how many of a call's trailing rows are padding
(``pad_rows``) and they leave the state where the last REAL token left
it.  Every real row of a prompt runs once (pieces tile ``work`` without
overlap) and every decoded token once (the carry chains chunks; a
refilled slot's first input is spliced in from its prefill's
output).  The state at a
prefix's end is not kept, so such an engine shares no prefix (no radix
match, ``preload_prefix`` raises, KV export ships nothing and install
installs nothing: the receiver prefills), and it refuses a draft model
and a mesh.

**Fused paged attention** (TPU): the paged decode read is one Pallas
kernel (``ops.pallas_kernels.paged_attention``) that attends through
the block table; the dense per-lane copy ``paged_kv_gather`` would
make never exists.  CPU and sharded (``mesh=``) serving gather then
attend, as does ``TTD_NO_PALLAS=1`` (the A/B leg; set BEFORE engine
construction: the choice compiles into the decode programs).
"""

from __future__ import annotations

import dataclasses
import logging
import os
import time
from collections import deque
from functools import partial
from typing import Optional

import contextlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger(__name__)

from tensorflow_train_distributed_tpu.runtime import compat, events
from tensorflow_train_distributed_tpu.runtime.lint import memcheck
from tensorflow_train_distributed_tpu.runtime.lint.registry import (
    compile_site,
    concurrency_guarded,
    dispatch_critical,
    memory_budget,
    thread_role,
)
from tensorflow_train_distributed_tpu import serving_kv
from tensorflow_train_distributed_tpu.models.generate import (
    _decode_model,
    cast_floating,
    filter_logits,
    has_lora_leaves,
    validate_sampling,
)
from tensorflow_train_distributed_tpu.models.layers import (
    flash_walk_layers,
    latent_walk_sizes,
)
from tensorflow_train_distributed_tpu.models.quant import (
    check_quant_pairing,
    maybe_quant_variables,
    quantized_inference,
)
from tensorflow_train_distributed_tpu.ops import attention as attention_ops
from tensorflow_train_distributed_tpu.ops.pallas_kernels import (
    paged_blocks_walked,
)  # with a window: from ``paged_first_block`` on


@dataclasses.dataclass
class _SlotState:
    request_id: int
    remaining: int                 # generated tokens still allowed
    tokens: list                   # prompt + generated, as far as read
    seed: int = 0                  # per-request sampling stream
    count: int = 1                 # tokens sampled so far (rng counter)
    done: bool = False
    # The first token where the last prefill piece left it, a device
    # scalar, until a harvest has read it (``_read_picks``); then None.
    # ``remaining`` and ``count`` include it from the insert on,
    # ``tokens`` from its read on.
    first: object = None


@dataclasses.dataclass(eq=False)
class _AwaitedPick:
    """A request of ONE token between its prefill and the read of that
    token (``_read_picks``): it takes no lane and holds no blocks."""

    request_id: int
    prompt: list
    first: object                  # device scalar


@dataclasses.dataclass
class _PrefillTask:
    """A request whose prefill is staged across ``serve_step``
    iterations: the slot is RESERVED (no other request can claim it)
    while the batch-1 cache is built piece by piece under the prefill
    budget.  ``cursor``/``d_cursor`` count completed target/draft
    pieces; the caches start ``None`` so staging itself does zero
    device work (pure host bookkeeping)."""

    request_id: int
    prompt: list
    max_new: int
    seed: int
    work: list                     # suffix after any matched prefix
    padded: np.ndarray             # [1, piece * n_pieces] token ids
    piece: int
    n_pieces: int
    resume: int = 0                # rng counter of the first pick
    pre_pair: Optional[tuple] = None   # matched stored prefix pair
    cursor: int = 0                # target pieces completed
    cache_1: object = None         # target batch-1 cache in progress
    first: object = None           # device pick after the last piece
    d_cursor: int = 0              # draft pieces completed
    d_cache_1: object = None
    kv: object = None              # serving_kv.LaneKV claim
    table: object = None           # np.int32 [n_blk] physical block row


def _adaptive_spec_killed() -> bool:
    """``TTD_NO_ADAPTIVE_SPEC=1`` pins the draft depth back to the
    fixed ``speculative_k`` bitwise (the controller is never built;
    every round runs the same static-k program a fixed engine runs).
    Read at construction: an env flip needs no redeploy of callers."""
    return os.environ.get("TTD_NO_ADAPTIVE_SPEC", "0") not in ("", "0")


def _hbm_autosize_killed() -> bool:
    """``TTD_NO_HBM_AUTOSIZE=1`` makes ``kv_pool_blocks='auto'`` fall
    back to the default heuristic (slots x lanes) with no budget set —
    bitwise the hand-tuned engine's defaults.  Read at construction."""
    return os.environ.get("TTD_NO_HBM_AUTOSIZE", "0") not in ("", "0")


def _device_hbm_bytes() -> Optional[int]:
    """Per-device memory capacity in bytes, for the autosize solve:
    ``TTD_HBM_BYTES=<bytes>`` overrides (tests and CPU hosts, where
    jax reports no limit); otherwise the first local device's
    ``memory_stats()['bytes_limit']`` (TPU/GPU backends report it;
    CPU typically returns None → the caller refuses with a clear
    error instead of guessing)."""
    env = os.environ.get("TTD_HBM_BYTES", "")
    if env not in ("", "0"):
        return int(env)
    dev = jax.local_devices()[0]
    stats = getattr(dev, "memory_stats", lambda: None)()
    if not stats:
        return None
    return int(stats.get("bytes_limit", 0) or 0) or None


#: Row-holding cache leaves: the paged pool's name -> (the batch-1
#: linear cache's name, dims a row has in the pool, dims it has in the
#: linear cache).  A linear leaf is [..., B, C, *row], a pool leaf
#: [..., blocks, block_size, *row] (leading axes: a layer axis under
#: scan_layers; the int8 scales' 2).  A pool stores a per-head K or V
#: row as the attention kernel copies it, its kv_heads * head_dim
#: values side by side (``layers.paged_pool_leaves``), where the linear
#: cache keeps [kv_heads, head_dim]: the same values in the same order.
#: Their int8 scales are [kv_heads] on both sides, a latent-attention
#: row [row] and the index key of its learned selection [index_dim]
#: (``layers.LatentAttention``: two kinds of row a token and layer).
_ROW_LEAVES = {"key_pool": ("key_cache", 1, 2),
               "value_pool": ("value_cache", 1, 2),
               "kv_pool_scales": ("kv_scales", 1, 1),
               "latent_pool": ("latent_cache", 1, 1),
               "index_pool": ("index_cache", 1, 1)}
#: Cache leaves that hold a lane's recurrent state and no rows
#: (``layers.DeltaAttention``): [..., B, *state] in the grid and in the
#: batch-1 cache alike, the batch axis first of the leaf's own dims;
#: an insert copies them whole.  ``pad_rows`` is the call's argument to
#: those layers (``_prefill_piece``), not state.
_STATE_LEAVES = {"delta_state": 3, "conv_tail": 2}
_LINEAR_ROW_DIMS = {lin: dims for lin, _, dims in _ROW_LEAVES.values()}
_POOL_OF = {lin: pool for pool, (lin, _, _) in _ROW_LEAVES.items()}


#: What an ``engine/step`` span says its step did: lanes active at the
#: dispatch, the cached positions they held (as the host knew them),
#: the blocks of the paged pool those reach over all slots (what the
#: fused attention kernel reads) and the block table's whole size
#: (slots x blocks a lane, what it read before it followed lengths),
#: the blocks ONE window layer's walk of its rings reaches over all
#: slots (``kv_window_blocks``: the same rule from the window's first
#: block on; 0 for a model without window layers),
#: prefill pieces run and the prompt tokens they carried, output tokens
#: handed to requests.  A step that harvests a decode chunk of a model
#: with routed experts adds, as means over the chunk's steps and expert
#: layers, the experts that took at least one row (``experts_hit``:
#: what the grouped matmuls read) and the rows' coefficient of
#: variation over the experts (``expert_load_cv``), both over the
#: experts the layer holds; where it holds a share of them, how many
#: (``experts_held``) and the share of the step's (token, choice) pairs
#: that fell on them (``routed_here``); where attention chooses its
#: rows, the rows a step and layer scored and attended over its live
#: lanes (``rows_scored``, ``rows_selected``): ``_count_sown``.
#: ``pieces`` counts prefill pieces (``_pieces_for``'s), ``piece_calls``
#: the piece programs launched for them: one call runs one piece, or
#: the budget's worth of consecutive pieces of one prompt
#: (``_advance_piece``, ``_piece_counts``).
#: ``kv_bytes``: ``kv_blocks`` in bytes, over the layers whose blocks
#: the allocator hands out (``bytes_per_block``; a window layer's rings
#: apart).  ``state_bytes``: the bytes of recurrent state the live
#: lanes hold over the model's linear layers (lanes x a lane's state
#: and tail x layers; 0 for a model without them), beside them.
#: ``first_deferred``: the prompts whose last piece the step enqueued,
#: each leaving its first token on the device for a harvest to read
#: (``_advance_piece``, ``_read_picks``); ``committed`` counts a first
#: token in the step whose harvest read it.
#: ``starved_ms`` / ``drains``: the milliseconds, and the times, the
#: device's queue was known empty while the engine had work
#: (``_launch``, ``_poll_drained``); ``away_ms``: from the previous
#: step's exit to this one's entry, the caller's pass between them.
_STEP_COUNTS = ("lanes", "positions", "kv_blocks", "kv_table_blocks",
                "kv_window_blocks", "kv_bytes", "state_bytes", "pieces",
                "piece_calls",
                "prefill_tokens", "first_deferred", "committed",
                "starved_ms", "drains", "away_ms")


def _ffn_passes(next_fun, args, kwargs, context):
    """Method interceptor for a trace that asks for the cache's shapes
    alone (``_cache_struct``): the cache's leaves are the attention
    layers', and a feed-forward block between them (dense or routed)
    hands on its input's shape, so its own trace, the larger part of a
    layer's, is left out."""
    from tensorflow_train_distributed_tpu.models.layers import MlpBlock
    from tensorflow_train_distributed_tpu.models.moe import MoEMlpBlock

    if (context.method_name == "__call__"
            and isinstance(context.module, (MlpBlock, MoEMlpBlock))):
        return args[0]
    return next_fun(*args, **kwargs)


def _bucket_len(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"prompt length {n} exceeds the largest prefill "
                     f"bucket {buckets[-1]}")


@concurrency_guarded
class ServingEngine:
    """Continuous-batching decoder over a fixed slot grid.

    ``submit()`` requests, then ``run()`` to completion.  Greedy by
    default — output token-identical to ``generate(config, params,
    prompt, max_new)`` greedy (pinned by tests/test_serving.py); with
    ``temperature``/``top_k``/``top_p`` set, each request samples from
    its OWN rng stream (seeded at submit), so sampled outputs are
    reproducible and independent of slot placement.  Either way slots
    only change *when* work happens, never the math: per-slot positions
    give every request the same RoPE/mask view it would have alone.
    """

    # The engine is single-threaded (the driver loop owns every
    # mutating call) EXCEPT these cross-thread surfaces.  The prefix
    # stores: handler threads validate while the driver LRU-touches —
    # every access locks (the PR 6 review-pass bug, now enforced).
    # The stats dicts: single-writer on the driver/offline loop (which
    # reads its own writes lock-free — the owner-role exemption), but
    # scrape-thread readers (`/metrics` FnCounters and gauges sampling
    # ``kv_prefix_hit_tokens``/``overlap_ratio``/... at scrape time)
    # take ``_stats_lock``, and every WRITE takes it too so a scrape
    # between the fields of one logical update (hits vs hit_tokens;
    # harvest_s vs overlapped_harvest_s) can no longer observe a torn
    # pair.
    _GUARDED_BY = {
        "_prefix_caches": ("_prefix_lock",),
        "kv_stats": ("_stats_lock", "driver", "main"),
        "prefill_stats": ("_stats_lock", "driver", "main"),
        "overlap_stats": ("_stats_lock", "driver", "main"),
        "spec_stats": ("_stats_lock", "driver", "main"),
        "_spec_ctrl": ("_stats_lock", "driver", "main"),
    }

    def __init__(self, config, params, *, slots: int = 8,
                 cache_len: Optional[int] = None, eos_id: Optional[int] = None,
                 chunk: int = 8, cast_params: bool = True,
                 quant_scales=None, mesh=None, rules=None,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 prefill_chunk: Optional[int] = None,
                 draft_config=None, draft_params=None,
                 draft_quant_scales=None,
                 speculative_k: int = 0,
                 spec_depths=None,
                 prompt_buckets=(32, 64, 128, 256, 512, 1024),
                 prefill_budget: Optional[int] = None,
                 kv_block_size: int = 16,
                 kv_pool_blocks=None,
                 prefix_cache_limit: int = 32,
                 hbm_budget_bytes: Optional[int] = None,
                 hbm_headroom: float = 0.1):
        # kv_cache_int8 configs SERVE here (the batch-1 cache and the
        # pool both quantize with the linear-cache recipe), and so do
        # window layers that a MoeConfig's ``attn_period`` names (a ring
        # of blocks a lane, below).  The dense family's ONE global
        # ``sliding_window`` and attention sinks (StreamingLLM: the
        # first ROWS kept attendable past the window) stay
        # generate()-only: their rolling cache and sink buffer have no
        # per-slot form yet.  A learned sink LOGIT in a window layer's
        # softmax (``KvKind.sink``) is another thing, a float a head
        # with no row behind it, and is served.
        if (getattr(config, "sliding_window", None) is not None
                or getattr(config, "attention_sinks", 0)):
            raise ValueError(
                "the serving engine holds a window layer's rows in a "
                "ring only where the config names its layers' kinds "
                "(MoeConfig.attn_period); a LlamaConfig's global "
                "sliding_window and attention_sinks (StreamingLLM sink "
                "ROWS kept past the window) serve through "
                "models.generate; a learned sink LOGIT in a window "
                "layer's softmax (KvKind.sink) holds no row and is "
                "served (kv_cache_int8 is supported here)")
        if has_lora_leaves(params):
            raise ValueError(
                "merge LoRA adapters before engine serving: params = "
                "models.lora.merge_lora(params, spec)")
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        # Sampling config is engine-level (a deployment knob, static in
        # the compiled programs); the rng stream is PER REQUEST (seeded
        # at submit) so outputs are reproducible regardless of slot
        # placement or chunk boundaries.  One shared validator with
        # generate().
        validate_sampling(temperature, top_k, top_p)
        self.temperature = float(temperature)
        self.top_k = top_k
        self.top_p = top_p
        self._greedy = temperature == 0.0
        self.config = config
        # Rows a query of the target's (False) and the draft's (True)
        # attention chooses among those it sees; 0: no selection.
        self._index_topk = {
            is_draft: getattr(c, "index_topk", 0)
            for is_draft, c in ((False, config), (True, draft_config))}
        # Their attention's latent sizes, for ``_flash_layers``; None:
        # plain K/V rows.
        self._latent_sizes = {
            is_draft: latent_walk_sizes(c)
            for is_draft, c in ((False, config), (True, draft_config))}
        self.slots = slots
        self.cache_len = cache_len or config.max_positions
        if self.cache_len > config.max_positions:
            raise ValueError(
                f"cache_len {self.cache_len} exceeds max_positions "
                f"{config.max_positions}")
        self.eos_id = eos_id
        self.chunk = chunk
        # HBM budget (memcheck, the third lint vertical): the byte
        # ceiling this engine's declared pools — grid KV pools, staged
        # prefill caches, stored prefix pairs — are held to.  None =
        # track-only: the ``TTD_MEMCHECK=1`` sanitizer still ledgers
        # every pool (the ttd_engine_hbm_bytes{pool=...} gauge feed)
        # but never raises; with a budget set, the allocation that
        # would exceed it raises MemoryBudgetError with the live set
        # diffed, and validate_request refuses admissions whose
        # projected bytes cannot fit (alongside the free-blocks
        # check).
        if hbm_budget_bytes is not None and hbm_budget_bytes < 1:
            raise ValueError(
                f"hbm_budget_bytes must be >= 1, got {hbm_budget_bytes}")
        self.hbm_budget_bytes = hbm_budget_bytes
        self._prefill_bytes_memo: Optional[int] = None
        # Dense-dispatch MoE prefill must run at the EXACT prompt
        # length: the router's per-group capacity is ⌈cf·k·S/E⌉ — a
        # bucket-padded S changes the capacity constant, so drop
        # behavior (and therefore tokens) would diverge from
        # generate()'s unpadded prefill.  Exact lengths cost one prefill
        # compile per distinct length instead of per bucket (and the
        # buckets are never consulted) — the engine warns per new
        # length.  dispatch="gmm" (dropless) routes every token
        # independently with no capacity competition, so pad tokens
        # cannot perturb real ones — bucketed AND chunked prefill stay
        # exact there (parity-pinned in tests/test_serving.py).
        from tensorflow_train_distributed_tpu.models.moe import MoeConfig

        self._exact_prefill = (isinstance(config, MoeConfig)
                               and config.dispatch != "gmm")
        # The sliding window of the config's window layers (None: it
        # has none): derived from the config, no flag.
        self._window = getattr(config, "attn_window", None)
        if self._window is not None and draft_config is not None:
            raise ValueError(
                "speculative decoding beside window layers is not served "
                "yet (a verify block's rollback over a ring is untested)")
        if getattr(draft_config, "attn_window", None) is not None:
            raise ValueError("a draft with window layers is not served")
        # Layers that keep a recurrent state a lane and no rows
        # (``MoeConfig.recurrent_layers``; 0: none).
        self._state_layers = getattr(config, "recurrent_layers", 0)
        if self._state_layers or getattr(draft_config,
                                         "recurrent_layers", 0):
            if draft_config is not None:
                raise ValueError(
                    "speculative decoding (draft_config) beside recurrent "
                    "layers is not served: a rejected draft token has "
                    "already moved the state, and no snapshot is kept to "
                    "roll it back to")
            if mesh is not None:
                raise ValueError(
                    "sharded serving (mesh=) beside recurrent layers is "
                    "not served: the state step is a single-device kernel "
                    "and the state's sharding rules are not written")
        # Whether requests may share cached prefix rows (the radix
        # index, preloaded pairs, KV handoff).  Not where routing
        # depends on the prefill's length, not beside window layers,
        # whose rows behind the window are gone, and not beside
        # recurrent layers, whose state at a prefix's end is not kept.
        self._share_prefix = (not self._exact_prefill
                              and self._window is None
                              and not self._state_layers)
        # Chunked prefill: long prompts run through the SAME per-piece
        # program in ``prefill_chunk``-token pieces (the decode cache
        # appends multi-token blocks at any position), bounding prefill
        # memory/compile variety to one chunk shape.  MoE must prefill
        # whole (per-chunk routing capacity would diverge from
        # generate()'s full-prompt prefill — the exact-length rule).
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError(
                    f"prefill_chunk must be >= 1, got {prefill_chunk}")
            if self._exact_prefill:
                raise ValueError(
                    "prefill_chunk is unsupported for dense-dispatch "
                    "MoE configs: the router's per-group capacity "
                    "depends on the prefill length, so chunking would "
                    "change routing vs generate() (dense MoE prefills "
                    "at the exact length; dispatch='gmm' is dropless "
                    "and supports chunked/bucketed prefill)")
        self.prefill_chunk = prefill_chunk
        self.prompt_buckets = tuple(
            b for b in sorted(prompt_buckets) if b <= self.cache_len)
        if (not self.prompt_buckets and not self._exact_prefill
                and prefill_chunk is None):
            raise ValueError("no prompt bucket fits cache_len")
        # int8 weight-only serving: same pairing contract as generate()
        # (one shared check), and every Dense runs the fused dequant
        # path via the (free when inactive) quantized_inference
        # interceptor.
        check_quant_pairing(params, quant_scales)
        if cast_params:
            params = cast_floating(params, config.dtype)
        self._variables = maybe_quant_variables(params, quant_scales)
        # Paged KV cache.  The pool is sized in BLOCKS: by default
        # slots * ceil(cache_len / block_size), every lane's whole
        # context; operators shrink/grow it with ``kv_pool_blocks``
        # (admission then keys on free blocks, not free slots).
        if kv_block_size < 1:
            raise ValueError(
                f"kv_block_size must be >= 1, got {kv_block_size}")
        self.kv_block_size = int(kv_block_size)
        self._kv_nblk_lane = -(-self.cache_len // self.kv_block_size)
        # ``kv_pool_blocks="auto"``: solve the pool size + HBM budget
        # exactly from the device's reported memory and the memcheck
        # projection (pool rows + batch-1 prefill transients + draft
        # pools + ``hbm_headroom``) — one binary lands correctly sized
        # on any chip.  The solve itself is DEFERRED below the draft
        # section: it eval_shapes BOTH models' caches, so both
        # variable trees must exist first.  ``TTD_NO_HBM_AUTOSIZE=1``
        # falls back to the default heuristic with no budget set —
        # bitwise the hand-tuned defaults.
        if not 0.0 <= hbm_headroom < 1.0:
            raise ValueError(
                f"hbm_headroom must be in [0, 1), got {hbm_headroom}")
        self._hbm_headroom = float(hbm_headroom)
        self._hbm_autosized = 0
        autosize = kv_pool_blocks == "auto"
        if autosize:
            if hbm_budget_bytes is not None:
                raise ValueError(
                    "kv_pool_blocks='auto' solves hbm_budget_bytes "
                    "itself; pass one or the other")
            if _hbm_autosize_killed():
                autosize = False
                kv_pool_blocks = None
        elif isinstance(kv_pool_blocks, str):
            raise ValueError(
                f"kv_pool_blocks must be an int or 'auto', got "
                f"{kv_pool_blocks!r}")
        if not autosize:
            if kv_pool_blocks is None:
                kv_pool_blocks = slots * self._kv_nblk_lane
            if kv_pool_blocks < 1:
                raise ValueError(
                    f"kv_pool_blocks must be >= 1, got {kv_pool_blocks}")
        # kv_stats counts ENGINE-visible cache economics (the /metrics
        # feed): tokens of prefill skipped via radix prefix hits,
        # blocks LRU-evicted under allocation pressure, and admissions
        # refused for want of blocks.
        self.kv_stats = {"prefix_hit_tokens": 0, "prefix_hits": 0,
                         "evictions": 0, "alloc_refusals": 0}
        # Prefill always runs batch-1 on a LINEAR cache (prefix reuse
        # replaces recompute with a pool gather, never changes the
        # math); the slot-grid decode/verify/insert programs are paged.
        # Its attention walks a call's queries a piece at a time
        # (``query_block``), so a call over several pieces of a prompt
        # (``_advance_piece``) costs what the pieces cost.
        self._prefill_model = _decode_model(
            config, self.cache_len, slot_decode=True,
            query_block=prefill_chunk or 0)
        # (the slot-grid decode model is built below, once
        # kv_pool_blocks has resolved — possibly via the autosize
        # solve, which needs the draft variables prepared first)
        # Speculative decoding across ALL slots: each round the draft
        # proposes k tokens per slot, the target verifies the k+1 block
        # in one call, and each slot accepts its own prefix — the
        # per-slot cache index makes the rollback a per-slot index
        # decrement (the library path, models/speculative.py, is batch-1
        # precisely because the shared-index cache cannot do this).
        self._spec_k = int(speculative_k)
        self._draft_model = None
        if (draft_config is None) != (draft_params is None):
            raise ValueError("draft_config and draft_params come together")
        if draft_quant_scales is not None and draft_config is None:
            raise ValueError("draft_quant_scales needs draft_config/params")
        if self._spec_k and draft_config is None:
            raise ValueError("speculative_k needs draft_config/params")
        if draft_config is not None:
            if self._spec_k < 1:
                raise ValueError(
                    f"draft_config needs speculative_k >= 1, got "
                    f"{self._spec_k}")
            if getattr(draft_config, "attention_sinks", 0):
                # Same screen as the target's: a bad draft config would
                # otherwise crash inside run(), aborting in-flight work.
                # (kv_cache_int8 drafts serve — same caches as the
                # target's.)
                raise ValueError(
                    "the draft uses the per-slot caches too; "
                    "attention_sinks draft configs are unsupported")
            from tensorflow_train_distributed_tpu.models.speculative import (
                _reject_config,
            )

            _reject_config("target", config)
            _reject_config("draft", draft_config)
            if draft_config.vocab_size != config.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_config.vocab_size} != target "
                    f"vocab {config.vocab_size}")
            if has_lora_leaves(draft_params):
                raise ValueError("merge the draft's LoRA adapters first")
            # int8 weight-only serving composes with speculation (the
            # production pairing: decode is weight-HBM-bound on BOTH
            # models) — each tree carries its own scales, same pairing
            # contract as the target's.  Acceptance is defined against
            # the quantized target's own distribution, so greedy stays
            # token-identical to int8 generate() and sampled keeps the
            # int8 target's law.
            check_quant_pairing(draft_params, draft_quant_scales)
            if cast_params:
                draft_params = cast_floating(draft_params,
                                             draft_config.dtype)
            self._draft_variables = maybe_quant_variables(
                draft_params, draft_quant_scales)
            # The draft shares the TARGET's block tables (its lanes'
            # logical layouts are identical — both caches hold the same
            # row sets by the speculative invariant), so one allocation
            # covers both pools; only the pool row shapes differ.
            self._draft_prefill_model = _decode_model(
                draft_config, self.cache_len, slot_decode=True,
                query_block=prefill_chunk or 0)
        # Acceptance-adaptive speculation (opt-in): precompiled
        # draft-depth buckets + a host-side controller that SELECTS
        # among them per round from measured acceptance — it never
        # changes any program's math (forced-depth parity pinned in
        # tests/test_spec_adaptive.py).  ``TTD_NO_ADAPTIVE_SPEC=1``
        # pins the fixed ``speculative_k`` program bitwise.
        self._spec_ctrl = None
        if spec_depths is not None:
            if draft_config is None:
                raise ValueError("spec_depths needs draft_config/params")
            if not _adaptive_spec_killed():
                from tensorflow_train_distributed_tpu.models.speculative import (  # noqa: E501
                    DepthController,
                )

                self._spec_ctrl = DepthController(spec_depths)
        # ── deferred pool sizing + slot-grid decode models ──
        if autosize:
            kv_pool_blocks, budget = self._solve_hbm_autosize(
                config, draft_config)
            self.hbm_budget_bytes = budget
            self._hbm_autosized = budget
        # Blocks of a window layer's ring a lane: the window of the
        # newest of the rows ONE model call adds (a decode step adds
        # one: speculation beside window layers is refused above, and
        # a chunk's steps add theirs one after another), and one block
        # more for a window that starts mid-block.  No prefill piece
        # writes a ring: pieces run on the batch-1 cache, which keeps
        # every row, and the insert copies the ring's blocks from it.
        self._ring_blocks = 0
        if self._window is not None:
            q_len = 1
            self._ring_blocks = 1 + -(-(self._window + q_len - 1)
                                      // self.kv_block_size)
        self._kv_pool = serving_kv.KVBlockPool(
            kv_pool_blocks, self.kv_block_size)
        self._radix = serving_kv.RadixPrefixIndex(self._kv_pool)
        self._model = _decode_model(
            config, self.cache_len, slot_decode=True,
            paged_kv_blocks=1 + kv_pool_blocks,
            kv_block_size=self.kv_block_size,
            ring_blocks=self._ring_blocks)
        if draft_config is not None:
            self._draft_model = _decode_model(
                draft_config, self.cache_len, slot_decode=True,
                paged_kv_blocks=1 + kv_pool_blocks,
                kv_block_size=self.kv_block_size)
        # Sharded serving: with a mesh, every device call runs under
        # jax.set_mesh + the logical-axis rules, so the models' logical
        # constraints shard weights/cache/activations (e.g. heads over
        # ``tensor``) exactly as in training — GSPMD inserts the
        # collectives; the engine's host logic is unchanged.  ``rules``
        # mirrors Trainer(..., rules=): pass the training-time rules so
        # serving shards the way the model trained (None = defaults).
        self._mesh = mesh
        self._rules = rules
        self._queue: deque = deque()
        self._outputs: dict = {}
        self._next_id = 0
        self._slot_states: list[Optional[_SlotState]] = [None] * slots
        self._cache = None  # built lazily on first insert (needs params)
        self._d_cache = None               # draft slots (speculative)
        # "rounds" counts ENGINE rounds (one _spec_round call);
        # "slot_rounds" counts active slots across them — the
        # denominator for acceptance rates (accepted/(slot_rounds·k)).
        self.spec_stats = {"rounds": 0, "slot_rounds": 0,
                           "drafted": 0, "drafted_accepted": 0,
                           "emitted": 0}
        self._cache_shapes: dict = {}  # (draft, batch, grid) -> eval_shape
        self._flash_layer_counts: dict = {}   # (draft, q_len) -> layers
        self._moe_prefill_lens: set = set()  # distinct exact-prefill lens
        # Stored batch-1 prefix pairs (``preload_prefix``; they cover
        # the sub-block tail the radix index cannot): LRU-BOUNDED —
        # keyed by tuple(tokens), these
        # hold device memory, and an unbounded dict leaks under many
        # distinct preloaded prefixes.  ``prefix_cache_limit`` caps the
        # entries; preload past it evicts the least recently matched.
        if prefix_cache_limit < 1:
            raise ValueError(f"prefix_cache_limit must be >= 1, got "
                             f"{prefix_cache_limit}")
        self.prefix_cache_limit = prefix_cache_limit
        from collections import OrderedDict
        self._prefix_caches: OrderedDict = OrderedDict()
        # The ONE engine structure gateway handler threads READ while
        # the driver thread writes: validate_request scans the prefix
        # stores concurrently with admission's LRU touches / preload's
        # eviction, and an OrderedDict mutated mid-iteration raises in
        # the READER.  Everything touching _prefix_caches
        # holds this lock (admission's hold is nanoseconds — dict
        # walks, never device work).
        import threading
        self._prefix_lock = threading.Lock()
        # Guards the stats dicts' cross-thread consistency: writes on
        # the driver loop are per-admission/per-step (never per-token),
        # scrape-thread readers (`/metrics` callables) take it so a
        # multi-field update is observed whole.  Declared in
        # ``_GUARDED_BY`` above; ttd-lint enforces the discipline.
        self._stats_lock = threading.Lock()
        # Per-lane claims and admission bookkeeping:
        # _lane_kv[slot] holds the LaneKV while the lane decodes;
        # _stale_slots are lanes retired/cancelled since the last
        # dispatch — their block-table rows must be zeroed (pointed at
        # the scratch block) BEFORE the next decode program runs, or
        # the one garbage chunk a retired lane still decodes would
        # write into blocks already freed to (and maybe reallocated
        # by) someone else.
        self._lane_kv: list = [None] * slots
        self._stale_slots: set = set()
        self._kv_refused_rid: Optional[int] = None  # dedup refusal count
        # prefill_budget: the prompt tokens of staged prefill a
        # serve_step may advance while a lane decodes (None = one
        # piece).
        if prefill_budget is not None and prefill_budget < 1:
            raise ValueError(
                f"prefill_budget must be >= 1 prompt tokens a step, or "
                f"None for the staged default of one prefill piece a "
                f"step; got {prefill_budget} (a budget as large as the "
                f"prompts admits a whole prompt in one step)")
        self.prefill_budget = prefill_budget
        # Pieces one ``_prefill_piece`` call may cover: one, or the
        # largest power of two of them that a step's budget holds
        # (``prefill_chunk`` engines; every other engine runs one piece
        # a call).  ONE shape beside the single piece, because a shape
        # is a whole trace of the model at the engine's first admission
        # (seconds of set-up: PERF.md, PR 38), and the largest because
        # a call of k pieces saves k - 1 reads of every weight.  Not
        # where attention chooses its rows (a learned selection): a
        # piece there is mostly its walk, which a call does not share,
        # and a call of two read slower than two pieces (PERF.md, PR
        # 38: measured, the cause not found).
        most = 1
        if (prefill_chunk is not None and prefill_budget is not None
                and not self._index_topk[False]):
            most = max(1, min(-(-prefill_budget // prefill_chunk),
                              -(-self.cache_len // prefill_chunk)))
        most = 1 << (most.bit_length() - 1)
        self._piece_counts = (1, most) if most > 1 else (1,)
        self._piece_shapes_ready = most == 1    # _compile_piece_shapes
        self._staging: dict = {}       # slot -> _PrefillTask (FIFO)
        # installments: budget installments run; staged_requests:
        # requests staged.
        self.prefill_stats = {"installments": 0, "staged_requests": 0}
        # The starved-device account (``_launch``, ``_poll_drained``):
        # an output of the newest program enqueued; when a poll found
        # it ready with work pending, the clock's reading then (None:
        # the queue is not known empty); the seconds so charged
        # (``device_starved_s``); the clock, a seam for tests; when the
        # last serve_step handed control back.
        self._newest = None
        self._drained_at: Optional[float] = None
        self._starved_s = 0.0
        self._clock = time.monotonic
        self._left_at: Optional[float] = None
        # What the running serve_step has done so far: the attrs its
        # ``engine/step`` span is given at exit (runtime/events.py,
        # CONTRACT).
        self._step_counts = dict.fromkeys(_STEP_COUNTS, 0)
        # The chunk in flight: rids pins which request occupied each
        # slot AT DISPATCH — harvest trims anything that retired or was
        # refilled since (the one-chunk decision lag made safe).
        self._inflight: Optional[dict] = None
        # Device-resident carry feeding the NEXT dispatch: (tok [slots],
        # counts [slots]) — never materialized on the host, so a chunk
        # can be enqueued while its predecessor still computes.
        self._carry = None
        # Slots refilled since the last dispatch -> the device scalar
        # that holds the lane's first token (``_carry_arrays``).
        self._refills: dict = {}
        # Requests of one token, prefilled, whose token no harvest has
        # read yet (a lane's unread first token is on its
        # ``_SlotState``).
        self._awaited: list = []
        # overlapped_harvests counts harvest passes that ran with a
        # successor chunk already in flight; the _s pair feeds
        # overlap_ratio() (the host-stall share the lookahead hides).
        self.overlap_stats = {"chunks": 0, "overlapped_harvests": 0,
                              "harvest_s": 0.0,
                              "overlapped_harvest_s": 0.0}
        # Fused paged attention (ops.pallas_kernels.paged_attention):
        # decided at construction from the same backend rule the
        # decode trace reads (the TPU runs it; TTD_NO_PALLAS=1, set
        # BEFORE the engine is built, does not) — recorded here so
        # dispatch spans can tag which leg ran.
        from tensorflow_train_distributed_tpu.ops import (
            pallas_kernels as _pk,
        )

        self.kv_cache_int8 = bool(getattr(config, "kv_cache_int8",
                                          False))
        # Same mesh rule as layers._fused_paged_ok: any >1-way mesh
        # keeps the XLA gather (GSPMD partitions it); a trivial mesh
        # does not veto the kernel.
        meshed = (self._mesh is not None
                  and any(v > 1 for v in self._mesh.shape.values()))
        self._fused_attn = bool(not meshed
                                and _pk.use_fused_paged_attention())
        # Span-arg form, precomputed: the dispatch-critical window must
        # not run int() (the dispatch lint cannot tell a host bool from
        # a device scalar there, and keeping the window conversion-free
        # is the cheaper discipline anyway).
        self._fused_tag = 1 if self._fused_attn else 0
        # Bytes of recurrent state the grid pins (every slot, every
        # linear layer: state and convolution tail) and a lane's share
        # of them; no rows, so no part of ``kv_pool_bytes``.
        self._state_pool_bytes = sum(
            int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
            for p, leaf in jax.tree_util.tree_flatten_with_path(
                self._cache_struct(self.slots, grid=True))[0]
            if getattr(p[-1], "key", "") in _STATE_LEAVES
        ) if self._state_layers else 0
        self._state_lane_bytes = self._state_pool_bytes // self.slots

        # Device bytes the paged pools pin (target + draft, int8 scale
        # pools included) — computed once from the memoized cache
        # eval_shape (host-only trace, no device work) so the /metrics
        # scrape thread reads a plain int.  The --kv-pool-blocks
        # oversizing lever is sized against this number.
        def _pool_parts(struct) -> dict:
            """Bytes of the row-holding leaves by what holds them, each
            layer's at its own row: ``<leaf>_bytes`` the blocks tables
            map (``latent_pool_bytes``, ``index_pool_bytes``,
            ``key_pool_bytes``, ...) and ``<leaf's rows>_ring_bytes`` a
            window layer's rings (``latent_ring_bytes``, ...)."""
            rings = self._ringed_modules(struct)
            parts: dict = {}
            for p, leaf in jax.tree_util.tree_flatten_with_path(struct)[0]:
                name = getattr(p[-1], "key", "")
                if name not in _ROW_LEAVES:
                    continue
                if self._path_key(p)[:-1] in rings:
                    name = name.replace("_pool", "_ring")
                parts[name + "_bytes"] = parts.get(name + "_bytes", 0) + (
                    int(np.prod(leaf.shape))
                    * jnp.dtype(leaf.dtype).itemsize)
            return parts

        self._kv_pool_parts = _pool_parts(
            self._cache_struct(self.slots, grid=True))
        self._kv_ring_bytes = sum(
            n for name, n in self._kv_pool_parts.items() if "_ring" in name)
        self._kv_pool_bytes = sum(self._kv_pool_parts.values())
        if self._draft_model is not None:
            # A draft has no window layers (refused above): no rings.
            self._kv_pool_bytes += sum(_pool_parts(self._cache_struct(
                self.slots, draft=True, grid=True)).values())
        # Per-block row bytes across the layers whose blocks the
        # allocator hands out (draft + int8 scale pools included; a
        # window layer's rings are the slots', not the allocator's):
        # its byte view of its own blocks, so block-count accounting
        # (serving_kv) can be read in BYTES too — what admission and
        # the memcheck gauges reason in.
        self._kv_pool.bytes_per_block = (
            (self._kv_pool_bytes - self._kv_ring_bytes)
            // (1 + self._kv_pool.n_blocks))
        if self.hbm_budget_bytes is not None:
            # Budgeted engines precompute the admission projection NOW:
            # validate_request runs on gateway HANDLER threads, which
            # must read a memoized int, never trace an eval_shape
            # concurrently with the driver.
            self._prefill_pair_bytes()

    def _compile_piece_shapes(self) -> None:
        """Run every piece shape this engine may dispatch once, on a
        zeroed batch-1 cache that is dropped, before the first request
        is admitted (``_advance_prefills``): which of them a request
        meets depends on where a step's budget falls in its prompt, so
        traffic that warms one would leave another to compile under
        the first request that meets it.  (Not at construction, which
        runs nothing on the device and takes parameters that are
        shapes alone.)"""
        self._piece_shapes_ready = True
        chunk = self.prefill_chunk
        padded = np.zeros((1, self._piece_counts[-1] * chunk), np.int32)
        with self._ctx():
            for k in self._piece_counts:
                t0 = time.monotonic()
                self._run_target_piece(self._fresh_cache(1), padded,
                                       chunk, 0, k * chunk, 0, k=k)
                if self._draft_model is not None:
                    self._run_draft_piece(
                        self._fresh_cache(1, draft=True), padded, chunk,
                        0, k)
                logger.info("prefill call of %d x %d tokens ready in "
                            "%.2f s", k, chunk, time.monotonic() - t0)
        self._newest = None

    def _ctx(self):
        """Mesh + logical-rules context for device calls (no-op unsharded).

        ``jax.set_mesh`` must wrap the jitted CALL, not sit inside the
        traced function (trainer.py:432 lesson)."""
        if self._mesh is None:
            return contextlib.nullcontext()
        from tensorflow_train_distributed_tpu.parallel import (
            sharding as sharding_lib,
        )

        stack = contextlib.ExitStack()
        stack.enter_context(sharding_lib.with_logical_rules(
            self._mesh, *(() if self._rules is None else (self._rules,))))
        stack.enter_context(compat.set_mesh(self._mesh))
        return stack

    # -- jitted programs ---------------------------------------------------

    def _pick(self, logits, seeds, counts):
        """Next token per slot from [slots, V] logits.

        Greedy: argmax.  Sampling: each slot draws from ITS OWN stream
        — key = fold_in(key(seed), tokens_drawn_so_far) — so a
        request's tokens do not depend on slot placement, neighbors, or
        chunk boundaries (reproducible under any contention).
        """
        with jax.named_scope("sample"):
            logits = logits.astype(jnp.float32)
            if self._greedy:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            logits = filter_logits(logits, temperature=self.temperature,
                                   top_k=self.top_k, top_p=self.top_p)
            keys = jax.vmap(jax.random.fold_in)(
                jax.vmap(jax.random.key)(seeds.astype(jnp.uint32)),
                counts)
            return jax.vmap(
                lambda k, l: jax.random.categorical(k, l)
            )(keys, logits).astype(jnp.int32)

    # Compile discipline (ttd-lint compilecheck + TTD_COMPILECHECK=1):
    # every program below declares which bucket rule pads its dynamic
    # dims, which args it donates, and how many distinct signatures one
    # engine may legitimately compile.  Prefill pieces see one shape
    # per prompt bucket (or ONE prefill_chunk shape) — except
    # dense-MoE exact-length prefill, which deliberately compiles per
    # distinct prompt length (the engine warns per new length), hence
    # the wider budget.  The grid programs (decode/spec/insert/reset)
    # are shape-fixed per engine: tiny budgets, so an un-bucketed
    # shape reaching them raises on the FIRST excess dispatch.
    @compile_site(buckets="prompt_buckets|prefill_chunk|exact(dense-MoE)",
                  donates=(2,), statics=(0,), max_compiles=32)
    @partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
    def _prefill_piece(self, variables, cache, tokens_1xl, local_idx,
                       seed, count0):
        """One batch-1 prefill piece appended to ``cache`` (a zeroed
        cache == fresh, so the whole-prompt case is a single piece).

        Pad rows in the final piece are harmless: causal masking keeps
        them invisible to the real rows (they sit AFTER every real
        position), the first token reads the logit at ``local_idx``
        (the last REAL row of this piece), and insert() pins the slot's
        index to the true prompt length so decode overwrites each pad
        row before any query can attend it (writes precede reads at
        every position).

        ``count0`` is the rng counter of the pick — 0 for a fresh
        request; a resumed request (failover re-admission whose prompt
        tail is its own earlier output) picks at its original stream
        position, so the continuation is the one the uninterrupted run
        would have sampled.
        """
        if self._state_layers:
            # Rows past the call's last real one must not move a
            # recurrent state: tell its layers how many there are.
            pad = tokens_1xl.shape[1] - 1 - local_idx
            cache = jax.tree_util.tree_map_with_path(
                lambda p, leaf: jnp.full_like(leaf, pad)
                if getattr(p[-1], "key", "") == "pad_rows" else leaf,
                cache)
        with quantized_inference():
            logits, vs = self._prefill_model.apply(
                dict(variables, cache=cache), tokens_1xl,
                mutable=["cache"])
        first = self._pick(logits[:, local_idx],
                           seed[None], count0[None])[0]
        return vs["cache"], first.astype(tokens_1xl.dtype)

    @compile_site(buckets="prompt_buckets|prefill_chunk",
                  donates=(2,), statics=(0,), max_compiles=32)
    @partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
    def _draft_prefill_piece(self, variables, cache, tokens_1xl):
        """Draft-model prefill piece (no token pick — the draft only
        needs its KV rows; pad rows are harmless by the same
        write-before-read rule as the target's)."""
        with quantized_inference():
            _, vs = self._draft_prefill_model.apply(
                dict(variables, cache=cache), tokens_1xl,
                mutable=["cache"])
        return vs["cache"]

    def _accept_block_sampled(self, d_block, q, logits, round_keys,
                              dtype, k):
        """Engine face of the shared rejection rule
        (``models.speculative.sampled_accept``): filter/softmax the
        target's raw ``logits`` [B, k+1, V] with the engine's sampling
        knobs and derive the per-slot acceptance uniforms (draw index
        k+1) and residual/bonus keys (k+2) from ``round_keys``.  ``k``
        is the ROUND's draft depth (a static under `_spec_round`'s
        trace) — under adaptive speculation different rounds run
        different depths, so the depth can no longer be read off
        ``self``."""
        from tensorflow_train_distributed_tpu.models.speculative import (
            sampled_accept,
        )

        p = jax.nn.softmax(filter_logits(
            logits, temperature=self.temperature, top_k=self.top_k,
            top_p=self.top_p), axis=-1)            # [B, k+1, V]
        us = jax.vmap(lambda kk: jax.random.uniform(
            jax.random.fold_in(kk, k + 1), (k,)))(round_keys)
        final_keys = jax.vmap(
            lambda kk: jax.random.fold_in(kk, k + 2))(round_keys)
        emit, emitted, a, final = sampled_accept(
            d_block, q, p, us, final_keys)
        return (emit.astype(dtype), emitted, a, final.astype(dtype))

    @compile_site(buckets="spec-depth buckets (one program per k)",
                  donates=(3, 4), statics=(0, 8), max_compiles=8)
    @partial(jax.jit, static_argnums=(0, 8), donate_argnums=(3, 4))
    def _spec_round(self, t_vars, d_vars, t_cache, d_cache, tok, seeds,
                    counts, k):
        """One speculative round for ALL slots: the draft proposes k
        tokens per slot (k+1 steps — the last append-only so both
        caches hold identical row sets), the target verifies each
        slot's k+1 block in one call, each slot accepts its own
        longest matching prefix, and both cache indices rewind
        PER SLOT by k+1-emitted (rows beyond stay stale-but-invisible:
        masks are position-based and writes precede reads).

        ``k`` is STATIC: each draft depth compiles its own program, so
        the adaptive controller picks among a fixed bucket set
        (``spec_depths``) without retracing — a fixed-depth engine only
        ever calls one signature.  Depth 0 degenerates to plain decode
        (one append-only draft step keeps the draft cache's row set in
        lockstep for later deepening; the empty d_block accepts
        trivially and the round emits exactly the target's own pick) —
        greedy depth-0 rounds are token-identical to `_decode_chunk`
        steps.

        Returns (t_cache, d_cache, emit [B, k+1], emitted [B],
        next_tok [B], accepted [B]).  Greedy: emitted tokens are
        exactly the target's greedy choices — token-identical to
        non-speculative serving (pinned in tests).  Sampled: draft
        proposals are accepted by the rejection rule
        (``_accept_block_sampled``), so outputs are distributed as
        plain sampled serving — same law, fewer target steps; the
        per-slot stream (``seeds``/``counts``) keys every draw, so a
        round is reproducible independent of slot placement.
        """
        round_keys = jax.vmap(jax.random.fold_in)(
            jax.vmap(jax.random.key)(seeds.astype(jnp.uint32)), counts)

        def draft_step(c, j):
            cache, tk = c
            with quantized_inference():
                logits, upd = self._draft_model.apply(
                    dict(d_vars, cache=cache), tk[:, None],
                    mutable=["cache"])
            logits = logits[:, -1].astype(jnp.float32)
            if self._greedy:
                nxt = jnp.argmax(logits, -1).astype(tk.dtype)
                return (upd["cache"], nxt), nxt
            filt = filter_logits(logits, temperature=self.temperature,
                                 top_k=self.top_k, top_p=self.top_p)
            keys = jax.vmap(lambda kk: jax.random.fold_in(kk, j))(
                round_keys)
            nxt = jax.vmap(jax.random.categorical)(keys, filt).astype(
                tk.dtype)
            return (upd["cache"], nxt), (nxt, jax.nn.softmax(filt, -1))

        (d_cache, _), scanned = jax.lax.scan(
            draft_step, (d_cache, tok), jnp.arange(k + 1))
        drafts = scanned if self._greedy else scanned[0]
        drafts = jnp.moveaxis(drafts, 0, 1)        # [B, k+1]; d0..dk
        d_block = drafts[:, :k]                    # [B, k]

        block = jnp.concatenate([tok[:, None], d_block], axis=1)
        with quantized_inference():
            logits, upd = self._model.apply(
                dict(t_vars, cache=t_cache), block, mutable=["cache"])
        t_cache = upd["cache"]
        logits = logits.astype(jnp.float32)        # [B, k+1, V]

        if self._greedy:
            # Per slot: emit the longest matching prefix then the
            # target's own pick (one shared rule with the batch-1
            # library path).
            from tensorflow_train_distributed_tpu.models.speculative import (
                accept_block,
            )

            preds = jnp.argmax(logits, -1).astype(tok.dtype)
            emit, emitted, a, next_tok = accept_block(d_block, preds)
        else:
            q = jnp.moveaxis(scanned[1], 0, 1)[:, :k]   # [B, k, V]
            emit, emitted, a, next_tok = self._accept_block_sampled(
                d_block, q, logits, round_keys, tok.dtype, k)

        # Per-slot rewind: both caches advanced k+1 this round; the
        # accepted context is old + emitted, i.e. index -= k+1-emitted.
        back = (k + 1) - emitted                   # [B]

        def rewind(path, leaf):
            if any(getattr(p, "key", "") == "index" for p in path):
                return leaf - back.astype(leaf.dtype)
            return leaf

        t_cache = jax.tree_util.tree_map_with_path(rewind, t_cache)
        d_cache = jax.tree_util.tree_map_with_path(rewind, d_cache)
        # counts + emitted: the NEXT round's rng counters, computed in
        # the same program so the device-resident carry costs zero
        # extra dispatches.
        return (t_cache, d_cache, emit, emitted, next_tok, a,
                counts + emitted)

    # -- paged-pool programs -----------------------------------------------

    @staticmethod
    def _path_key(path) -> tuple:
        return tuple(getattr(k, "key", str(k)) for k in path)

    @classmethod
    def _ringed_modules(cls, cache) -> set:
        """Path keys of the modules of a grid cache tree that hold
        their rows in a ring (a window layer: the module with a
        ``window_table``)."""
        return {cls._path_key(p)[:-1] for p, _ in
                jax.tree_util.tree_flatten_with_path(cache)[0]
                if getattr(p[-1], "key", "") == "window_table"}

    def _ring_row(self, slot):
        """Slot ``slot``'s ring in a window layer's pool: block 0 is
        scratch, ring ``s`` the ``ring_blocks`` blocks after it."""
        return (1 + slot * self._ring_blocks
                + jnp.arange(self._ring_blocks, dtype=jnp.int32))

    @classmethod
    def _paired_leaves(cls, pooled, linear) -> list:
        """[(pool leaf's path key, linear leaf's path key)] for every
        row-holding leaf of a paged cache tree and the
        batch-1 LINEAR tree of the same model.  A pool pairs with the
        linear leaf of its name (``_ROW_LEAVES``) below the module that
        holds the pool: the attention module itself where the layers
        are unrolled, the depth scan where it carries every layer's
        pools as one leaf (``llama._ScannedBlock``; the linear cache
        stays a scanned leaf of the attention below it, with the same
        leading layer axis)."""
        lin_keys = [cls._path_key(p) for p, _ in
                    jax.tree_util.tree_flatten_with_path(linear)[0]]
        pairs = []
        for p, _ in jax.tree_util.tree_flatten_with_path(pooled)[0]:
            key = cls._path_key(p)
            if key[-1] not in _ROW_LEAVES:
                continue
            held = [k for k in lin_keys
                    if k[-1] == _ROW_LEAVES[key[-1]][0]
                    and k[:len(key) - 1] == key[:-1]]
            if len(held) != 1:
                raise ValueError(
                    f"pool leaf {key} pairs with {len(held)} linear "
                    f"cache leaves, not one: {held}")
            pairs.append((key, held[0]))
        return pairs

    def _scatter_rows_tree(self, cache, cache_1, table_row, start, end,
                           slot=None):
        """Put the batch-1 LINEAR cache's rows [start, end) into the
        paged pool at ``table_row``'s blocks (traced helper shared by
        insert and preload; leaves pair by ``_paired_leaves``).  The
        pool is written a BLOCK at a time, whole tiles of it where it
        lies (a scatter of single rows under a leading layer axis made
        the compiler move the whole pool to another layout and back:
        compile, PR 29): a block with no row in [start, end) is sent
        out of range and DROPPED — the shared-block copy-on-write
        guard (rows before ``start`` belong to radix-shared blocks this
        lane must never write) — and in a block the span covers in
        part, the rows outside it keep the bytes they had.  int8
        configs carry the per-row scales along the same map: the pool
        stores exactly the bytes the batch-1 prefill quantized, which
        is what keeps int8 paged parity bitwise.

        A window layer's pool (``_ringed_modules``) takes the last
        ``ring_blocks`` blocks that end with row ``end - 1``, each at
        its number modulo the ring, into ``slot``'s ring: the rows a
        decode step's window can reach, whole blocks of the batch-1
        cache (no block of a ring is shared, so ``start`` says nothing
        there); with no ``slot`` (a preload) its pools stay as they
        are."""
        bs, n_blk = self.kv_block_size, self._kv_nblk_lane
        rings = self._ringed_modules(cache)
        if rings and slot is not None:
            ring = self._ring_blocks
            entry = jnp.arange(ring)
            top = (end - 1) // bs           # the block of the last row
            # The newest block of each entry's residue, none before 0.
            ring_src = top - jnp.mod(top - entry, ring)
            ring_dst = jnp.where(ring_src >= 0, self._ring_row(slot),
                                 1 + self.slots * ring)
        pos = jnp.arange(n_blk * bs).reshape(n_blk, bs)
        live = (pos >= start) & (pos < end)
        blocks = jnp.where(live.any(axis=1), table_row,
                           1 + self._kv_pool.n_blocks)
        flat_1 = {self._path_key(p): leaf for p, leaf
                  in jax.tree_util.tree_flatten_with_path(cache_1)[0]}
        source = dict(self._paired_leaves(cache, cache_1))

        def scatter(path, leaf):
            key = self._path_key(path)
            if key not in source or (key[:-1] in rings and slot is None):
                return leaf
            _, row_dims, lin_row_dims = _ROW_LEAVES[key[-1]]
            axis = leaf.ndim - (2 + row_dims)      # the pool's blocks
            # [..., 1, C, *row] → [..., n_blk, bs, *the pool's row]:
            # drop the batch-1 dim, lay a row's values side by side,
            # cut the rows into blocks.
            src = jnp.squeeze(flat_1[source[key]],
                              axis=-(2 + lin_row_dims))
            src = src.reshape(src.shape[:axis + 1] + leaf.shape[axis + 2:])
            tail = [(0, 0)] * src.ndim
            tail[axis] = (0, n_blk * bs - self.cache_len)
            src = jnp.pad(src, tail)
            src = src.reshape(leaf.shape[:axis] + (n_blk,)
                              + leaf.shape[axis + 1:])
            if key[:-1] in rings:
                return leaf.at[(slice(None),) * axis + (ring_dst,)].set(
                    jnp.take(src, ring_src, axis=axis, mode="clip")
                    .astype(leaf.dtype), mode="drop")
            had = jnp.take(leaf, blocks, axis=axis, mode="clip")
            new = jnp.where(live.reshape(live.shape + (1,) * row_dims),
                            src.astype(leaf.dtype), had)
            return leaf.at[(slice(None),) * axis + (blocks,)].set(
                new, mode="drop")

        return jax.tree_util.tree_map_with_path(scatter, cache)

    @compile_site(buckets="slot-grid (shape-fixed per engine)",
                  donates=(1,), statics=(0,), max_compiles=4)
    @partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
    def _paged_insert(self, cache, cache_1, slot, table_row, start,
                      true_len):
        """Scatter the prefilled rows [start, true_len) into the lane's
        blocks, install its block-table row, and pin its index to the
        TRUE prompt length (rows below ``start`` come from
        radix-shared blocks and are already there)."""
        cache = self._scatter_rows_tree(cache, cache_1, table_row,
                                        start, true_len, slot)
        flat_1 = {self._path_key(p): leaf for p, leaf
                  in jax.tree_util.tree_flatten_with_path(cache_1)[0]}

        def pin(path, leaf):
            name = getattr(path[-1], "key", "")
            if name in _STATE_LEAVES:
                # A lane's recurrent state and tail, whole: the leaf of
                # the same path in the batch-1 cache.
                with jax.named_scope("state_pool/write"):
                    return jax.lax.dynamic_update_slice_in_dim(
                        leaf, flat_1[self._path_key(path)].astype(
                            leaf.dtype), slot,
                        axis=leaf.ndim - (1 + _STATE_LEAVES[name]))
            if name == "block_table":
                return leaf.at[..., slot, :].set(table_row)
            if name == "window_table":
                return leaf.at[..., slot, :].set(self._ring_row(slot))
            if name == "index":
                return leaf.at[..., slot].set(true_len)
            return leaf

        return jax.tree_util.tree_map_with_path(pin, cache)

    @compile_site(buckets="slot-grid (shape-fixed per engine)",
                  donates=(1,), statics=(0,), max_compiles=4)
    @partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
    def _paged_preload(self, cache, cache_1, table_row, start, end):
        """Scatter a preloaded prefix's rows [start, end) into
        radix-held blocks — no lane: tables and indices are untouched
        (``start`` skips blocks the radix already caches — shared
        blocks are never rewritten, the COW rule)."""
        return self._scatter_rows_tree(cache, cache_1, table_row,
                                       start, end)

    @compile_site(buckets="slot-grid (shape-fixed per engine)",
                  donates=(), statics=(0, 3), max_compiles=4)
    @partial(jax.jit, static_argnums=(0, 3))
    def _gather_prefix(self, cache, table_row, draft, matched):
        """The inverse of ``_scatter_rows_tree``: read a lane's leading
        ``matched`` rows out of the pool into a fresh batch-1 LINEAR
        cache (index pinned to ``matched``), so the suffix prefill runs
        the piece programs a fresh prompt's does — a prefix hit
        replaces recompute with this copy.  Rows past ``matched``
        gather whatever the lane's owned blocks hold — garbage the
        write-before-read prefill rule keeps invisible."""
        if self._ringed_modules(cache):
            raise ValueError(
                "a window layer's rows behind its window are gone: no "
                "prefix is gathered out of its ring")
        pools = {self._path_key(p): leaf for p, leaf
                 in jax.tree_util.tree_flatten_with_path(cache)[0]}
        struct = self._cache_struct(1, draft=draft)
        source = {lin: pool for pool, lin
                  in self._paired_leaves(cache, struct)}

        def build(path, s):
            name = getattr(path[-1], "key", "")
            if name == "index":
                return jnp.full(s.shape, matched, s.dtype)
            src = pools[source[self._path_key(path)]]
            # Pool [..., nb, bs, *row] → batch-1 [..., 1, C, *row]: the
            # lane's blocks, whole, in its table's order; their rows
            # are its logical rows (the linear cache's own row dims:
            # the same values), the batch dim re-inserted before them.
            axis = src.ndim - (2 + _ROW_LEAVES[_POOL_OF[name]][1])
            take = jnp.take(src, table_row, axis=axis)
            take = take.reshape(take.shape[:axis] + (-1,)
                                + take.shape[axis + 2:])
            take = jax.lax.slice_in_dim(take, 0, self.cache_len, axis=axis)
            return jnp.expand_dims(take, axis=axis).reshape(
                s.shape).astype(s.dtype)

        return jax.tree_util.tree_map_with_path(build, struct)

    @compile_site(buckets="slot-grid (shape-fixed per engine)",
                  donates=(1,), statics=(0,), max_compiles=4)
    @partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
    def _reset_lanes(self, cache, stale):
        """Point ``stale`` lanes' block tables at the scratch block and
        zero their indices: a retired/cancelled lane's blocks go back
        to the pool at harvest, but one more garbage chunk for it is in
        (or headed for) the device queue — this runs BEFORE that chunk,
        so its writes land in scratch instead of blocks someone else
        now owns."""
        def rst(path, leaf):
            name = getattr(path[-1], "key", "")
            if name in ("block_table", "window_table"):
                return jnp.where(stale[:, None], 0, leaf)
            if name == "index":
                return jnp.where(stale, 0, leaf)
            return leaf

        return jax.tree_util.tree_map_with_path(rst, cache)

    @compile_site(buckets="slot-grid (shape-fixed per engine)",
                  donates=(), statics=(0,), max_compiles=4)
    @partial(jax.jit, static_argnums=(0,))
    def _splice_refills(self, tok, counts, refill_counts, picks):
        """The carry with the refilled slots' values in it, as ONE
        program whatever the step refilled: ``refill_counts`` [slots]
        holds a refilled slot's rng counter and -1 elsewhere; ``picks``
        is a device scalar a slot, a refilled slot's first token where
        its last prefill piece left it and any of those elsewhere (not
        read: the tuple keeps one structure, so one compile)."""
        refilled = refill_counts >= 0
        return (jnp.where(refilled, jnp.stack(picks).astype(tok.dtype),
                          tok),
                jnp.where(refilled, refill_counts, counts))

    @compile_site(buckets="slot-grid (the un-bucketed-prompt storm "
                          "surfaces HERE when prefill discipline "
                          "slips)",
                  donates=(2,), statics=(0,), max_compiles=4)
    @partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
    def _decode_chunk(self, variables, cache, tok, seeds, counts):
        """``chunk`` decode steps for all slots; one device round-trip.
        ``seeds``/``counts`` [slots]: each slot's sampling stream and
        how many tokens it has already drawn (greedy ignores both).
        Also returns the NEXT chunk's (tok, counts) carry — computed
        inside the same program so chunks chain with zero extra
        dispatches — and, last, what the layers sowed in each step, by
        name and stacked over the layers that sowed it ([chunk, layers,
        ...]): ``expert_rows``, the rows each expert held here took;
        ``routed_here`` and ``rows`` as ``_count_sown`` reads them.
        None for a model that sows nothing (no routed experts)."""
        def step(carry, j):
            cache, tok = carry
            with quantized_inference():
                logits, upd = self._model.apply(
                    dict(variables, cache=cache), tok[:, None],
                    mutable=["cache", "moe_stats", "attn_stats"])
            nxt = self._pick(logits[:, -1], seeds, counts + j).astype(
                tok.dtype)
            sown = {}
            for coll in ("moe_stats", "attn_stats"):
                for path, leaf in jax.tree_util.tree_flatten_with_path(
                        upd.get(coll, {}))[0]:
                    sown.setdefault(self._path_key(path)[-2],
                                    []).append(leaf)
            return (upd["cache"], nxt), (
                nxt, {n: jnp.stack(v) for n, v in sown.items()} or None)

        (cache, last), (toks, sown) = jax.lax.scan(
            step, (cache, tok), jnp.arange(self.chunk))
        return (cache, jnp.moveaxis(toks, 0, 1),    # [slots, chunk]
                last, counts + self.chunk, sown)

    # -- host-side loop ----------------------------------------------------

    @thread_role("handler", "driver", "main")
    def validate_request(self, prompt, max_new_tokens: int,
                         seed: Optional[int] = None,
                         resume_from: int = 0) -> list:
        """All of ``submit()``'s checks WITHOUT enqueuing; returns the
        normalized prompt (a list of ints).  Read-only, so the HTTP
        gateway's handler threads can reject bad requests (400) before
        handing admission to the single engine-owning driver thread —
        the engine's mutating calls stay single-threaded."""
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if seed is not None and not 0 <= seed < 2 ** 32:
            # Catch at submit: an out-of-range seed would OverflowError
            # inside run(), aborting every in-flight request.
            raise ValueError(f"seed must be a uint32, got {seed}")
        if not prompt:
            raise ValueError("empty prompt")
        if resume_from < 0 or resume_from >= len(prompt):
            # The resumed tail is part of the prompt, and at least one
            # ORIGINAL prompt token must remain under it.
            raise ValueError(
                f"resume_from must be in [0, len(prompt)), got "
                f"{resume_from} for a {len(prompt)}-token prompt")
        if max_new_tokens < 0:
            raise ValueError(f"max_new_tokens must be >= 0, got "
                             f"{max_new_tokens}")
        if len(prompt) + max_new_tokens > self.cache_len:
            raise ValueError(
                f"prompt {len(prompt)} + {max_new_tokens} new exceeds "
                f"cache_len={self.cache_len}")
        # Admission is keyed on BLOCKS: a request whose worst-case
        # block need exceeds the whole pool could never be granted a
        # lane — reject now instead of deadlocking the queue.
        need = -(-(len(prompt) + max_new_tokens) // self.kv_block_size)
        if need > self._kv_pool.n_blocks:
            raise ValueError(
                f"request needs {need} KV blocks "
                f"(block_size={self.kv_block_size}) but the pool "
                f"has {self._kv_pool.n_blocks}")
        if self.hbm_budget_bytes is not None:
            # Projected BYTES alongside the free-blocks check: this
            # request's marginal device allocation is one batch-1
            # prefill cache pair — refuse admission when the live
            # ledger (pools, stored prefixes, in-flight prefills)
            # plus that pair cannot fit the declared budget.  An
            # engine whose POOL alone exceeds the budget is not
            # screened here: the pool allocator itself raises
            # MemoryBudgetError at first insert with the live set
            # diffed, which is the clearer error for a sizing bug.
            live = (memcheck.live_bytes(owner=self)
                    if memcheck.armed() else 0)
            projected = live + self._prefill_pair_bytes()
            if projected > self.hbm_budget_bytes:
                raise ValueError(
                    f"admission needs a projected {projected} bytes "
                    f"(live pools + one prefill cache pair) but "
                    f"hbm_budget_bytes={self.hbm_budget_bytes} — "
                    f"shrink --kv-pool-blocks/slots or raise the "
                    f"budget")
        if (not self._exact_prefill and self.prefill_chunk is None
                and not resume_from):
            # Catch at submit time: failing later inside run() would
            # drop this request silently and abort others mid-flight.
            # Only the SUFFIX after the longest preloaded prefix needs
            # a bucket — a long shared system prompt plus a short tail
            # is the feature's primary use (preload before submit: a
            # prefix loaded later cannot rescue an already-rejected
            # request).
            # The rule is anchored on the STORED preloads, which the
            # operator declared, not on the radix index (its entries
            # evict under pressure; admission chunks a grown suffix,
            # but validation must stay deterministic).
            # RESUMED requests are exempt: the original admission
            # already passed this policy bound, the resumed tail is the
            # request's own output, and ``_pieces_for`` chunks any span
            # into largest-bucket pieces (the long-preload mechanics) —
            # rejecting here would kill an accepted half-streamed
            # request as 'invalid' mid-failover.
            work = len(prompt) - self._match_prefix(prompt)[0]
            if work > self.prompt_buckets[-1]:
                raise ValueError(
                    f"prompt length {len(prompt)} (suffix {work} after "
                    f"the longest preloaded prefix) exceeds the largest "
                    f"prefill bucket {self.prompt_buckets[-1]}")
        return prompt

    @thread_role("driver", "main")
    def submit(self, prompt, max_new_tokens: int,
               seed: Optional[int] = None, resume_from: int = 0) -> int:
        """Enqueue a request; returns its id (resolved by ``run()``).

        ``seed`` names the request's sampling stream (ignored under
        greedy); default: the request id — distinct per request,
        reproducible across identical engine sessions.

        ``resume_from=g`` declares the prompt's LAST ``g`` tokens to be
        this request's own earlier output (the failover re-admission
        contract): the rng counter starts at ``g`` instead of 0, so a
        seeded-sampling continuation lands exactly where the
        uninterrupted stream would have — the re-admitted request's
        output is the original's, minus the tokens already delivered.
        Greedy ignores the counter and resumes for free."""
        prompt = self.validate_request(prompt, max_new_tokens, seed,
                                       resume_from)
        rid = self._next_id
        self._next_id += 1
        self._queue.append(
            (rid, prompt, max_new_tokens,
             rid if seed is None else seed, resume_from))
        events.instant("engine/queued", rid=rid, prompt_len=len(prompt),
                       max_new=max_new_tokens)
        return rid

    @thread_role("driver", "main")
    def cancel(self, request_id: int) -> bool:
        """Abandon a live request: drop it from the queue, discard its
        staged partial prefill, or free its slot so the next refill
        reuses it (the gateway's deadline lever).  A freed slot's cache
        rows go stale-but-invisible — position masks hide them and the
        next ``_paged_insert`` re-pins the slot index, the same rule
        stale rows already obey between ``run()`` cycles; a cancelled
        staged prefill frees its lane IMMEDIATELY (the partial batch-1
        cache is simply dropped — it never touched the slot grid).
        A lane whose first token no harvest has read yet is freed like
        any other (the token is not needed), and a request of one token
        whose token is awaited is dropped.
        Returns False when the id is unknown or already finished (its
        output, if any, stays harvestable)."""
        for i, item in enumerate(self._queue):
            if item[0] == request_id:
                del self._queue[i]
                events.instant("engine/cancel", rid=request_id,
                               where="queued")
                return True
        for slot, task in self._staging.items():
            if task.request_id == request_id:
                # Partial prefill lived in the batch-1 cache only; the
                # claim's blocks were never read — free them.
                self._kv_release(task.kv)
                del self._staging[slot]
                events.instant("engine/cancel", rid=request_id,
                               where="staged")
                return True
        for slot, state in enumerate(self._slot_states):
            if state is not None and state.request_id == request_id:
                # Prompt blocks stay radix-cached (inserted at
                # finalize); the generated tail is dropped with the
                # lane.
                self._lane_release(slot)
                self._slot_states[slot] = None
                events.instant("engine/cancel", rid=request_id,
                               where="slot")
                return True
        for i, pick in enumerate(self._awaited):
            if pick.request_id == request_id:
                # Prefilled, its one token not read yet: nothing held.
                del self._awaited[i]
                events.instant("engine/cancel", rid=request_id,
                               where="awaited")
                return True
        return False

    def active_slots(self) -> int:
        """Slots currently occupied by a request — decoding or staged
        mid-prefill (occupancy gauge: a prefilling lane is reserved)."""
        return (sum(s is not None for s in self._slot_states)
                + len(self._staging))

    def staged_rids(self) -> tuple:
        """Request ids whose prefill is staged in a reserved lane —
        the driver's slot-grant signal for requests the decode
        snapshot cannot show yet (a staged lane is granted: no other
        request can claim it)."""
        return tuple(t.request_id for t in self._staging.values())

    def queue_depth(self) -> int:
        """Requests accepted but not yet in a slot."""
        return len(self._queue)

    def _cache_struct(self, batch: int, draft: bool = False,
                      grid: bool = False):
        """Memoized eval_shape of a cache tree: ``grid`` selects the
        slot-grid decode model (the paged pool + block tables),
        otherwise the batch-1 LINEAR prefill model.  One
        trace per (draft, batch, grid) — re-tracing per request would
        put host latency in the serving loop."""
        key = (draft, batch, grid)
        shapes = self._cache_shapes.get(key)
        if shapes is None:
            if grid:
                model = self._draft_model if draft else self._model
            else:
                model = (self._draft_prefill_model if draft
                         else self._prefill_model)
            variables = (self._draft_variables if draft
                         else self._variables)

            def shape_fn(variables):
                with quantized_inference(), nn.intercept_methods(
                        _ffn_passes):
                    return model.apply(
                        variables, jnp.zeros((batch, 1), jnp.int32),
                        mutable=["cache"])[1]["cache"]

            shapes = jax.eval_shape(shape_fn, variables)
            self._cache_shapes[key] = shapes
        return shapes

    def _flash_layers(self, draft: bool, q_len: int) -> int:
        """Attention layers of the batch-1 prefill model whose walk of
        a call of ``q_len`` tokens runs a kernel, over plain rows or
        latent ones (``layers.flash_walk_layers``), for
        ``prefill/piece``; under ``_ctx`` (a mesh vetoes the
        kernels)."""
        key = (draft, q_len)
        if key not in self._flash_layer_counts:
            self._flash_layer_counts[key] = flash_walk_layers(
                self._cache_struct(1, draft), q_len,
                self._latent_sizes[draft])
        return self._flash_layer_counts[key]

    # Memory discipline (ttd-lint memcheck + TTD_MEMCHECK=1): THE
    # engine allocator — every cache tree this engine mints on device
    # comes through here (or through _admission_cache_1 below, whose
    # gather/copy paths mint the same batch-1 layout).  The pool split
    # mirrors what an operator budgets: the slot-grid pools (target
    # "kv_pool", draft "draft_pool") are owner-lifetime — allocated
    # once, alive until the engine dies, exact in the gauges — while
    # batch-1 prefill caches are leaf-lifetime transients (the charge
    # is the admission-time budget gate; donation threads the buffers
    # through the piece programs as successors the ledger cannot see).
    # Projection comes from the memoized cache eval_shape, so an
    # over-budget pool raises BEFORE any buffer exists.
    @memory_budget(
        pool=lambda self, batch, draft=False, grid=False:
            (("draft_pool" if draft else "kv_pool") if grid
             else ("draft_prefill" if draft else "prefill_cache")),
        budget_fn=lambda self, *a, **k: self.hbm_budget_bytes,
        project_fn=lambda self, batch, draft=False, grid=False:
            memcheck.tree_bytes(self._cache_struct(batch, draft, grid)),
        lifetime=lambda self, batch, draft=False, grid=False:
            ("owner" if grid else "leaf"))
    def _fresh_cache(self, batch: int, draft: bool = False,
                     grid: bool = False):
        """Zeroed cache tree for ``batch`` rows (target or draft model;
        ``grid``: the slot-grid decode layout vs the batch-1 linear
        prefill layout).  Prefill asks for a fresh batch-1 cache per
        request — donation consumes the buffers."""
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self._cache_struct(batch, draft, grid))

    def _pieces_for(self, m: int):
        """(piece_len, n_pieces) for prefilling an m-token span — THE
        piece-sizing rule for request suffixes and preloaded prefixes
        alike.  Bucket mode runs spans longer than the largest bucket
        as largest-bucket-sized pieces (appends at the running index,
        the same mechanics as chunked prefill), so long shared system
        prompts preload without a dedicated chunk setting."""
        if self._exact_prefill:
            return m, 1
        if self.prefill_chunk is not None:
            return self.prefill_chunk, -(-m // self.prefill_chunk)
        piece = _bucket_len(min(m, self.prompt_buckets[-1]),
                            self.prompt_buckets)
        return piece, -(-m // piece)

    def _run_target_piece(self, cache_1, padded, piece: int, i: int,
                          m: int, seed: int, rng0: int = 0, k: int = 1):
        """Pieces ``i .. i + k - 1`` of a target prefill as one call —
        THE single source of the per-piece layout/local-idx rule,
        shared by request admission (``_advance_piece``) and prefix
        preload (``_prefill_tokens``).  ``rng0``: the first pick's rng
        counter (resume-from-token admission continues a stream; fresh
        requests pick at 0)."""
        toks = jnp.asarray(padded[:, i * piece:(i + k) * piece])
        # local_idx only matters on the call holding the last real
        # token (the final one).
        local = min(m - 1 - i * piece, k * piece - 1)
        return self._launch(self._prefill_piece, self._variables,
                            cache_1, toks, jnp.int32(max(local, 0)),
                            jnp.uint32(seed), jnp.int32(rng0))

    def _run_draft_piece(self, d_cache_1, padded, piece: int, i: int,
                         k: int = 1):
        """Pieces ``i .. i + k - 1`` of a draft prefill as one call
        (same piece grid as the target's — both caches must hold
        identical row sets)."""
        toks = jnp.asarray(padded[:, i * piece:(i + k) * piece])
        return self._launch(self._draft_prefill_piece,
                            self._draft_variables, d_cache_1, toks)

    def _prefill_tokens(self, work, *, seed: int, cache_1, draft: bool):
        """Append ``work`` to a batch-1 cache in compile-bounded pieces,
        all at once (prefix preload; target and draft).  Returns
        (cache, first_token) — ``first`` is the pick at the last REAL
        row (None for the draft, which only needs its KV rows)."""
        m = len(work)
        piece, n_pieces = self._pieces_for(m)
        padded = np.zeros((1, piece * n_pieces), np.int32)
        padded[0, :m] = work
        first = None
        for i in range(n_pieces):
            if draft:
                cache_1 = self._run_draft_piece(cache_1, padded,
                                                piece, i)
            else:
                cache_1, first = self._run_target_piece(
                    cache_1, padded, piece, i, m, seed)
        return cache_1, first

    @thread_role("main", "driver")
    def preload_prefix(self, tokens) -> None:
        """Prefill a shared prompt prefix ONCE; every later request
        whose prompt strictly extends it prefills only the suffix.

        The production lever for shared system prompts / few-shot
        preambles: the stored batch-1 cache is copied per request
        (donation-safe) and the suffix pieces append at the prefix's
        true position — causal masks and RoPE read positions from the
        per-slot index, so outputs are token-identical to a full
        prefill (pinned in tests/test_serving.py).  Under speculative
        serving the DRAFT model's prefix cache is stored alongside the
        target's (both prefill once, both reuse).  Restriction:
        dense-dispatch MoE prefills at the exact full length (routing
        capacity is length-dependent) and serves without prefix reuse.
        """
        tokens = [int(t) for t in np.asarray(tokens).reshape(-1)]
        if not tokens:
            raise ValueError("empty prefix")
        if self._exact_prefill:
            raise ValueError(
                "prefix caching needs length-independent routing; "
                "dense-dispatch MoE prefills at the exact prompt length "
                "(dispatch='gmm' supports prefix caching)")
        if self._state_layers:
            raise ValueError(
                "this model has recurrent layers: the state at a "
                "prefix's end is not kept (only a lane's newest is), so "
                "no prefix is shared between requests: preload_prefix "
                "and the radix index are off")
        if self._window is not None:
            raise ValueError(
                f"this model has window layers (window {self._window}): "
                f"their rows behind the window are not kept and cannot "
                f"be rebuilt without running every layer below over "
                f"them, so no prefix is shared between requests")
        n = len(tokens)
        if n >= self.cache_len:
            raise ValueError(
                f"prefix length {n} must leave cache room "
                f"(cache_len={self.cache_len})")
        # Pin the stored index to the TRUE prefix length: suffix
        # pieces must append at position n, not after the pad rows
        # (which stay harmless — overwritten before any read).
        def pin(path, leaf):
            if any(getattr(k, "key", "") == "index" for k in path):
                return jnp.full_like(leaf, n)
            return leaf

        with self._ctx(), events.span("prefill/prefix", tokens=n):
            cache_1, _ = self._prefill_tokens(
                tokens, seed=0, cache_1=self._fresh_cache(1),
                draft=False)
            cache_1 = jax.tree_util.tree_map_with_path(pin, cache_1)
            d_cache_1 = None
            if self._draft_model is not None:
                d_cache_1, _ = self._prefill_tokens(
                    tokens, seed=0,
                    cache_1=self._fresh_cache(1, draft=True), draft=True)
                d_cache_1 = jax.tree_util.tree_map_with_path(
                    pin, d_cache_1)
        # LRU bound: these entries hold device memory (a batch-1 cache
        # pair each) and used to accumulate forever — evict the least
        # recently MATCHED prefix past the limit.
        with self._prefix_lock:
            self._prefix_caches[tuple(tokens)] = (cache_1, d_cache_1)
            self._prefix_caches.move_to_end(tuple(tokens))
            while len(self._prefix_caches) > self.prefix_cache_limit:
                self._prefix_caches.popitem(last=False)
        # The STORED pair is a held-as-minted device tree (copied per
        # admission, freed at LRU eviction) — exactly the
        # leaf-lifetime contract, so the memcheck ledger tracks the
        # prefix store byte-exactly and an unbounded preload pattern
        # trips the budget here instead of OOMing later.
        memcheck.track(self, "prefix_cache", (cache_1, d_cache_1),
                       label=f"prefix{n}",
                       budget=self.hbm_budget_bytes)
        # The radix index is seeded with the prefix's full blocks too
        # (scattered from the just-built cache — no second prefill), so
        # later requests share them through the pool like any other
        # radix hit; the stored batch-1 pair keeps covering the
        # sub-block tail (a prefix shorter than one block has no
        # shareable blocks at all).
        with self._ctx():
            self._seed_radix_from_cache(tokens, cache_1, d_cache_1)

    def _seed_radix_from_cache(self, tokens, cache_1, d_cache_1) -> None:
        """Scatter a preloaded prefix's FULL blocks from its batch-1
        cache into freshly allocated pool blocks and hand them to the
        radix index (tree-held: shared by every later matching request,
        LRU-evicted only under pressure)."""
        n = len(tokens)
        bs = self.kv_block_size
        m = n // bs                       # full, shareable blocks
        if m == 0:
            return                        # sub-block prefix: pair-only
        matched, shared = self._radix.match(tokens[:m * bs],
                                            allow_full=True)
        if matched >= m * bs:
            return                        # every full block is cached
        # Pin the already-cached leading blocks against the eviction
        # our own allocation below may trigger.
        for b in shared:
            self._kv_pool.ref(b)
        try:
            n_new = m - len(shared)
            fresh = self._kv_pool.alloc(n_new)
            if fresh is None:
                evicted = self._radix.evict_for(n_new)
                if evicted:
                    with self._stats_lock:
                        self.kv_stats["evictions"] += evicted
                    events.instant("kv/evict", blocks=evicted)
                fresh = self._kv_pool.alloc(n_new)
            if fresh is None:
                logger.warning(
                    "preload_prefix: KV pool too busy to share the "
                    "prefix's %d blocks (%d free); the batch-1 cache "
                    "still serves it", n_new,
                    self._kv_pool.free_blocks())
                return
            row = shared + fresh
            table_np = np.zeros((self._kv_nblk_lane,), np.int32)
            table_np[:len(row)] = row
            table_j = jnp.asarray(table_np)
            start, end = jnp.int32(matched), jnp.int32(m * bs)
            if self._cache is None:
                self._cache = self._fresh_cache(self.slots, grid=True)
            self._cache = self._launch(self._paged_preload, self._cache,
                                       cache_1, table_j, start, end)
            if self._draft_model is not None:
                if self._d_cache is None:
                    self._d_cache = self._fresh_cache(
                        self.slots, draft=True, grid=True)
                self._d_cache = self._launch(
                    self._paged_preload, self._d_cache, d_cache_1,
                    table_j, start, end)
            self._radix.insert(tokens[:m * bs], lambda j: row[j])
            # The tree took one reference per NEW node; release the
            # allocation's own (a node already present keeps its
            # canonical block, so ours frees here).
            for b in fresh:
                self._kv_pool.deref(b)
        finally:
            for b in shared:
                self._kv_pool.deref(b)

    # Row-holding cache leaves, by batch-1 linear name, with the axis
    # their rows live on ([..., 1, C, *row]): the serialization
    # manifest for KV handoff.
    _KV_LEAF_ROW_AXIS = {lin: -(1 + dims)
                         for lin, dims in _LINEAR_ROW_DIMS.items()}

    @thread_role("main", "driver")
    def export_prefix_kv(self, tokens):
        """Serialize the KV of ``tokens``' full leading blocks for a
        prefill→decode handoff: ``(meta, blob)``, or None when there is
        nothing exportable (no prefix sharing, sub-block prompt, pool
        too busy to share).

        The prefill side of disaggregated serving: prefill the prompt's
        block-aligned head (``preload_prefix`` — the tested machinery,
        which also makes repeat prompts free on this worker), then
        gather those pool rows back out (``_gather_prefix``) and ship
        the bytes VERBATIM — the pool already stores the
        ``_quantize_kv_rows`` output, so the receiving pool installs
        bit-identical rows and the decode-side radix hit reproduces the
        exact local-prefill output.  At least one suffix token is left
        unexported (its logit picks the first generated token on the
        decode worker, same as any radix hit).  Mutates engine state —
        callers marshal onto the engine's owning thread
        (``EngineDriver.call``)."""
        if not self._share_prefix:
            return None
        tokens = [int(t) for t in np.asarray(tokens).reshape(-1)]
        bs = self.kv_block_size
        m = max(0, (len(tokens) - 1) // bs)   # full blocks, head only
        if m == 0:
            return None
        head = tokens[:m * bs]
        matched, shared = self._radix.match(head, allow_full=True,
                                            record=False)
        if matched < m * bs:
            self.preload_prefix(head)
            matched, shared = self._radix.match(head, allow_full=True,
                                                record=False)
        if matched < m * bs or self._cache is None:
            return None               # pool too busy to share the head
        for b in shared:
            self._kv_pool.ref(b)
        try:
            table_np = np.zeros((self._kv_nblk_lane,), np.int32)
            table_np[:len(shared)] = shared
            table_j = jnp.asarray(table_np)
            with self._ctx(), events.span("kv/export", tokens=m * bs):
                leaves, blob = self._serialize_rows(table_j, m * bs)
        finally:
            for b in shared:
                self._kv_pool.deref(b)
        meta = {"tokens": head, "n": m * bs,
                "draft": self._draft_model is not None,
                "leaves": leaves}
        return meta, blob

    def _serialize_rows(self, table_j, n: int):
        """The ONE wire byte-recipe every KV-bearing frame ships
        (``KV_HANDOFF`` and ``MIGRATE``): gather the first ``n`` pool
        rows reachable through ``table_j`` into a batch-1 linear cache
        pair, slice each row-holding leaf, and concatenate contiguous
        bytes in path-sorted manifest order (the installer replays the
        manifest positionally).  Returns ``(leaves, blob)``.  Callers
        hold refs on (or own) the table's blocks and run on the
        engine-owning thread."""
        span = jnp.int32(n)
        pairs = [(False, self._launch(
            self._gather_prefix, self._cache, table_j, False, span))]
        if self._draft_model is not None:
            pairs.append((True, self._launch(
                self._gather_prefix, self._d_cache, table_j, True, span)))
        leaves, chunks = [], []
        for draft, cache_1 in pairs:
            flat = jax.tree_util.tree_flatten_with_path(cache_1)[0]
            for p, leaf in sorted(
                    flat, key=lambda pl: self._path_key(pl[0])):
                name = getattr(p[-1], "key", "")
                axis = self._KV_LEAF_ROW_AXIS.get(name)
                if axis is None:
                    continue
                idx = [slice(None)] * leaf.ndim
                idx[axis] = slice(0, n)
                arr = np.asarray(jax.device_get(leaf[tuple(idx)]))
                leaves.append({
                    "path": list(self._path_key(p)),
                    "draft": draft,
                    "dtype": arr.dtype.str,
                    "shape": list(arr.shape)})
                chunks.append(np.ascontiguousarray(arr).tobytes())
        return leaves, b"".join(chunks)

    @thread_role("main", "driver")
    def install_prefix_kv(self, meta, blob) -> int:
        """Install handed-off KV rows into this engine's pool + radix
        index; returns the warm-token count (0 = refused, benign — the
        request simply prefills locally with identical output).

        The decode side of the handoff: rebuild the batch-1 linear
        cache pair from the wire bytes (exact dtypes — the rows stay
        bit-identical to the sender's pool) and hand it to
        ``_seed_radix_from_cache``, the SAME path ``preload_prefix``
        seeds the radix through, so allocation, eviction pressure, COW
        and partial-failure semantics are all the tested ones.  Mutates
        engine state — callers marshal onto the engine's owning thread
        (``EngineDriver.call``)."""
        if not self._share_prefix:
            return 0
        tokens = [int(t) for t in meta.get("tokens", ())]
        n = int(meta.get("n", 0))
        bs = self.kv_block_size
        if n <= 0 or n % bs or n != len(tokens):
            raise ValueError(f"bad handoff span: n={n} over "
                             f"{len(tokens)} tokens (block_size={bs})")
        if n >= self.cache_len:
            raise ValueError(f"handoff span {n} exceeds "
                             f"cache_len={self.cache_len}")
        matched, _ = self._radix.match(tokens, allow_full=True,
                                       record=False)
        if matched >= n:
            return n                  # already warm — nothing to do
        if bool(meta.get("draft")) != (self._draft_model is not None):
            return 0   # speculative mismatch: both caches must hold
        #              # identical row sets, so refuse → local prefill
        arrays, off = {}, 0
        for leaf in meta.get("leaves", ()):
            dtype = np.dtype(leaf["dtype"])
            shape = tuple(int(d) for d in leaf["shape"])
            count = int(np.prod(shape)) if shape else 1
            end = off + count * dtype.itemsize
            if end > len(blob):
                raise ValueError(
                    f"handoff blob truncated: leaf {leaf['path']} "
                    f"needs bytes [{off}, {end}) of {len(blob)}")
            arrays[(bool(leaf.get("draft")), tuple(leaf["path"]))] = (
                np.frombuffer(blob, dtype, count, off).reshape(shape))
            off = end
        if off != len(blob):
            raise ValueError(f"handoff blob has {len(blob) - off} "
                             f"trailing bytes")

        def build_one(draft: bool):
            want = {pk: a for (d, pk), a in arrays.items()
                    if d is draft}

            def fill(path, leaf):
                name = getattr(path[-1], "key", "")
                if name == "index":
                    return jnp.full_like(leaf, n)
                axis = self._KV_LEAF_ROW_AXIS.get(name)
                arr = want.get(self._path_key(path))
                if axis is None or arr is None:
                    return leaf
                idx = [slice(None)] * leaf.ndim
                idx[axis] = slice(0, n)
                want_shape = tuple(leaf[tuple(idx)].shape)
                if arr.shape != want_shape or (arr.dtype
                                               != leaf.dtype):
                    raise ValueError(
                        f"handoff leaf {self._path_key(path)} is "
                        f"{arr.dtype}{list(arr.shape)}, this engine "
                        f"needs {leaf.dtype}{list(want_shape)}")
                return leaf.at[tuple(idx)].set(jnp.asarray(arr))

            return jax.tree_util.tree_map_with_path(
                fill, self._fresh_cache(1, draft=draft))

        with self._ctx(), events.span("kv/install", tokens=n):
            cache_1 = self._launch(build_one, False)
            d_cache_1 = (self._launch(build_one, True)
                         if self._draft_model is not None else None)
            self._seed_radix_from_cache(tokens, cache_1, d_cache_1)
        matched, _ = self._radix.match(tokens, allow_full=True,
                                       record=False)
        return matched

    @thread_role("main", "driver")
    def export_lane(self, request_id: int):
        """Serialize a live request's FULL migration state —
        ``(meta, blob)`` — or None when the id is unknown/finished.

        The source half of live mid-stream migration (the MIGRATE
        frame's payload).  ``meta["kind"]`` names where the request
        lived:

        - ``"lane"``: a decoding slot.  ``tokens`` is the authoritative
          prompt+generated history (a snapshot taken between engine
          steps, so it is always >= what any relay has delivered),
          ``remaining``/``seed``/``count`` restore the budget and the
          rng counter, and ``meta["kv"]`` + ``blob`` carry the lane's
          full-block pool rows in the exact ``KV_HANDOFF`` byte recipe
          (``_serialize_rows`` — int8 + scales, bit-identical rows) so
          the target resumes WITHOUT re-prefilling the head.  Rows are
          gathered from the lane's OWN block table: valid for
          ``[0, len(tokens) - 1)`` (the last sampled token was never
          fed back), hence the head stops at the last full block under
          that bound.  A sub-block lane, or one that shares no prefix,
          exports with ``kv=None`` — the target re-prefills, which is
          exactly the failover path and stays bitwise by the same
          contract.
        - ``"staged"``: mid-admission in a reserved lane.  The partial
          batch-1 prefill is NOT shipped (pieces are cheap to redo and
          piece boundaries are engine-local); the staged cursor rides
          along so operators can see how far admission got.
        - ``"queued"``: accepted but never placed — parameters only.

        Export is read-only: the caller decides whether the move
        committed and then ``cancel()``s this side (the
        ``EngineDriver.export_lane`` wrapper does both atomically on
        the engine-owning thread, so no token can generate after the
        snapshot).  The history has to be whole, so a lane whose first
        token no harvest has read yet has it read HERE, between steps
        and off the serving path (outside ``engine/step``): the read may
        wait for the lane's last piece, and the token may retire the
        lane, which then has nothing to move.  A request of one token
        whose token is awaited is finished but for that read and
        exports as None."""
        for item in self._queue:
            if item[0] == request_id:
                _, prompt, max_new, seed, resume = item
                return {"kind": "queued", "prompt": list(prompt),
                        "max_new": int(max_new), "seed": int(seed),
                        "resume_from": int(resume), "kv": None}, b""
        for task in self._staging.values():
            if task.request_id == request_id:
                return {"kind": "staged", "prompt": list(task.prompt),
                        "max_new": int(task.max_new),
                        "seed": int(task.seed),
                        "resume_from": int(task.resume),
                        "cursor": int(task.cursor), "kv": None}, b""
        for slot, state in enumerate(self._slot_states):
            if state is None or state.request_id != request_id:
                continue
            if state.first is not None:
                self._commit_picks([(slot, state, int(state.first))])
                if self._slot_states[slot] is not state:
                    return None         # its first token ended it
            meta = {"kind": "lane",
                    "tokens": [int(t) for t in state.tokens],
                    "remaining": int(state.remaining),
                    "last_token": int(state.tokens[-1]),
                    "seed": int(state.seed), "count": int(state.count),
                    "done": bool(state.done), "kv": None}
            blob = b""
            bs = self.kv_block_size
            m = max(0, (len(state.tokens) - 1) // bs)
            kv = self._lane_kv[slot] if self._share_prefix else None
            if kv is not None and m > 0 and self._cache is not None:
                head = [int(t) for t in state.tokens[:m * bs]]
                # The lane's claim already holds a ref on every block
                # in its table, and we run between steps on the
                # engine-owning thread — no eviction can race the
                # gather, so no extra pinning is needed.
                table_j = self._kv_table(kv)
                with self._ctx(), events.span("kv/export",
                                              tokens=m * bs):
                    leaves, blob = self._serialize_rows(table_j,
                                                        m * bs)
                meta["kv"] = {"tokens": head, "n": m * bs,
                              "draft": self._draft_model is not None,
                              "leaves": leaves}
            return meta, blob
        return None

    @thread_role("main", "driver")
    def install_lane(self, meta, blob) -> int:
        """Install a migrated lane's KV rows into this engine's pool +
        radix index; returns the warm-token count (0 = nothing to
        install or refused — benign: the re-admitted request simply
        prefills locally with identical output, the failover path).

        The target half of migration.  Only the KV needs engine-side
        installation — the request itself is re-admitted through the
        pool's normal resume-from-token placement, which radix-hits
        the rows seeded here (``install_prefix_kv`` → the SAME
        ``_seed_radix_from_cache`` path as a prefill→decode handoff,
        so allocation, eviction pressure and partial-failure semantics
        are all the tested ones).  Raises ValueError on a torn or
        lying manifest — the transport classifies that as a protocol
        failure of the one replica."""
        kv = meta.get("kv") if isinstance(meta, dict) else None
        if not kv or not blob:
            return 0
        return self.install_prefix_kv(dict(kv), blob)

    def _match_prefix(self, prompt, touch: bool = False):
        """Longest stored prefix the prompt strictly extends →
        (prefix_len, (target_cache, draft_cache_or_None));
        (0, None) when none applies.  ``touch`` refreshes the winner's
        LRU recency — admission paths (the driver loop) pass True;
        ``validate_request`` passes False.  Either way the walk holds
        ``_prefix_lock``: handler threads validate concurrently with
        the driver's LRU touches, and an OrderedDict mutated
        mid-iteration raises in the READER."""
        with self._prefix_lock:
            if not self._prefix_caches:
                return 0, None
            best, best_key, best_pair = 0, None, None
            for toks, pair in self._prefix_caches.items():
                m = len(toks)
                if best < m < len(prompt) and prompt[:m] == list(toks):
                    best, best_key, best_pair = m, toks, pair
            if touch and best_key is not None:
                self._prefix_caches.move_to_end(best_key)
            return best, best_pair

    def _note_moe_prefill_len(self, n: int) -> None:
        if not self._exact_prefill or n in self._moe_prefill_lens:
            return
        self._moe_prefill_lens.add(n)
        if len(self._moe_prefill_lens) > 1:
            # Compile-storm hazard: MoE prefills at the EXACT length
            # (router capacity depends on it), so every distinct
            # prompt length is a new XLA program.  Warn once per
            # length; mitigation: pad/truncate prompts to a few
            # lengths host-side (MIGRATION.md §8).
            logger.warning(
                "MoE engine prefill compiling for new prompt length "
                "%d (%d distinct lengths so far — one program each; "
                "consider padding prompts to a few fixed lengths)",
                n, len(self._moe_prefill_lens))

    # -- paged-pool admission (block claims, prefix hits, eviction) --------

    def _kv_claim(self, rid: int, prompt, max_new: int):
        """Claim a lane's physical blocks: radix-match the prompt's
        block-aligned prefix (shared blocks, one extra ref each), then
        allocate the rest — evicting LRU retired radix entries under
        pressure.  Returns a ``serving_kv.LaneKV`` or None when the
        pool cannot supply the blocks (the request is REFUSED admission
        and keeps its queue place — blocks free as lanes retire; never
        a corrupted live lane)."""
        bs = self.kv_block_size
        need = -(-min(len(prompt) + max_new, self.cache_len) // bs)
        # A block-starved queue head retries this claim every engine
        # step: on retries, skip the flight-recorder span and the radix
        # hit stats (one admission must not read as thousands), same
        # per-request rule as the refusal counter below.
        retry = rid == self._kv_refused_rid
        matched, shared = ((0, []) if not self._share_prefix
                           else self._radix.match(prompt,
                                                  record=not retry))
        # Ref the shared blocks BEFORE allocating: eviction only takes
        # refcount-1 leaves, so the refs pin the matched path against
        # the very eviction the allocation below may trigger.
        for b in shared:
            self._kv_pool.ref(b)
        n_owned = need - len(shared)
        with (contextlib.nullcontext() if retry
              else events.span("kv/alloc", rid=rid, blocks=n_owned,
                               shared=len(shared), pool="full")):
            owned = self._kv_pool.alloc(n_owned)
            if owned is None:
                evicted = self._radix.evict_for(n_owned)
                if evicted:
                    with self._stats_lock:
                        self.kv_stats["evictions"] += evicted
                    events.instant("kv/evict", blocks=evicted)
                owned = self._kv_pool.alloc(n_owned)
        if owned is None:
            for b in shared:
                self._kv_pool.deref(b)
            # Count one refusal PER REQUEST, not per retry: the queue
            # head is re-claimed every serve_step while it waits, and a
            # per-attempt count would report thousands of "refusals"
            # for one waiting request.
            if self._kv_refused_rid != rid:
                self._kv_refused_rid = rid
                with self._stats_lock:
                    self.kv_stats["alloc_refusals"] += 1
                events.instant("kv/refused", rid=rid, blocks=n_owned)
            return None
        if matched:
            with self._stats_lock:
                self.kv_stats["prefix_hits"] += 1
                self.kv_stats["prefix_hit_tokens"] += matched
            events.instant("kv/prefix_hit", rid=rid, tokens=matched)
        return serving_kv.LaneKV(request_id=rid, matched=matched,
                                 shared=shared, owned=owned)

    def _kv_release(self, kv) -> None:
        """Drop the lane's references; blocks nobody else (radix or a
        sharing lane) holds return to the free list."""
        for b in kv.blocks():
            self._kv_pool.deref(b)

    def _kv_table(self, kv):
        """The lane's device block-table row (scratch-padded)."""
        return jnp.asarray(
            np.asarray(kv.table(self._kv_nblk_lane), np.int32))

    def _lane_claim(self, slot: int, kv, prompt) -> None:
        """Install a lane's claim at insert time and feed the radix
        index with the prompt's full blocks (their rows are valid —
        prefill wrote [0, len(prompt)) before this), so LATER requests
        with the same prefix share them immediately."""
        self._lane_kv[slot] = kv
        self._stale_slots.discard(slot)
        if self._share_prefix:
            table = kv.table(self._kv_nblk_lane)
            self._radix.insert(prompt, lambda j: table[j])

    def _lane_release(self, slot: int, tokens=None) -> None:
        """Retire/cancel a lane's claim: optionally extend the radix
        index with the request's generated full blocks (rows are valid
        up to ``len(tokens) - 1`` — the final token was never fed back,
        so its row may not exist), then drop the lane's refs and mark
        the lane stale so the next dispatch points its table at
        scratch before any in-flight garbage chunk can land in freed
        blocks."""
        kv = self._lane_kv[slot]
        if kv is None:
            return
        if tokens is not None and self._share_prefix:
            bs = self.kv_block_size
            keep = tokens[:((len(tokens) - 1) // bs) * bs]
            table = kv.table(self._kv_nblk_lane)
            self._radix.insert(keep, lambda j: table[j])
        self._kv_release(kv)
        self._lane_kv[slot] = None
        self._stale_slots.add(slot)

    @dispatch_critical
    def _flush_stale_lanes(self) -> None:
        """Zero retired/cancelled lanes' block-table rows before the
        next decode program (their freed blocks may already belong to
        someone else; the garbage chunk must write scratch)."""
        if not self._stale_slots:
            return
        if self._cache is None:
            self._stale_slots.clear()
            return
        mask = np.zeros((self.slots,), bool)
        for s in self._stale_slots:
            mask[s] = True
        jm = jnp.asarray(mask)
        self._cache = self._launch(self._reset_lanes, self._cache, jm)
        if self._d_cache is not None:
            self._d_cache = self._launch(self._reset_lanes,
                                         self._d_cache, jm)
        self._stale_slots.clear()

    def _admission_match(self, kv, prompt):
        """(pre_len, pre_pair) for an admission: the radix match
        (kv.matched, gather path) unless a STORED preload pair covers
        more — sub-block prefix tails only the batch-1 pair can
        represent (a prefix shorter than a block has no shareable
        blocks; a 20-token prefix at block 16 shares one block and
        copies the 4-token tail).  Suffix prefill piece sizing follows
        ``pre_len``."""
        pre_len, pre_pair = kv.matched, None
        if self._share_prefix:
            lin_len, lin_pair = self._match_prefix(prompt, touch=True)
            if lin_len > pre_len:
                pre_len, pre_pair = lin_len, lin_pair
        return pre_len, pre_pair

    @memory_budget(
        pool=lambda self, pre_pair, kv, table_j, draft:
            ("draft_prefill" if draft else "prefill_cache"),
        budget_fn=lambda self, *a, **k: self.hbm_budget_bytes,
        project_fn=lambda self, pre_pair, kv, table_j, draft:
            memcheck.tree_bytes(self._cache_struct(1, draft=draft)),
        lifetime="leaf")
    def _admission_cache_1(self, pre_pair, kv, table_j, draft: bool):
        """The batch-1 cache a request's suffix prefill appends to:
        fresh when nothing matched; the stored prefix cache's copy when
        a preloaded pair won the match; a pool gather of the
        radix-matched rows otherwise (copy instead of recompute — same
        downstream piece programs every way).  All three paths mint
        the same batch-1 layout, which is what the @memory_budget
        projection charges (the nested ``_fresh_cache`` call defers to
        this outermost charge — the sanitizer's re-entrancy rule)."""
        kind = self._admission_kind(pre_pair, kv)
        if kind == "copy":
            return self._launch(jax.tree.map, jnp.copy,
                                pre_pair[1 if draft else 0])
        if kind == "fresh":
            return self._launch(self._fresh_cache, 1, draft=draft)
        cache = self._d_cache if draft else self._cache
        if cache is None:          # defensive: matched blocks imply a
            cache = self._launch(self._fresh_cache, self.slots,
                                 draft=draft, grid=True)
            if draft:              # built grid, so keep it
                self._d_cache = cache
            else:
                self._cache = cache
        return self._launch(self._gather_prefix, cache, table_j, draft,
                            jnp.int32(kv.matched))

    def _admission_kind(self, pre_pair, kv) -> str:
        """Which of ``_admission_cache_1``'s three a request gets (the
        ``prefill/cache`` span's ``kind``)."""
        if pre_pair is not None:
            return "copy"
        if kv.matched == 0:
            return "fresh"
        return "gather"

    def kv_blocks_total(self) -> int:
        """Allocatable physical blocks in the paged pool."""
        return self._kv_pool.n_blocks

    def kv_blocks_in_use(self) -> int:
        """Blocks currently referenced (live lanes + radix cache)."""
        return self._kv_pool.blocks_in_use()

    def kv_bytes_in_use(self) -> int:
        """Referenced pool blocks in device BYTES (live lanes + radix
        cache at the real per-block row cost) — the occupancy half of
        ``kv_pool_bytes()``'s constant capacity, relayed per worker in
        stats frames and shown per replica in /healthz."""
        return self._kv_pool.bytes_in_use()

    def kv_pool_bytes(self) -> int:
        """Device bytes the paged KV pools pin across layers (target +
        draft; int8 scale pools included).  Constant
        per engine — the pool never grows — so scrape threads read a
        plain int; the ``--kv-pool-blocks`` oversizing lever budgets
        against this."""
        return self._kv_pool_bytes

    def kv_pool_parts(self) -> dict:
        """``kv_pool_bytes()``'s parts for the target model, by kind of
        row and by what holds it: ``latent_pool_bytes`` and
        ``index_pool_bytes`` (the blocks tables map),
        ``latent_ring_bytes`` (a window layer's rings), ``key_`` /
        ``value_`` likewise; each layer at its own row's width."""
        return dict(self._kv_pool_parts)

    def state_pool_bytes(self) -> int:
        """Device bytes of recurrent state the slot grid pins: every
        slot's state and convolution tail in every linear layer (0 for
        a model without them).  Constant per engine, and apart from
        ``kv_pool_bytes()``: these are no rows, no table maps them and
        no length walks them."""
        return self._state_pool_bytes

    def _prefill_pair_bytes(self) -> int:
        """Bytes of one batch-1 prefill cache pair (target + draft) —
        the marginal device allocation an admission mints; memoized
        off the same cache eval_shape the pool-bytes gauge uses
        (host-only trace, no device work)."""
        if self._prefill_bytes_memo is None:
            n = memcheck.tree_bytes(self._cache_struct(1))
            if self._draft_model is not None:
                n += memcheck.tree_bytes(self._cache_struct(1,
                                                            draft=True))
            self._prefill_bytes_memo = n
        return self._prefill_bytes_memo

    def hbm_autosized_bytes(self) -> int:
        """The HBM budget the autosize solve installed (0 when the
        engine was hand-sized or the solve was killed) — the
        ``ttd_engine_hbm_autosized_bytes`` gauge feed.  Written once at
        construction, so scrape threads read a plain int."""
        return self._hbm_autosized

    def _solve_hbm_autosize(self, config, draft_config):
        """``kv_pool_blocks='auto'``: solve (kv_pool_blocks,
        hbm_budget_bytes) EXACTLY from the device's reported HBM and
        the memcheck projection.  Grid cache bytes are linear in the
        block count (pool rows scale; block tables, indices, and
        scratch rows don't), so two eval_shape probes (n=1, n=2) give
        the intercept/slope, and the solve takes the largest n with

            grid_bytes(n) + batch-1 prefill transients
                <= avail * (1 - hbm_headroom)

        The right-hand side becomes ``hbm_budget_bytes``, so the
        ``@memory_budget`` ledger enforces the same arithmetic the
        solve used: an autosized engine's own pools and admission
        transients fit by construction (zero MemoryBudgetError — the
        exactness tests/test_spec_adaptive.py pins).  Host-only
        eval_shape traces; nothing allocates here.  Called from the
        ctor BEFORE ``_cache_shapes`` exists, hence the direct
        eval_shape instead of ``_cache_struct``."""
        avail = _device_hbm_bytes()
        if avail is None:
            raise ValueError(
                "kv_pool_blocks='auto' needs a device memory report "
                "(device.memory_stats()) or TTD_HBM_BYTES=<bytes>")

        def tree_b(model, variables, batch):
            def shape_fn(v):
                with quantized_inference():
                    return model.apply(
                        v, jnp.zeros((batch, 1), jnp.int32),
                        mutable=["cache"])[1]["cache"]

            return memcheck.tree_bytes(
                jax.eval_shape(shape_fn, variables))

        def grid_bytes(n):
            b = tree_b(
                _decode_model(config, self.cache_len, slot_decode=True,
                              paged_kv_blocks=1 + n,
                              kv_block_size=self.kv_block_size),
                self._variables, self.slots)
            if draft_config is not None:
                b += tree_b(
                    _decode_model(draft_config, self.cache_len,
                                  slot_decode=True,
                                  paged_kv_blocks=1 + n,
                                  kv_block_size=self.kv_block_size),
                    self._draft_variables, self.slots)
            return b

        trans = tree_b(self._prefill_model, self._variables, 1)
        if draft_config is not None:
            trans += tree_b(self._draft_prefill_model,
                            self._draft_variables, 1)
        b1, b2 = grid_bytes(1), grid_bytes(2)
        slope, intercept = b2 - b1, 2 * b1 - b2
        usable = int(avail * (1.0 - self._hbm_headroom))
        n = (usable - intercept - trans) // slope
        if n < 1:
            raise ValueError(
                f"kv_pool_blocks='auto': no pool fits — device HBM "
                f"{avail} bytes minus {self._hbm_headroom:.0%} headroom "
                f"leaves {usable}, but one block of pools plus batch-1 "
                f"prefill transients needs "
                f"{intercept + slope + trans} (shrink hbm_headroom, "
                f"slots, or cache_len)")
        return int(n), usable

    def fused_attn(self) -> bool:
        """Whether the decode programs were compiled with the fused
        paged-attention kernel (False on CPU, under a mesh, or under
        TTD_NO_PALLAS=1)."""
        return self._fused_attn

    def _spec_depth(self) -> int:
        """Draft depth the NEXT speculative round dispatches at: the
        controller's pick under adaptive speculation, else the fixed
        ``speculative_k`` (0 on a plain-decode engine).  Host int —
        read BEFORE the dispatch window opens."""
        with self._stats_lock:
            ctrl = self._spec_ctrl
            return self._spec_k if ctrl is None else ctrl.depth()

    @thread_role("handler", "driver")
    def spec_depth(self) -> int:
        """Scrape face of ``_spec_depth`` — the
        ``ttd_engine_spec_depth`` gauge feed (a fixed engine reports
        its constant k; a plain-decode engine reports 0)."""
        return self._spec_depth()

    @thread_role("handler", "driver")
    def spec_accepted_tokens(self) -> int:
        """Cumulative draft tokens the target ACCEPTED across
        speculative rounds (the numerator of the fleet acceptance
        rate; ``ttd_engine_spec_accepted_tokens_total``)."""
        with self._stats_lock:
            return self.spec_stats["drafted_accepted"]

    @thread_role("handler", "driver")
    def spec_drafted_tokens(self) -> int:
        """Cumulative draft tokens PROPOSED across speculative rounds
        (k per slot-round at the round's dispatched depth — the
        denominator; ``ttd_engine_spec_drafted_tokens_total``)."""
        with self._stats_lock:
            return self.spec_stats["drafted"]

    def spec_telemetry(self) -> dict:
        """Per-depth controller telemetry (rounds, acceptance EWMA) —
        bench/debug surface; {} for fixed-depth engines."""
        with self._stats_lock:
            ctrl = self._spec_ctrl
            return {} if ctrl is None else ctrl.telemetry()

    @thread_role("handler", "driver")
    def kv_prefix_hit_tokens(self) -> int:
        """Cumulative prompt tokens whose prefill was skipped via
        radix prefix hits (the prefill-compute-saved counter; the
        `/metrics` FnCounter samples this from handler threads at
        scrape time, so the read locks)."""
        with self._stats_lock:
            return self.kv_stats["prefix_hit_tokens"]

    @thread_role("handler", "driver")
    def kv_evictions(self) -> int:
        """Cumulative blocks LRU-evicted from the radix cache under
        allocation pressure (scrape-sampled: the read locks)."""
        with self._stats_lock:
            return self.kv_stats["evictions"]

    # -- admission: staged prefill under a budget ---------------------------

    def _stage_from_queue(self) -> None:
        """Claim free lanes for queued requests as staged-prefill
        tasks.  Host-only bookkeeping — no device work happens until a
        budget installment advances the task — so this is safe to call
        anywhere in the step."""
        for slot in range(self.slots):
            if not self._queue:
                return
            if (self._slot_states[slot] is not None
                    or slot in self._staging):
                continue
            while self._queue:
                rid, prompt, max_new, seed, resume = \
                    self._queue.popleft()
                if max_new == 0:
                    self._outputs[rid] = list(prompt)
                    continue
                # A block-starved queue head comes back every step: its
                # retries record no span (``_kv_claim``'s rule).
                with (contextlib.nullcontext()
                      if rid == self._kv_refused_rid
                      else events.span("prefill/stage", rid=rid,
                                       tokens=len(prompt))) as stage:
                    staged = self._stage_request(
                        slot, rid, prompt, max_new, seed, resume)
                    if stage is not None:
                        stage.set(matched=staged or 0)
                self._poll_drained()
                if staged is None:
                    return
                break

    def _stage_request(self, slot: int, rid: int, prompt, max_new: int,
                       seed: int, resume: int):
        """Stage one request into ``slot`` (the ``prefill/stage`` span's
        body): claim its blocks, match a prefix, pad what is left into
        pieces.  Returns the prompt tokens a prefix supplied, or None
        when the pool has no blocks for it: the request goes back to
        the queue's head and staging stops (FIFO — nothing behind may
        jump the head; blocks free as lanes retire)."""
        kv = self._kv_claim(rid, prompt, max_new)
        if kv is None:
            self._queue.appendleft((rid, prompt, max_new, seed, resume))
            return None
        table_j = self._kv_table(kv)
        if self._ring_blocks:
            # The slot's own ring in each window layer: claimed with
            # the slot, nothing to refuse.
            events.instant("kv/alloc", rid=rid,
                           blocks=self._ring_blocks, shared=0,
                           pool="window")
        if self._state_layers:
            # The slot's own state in each linear layer: claimed with
            # the slot, no blocks, nothing to refuse.
            events.instant("kv/alloc", rid=rid, blocks=0, shared=0,
                           pool="state")
        pre_len, pre_pair = self._admission_match(kv, prompt)
        work = prompt[pre_len:]
        self._note_moe_prefill_len(len(prompt))
        m = len(work)
        piece, n_pieces = self._pieces_for(m)
        padded = np.zeros((1, piece * n_pieces), np.int32)
        padded[0, :m] = work
        self._staging[slot] = _PrefillTask(
            request_id=rid, prompt=list(prompt),
            max_new=max_new, seed=seed, work=work,
            padded=padded, piece=piece, n_pieces=n_pieces,
            resume=resume, pre_pair=pre_pair, kv=kv,
            table=table_j)
        with self._stats_lock:
            self.prefill_stats["staged_requests"] += 1
        return pre_len

    def _finalize_prefill(self, slot: int, task: _PrefillTask) -> None:
        """Both caches complete: insert into the slot grid and flip the
        lane to decoding, its first token PENDING: the pick stays on
        the device, where the lane's first chunk takes it from
        (``_carry_arrays``), and a harvest takes the host's copy
        (``_read_picks``).  ``remaining`` and ``count`` need no token to
        be known, so the lane is scheduled like any other from here;
        ``progress()`` / ``snapshot()`` show the prompt alone until the
        token is read.  Nothing here waits for the device: with a lane
        decoding, the piece and the insert stay queued behind the chunk
        in flight and the next chunk is enqueued directly behind them.
        Caller holds ``self._ctx()``."""
        state = _SlotState(request_id=task.request_id,
                           remaining=task.max_new - 1,
                           tokens=list(task.prompt), seed=task.seed,
                           count=task.resume + 1, first=task.first)
        with events.span("prefill/insert", rid=task.request_id):
            self._insert_lane(slot, task)
            self._lane_claim(slot, task.kv, task.prompt)
        self._poll_drained()
        # Staging is cleared BEFORE the slot state is set: the gateway's
        # metrics thread reads active_slots() (= decoding + staged)
        # concurrently, and this order keeps a torn read at or below
        # the true occupancy instead of reporting slots_in_use >
        # slots_total (the overlap_ratio() torn-read rule).
        del self._staging[slot]
        self._slot_states[slot] = state
        self._refills[slot] = task.first    # next dispatch splices it
        events.instant("slot/insert", rid=task.request_id, slot=slot)

    def _insert_lane(self, slot: int, task: _PrefillTask) -> None:
        """A finished prefill's batch-1 cache(s) into lane ``slot`` of
        the slot grid (target, then draft)."""
        n = len(task.prompt)
        grids = [("_cache", task.cache_1, False)]
        if self._draft_model is not None:
            grids.append(("_d_cache", task.d_cache_1, True))
        for attr, cache_1, draft in grids:
            grid = getattr(self, attr)
            if grid is None:
                grid = self._launch(self._fresh_cache, self.slots,
                                    draft=draft, grid=True)
            grid = self._launch(
                self._paged_insert, grid, cache_1, jnp.int32(slot),
                task.table, jnp.int32(task.kv.matched), jnp.int32(n))
            setattr(self, attr, grid)

    def _advance_piece(self, slot: int, task: _PrefillTask,
                       room: int = 1) -> int:
        """Run ONE installment of ``task`` — its next target (then
        draft) prefill pieces as one call, plus the finalize/insert
        when they were the last — and return its token cost.  The call
        covers ``k`` pieces: the largest count the engine compiled
        (``_piece_counts``) that the task has left (of the target's,
        then of the draft's: both caches hold the same rows and follow
        the same rule) and the step has ``room`` for.  A request's rng
        inputs and the mathematics of its rows depend on the request
        alone; which PROGRAMS run its prompt depends on where the
        steps' budgets fall in it, so two schedules agree on a
        request's output to rounding (every attention row to the bit:
        ``ops.attention.prefix_attention`` walks a call's queries a
        piece at a time; the matmuls are row-wise), not by construction
        to the bit.  The draft's pieces follow the target's without
        waiting for the target's pick: a request that stops at its
        first token (``eos_id``) is learnt at a harvest, so with a
        draft model it pays a draft prefill it would not need (one path
        for every request; a request of ONE token is known to the host
        and pays none)."""
        draft = task.cursor >= task.n_pieces
        i = task.d_cursor if draft else task.cursor
        k = self._piece_counts[-1]
        if min(task.n_pieces - i, room) < k:
            k = 1
        real = min(k * task.piece, len(task.work) - i * task.piece)
        self._step_counts["pieces"] += k
        self._step_counts["piece_calls"] += 1
        if not draft:           # the draft's pieces re-run the same tokens
            self._step_counts["prefill_tokens"] += real
        # The cache rows the attention of the call's LAST piece walks,
        # by its own rule (``prefix_tiles_walked``: each piece of a
        # call walks its own): from row 0, whatever prefix was matched
        # and gathered, to the end of the piece's last tile.  Of them,
        # those a learned selection counts over for its k-th score
        # (``select_tiles_counted``: none where no query of the piece
        # sees more than ``index_topk`` rows).
        tile = attention_ops.PREFIX_TILE
        start = np.int64(len(task.prompt) - len(task.work)
                         + (i + k - 1) * task.piece)
        top = self._index_topk[draft]
        rows = min(self.cache_len, tile * int(
            attention_ops.prefix_tiles_walked(
                start, task.piece, tile, self.cache_len)))
        select_rows = min(self.cache_len, tile * int(
            attention_ops.select_tiles_counted(
                start, task.piece, top, tile, self.cache_len))
        ) if 0 < top < self.cache_len else 0
        # Of ``rows``, those a WINDOW layer's walk reads: from its
        # window's first tile on (``prefix_first_tile``).
        window_rows = 0 if self._window is None else max(
            0, rows - tile * int(attention_ops.prefix_first_tile(
                start, tile, self._window)))
        with self._ctx(), events.span(
                "prefill/piece", rid=task.request_id,
                piece=task.cursor + task.d_cursor, pieces=k,
                n_pieces=task.n_pieces, tokens=real, rows=rows,
                select_rows=select_rows, window_rows=window_rows,
                cache_rows=self.cache_len,
                flash_layers=self._flash_layers(draft, k * task.piece)):
            if (task.d_cache_1 if draft else task.cache_1) is None:
                with events.span("prefill/cache", rid=task.request_id,
                                 kind=self._admission_kind(task.pre_pair,
                                                           task.kv)):
                    cache_1 = self._admission_cache_1(
                        task.pre_pair, task.kv, task.table, draft=draft)
                if draft:
                    task.d_cache_1 = cache_1
                else:
                    task.cache_1 = cache_1
                self._poll_drained()
            with events.span("prefill/dispatch", rid=task.request_id,
                             piece=task.cursor + task.d_cursor, pieces=k,
                             tokens=real, rows=rows, draft=int(draft)):
                if draft:
                    task.d_cache_1 = self._run_draft_piece(
                        task.d_cache_1, task.padded, task.piece,
                        task.d_cursor, k)
                else:
                    task.cache_1, task.first = self._run_target_piece(
                        task.cache_1, task.padded, task.piece,
                        task.cursor, len(task.work), task.seed,
                        task.resume, k)
            self._poll_drained()
            if not draft:
                task.cursor += k
                if task.cursor == task.n_pieces:
                    # The pick stays on the device: a read here would
                    # wait for the newest program on the queue and
                    # leave the device idle from its end to the next
                    # step's dispatch.  A harvest reads it
                    # (``_read_picks``).
                    self._step_counts["first_deferred"] += 1
                    if task.max_new == 1:
                        # One token, whatever it is: no lane, no insert
                        # and no draft prefill.  Its blocks were never
                        # written: hand them straight back.  The output
                        # is written when the token is read.
                        self._kv_release(task.kv)
                        self._awaited.append(_AwaitedPick(
                            task.request_id, list(task.prompt),
                            task.first))
                        del self._staging[slot]
                    elif self._draft_model is None:
                        self._finalize_prefill(slot, task)
                return k * task.piece
            # Target done, request unresolved: that was a draft call.
            task.d_cursor += k
            if task.d_cursor == task.n_pieces:
                self._finalize_prefill(slot, task)
            return k * task.piece

    def _advance_prefills(self) -> None:
        """Advance staged prefills by at most ``prefill_budget`` tokens
        (default: one piece) in request-arrival order, the head
        request's pieces first and as ONE call as far as the budget has
        room for them (``_advance_piece``: the pieces of a step mostly
        belong to one prompt, and one call reads every weight once).  A
        decode chunk is in flight AHEAD of this work on the device
        queue whenever a lane is decoding, so decoding lanes lose no
        more cadence to it than the budget.  With no lane decoding
        there is nobody to delay, so the budget is waived and admission
        runs at full speed, in the largest calls the engine compiled.
        Only enqueues: a prompt's last piece and its lane's insert go
        onto the queue and the pick stays there (``_advance_piece``,
        ``_finalize_prefill``), so the chunk in flight still has its
        successors behind it when this returns."""
        self._stage_from_queue()
        if not self._staging:
            return
        if not self._piece_shapes_ready:
            self._compile_piece_shapes()
        decoding = any(s is not None for s in self._slot_states)
        spent = 0
        while self._staging:
            slot = next(iter(self._staging))
            task = self._staging[slot]
            if not decoding:
                room = self._piece_counts[-1]
            elif self.prefill_budget is None:
                room = 1
            else:           # pieces until the budget is spent, >= 1
                room = -(-(self.prefill_budget - spent) // task.piece)
            spent += self._advance_piece(slot, task, room)
            with self._stats_lock:
                self.prefill_stats["installments"] += 1
            if slot not in self._staging:
                # Resolved or inserted: restage so a freed lane keeps
                # the budget flowing to the next queued request.
                self._stage_from_queue()
            if decoding and (self.prefill_budget is None
                             or spent >= self.prefill_budget):
                break

    # -- the starved-device account -----------------------------------------

    def _launch(self, program, *args, **kwargs):
        """THE enqueue of a device program: every site that puts a
        program on the device's queue (chunk, piece, insert, reset,
        gather, a fresh or copied cache, the carry's splice) calls it
        through here.  Before the enqueue, a queue known empty
        (``_drained_at``) is charged to ``starved_ms`` up to now: the
        stream runs in order, so from the moment its newest output was
        seen ready to this enqueue the device had nothing of this
        engine's to do.  The handle on the previous program is dropped
        first, because this one may be donated its buffers; an output
        of this one takes its place."""
        if self._drained_at is not None:
            dt = self._clock() - self._drained_at
            self._drained_at = None
            self._step_counts["starved_ms"] += 1e3 * dt
            with self._stats_lock:
                self._starved_s += dt
        self._newest = None
        out = program(*args, **kwargs)
        self._newest = self._handle_of(out)
        return out

    @staticmethod
    def _handle_of(out):
        """The output of a program that stands for it in
        ``_poll_drained``: its last leaf (a chunk's counts, a piece's
        first token; a cache leaf where the program returns a cache
        alone)."""
        return jax.tree.leaves(out)[-1]

    def _has_work(self) -> bool:
        return self.pending() > 0

    def _poll_drained(self) -> None:
        """At a stage's boundary and at the return of a ``*/wait``: if
        the newest program's output is ready (``is_ready()`` asks, it
        does not wait) the device's queue is empty; with work pending
        that moment is kept until the next ``_launch`` charges it.  A
        handle is polled only before the enqueue that may consume it
        (``_launch`` drops it first)."""
        handle = self._newest
        if (handle is not None and self._drained_at is None
                and handle.is_ready() and self._has_work()):
            self._drained_at = self._clock()
            self._step_counts["drains"] += 1

    @thread_role("handler", "driver")
    def device_starved_s(self) -> float:
        """Cumulative seconds the engine left the device with an empty
        queue while it had work (a lane decoding, a task staged, a
        queue): each from the poll that found the newest program's
        output ready to the next enqueue, so a lower bound of the
        device's idle with work pending, on the engine's own clock and
        with no capture running (``engine/step``'s ``starved_ms`` is
        the same a step).  The gateway exposes it as
        ``ttd_engine_device_starved_seconds``; it counts under
        ``TTD_NO_TRACE=1`` too.  Scraped from handler threads, so the
        read locks."""
        with self._stats_lock:
            return self._starved_s

    def _consume(self, state, tokens) -> None:
        """Append generated tokens to a slot's request, enforcing the
        budget and EOS — the ONE termination rule for chunked and
        speculative harvests alike."""
        before = len(state.tokens)
        for t in tokens:
            state.count += 1
            state.remaining -= 1
            if self._append(state, int(t)):
                break
        self._step_counts["committed"] += len(state.tokens) - before

    def _append(self, state, t: int) -> bool:
        """One token that ``count`` and ``remaining`` already include
        onto its request; True when it was the request's last."""
        state.tokens.append(t)
        if (state.remaining <= 0
                or (self.eos_id is not None and t == self.eos_id)):
            state.done = True
        return state.done

    def _retire_if_done(self, slot, state):
        if state.done:
            # Feed the radix index with the finished request's
            # generated full blocks (a follow-up turn extending this
            # conversation hits warm KV), then free the rest.
            self._lane_release(slot, tokens=state.tokens)
            self._outputs[state.request_id] = state.tokens
            self._slot_states[slot] = None
            events.instant("slot/retire", rid=state.request_id,
                           slot=slot, tokens=len(state.tokens))

    def _harvest(self, toks: np.ndarray, rids):
        """``rids``: the slot->request map captured at dispatch — a
        slot whose occupant changed since (retired and refilled, or
        cancelled) must NOT consume this chunk's tokens; they belong
        to the previous tenant and are trimmed here."""
        for slot, state in enumerate(self._slot_states):
            if state is None or state.request_id != rids[slot]:
                continue
            self._consume(state, toks[slot])
            self._retire_if_done(slot, state)

    def _harvest_spec(self, emit, emitted, next_tok, accepted, k,
                      rids):
        """Consume each slot's emitted prefix from a speculative round
        (variable per slot; budget/EOS via the shared consume rule),
        tracking acceptance stats.  The round's bonus token is the last
        emitted one, so a surviving slot's newest token already is
        ``next_tok`` after consuming.  ``k``: the depth the round was
        DISPATCHED at (recorded in the in-flight dict — under adaptive
        speculation the current pick may already differ); it sizes the
        drafted-token denominator and feeds the controller's
        acceptance observation.  ``rids``: the trim guard, same rule
        as ``_harvest``."""
        del next_tok  # == emit[slot, emitted-1], consumed above
        with self._stats_lock:
            self.spec_stats["rounds"] += 1  # engine, not slot-rounds
        n_slots = acc_sum = 0
        for slot, state in enumerate(self._slot_states):
            if state is None or state.request_id != rids[slot]:
                continue
            before = len(state.tokens)
            self._consume(state, emit[slot, :int(emitted[slot])])
            n_slots += 1
            acc_sum += int(accepted[slot])
            with self._stats_lock:
                self.spec_stats["slot_rounds"] += 1
                self.spec_stats["drafted"] += k
                self.spec_stats["drafted_accepted"] += int(accepted[slot])
                self.spec_stats["emitted"] += len(state.tokens) - before
            self._retire_if_done(slot, state)
        if self._spec_ctrl is not None:
            # One observation per harvested round, aggregated over the
            # slots that survived the trim guard (a fully-trimmed
            # garbage round still advances the dwell clock — the
            # controller's decisions stay a pure function of the
            # request stream).  Wall time is NOT fed here: depth
            # choices must be deterministic from acceptance alone.
            with self._stats_lock:
                self._spec_ctrl.observe(k * n_slots, acc_sum)

    def pending(self) -> int:
        """Requests not yet finished (queued + staged mid-prefill +
        decoding + those of one token whose token is awaited)."""
        return (len(self._queue) + len(self._staging)
                + sum(s is not None for s in self._slot_states)
                + len(self._awaited))

    def progress(self) -> dict:
        """Token COUNTS so far per in-flight request, ``{request_id:
        len(prompt + generated)}`` — the O(slots) poll for TTFT/pace
        tracking (``snapshot()`` copies whole token lists; benches
        polling every step want this instead).  A lane shows its prompt
        alone from its insert until a harvest has read its first token
        (``_read_picks``), then prompt + first + what its chunks
        gave."""
        return {s.request_id: len(s.tokens)
                for s in self._slot_states if s is not None}

    def snapshot(self) -> dict:
        """Tokens generated SO FAR for every in-flight request,
        ``{request_id: [prompt + generated]}`` — the streaming view
        between ``serve_step()`` calls (tokens arrive chunk-wise; a
        finished request leaves the snapshot and is returned by the
        step that completed it; a lane's first token arrives with the
        first harvest that finds it run, ``progress()``).  Copies, so
        callers may mutate."""
        return {s.request_id: list(s.tokens)
                for s in self._slot_states if s is not None}

    # -- decode: one chunk dispatched ahead of the harvest ------------------

    @dispatch_critical
    def _carry_arrays(self):
        """The next dispatch's (tok, counts): the device-resident carry
        from the previous chunk, with each slot refilled since spliced
        in from where its values are: the first token from the DEVICE,
        where the lane's last prefill piece left it (the host may not
        have read it yet), the rng counter from the host.  One route
        for the session's first dispatch (a carry of zeros, every live
        lane a refill) and every later one, and ONE program a dispatch
        whatever it refilled (``_splice_refills``), which only ENQUEUES:
        a lane's first chunk goes onto the queue directly behind its
        last piece and its insert, with no host round trip between
        them.  Retired-but-unrefilled slots keep garbage carry and
        decode garbage, as idle slots do."""
        if self._carry is None:
            zeros = np.zeros((self.slots,), np.int32)
            self._carry = (jnp.asarray(zeros), jnp.asarray(zeros))
        tok, counts = self._carry
        refills = {slot: pick for slot, pick in self._refills.items()
                   if self._slot_states[slot] is not None}  # not cancelled
        self._refills.clear()
        if refills:
            refill_counts = np.full((self.slots,), -1, np.int32)
            picks = [next(iter(refills.values()))] * self.slots
            for slot, pick in refills.items():
                refill_counts[slot] = self._slot_states[slot].count
                picks[slot] = pick
            jcounts = jnp.asarray(refill_counts)
            # The put took its time, and the lanes' reset ahead of it
            # is short: ask again before the splice is enqueued.
            self._poll_drained()
            tok, counts = self._launch(self._splice_refills, tok, counts,
                                       jcounts, tuple(picks))
        return tok, counts

    def _count_dispatch(self, held: list, spec_k: int) -> None:
        """``engine/step``'s account of a decode dispatch, from the
        positions each active lane ``held`` as the host knows them.
        ``kv_blocks`` is the fused attention kernel's own rule
        (``paged_blocks_walked``: the blocks a lane's rows and this
        call's ``spec_k + 1`` queries reach, one for an idle slot) over
        all slots, of the ``kv_table_blocks`` their tables have;
        ``kv_window_blocks`` the same rule from a window's first block
        on, for one window layer (0 without one); ``kv_bytes`` what
        ``kv_blocks`` are in bytes."""
        kv_table_blocks = self.slots * self._kv_nblk_lane
        lengths = np.asarray(held, np.int64)
        kv_blocks = self.slots - len(held) + int(paged_blocks_walked(
            lengths, spec_k + 1, self.kv_block_size,
            self._kv_nblk_lane).sum())
        kv_window_blocks = 0
        if self._window is not None:
            kv_window_blocks = self.slots - len(held) + int(
                paged_blocks_walked(
                    lengths, spec_k + 1, self.kv_block_size,
                    self._kv_nblk_lane, self._window).sum())
        kv_bytes = kv_blocks * self._kv_pool.bytes_per_block
        self._step_counts.update(
            lanes=len(held), positions=sum(held), kv_blocks=kv_blocks,
            kv_table_blocks=kv_table_blocks,
            kv_window_blocks=kv_window_blocks, kv_bytes=kv_bytes,
            state_bytes=len(held) * self._state_lane_bytes)

    def _count_sown(self, sown) -> None:
        """``engine/step``'s account of what the layers of a harvested
        decode chunk sowed, as ``_decode_chunk`` returns it (None:
        nothing), each a mean over the chunk's steps and the layers
        that sowed it: ``expert_rows`` [chunk, layers, experts held],
        ``routed_here`` [chunk, layers], ``rows`` [chunk, layers, 2
        (scored, selected)]."""
        if not sown:
            return
        counts = {}
        if "expert_rows" in sown:
            rows = sown["expert_rows"].astype(np.float64)
            mean = rows.mean(axis=-1)
            counts.update(
                experts_hit=float((rows > 0).sum(axis=-1).mean()),
                expert_load_cv=float(
                    (rows.std(axis=-1) / np.maximum(mean, 1e-9)).mean()))
        if "routed_here" in sown:
            counts.update(
                experts_held=int(sown["expert_rows"].shape[-1]),
                routed_here=float(sown["routed_here"].mean()))
        if "rows" in sown:
            scored, selected = sown["rows"].astype(
                np.float64).mean(axis=(0, 1))
            counts.update(rows_scored=float(scored),
                          rows_selected=float(selected))
        self._step_counts.update(counts)

    @dispatch_critical
    def _dispatch_chunk(self) -> None:
        """Enqueue one decode chunk (or speculative round) for ALL
        slots from the device-resident carry.  No host sync: the call
        returns while the device may still be computing the PREVIOUS
        chunk — the successor simply queues behind it.  Captures the
        dispatch-time slot->request map the harvest's trim guard
        needs."""
        with self._ctx(), events.span(
                "decode/dispatch", fused=self._fused_tag) as dispatch:
            # The host's prelude is a span of its own; the program call
            # below lies directly under ``decode/dispatch``.
            with events.span("decode/stage",
                             stale=len(self._stale_slots),
                             refills=len(self._refills)):
                seeds = np.zeros((self.slots,), np.uint32)
                rids: list = [None] * self.slots
                held = []
                for slot, state in enumerate(self._slot_states):
                    if state is not None:
                        seeds[slot] = state.seed
                        rids[slot] = state.request_id
                        # its positions: the tokens read and the
                        # first token where no harvest has read it
                        held.append(len(state.tokens)
                                    + (state.first is not None))
                # Depth for THIS round: the controller's pick (adaptive)
                # or the fixed k.  Host ints end to end — read before
                # the first enqueue (the controller is
                # _stats_lock-guarded; the enqueues must stay
                # conversion- and contention-free).
                k = self._spec_depth()
                self._count_dispatch(held, k)
                # Retired/cancelled lanes' tables must point at scratch
                # BEFORE this chunk: their freed blocks may already be
                # reallocated, and this chunk decodes them as garbage.
                self._poll_drained()
                self._flush_stale_lanes()
                tok, counts = self._carry_arrays()
                self._poll_drained()
                jseeds = jnp.asarray(seeds)
            dispatch.set(spec_k=k)
            self._poll_drained()
            if self._draft_model is not None:
                (self._cache, self._d_cache, emit, emitted, next_tok,
                 acc, counts_next) = self._launch(
                    self._spec_round,
                    self._variables, self._draft_variables, self._cache,
                    self._d_cache, tok, jseeds, counts, k)
                # Continuing slots consumed exactly ``emitted`` tokens,
                # so the device advances their rng counters itself —
                # the property that lets round N+1 enqueue before round
                # N's host copy exists.
                self._carry = (next_tok, counts_next)
                self._inflight = {"spec": True, "rids": rids, "k": k,
                                  "emit": emit, "emitted": emitted,
                                  "next_tok": next_tok, "acc": acc}
            else:
                (self._cache, toks, last, counts_next,
                 sown) = self._launch(
                    self._decode_chunk,
                    self._variables, self._cache, tok, jseeds, counts)
                self._carry = (last, counts_next)
                self._inflight = {"spec": False, "rids": rids,
                                  "toks": toks, "sown": sown}
            # Every first token still unread was picked by a program
            # ahead of this chunk on the queue: this chunk's harvest
            # reads them without a wait.
            self._inflight["picks"] = [
                holder for _, holder in self._pending_picks()]
        self._poll_drained()
        with self._stats_lock:
            self.overlap_stats["chunks"] += 1

    @dispatch_critical
    def _skip_eager_dispatch(self) -> bool:
        """Whether to fall back to harvest-first for this one step:
        when EVERY active slot certainly retires in the in-flight chunk
        (budget exhaustion is host-predictable — ``remaining`` is
        known; EOS is not), an eager successor would be garbage end to
        end — the tail chunk of a session, or a mass-retirement
        boundary where the whole next chunk should decode refills
        instead.

        A SINGLE retiring lane keeps eager dispatch: its garbage costs
        one lane-chunk (~chunk/slots of device work, often zero when
        the queue is empty — the chunk is lockstep across slots), which
        measures cheaper than surrendering the overlapped host pass
        (policy A/B'd on the CPU mesh; revisit on silicon).

        Horizons: a plain chunk emits exactly ``chunk`` tokens per
        lane, so ``remaining <= chunk`` is certain retirement; a
        speculative round GUARANTEES only one emitted token (every
        draft rejected), so only ``remaining <= 1`` is certain —
        anything looser would surrender the overlap for up to k+1
        rounds at every batch tail."""
        horizon = (1 if self._draft_model is not None else self.chunk)
        certain = [s.remaining <= horizon
                   for s in self._slot_states if s is not None]
        return bool(certain) and all(certain)

    def _pending_picks(self) -> list:
        """``(slot, holder)`` of every first token no harvest has read,
        in no order: a lane's ``_SlotState`` under its slot, a request
        of one token (``_AwaitedPick``) under None."""
        return [(slot, state)
                for slot, state in enumerate(self._slot_states)
                if state is not None and state.first is not None] + [
            (None, pick) for pick in self._awaited]

    def _read_picks(self, due=None) -> list:
        """Host copies of the pending first tokens that have run,
        ``[(slot, holder, token)]`` for ``_commit_picks``: those among
        ``due`` (the picks a chunk was dispatched behind, read once
        that chunk has run: the queue runs in order, so each returns at
        once) and any other whose ``is_ready()`` says so (it asks and
        does not wait).  ``due=None`` reads every one and may wait: for
        when no lane decodes."""
        return [(slot, holder, int(holder.first))
                for slot, holder in self._pending_picks()
                if due is None or any(holder is d for d in due)
                or holder.first.is_ready()]

    def _commit_picks(self, read) -> None:
        """Hand each first token ``_read_picks`` read to its request:
        onto a lane's tokens, under the ONE termination rule (a first
        token that is ``eos_id`` retires its lane here, and the rid
        guard trims what the lane decoded meanwhile); with its prompt
        as the output of a request of one token."""
        for slot, holder, first in read:
            holder.first = None
            self._step_counts["committed"] += 1
            if slot is None:
                self._awaited.remove(holder)
                self._outputs[holder.request_id] = holder.prompt + [first]
            else:
                self._append(holder, first)
                self._retire_if_done(slot, holder)

    def _harvest_prev(self, inf: dict, overlapped: bool) -> None:
        """Materialize the previous chunk's host copy (this blocks
        until THAT chunk finishes — when ``overlapped``, the successor
        is already enqueued and keeps the device busy through the wait
        and the host passes that follow) and consume it under the
        dispatch-time rid guard.  The first tokens that have run are
        read in the same wait (``_read_picks``: none of them waits for
        more than the chunk did) and handed over BEFORE the chunk is
        consumed: the first chunk that carried a lane was dispatched
        behind the lane's pick, so a lane's first token always precedes
        its chunks' tokens.  Only the post-materialization host
        pass is timed into ``overlap_stats``: the block inside
        ``np.asarray`` is device time, not host-harvest time, and would
        drown the ratio."""
        rids = inf["rids"]
        with events.span("decode/wait", overlapped=overlapped):
            if inf["spec"]:
                args = (np.asarray(inf["emit"]),
                        np.asarray(inf["emitted"]),
                        np.asarray(inf["next_tok"]),
                        np.asarray(inf["acc"]))
            else:
                toks = np.asarray(inf["toks"])
                sown = jax.tree.map(np.asarray, inf["sown"])
            picks = self._read_picks(inf["picks"])
        self._poll_drained()
        t0 = time.perf_counter()
        with events.span("decode/harvest", overlapped=overlapped):
            self._commit_picks(picks)
            if inf["spec"]:
                self._harvest_spec(*args, inf["k"], rids=rids)
            else:
                self._harvest(toks, rids=rids)
                self._count_sown(sown)
        dt = time.perf_counter() - t0
        with self._stats_lock:
            self.overlap_stats["harvest_s"] += dt
            if overlapped:
                self.overlap_stats["overlapped_harvests"] += 1
                self.overlap_stats["overlapped_harvest_s"] += dt

    @thread_role("handler", "driver")
    def overlap_ratio(self) -> float:
        """Fraction of host harvest wall time spent with a successor
        chunk concurrently in flight — the host-stall share the
        lookahead hides.  The gateway exposes it as
        ``ttd_engine_overlap_ratio``.

        Scraped from the gateway's metrics thread while the driver
        harvests: the pair is read under ``_stats_lock`` (and the
        writer updates both fields under it), so a scrape can no
        longer land between the denominator and numerator bumps and
        report a torn ratio."""
        with self._stats_lock:
            num = self.overlap_stats["overlapped_harvest_s"]
            total = self.overlap_stats["harvest_s"]
        if total <= 0.0:
            return 0.0
        return min(1.0, num / total)

    @thread_role("driver", "main")
    def serve_step(self) -> dict:
        """ONE service iteration, then control goes back to the caller,
        who may ``submit()`` new requests between steps (online
        serving: the queue never has to be complete up front).  Returns
        the requests that FINISHED this step, ``{request_id: tokens}``
        (possibly empty); poll ``pending()`` for completion.

        The step is pipelined: the successor chunk is dispatched from
        the device-resident carry BEFORE the in-flight chunk's host
        copy is touched, so stop detection, admission and the caller's
        streaming/deadline passes (which run between ``serve_step``
        calls) all hide under device compute.  The guarantee: while a
        lane decodes, a chunk is in flight across EVERY return, and
        every host read of a device value inside the step waits for a
        program that has a successor queued behind it.  A step that
        enqueues a prompt's last piece reads nothing of it: the first
        token stays on the device (``first_deferred``), the lane's
        first chunk takes it from there, and a harvest reads it once
        it has run (``_read_picks``).  So a client sees a first token
        with the first harvest that finds it ready: with the lane's
        first chunk at the latest, a step or two after the piece was
        enqueued.  Stop decisions lag one chunk, a stop at the first
        token by as much as that; the harvest trims the overshoot.  A
        finished session leaves one garbage chunk in flight —
        harmless, discarded by the next cycle's trim guard.

        The whole step is one ``engine/step`` span, the parent of the
        ``decode/*`` and ``prefill/*`` spans recorded inside it (every
        stage of the host's work is one); at exit it is given what the
        step did (``_STEP_COUNTS``, the starved-device account among
        them: ``_launch``, ``_poll_drained``) and the queue's depth."""
        with events.span("engine/step") as step:
            self._step_counts = dict.fromkeys(_STEP_COUNTS, 0)
            if self._left_at is not None:
                self._step_counts["away_ms"] = 1e3 * (
                    self._clock() - self._left_at)
            self._poll_drained()
            prev, self._inflight = self._inflight, None
            # DECODE PRIORITY: the successor chunk for occupied lanes
            # goes onto the device queue before any admission work, so
            # active lanes never wait behind a new prompt's prefill.
            dispatched = False
            if (any(s is not None for s in self._slot_states)
                    and not self._skip_eager_dispatch()):
                self._dispatch_chunk()      # device busy through the
                dispatched = True           # host passes below
            # One budget installment of admission, queued BEHIND the
            # chunk just dispatched (or behind ``prev``, still in
            # flight) — the gap it can add to an active lane is bounded
            # by the budget.
            self._advance_prefills()
            if prev is not None:
                self._harvest_prev(prev, overlapped=dispatched)
            # Lanes the harvest freed stage immediately (host-only) so
            # their first installment rides the next step's budget.
            self._stage_from_queue()
            if not dispatched and any(s is not None
                                      for s in self._slot_states):
                # Nothing was in flight to hide this pass behind (first
                # step of a session / a harvest-first fallback step /
                # post-idle restart): dispatch now so the NEXT step's
                # harvest overlaps.
                self._dispatch_chunk()
            if self._inflight is None and self._awaited:
                # No lane decodes and nothing is in flight to read the
                # awaited tokens behind: read them here.  This waits
                # for the newest program on the queue, and delays
                # nobody.
                with events.span("prefill/wait",
                                 rid=self._awaited[-1].request_id):
                    picks = self._read_picks()
                self._poll_drained()
                self._commit_picks(picks)
            out, self._outputs = self._outputs, {}
            self._poll_drained()
            if self._drained_at is not None and not self._has_work():
                # The work went without an enqueue (its last request
                # retired or was cancelled): idle from here on has no
                # request in the engine and is not charged.
                self._drained_at = None
            step.set(queued=len(self._queue), **self._step_counts)
            self._left_at = self._clock()
        return out

    @thread_role("main", "driver")
    def run(self) -> dict:
        """Serve every submitted request to completion; returns
        ``{request_id: [prompt + generated tokens]}``.  (A loop over
        ``serve_step()`` — use that directly for online serving.)"""
        out: dict = {}
        while self.pending():
            out.update(self.serve_step())
        return out
