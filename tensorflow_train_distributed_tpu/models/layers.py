"""Shared transformer building blocks with logical-axis shardings.

One set of layers serves BERT (config[2]), Transformer-big (config[3]) and
Llama (config[4]).  Every weight and activation carries logical axis names
(``parallel.sharding`` vocabulary), so the same module tensor-parallelizes
under dp×tp, sequence-parallelizes under dp×sp, and fsdp-shards under fsdp —
the DTensor-Layout role from the reference's stretch config, without
per-strategy model code.

Megatron-style TP falls out of the annotations: qkv/mlp-in kernels shard
their *output* dim on ``tensor`` (("embed","heads"), ("embed","mlp")),
out-proj/mlp-out shard their *input* dim (("heads","embed") is not used —
("mlp","embed") etc.), so GSPMD inserts exactly the two allreduces per block
Megatron prescribes.
"""

from __future__ import annotations

import contextlib
import math
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tensorflow_train_distributed_tpu.runtime import compat
from tensorflow_train_distributed_tpu.ops.attention import (
    multihead_attention_kernel,
)

Dtype = Any


def _active_mesh(axis: str):
    """The ambient (abstract) mesh if it shards ``axis``, else None."""
    mesh = compat.get_abstract_mesh()
    if mesh is None or mesh.empty or mesh.shape.get(axis, 1) <= 1:
        return None
    return mesh


def _seq_parallel_mesh(seq_parallel: Optional[str]):
    """The ambient (abstract) mesh when SP is requested and usable."""
    if seq_parallel is None:
        return None
    return _active_mesh("seq")


def dense(features, logical_axes, *, use_bias=True, dtype=jnp.float32,
          name=None, kernel_init=None):
    return nn.DenseGeneral(
        features, use_bias=use_bias, dtype=dtype, name=name,
        kernel_init=nn.with_logical_partitioning(
            kernel_init or nn.initializers.lecun_normal(), logical_axes),
    )


class Embed(nn.Module):
    """Token embedding, vocab-sharded, with optional logit tying."""

    vocab_size: int
    features: int
    dtype: Dtype = jnp.float32

    def setup(self):
        self.embedding = self.param(
            "embedding",
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=1.0), ("vocab", "embed")),
            (self.vocab_size, self.features),
        )

    @jax.named_scope("embed")
    def __call__(self, ids):
        emb = self.embedding.astype(self.dtype)
        if _active_mesh("fsdp") is not None:
            # ZeRO-3 semantics: gather the table's embed shards at the
            # use site so the output is born batch-sharded.  Without
            # this the output inherits the table's embed→fsdp sharding
            # and SPMD can only transition an activation from embed- to
            # batch-sharding by involuntary full rematerialization
            # (replicate-then-partition, warned by spmd_partitioner) —
            # wasted HBM + ICI every step on real multi-chip hardware.
            # "vocab" stays as annotated (tensor-sharded): only the
            # embed/fsdp dim needed gathering, and a (None, None)
            # constraint would all-gather the table over tensor too
            # (~260 MB/chip extra at llama2_7b scale).
            emb = nn.with_logical_constraint(emb, ("vocab", None))
        x = jnp.take(emb, ids, axis=0)
        return nn.with_logical_constraint(x, ("batch", "length", "embed"))

    def attend(self, x):
        """Tied output logits: x @ E^T (used by Llama/BERT heads)."""
        return jnp.einsum("ble,ve->blv", x, self.embedding.astype(x.dtype))


def sinusoidal_positions(seq_len: int, features: int) -> np.ndarray:
    """Fixed sin/cos table (Transformer-big / reference Keras convention)."""
    pos = np.arange(seq_len)[:, None]
    div = np.exp(np.arange(0, features, 2) / features * -np.log(10000.0))
    table = np.zeros((seq_len, features), np.float32)
    table[:, 0::2] = np.sin(pos * div)
    table[:, 1::2] = np.cos(pos * div)
    return table


def llama3_scaled_freqs(freqs, scaling):
    """Llama-3.x frequency-dependent RoPE scaling (HF
    ``_compute_llama3_parameters``): long wavelengths divide by
    ``factor``, short ones stay, the middle band interpolates smoothly.
    ``scaling`` = (factor, low_freq_factor, high_freq_factor,
    original_max_positions)."""
    factor, low, high, old_len = scaling
    wavelen = 2.0 * np.pi / freqs
    low_wl = old_len / low
    high_wl = old_len / high
    scaled = jnp.where(wavelen > low_wl, freqs / factor, freqs)
    smooth = (old_len / wavelen - low) / (high - low)
    smoothed = (1.0 - smooth) / factor * freqs + smooth * freqs
    medium = (wavelen >= high_wl) & (wavelen <= low_wl)
    return jnp.where(medium, smoothed, scaled)


def yarn_scaled_freqs(freqs, scaling, base: float):
    """YaRN frequencies (arXiv:2309.00071; DeepSeek-V3's
    ``precompute_freqs_cis``): each of the ``dim / 2`` frequencies is
    blended between itself and itself / ``factor`` by a linear ramp
    over the pair index, from the pair that turns ``beta_fast`` times
    in ``original_max`` positions (kept) to the one that turns
    ``beta_slow`` times (divided).  ``scaling`` = (factor, beta_fast,
    beta_slow, original_max_positions); cos and sin stay unscaled (the
    attention's softmax scale carries ``yarn_mscale``)."""
    factor, fast, slow, old_len = scaling
    dim = 2 * freqs.shape[0]

    def pair_of(turns):
        return (dim * math.log(old_len / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(pair_of(fast)), 0)
    high = min(math.ceil(pair_of(slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    return freqs / factor * ramp + freqs * (1.0 - ramp)


def yarn_mscale(factor: float, mscale_all_dim: float = 1.0) -> float:
    """What YaRN multiplies the attention logits' scale by, squared by
    the caller (once for the query, once for the key)."""
    return 0.1 * mscale_all_dim * math.log(factor) + 1.0 if factor > 1 \
        else 1.0


def scaled_freqs(freqs, scaling, base: float):
    """``apply_rope``'s ``scaling=`` rule, chosen by the tuple's tag:
    ``("yarn", ...)`` is YaRN; four bare numbers are the llama3 rule
    (``LlamaConfig.rope_scaling`` predates the tag)."""
    if not isinstance(scaling[0], str):
        return llama3_scaled_freqs(freqs, scaling)
    if scaling[0] != "yarn":
        raise ValueError(f"unknown rope scaling {scaling[0]!r} (expected "
                         "'yarn' or the llama3 four numbers)")
    return yarn_scaled_freqs(freqs, tuple(scaling[1:5]), base)


def rope_attention_factor(scaling) -> float:
    """What cos and sin are multiplied by: the sixth entry of a
    ``("yarn", factor, beta_fast, beta_slow, original_max,
    attention_factor)`` tuple (transformers' YaRN convention: the
    rotated part of q and of k each carry it, the unrotated part does
    not); 1 for every shorter tuple, whose users scale the softmax
    instead (``yarn_mscale``)."""
    tagged = scaling is not None and isinstance(scaling[0], str)
    return float(scaling[5]) if tagged and len(scaling) > 5 else 1.0


@jax.named_scope("attn/qkv")    # the rotation belongs to q and k's making
def apply_rope(x, positions, *, base: float = 10000.0, scaling=None,
               rotary_dim: Optional[int] = None):
    """RoPE applied to [B, S, H, D] at integer ``positions`` [B, S].

    Applied separately to q and k so each uses its own positions (KV-cache
    decode and cross-length attention need different q/k position vectors).
    ``scaling``: optional rope-scaling tuple, ``("yarn", factor,
    beta_fast, beta_slow, original_max[, attention_factor])`` or the
    llama3 four numbers (``scaled_freqs``).  ``rotary_dim``: the first
    so many values of a head are rotated (half-split among themselves,
    frequencies over ``rotary_dim``) and the rest pass as they are
    (``partial_rotary_factor``); None rotates the whole head.
    """
    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        turned = apply_rope(x[..., :rotary_dim], positions, base=base,
                            scaling=scaling)
        return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
    head_dim = x.shape[-1]
    freqs = 1.0 / base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim)
    if scaling is not None:
        freqs = scaled_freqs(freqs, scaling, base)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,D/2]
    sin = jnp.sin(angles)[:, :, None, :]
    cos = jnp.cos(angles)[:, :, None, :]
    factor = rope_attention_factor(scaling)
    if factor != 1.0:
        sin, cos = sin * factor, cos * factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def _quantize_kv_rows(t):
    """Symmetric int8 quantization of KV rows, one f32 scale per
    (..., row, kv_head) amax'd over head_dim — THE one KV quantization
    recipe.  The shared-index linear cache, the engine's batch-1
    per-slot cache, and the paged block pool all store exactly these
    values, which is what makes the cross-layout int8 parity bitwise
    (pinned in tests/test_serving_paged.py)."""
    amax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    qt = jnp.clip(jnp.round(t.astype(jnp.float32) / scale[..., None]),
                  -127, 127).astype(jnp.int8)
    return qt, scale


def paged_pool_leaves(blocks: int, block_size: int, kv_heads: int,
                      head_dim: int, dtype, int8: bool,
                      v_head_dim: Optional[int] = None) -> dict:
    """name -> (shape, dtype) of one layer's paged K/V pool leaves, as
    they are STORED: a row is its ``kv_heads * head_dim`` values side by
    side, [blocks, block_size, row], which is the shape the attention
    kernel copies blocks in, so nothing between the cache and the
    kernel takes a view of a pool (a value row is ``kv_heads *
    v_head_dim`` wide where the value head has a size of its own:
    nothing is padded).  int8 rows bring their float32
    scales, [2 (K, V), blocks, block_size, kv_heads].  One table for
    the module that owns a layer's pools (``MultiHeadAttention``) and
    for the depth scan that owns every layer's
    (``llama._ScannedBlock``, one leading layer axis)."""
    store = jnp.int8 if int8 else dtype
    leaves = {"key_pool": ((blocks, block_size, kv_heads * head_dim),
                           store),
              "value_pool": ((blocks, block_size,
                              kv_heads * (v_head_dim or head_dim)),
                             store)}
    if int8:
        leaves["kv_pool_scales"] = (
            (2, blocks, block_size, kv_heads), jnp.float32)
    return leaves


def _paged_dest(table, positions, block_size: int, blocks: int,
                ring_of: Optional[int] = None):
    """Where a step's rows go: (physical block, row in it) per (lane,
    token).  The table lookup CLIPS the block index (gather semantics
    would otherwise wrap); a position past the table's width gets block
    ``blocks``, out of range, so that the scatter DROPS it — an overrun
    lane goes silently inert, the linear path's rule.  A table slot the
    engine zeroed sends its rows to the scratch block 0.  ``ring_of``:
    the table is a sliding-window layer's RING over a context of so
    many rows: position ``p`` lies in entry ``(p // block_size) %
    width``, and a position past the context is the overrun."""
    n_blk = table.shape[1]
    if ring_of is None:
        blk = jnp.clip(positions // block_size, 0, n_blk - 1)
        rows = n_blk * block_size
    else:
        blk = jnp.mod(positions // block_size, n_blk)
        rows = ring_of
    phys = jnp.take_along_axis(table, blk, axis=1)              # [B, q]
    return (jnp.where(positions < rows, phys, blocks),
            positions % block_size)


def _set_pool_rows(pool, lead: tuple, phys, row, rows):
    """``pool[*lead, phys, row] = rows``: THE write of a decode step's
    rows into a paged pool, one scatter of lanes x q_len rows on the
    buffer the pool lives in (no slab is taken out and put back, no
    view reshaped).  ``lead`` indexes the pool's leading axes: the
    layer of a pool the depth scan carries, the K/V half of a scales
    pool."""
    return pool.at[(*lead, phys, row)].set(rows.astype(pool.dtype),
                                           mode="drop")


class RMSNorm(nn.Module):
    """Llama-family norm; scale is replicated ("norm" logical axis).

    ``zero_centered`` (the Gemma convention): output = x̂ · (1 + scale)
    with zeros-init — the parameter stores the DEVIATION from identity,
    so weight decay pulls toward identity and HF Gemma checkpoints map
    verbatim."""

    epsilon: float = 1e-5
    dtype: Dtype = jnp.float32
    zero_centered: bool = False

    @nn.compact
    @jax.named_scope("norm")
    def __call__(self, x):
        from tensorflow_train_distributed_tpu.ops.pallas_kernels import (
            rms_norm,
        )

        init = (nn.initializers.zeros if self.zero_centered
                else nn.initializers.ones)
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(init, ("norm",)),
            (x.shape[-1],),
        )
        if self.zero_centered:
            scale = scale + 1.0
        # Fused pallas kernel on TPU (one VMEM pass, custom VJP); the
        # reference jnp path elsewhere — identical numerics (f32 accum).
        return rms_norm(x, scale, epsilon=self.epsilon).astype(self.dtype)


def _one_way_mesh() -> bool:
    """No ambient mesh is more than one way: the hand kernels are
    single-device, so sharded serving keeps the XLA paths GSPMD can
    partition."""
    mesh = compat.get_abstract_mesh()
    return (mesh is None or mesh.empty
            or all(v <= 1 for v in mesh.shape.values()))


def fused_paged_ok() -> bool:
    """Whether a paged decode step should run its fused kernel: the
    env/backend decision (``use_fused_paged_attention``), vetoed under
    any >1-way ambient mesh (``_one_way_mesh``)."""
    from tensorflow_train_distributed_tpu.ops import pallas_kernels as pk

    return _one_way_mesh() and pk.use_fused_paged_attention()


def flash_walk_ok(q_len: int, k_cache, v_cache, scales=None) -> bool:
    """Whether attention of ``q_len`` queries a lane over a linear cache
    (``MultiHeadAttention._cache_attend`` with ``start``) runs
    ``pallas_kernels.prefix_flash_attention``: plain rows (no int8
    ``scales``) that the kernel takes (``prefix_flash_engages``: bf16,
    whole query blocks, whole lane tiles, the backend's decision) and
    no >1-way ambient mesh.  Everything else walks in XLA
    (``ops.attention.prefix_attention``)."""
    from tensorflow_train_distributed_tpu.ops import pallas_kernels as pk

    return (scales is None and _one_way_mesh()
            and pk.prefix_flash_engages(q_len, k_cache, v_cache))


def latent_walk_ok(q_len: int, cache, **sizes) -> bool:
    """Whether attention of ``q_len`` queries a lane over a linear cache
    of latent rows (``LatentAttention._linear_step``) runs
    ``pallas_kernels.prefix_flash_latent``: rows and head ``sizes``
    (``rank``, ``nope``, ``rope``, ``vd``) that the kernel takes
    (``prefix_flash_latent_engages``) and no >1-way ambient mesh.
    Everything else walks in XLA, as beside ``flash_walk_ok``."""
    from tensorflow_train_distributed_tpu.ops import pallas_kernels as pk

    return _one_way_mesh() and pk.prefix_flash_latent_engages(
        q_len, cache, **sizes)


def latent_walk_sizes(of) -> Optional[dict]:
    """The sizes ``latent_walk_ok`` judges a walk by, of a
    ``LatentAttention`` or of a model's config; None for a config
    whose attention is not latent."""
    if not getattr(of, "kv_lora_rank", None):
        return None
    return dict(rank=of.kv_lora_rank, nope=of.qk_nope_dim,
                rope=of.qk_rope_dim, vd=of.v_head_dim)


def flash_walk_layers(cache, q_len: int, latent=None) -> int:
    """Attention layers of a linear ``cache`` tree (a model's "cache"
    collection) whose walk of a call of ``q_len`` queries runs a
    kernel: every ``key_cache`` beside a ``value_cache`` and no
    ``kv_scales`` (``flash_walk_ok``) and, where the model's attention
    is latent (``latent``: its ``latent_walk_sizes``), every
    ``latent_cache`` (``latent_walk_ok``); a depth scan's stacked
    layers each counted."""
    n = 0
    if isinstance(cache, dict):
        k, v = cache.get("key_cache"), cache.get("value_cache")
        if k is not None and v is not None and flash_walk_ok(
                q_len, k, v, cache.get("kv_scales")):
            n += math.prod(k.shape[:-4])
        rows = cache.get("latent_cache")
        if rows is not None and latent and latent_walk_ok(
                q_len, rows, **latent):
            n += math.prod(rows.shape[:-3])
        n += sum(flash_walk_layers(c, q_len, latent)
                 for c in cache.values())
    return n


class MultiHeadAttention(nn.Module):
    """MHA/GQA over the shared attention kernel.

    Weights: q/k/v ("embed", "heads", "kv"); out ("heads", "kv", "embed").
    Activations constrained to ("batch", "length", "heads", "kv") so a seq
    axis shards length and a tensor axis shards heads.
    """

    num_heads: int
    head_dim: int
    num_kv_heads: Optional[int] = None  # GQA; None → MHA
    dtype: Dtype = jnp.float32
    causal: bool = False
    use_rope: bool = False
    rope_base: float = 10000.0
    # Llama-3.x rope scaling tuple (factor, low_freq_factor,
    # high_freq_factor, original_max_positions); None = plain RoPE.
    rope_scaling: Optional[tuple] = None
    dropout_rate: float = 0.0
    # Sequence/context parallelism: "ring" | "ulysses" | None.  Takes
    # effect when the ambient mesh (jax.set_mesh, as the Trainer binds)
    # has a seq axis > 1; self-attention only.
    seq_parallel: Optional[str] = None
    # Sliding-window causal attention (Mistral convention): each query
    # sees the last ``window`` keys including itself.  Long training
    # sequences take the O(S·window) chunked path; decode keeps a
    # rolling window-sized KV cache.  Composes with ring/Ulysses SP.
    window: Optional[int] = None
    # StreamingLLM attention sinks (needs ``window``): the first
    # ``sinks`` positions stay attendable past the window — keeps
    # unbounded streaming decode stable.  Decode stores them in a small
    # separate buffer beside the rolling ring; both SP methods compose
    # (ring broadcasts shard 0's sink block with one tiny psum).
    sinks: int = 0
    # Autoregressive decode: keep a KV cache of ``cache_len`` positions in
    # the mutable "cache" collection; each call appends this call's k/v at
    # the running index and attends over the filled prefix.  Works for
    # prefill (q_len = prompt length) and stepping (q_len = 1) alike.
    decode: bool = False
    cache_len: int = 0
    # int8 KV cache (decode only): rows quantize per (position,
    # kv_head) with an f32 scale — halves cache HBM vs bf16 (cache
    # reads dominate large-batch/long-context decode) and the dequant
    # fuses into the attention read.  Composes with the shared-index
    # linear cache, the engine's batch-1 per-slot cache, AND the paged
    # block pool (scales ride in a parallel pool var).  Unsupported with the
    # rolling window cache (roll/concat would need scale plumbing; the
    # window already bounds cache memory).
    kv_cache_int8: bool = False
    # Per-slot decode (continuous-batching serving, serving.ServingEngine): the
    # cache index is a VECTOR [B] — each batch row ("slot") sits at its
    # own position, so requests of different lengths decode together and
    # a finished slot can be refilled mid-flight.  Writes become
    # per-row scatters and the causal mask goes per-slot; RoPE reads
    # each slot's own position.  Linear cache, full-precision or
    # kv_cache_int8 (window/sinks keep the shared-index fast path).
    slot_decode: bool = False
    # Paged KV cache (serving.ServingEngine's slot grid; needs
    # slot_decode): instead of one contiguous [B, cache_len] strip per
    # lane, KV rows live in a FIXED pool of ``paged_kv_blocks`` physical
    # blocks of ``kv_block_size`` rows, and each lane maps its logical
    # positions through a per-lane block table (a [B, ceil(cache_len /
    # kv_block_size)] cache variable the engine rewrites host-side at
    # insert/retire).  Shapes stay static — the pool never grows — so
    # jit/sharding see the same program session-long; only table
    # CONTENTS change, which is what lets requests share prompt-prefix
    # blocks copy-on-write (serving_kv.RadixPrefixIndex).  Block 0 is
    # the engine's scratch block: idle/retired lanes' garbage writes
    # land there (their table rows are zeroed), the paged analog of the
    # linear cache's stale-row rule.
    paged_kv_blocks: int = 0
    kv_block_size: int = 0
    # Projection biases (BERT-style encoders; Llama-family stays False).
    use_bias: bool = False
    # q/k/v biases ONLY, out-proj unbiased (the Qwen-family convention;
    # ``use_bias`` keeps the all-projection BERT meaning).
    qkv_bias: bool = False
    # Fuse q/k/v into ONE gemm ("qkv" kernel, [embed, (H+2·KV)·D]).
    # MFU lever for small decoders where three launch-bound projections
    # under-fill the MXU; self-attention only, and the param tree
    # differs from the split layout (checkpoints are not interchangeable
    # — pick per config, before training).  Under a tensor mesh the
    # post-gemm q/k/v slices cut across the fused dim's shards, so keep
    # it for single-chip/dp serving and training runs.
    fused_qkv: bool = False
    # Partial rotary (``partial_rotary_factor``): the first
    # ``rotary_dim`` values of a head are rotated, the rest pass
    # (``apply_rope``); None rotates the whole head.
    rotary_dim: Optional[int] = None
    # Per-head output gate: ``sigmoid(x @ W_g)``, one value a head from
    # the layer's own input, multiplies the head's attention output
    # before the out projection (bias-free ``gate`` kernel).
    out_gate: bool = False
    # Paged serving of a ``window`` layer: a lane's rows live in a RING
    # of ``ring_blocks`` blocks of this layer's own pool (1 + lanes x
    # ring_blocks blocks; position p in ring entry (p // block_size) %
    # ring_blocks), so the layer's memory and a step's reads are
    # bounded by the window whatever the context.  The engine sets it
    # (``serving.ServingEngine``); ``paged_kv_blocks`` is then the full
    # layers' business alone.
    ring_blocks: int = 0
    # Queries of one call that attention over a linear cache walks at
    # a time (``ops.attention.prefix_attention``'s ``block``): a call
    # over k prefill pieces of a prompt reads what the pieces read.
    # The engine sets it to its ``prefill_chunk``; 0: all at once.
    query_block: int = 0
    # A value head's size where it is not the key head's ``head_dim``
    # (None: it is): the value projection, the value cache and pool
    # and the heads' outputs are that wide, queries and keys stay
    # ``head_dim``.
    v_head_dim: Optional[int] = None
    # The projected values are multiplied by this before they are
    # cached (``attention_value_scale``).
    value_scale: float = 1.0
    # A learned sink in the softmax: one float32 logit a query head
    # (``sink/bias`` [num_heads]) that joins every row's denominator
    # and carries no value, ``p_j = exp(s_j - m) / (exp(b - m) +
    # sum_i exp(s_i - m))``.  No cached row and no buffer stands
    # behind it, unlike the StreamingLLM ``sinks`` above, which keep
    # the first ROWS attendable.
    sink: bool = False

    @property
    def _v_dim(self) -> int:
        return self.v_head_dim or self.head_dim

    def _sink_logits(self):
        """float32 [num_heads] sink logits, or None."""
        if not self.sink:
            return None
        return BiasParam((self.num_heads,), ("heads",),
                         name="sink")().astype(jnp.float32)

    def _rope(self, t, positions):
        return apply_rope(t, positions, base=self.rope_base,
                          scaling=self.rope_scaling,
                          rotary_dim=self.rotary_dim)

    def _gate(self, x):
        """[B, S, H] gate of the heads' outputs, or None."""
        if not self.out_gate:
            return None
        with jax.named_scope("attn/gate"):
            return jax.nn.sigmoid(nn.Dense(
                self.num_heads, use_bias=False, dtype=self.dtype,
                name="gate",
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), ("embed", "heads")),
            )(x))

    @staticmethod
    def _gated(out, gate):
        """The heads' outputs [B, S, H, D] under ``_gate``'s values."""
        if gate is None:
            return out
        with jax.named_scope("attn/gate"):
            return out * gate[..., None].astype(out.dtype)

    def _proj(self, x, heads, name, width=None):
        # Plain 2-D kernel (embed, heads*head_dim) + reshape: maps onto
        # the MXU as one big matmul, and sidesteps flax's DenseGeneral
        # boxed-kernel reshape which mis-applies logical constraints
        # under an active mesh.  "heads" on the fused dim still gives
        # Megatron TP (heads*head_dim stays divisible by the tensor
        # axis whenever heads is).  Shared by the training and decode
        # paths — the submodule name/init/partitioning contract between
        # them lives here and only here.
        width = width or self.head_dim
        y = nn.Dense(
            heads * width,
            use_bias=self.use_bias or self.qkv_bias, dtype=self.dtype,
            name=name,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "heads")),
        )(x)
        y = y.reshape(*x.shape[:-1], heads, width)
        return nn.with_logical_constraint(
            y, ("batch", "length", self._head_ax(heads), "kv"))

    @jax.named_scope("attn/qkv")
    def _qkv(self, x):
        """Self-attention q/k/v: three gemms, or one fused gemm
        (``fused_qkv``) split head-wise after the reshape."""
        kv_heads = self.num_kv_heads or self.num_heads
        if not self.fused_qkv:
            return (self._proj(x, self.num_heads, "query"),
                    self._proj(x, kv_heads, "key"),
                    self._value(x, kv_heads))
        if self.v_head_dim or self.value_scale != 1.0:
            raise ValueError("fused_qkv cuts one gemm into equal heads: "
                             "no v_head_dim or value_scale beside it")
        tot = self.num_heads + 2 * kv_heads
        y = nn.Dense(
            tot * self.head_dim, use_bias=self.use_bias or self.qkv_bias,
            dtype=self.dtype, name="qkv",
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "heads")),
        )(x)
        y = y.reshape(*x.shape[:-1], tot, self.head_dim)
        y = nn.with_logical_constraint(
            y, ("batch", "length", self._head_ax(tot), "kv"))
        return (y[..., :self.num_heads, :],
                y[..., self.num_heads:self.num_heads + kv_heads, :],
                y[..., self.num_heads + kv_heads:, :])

    def _value(self, x, kv_heads):
        """The value projection: ``v_head_dim`` wide, times
        ``value_scale``."""
        v = self._proj(x, kv_heads, "value", self._v_dim)
        return v if self.value_scale == 1.0 else v * self.value_scale

    def _head_ax(self, heads):
        """Logical axis for a ``heads``-sized activation dim.

        GQA with fewer kv heads than the tensor degree ("heads" maps to
        the tensor axis in DEFAULT_RULES): replicate the head axis
        instead of letting GSPMD pad-shard a 2-head dim over 4 ways and
        relayout it inside the decode while-loop by involuntary full
        rematerialization (caught by the driver dryrun's sharded-serving
        step, which asserts on the warning)."""
        mesh = _active_mesh("tensor")
        if mesh is not None and heads % mesh.shape["tensor"]:
            return None
        return "heads"

    @jax.named_scope("attn/out")
    def _out_proj(self, x, features):
        return nn.Dense(
            features, use_bias=self.use_bias, dtype=self.dtype, name="out",
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("heads", "embed")),
        )(x)

    @nn.compact
    def __call__(self, x_q, x_kv=None, *, mask=None, positions=None,
                 segment_ids=None, deterministic: bool = True,
                 kv_pools=None):
        if kv_pools is not None and not (self.decode
                                         and self.paged_kv_blocks):
            raise ValueError("kv_pools are the depth scan's carried "
                             "paged pools: they go with decode=True "
                             "and paged_kv_blocks")
        if self.decode:
            if (x_kv is not None or mask is not None
                    or segment_ids is not None or positions is not None):
                raise ValueError(
                    "decode=True is causal self-attention over the KV "
                    "cache; cross-attention inputs (x_kv), dense masks, "
                    "segment ids and explicit positions are not supported "
                    "in decode mode (the cache index supplies positions)")
            return self._decode_step(x_q, kv_pools)
        if self.slot_decode:
            raise ValueError("slot_decode requires decode=True (it is a "
                             "KV-cache mode)")
        if self.paged_kv_blocks:
            raise ValueError("paged_kv_blocks requires decode=True + "
                             "slot_decode=True (it is a serving KV-cache "
                             "mode)")
        if segment_ids is not None and x_kv is not None:
            raise ValueError(
                "segment_ids (sequence packing) applies to self-attention "
                "only")
        x_kv = x_q if x_kv is None else x_kv
        kv_heads = self.num_kv_heads or self.num_heads

        if x_kv is x_q:
            q, k, v = self._qkv(x_q)
        else:
            if self.fused_qkv:
                raise ValueError("fused_qkv is self-attention only "
                                 "(q and kv read different inputs)")
            q = self._proj(x_q, self.num_heads, "query")
            k = self._proj(x_kv, kv_heads, "key")
            v = self._value(x_kv, kv_heads)

        if self.use_rope:
            if positions is None:
                # Default q positions follow the causal-mask alignment: for
                # causal cross-length attention q is the *suffix* of the kv
                # sequence (bottom-right alignment), so its positions start
                # at kv_len - q_len; callers with other layouts (KV cache at
                # arbitrary offsets) pass explicit ``positions``.
                offset = x_kv.shape[1] - x_q.shape[1] if self.causal else 0
                positions = jnp.broadcast_to(
                    jnp.arange(x_q.shape[1]) + offset, x_q.shape[:2])
            # Self-attention with caller positions (packed segments):
            # keys live at the SAME positions as their queries.
            kv_positions = (positions if x_kv is x_q
                            else jnp.broadcast_to(
                                jnp.arange(x_kv.shape[1]), x_kv.shape[:2]))
            q = self._rope(q, positions)
            k = self._rope(k, kv_positions)

        # [B, S, H, D] → [B, H, S, D] for the kernel.
        qh, kh, vh = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        sp_mesh = _seq_parallel_mesh(self.seq_parallel)
        sink_logits = self._sink_logits()
        if sp_mesh is not None and (self.sink or self.v_head_dim):
            raise ValueError("seq_parallel attention takes no sink "
                             "logit and no v_head_dim")
        if sp_mesh is None and kv_heads != self.num_heads:
            # GQA: repeat KV groups to full heads (XLA fuses the broadcast).
            # The SP path rotates/reshards the *unrepeated* KV and repeats
            # inside the shard_map body, saving ICI traffic.
            rep = self.num_heads // kv_heads
            kh = jnp.repeat(kh, rep, axis=1)
            vh = jnp.repeat(vh, rep, axis=1)
        if sp_mesh is not None:
            if mask is not None:
                raise ValueError(
                    "seq_parallel attention supports causal/full (+ packed "
                    "segment_ids), not dense masks")
            if x_kv is not x_q:
                raise ValueError("seq_parallel supports self-attention only")
            from tensorflow_train_distributed_tpu.parallel.ring_attention \
                import shard_mapped_attention

            out = shard_mapped_attention(
                sp_mesh, qh, kh, vh, method=self.seq_parallel,
                causal=self.causal, segment_ids=segment_ids,
                window=self.window, sinks=self.sinks,
            ).transpose(0, 2, 1, 3)
        else:
            out = multihead_attention_kernel(
                qh, kh, vh, causal=self.causal, mask=mask,
                segment_ids=segment_ids, window=self.window,
                sinks=self.sinks, sink_logits=sink_logits,
            ).transpose(0, 2, 1, 3)
        out = nn.with_logical_constraint(
            out, ("batch", "length", self._head_ax(self.num_heads), "kv"))
        if self.dropout_rate > 0 and not deterministic:
            out = nn.Dropout(self.dropout_rate)(out,
                                                deterministic=deterministic)
        out = self._gated(out, self._gate(x_q))
        out = out.reshape(*out.shape[:-2], self.num_heads * self._v_dim)
        y = self._out_proj(out, x_q.shape[-1])
        return nn.with_logical_constraint(y, ("batch", "length", "embed"))

    def _decode_step(self, x, kv_pools=None):
        """Append x's tokens to the KV cache, attend over the prefix.

        Submodule names match the training path exactly, so params trained
        (or imported) without decode load unchanged; only the "cache"
        collection is new.  Causal structure comes from the index mask, not
        the kernel — decode q_len is tiny, the einsum path is the right
        tool.

        With ``window`` set and ``cache_len > window``, the cache is a
        ROLLING ring buffer of ``window`` rows (slot = position %% window)
        — serving memory and per-step attention cost scale with the
        window, not the total generation length (Mistral 32k decode keeps
        a 4k cache/layer).  Multi-token calls work at any position
        (first prefill, chunked prefill, speculative blocks): the block
        attends over (unrolled ring, fresh block) with the window band,
        and the last ``window`` positions re-pack into the ring.
        """
        if self.cache_len <= 0:
            raise ValueError("decode=True needs cache_len > 0")
        if self.paged_kv_blocks and not self.slot_decode:
            raise ValueError(
                "paged_kv_blocks requires slot_decode=True (the paged "
                "pool is the serving engine's per-lane cache mode)")
        if self.slot_decode:
            if self.sinks:
                raise ValueError(
                    "slot_decode (per-slot cache positions) holds no "
                    "attention sinks: they keep the shared-index path")
            if self.window is not None and self.kv_cache_int8:
                raise ValueError(
                    "a window layer's per-slot caches hold no int8 rows")
            if self.paged_kv_blocks:
                if self.paged_kv_blocks < 2:
                    raise ValueError(
                        "paged_kv_blocks must be >= 2 (block 0 is the "
                        f"reserved scratch block), got "
                        f"{self.paged_kv_blocks}")
                if self.kv_block_size < 1:
                    raise ValueError(
                        f"kv_block_size must be >= 1, got "
                        f"{self.kv_block_size}")
                y, pools = self._paged_decode_step(x, kv_pools)
                return y if kv_pools is None else (y, pools)
            return self._slot_decode_step(x)
        if self.sinks and (self.window is None
                           or self.sinks > self.window):
            raise ValueError(
                f"sinks={self.sinks} needs a sliding window >= sinks, "
                f"got window={self.window}")
        rolling = (self.window is not None
                   and self.cache_len > self.window)
        if self.kv_cache_int8 and (rolling or self.sinks):
            raise ValueError(
                "kv_cache_int8 supports the LINEAR cache only (the "
                "rolling window ring / sink buffers would need scale "
                "plumbing through roll/concat, and the window already "
                "bounds cache memory)")
        cache_rows = self.window if rolling else self.cache_len
        kv_heads = self.num_kv_heads or self.num_heads
        b, q_len, _ = x.shape
        # STATIC first-call signal: the cache collection does not exist
        # yet on the very first apply (generate's prefill) — a Python
        # bool, trustworthy under jit, unlike sniffing whether `cur` is
        # a tracer (inside jit even the fresh-init zero is one).
        fresh_cache = not self.has_variable("cache", "index")

        q, k, v = self._qkv(x)

        cache_dtype = jnp.int8 if self.kv_cache_int8 else self.dtype
        cache_k = self.variable(
            "cache", "key_cache", jnp.zeros,
            (b, cache_rows, kv_heads, self.head_dim), cache_dtype)
        cache_v = self.variable(
            "cache", "value_cache", jnp.zeros,
            (b, cache_rows, kv_heads, self._v_dim), cache_dtype)
        if self.kv_cache_int8:
            # One f32 scale per (batch, row, kv_head): symmetric over the
            # head_dim — the standard per-token KV quantization grain.
            kv_scales = self.variable(
                "cache", "kv_scales", jnp.zeros,
                (2, b, cache_rows, kv_heads), jnp.float32)
        index = self.variable(
            "cache", "index", lambda: jnp.zeros((), jnp.int32))
        cur = index.value

        positions = cur + jnp.arange(q_len)
        if self.use_rope:
            pos_b = jnp.broadcast_to(positions, (b, q_len))
            q = self._rope(q, pos_b)
            k = self._rope(k, pos_b)
        index.value = cur + q_len
        gate = self._gate(x)
        if self.sink and rolling:
            raise ValueError("the rolling window cache takes no sink "
                             "logit (the serving engine's ring does)")
        sink_logits = self._sink_logits()

        if rolling and q_len > 1:
            return self._rolling_block(x, q, k, v, cache_k, cache_v,
                                       cur, kv_heads, b, q_len,
                                       fresh_cache, gate)

        kdt = cache_k.value.dtype
        if rolling:
            # Single-token step: own slot = cur % window; slot j then
            # holds absolute position cur - ((cur - j) % window), which
            # is automatically within the window — unfilled slots
            # (negative position) and slots the SINK buffer serves
            # (position < sinks) are masked out.
            w = self.window
            slot = jnp.mod(cur, w)
            cache_k.value = jax.lax.dynamic_update_slice(
                cache_k.value, k.astype(kdt), (0, slot, 0, 0))
            cache_v.value = jax.lax.dynamic_update_slice(
                cache_v.value, v.astype(kdt), (0, slot, 0, 0))
            j = jnp.arange(w)
            slot_pos = cur - jnp.mod(cur - j, w)  # mod ≥ 0 (Python sem.)
            # Exclusivity: the sink buffer serves positions < sinks, the
            # ring serves >= sinks — uniform at every cur, no double
            # counting even while the sink range itself is decoding.
            mask = (slot_pos >= max(self.sinks, 0))[None, :]  # [1, cache]
            kc, vc = cache_k.value, cache_v.value
            if self.sinks:
                sink_k, sink_v = self._sink_buffers(b, kv_heads)
                self._write_sinks(sink_k, sink_v, k, v, cur, q_len, kdt)
                kc = jnp.concatenate([sink_k.value, kc], axis=1)
                vc = jnp.concatenate([sink_v.value, vc], axis=1)
                # Causal: sink position si visible once decoded (si <=
                # cur); unwritten rows are > cur and excluded with it.
                mask = jnp.concatenate(
                    [(jnp.arange(self.sinks) <= cur)[None, :], mask],
                    axis=1)
            return self._cache_attend(q, kc, vc, kv_heads, b, q_len,
                                      x.shape[-1], mask=mask[None, None],
                                      gate=gate)
        scales = None
        if self.kv_cache_int8:
            # Quantize this call's rows: amax over head_dim per
            # (batch, position, kv_head) — the shared recipe.
            qk, sk = _quantize_kv_rows(k)
            qv, sv = _quantize_kv_rows(v)
            cache_k.value = jax.lax.dynamic_update_slice(
                cache_k.value, qk, (0, cur, 0, 0))
            cache_v.value = jax.lax.dynamic_update_slice(
                cache_v.value, qv, (0, cur, 0, 0))
            kv_scales.value = jax.lax.dynamic_update_slice(
                kv_scales.value, jnp.stack([sk, sv]), (0, 0, cur, 0))
            scales = tuple(kv_scales.value)     # dequant at read
        else:
            cache_k.value = jax.lax.dynamic_update_slice(
                cache_k.value, k.astype(kdt), (0, cur, 0, 0))
            cache_v.value = jax.lax.dynamic_update_slice(
                cache_v.value, v.astype(kdt), (0, cur, 0, 0))
        # A window no shorter than the cache (the linear cache under a
        # window) hides no row a position inside the cache can see, so
        # the prefix rule is the whole mask here.
        return self._cache_attend(q, cache_k.value, cache_v.value,
                                  kv_heads, b, q_len, x.shape[-1],
                                  start=cur, scales=scales, gate=gate,
                                  sink_logits=sink_logits)

    def _sink_buffers(self, b, kv_heads):
        """The StreamingLLM sink KV buffer pair ([B, sinks, Hkv, D])."""
        sink_k = self.variable(
            "cache", "sink_key", jnp.zeros,
            (b, self.sinks, kv_heads, self.head_dim), self.dtype)
        sink_v = self.variable(
            "cache", "sink_value", jnp.zeros,
            (b, self.sinks, kv_heads, self._v_dim), self.dtype)
        return sink_k, sink_v

    def _write_sinks(self, sink_k, sink_v, k, v, cur, q_len, kdt):
        """Merge any of this call's rows that land in the sink range
        (positions [cur, cur+q_len) ∩ [0, sinks)) into the sink buffers
        — trace-safe at any ``cur``, a no-op once cur >= sinks."""
        sp = jnp.arange(self.sinks)
        covered = (sp >= cur) & (sp < cur + q_len)
        row = jnp.clip(sp - cur, 0, q_len - 1)
        sel = covered[None, :, None, None]
        sink_k.value = jnp.where(
            sel, jnp.take(k, row, axis=1).astype(kdt), sink_k.value)
        sink_v.value = jnp.where(
            sel, jnp.take(v, row, axis=1).astype(kdt), sink_v.value)

    def _slot_decode_step(self, x):
        """Per-slot KV-cache decode: every batch row has its own index.

        The continuous-batching engine (``serving.ServingEngine``) keeps B
        independent requests in flight; this is the same append-and-
        attend contract as ``_decode_step`` with three per-slot changes:
        the "index" cache variable is [B]; rows write via a per-row
        scatter at each slot's own position (out-of-range positions are
        DROPPED by jax scatter semantics — an overrun slot goes silently
        inert, the engine's budget accounting keeps that unobservable);
        and the causal mask compares against per-slot positions.  A
        refilled slot's stale rows are harmless: position p's row is
        always rewritten before any query can attend it (mask is
        kv_pos <= position and writes happen first).  The attention
        walks the row tiles the longest slot holds and no others
        (``_cache_attend``): a prefill piece at the head of a long
        cache does not pay for the rows behind it.

        ``kv_cache_int8`` composes: rows store int8 with the shared
        per-(slot, position, kv_head) scale recipe
        (``_quantize_kv_rows``) in a [2, B, cache_len, kv_heads] scale
        var, dequant fused into the attention read — the serving
        engine's batch-1 prefill cache for int8 configs.
        """
        kv_heads = self.num_kv_heads or self.num_heads
        b, q_len, _ = x.shape

        q, k, v = self._qkv(x)

        cache_dtype = jnp.int8 if self.kv_cache_int8 else self.dtype
        cache_k = self.variable(
            "cache", "key_cache", jnp.zeros,
            (b, self.cache_len, kv_heads, self.head_dim), cache_dtype)
        cache_v = self.variable(
            "cache", "value_cache", jnp.zeros,
            (b, self.cache_len, kv_heads, self._v_dim), cache_dtype)
        if self.kv_cache_int8:
            kv_scales = self.variable(
                "cache", "kv_scales", jnp.zeros,
                (2, b, self.cache_len, kv_heads), jnp.float32)
        index = self.variable(
            "cache", "index", lambda: jnp.zeros((b,), jnp.int32))
        cur = index.value                                   # [B]
        positions = cur[:, None] + jnp.arange(q_len)        # [B, q]
        if self.use_rope:
            q = self._rope(q, positions)
            k = self._rope(k, positions)
        index.value = cur + q_len

        kdt = cache_k.value.dtype
        bidx = jnp.arange(b)[:, None]
        scales = None
        if self.kv_cache_int8:
            qk, sk = _quantize_kv_rows(k)
            qv, sv = _quantize_kv_rows(v)
            cache_k.value = cache_k.value.at[bidx, positions].set(qk)
            cache_v.value = cache_v.value.at[bidx, positions].set(qv)
            kv_scales.value = kv_scales.value.at[
                :, bidx, positions].set(jnp.stack([sk, sv]))
            scales = tuple(kv_scales.value)
        else:
            cache_k.value = cache_k.value.at[bidx, positions].set(
                k.astype(kdt))
            cache_v.value = cache_v.value.at[bidx, positions].set(
                v.astype(kdt))
        # A window layer keeps every row here (the batch-1 prefill
        # cache is ``cache_len`` long) and walks the tiles its window
        # reaches.
        return self._cache_attend(q, cache_k.value, cache_v.value,
                                  kv_heads, b, q_len, x.shape[-1],
                                  start=cur, scales=scales,
                                  window=self.window, gate=self._gate(x),
                                  sink_logits=self._sink_logits())

    def _paged_decode_step(self, x, kv_pools=None):
        """Per-slot decode over the PAGED pool: same append-and-attend
        contract as ``_slot_decode_step``, with the lane's contiguous
        cache strip replaced by a block-table indirection.

        The pools lie as the kernel reads them (``paged_pool_leaves``:
        [blocks, block_size, kv_heads * head_dim]) and this step's rows
        go into them IN PLACE: one scatter of lanes x q_len rows a pool
        (``_set_pool_rows``) at ``pool[table[b, p // bs], p %% bs]``.
        Positions past the table width are DROPPED, the linear path's
        overrun rule; positions in table slots the engine zeroed land
        in the scratch block — garbage nobody reads (``_paged_dest``).

        Who owns the pools follows where the module stands.  Alone (a
        model that unrolls its layers) it holds its layer's pools as
        its own cache variables.  Under the depth scan
        (``llama._ScannedBlock``) every layer's pools are ONE buffer
        each, [layers, blocks, ...], that the scan carries:
        ``kv_pools`` is ``(layer, pools)``, the rows go to
        ``pool[layer, ...]``, the kernel reads the same buffer through
        the table moved by ``layer * blocks``, and the step returns
        ``(y, pools)``.  Neither way is a layer's slab taken out of a
        stack and put back, nor a pool copied to another layout.

        Reads gather the lane's logical rows back into a [B, cache_len]
        view (``ops.pallas_kernels.paged_kv_gather`` — pure-jax on CPU,
        a scalar-prefetch block-copy kernel on TPU) and attend exactly
        as the linear path does: same mask, same positions, same einsum
        shapes, so outputs are bitwise-identical to the linear cache
        whenever the gathered bytes are (which the engine's block
        bookkeeping guarantees — pinned in tests/test_serving_paged.py).

        On TPU (or under ``TTD_FUSED_ATTN_INTERPRET=1``), the gather +
        attend pair is replaced by ONE fused kernel
        (``ops.pallas_kernels.paged_attention``) that computes
        flash-style decode attention directly through the block table
        — the dense per-lane KV view is never materialized, halving
        decode's HBM traffic.  ``TTD_NO_PALLAS=1`` keeps the gather
        path (the byte-comparable A/B leg).  Sharded serving
        (an ambient mesh) keeps the gather path: GSPMD partitions the
        XLA gather, while the hand kernel is single-device.

        ``kv_cache_int8`` composes: pools store int8 rows quantized by
        the shared per-(row, kv_head) recipe, scales ride in a parallel
        [2, num_blocks, block_size, kv_heads] pool written by the same
        scatter (carried with the others under the scan, where a read
        takes the layer's scales out: a thirty-second of a slab's
        bytes), and the dequant happens at read — fused into the
        kernel's block load, or into the gathered view's attention read
        on the A/B leg.
        """
        from tensorflow_train_distributed_tpu.ops import pallas_kernels \
            as pk

        kv_heads = self.num_kv_heads or self.num_heads
        b, q_len, _ = x.shape
        bs = self.kv_block_size
        nb = self.paged_kv_blocks
        n_blk = -(-self.cache_len // bs)
        ring = self.window is not None
        if ring:
            # A window layer's own pool: a ring of blocks a lane and the
            # scratch block, its table under a name of its own.
            if kv_pools is not None:
                raise ValueError("a depth scan's carried pools hold no "
                                 "window layer's ring")
            if (self.ring_blocks * bs
                    < self.window + q_len - 1):
                raise ValueError(
                    f"a ring of {self.ring_blocks} blocks of {bs} rows "
                    f"cannot hold a window of {self.window} and "
                    f"{q_len} new rows")
            nb, n_blk = 1 + b * self.ring_blocks, self.ring_blocks

        q, k, v = self._qkv(x)

        if kv_pools is None:            # this layer's own pools
            own = {name: self.variable("cache", name, jnp.zeros, shape,
                                       dtype)
                   for name, (shape, dtype) in paged_pool_leaves(
                       nb, bs, kv_heads, self.head_dim, self.dtype,
                       self.kv_cache_int8, self.v_head_dim).items()}
            layer, pools = None, {n: var.value for n, var in own.items()}
        else:                           # the depth scan's, carried
            own, (layer, pools) = {}, kv_pools
        lead = () if layer is None else (layer,)
        # All-zero init: every lane starts mapped to the scratch block,
        # so pre-insert garbage decode is self-contained by
        # construction.
        table = self.variable(
            "cache", "window_table" if ring else "block_table", jnp.zeros,
            (b, n_blk), jnp.int32)
        index = self.variable(
            "cache", "index", lambda: jnp.zeros((b,), jnp.int32))
        cur = index.value                                   # [B]
        positions = cur[:, None] + jnp.arange(q_len)        # [B, q]
        if self.use_rope:
            q = self._rope(q, positions)
            k = self._rope(k, positions)
        index.value = cur + q_len
        gate = self._gate(x)
        sink_logits = self._sink_logits()

        # This step's rows into the pools, under the name the
        # device-scope contract (PERF.md §3) gives the write.
        with jax.named_scope("kv_pool/write/window" if ring
                             else "kv_pool/write"):
            if self.kv_cache_int8:
                k_store, sk = _quantize_kv_rows(k)
                v_store, sv = _quantize_kv_rows(v)
            else:
                k_store, v_store = k, v
            phys, row = _paged_dest(
                table.value, positions, bs, nb,
                ring_of=self.cache_len if ring else None)
            pools = dict(
                pools,
                key_pool=_set_pool_rows(
                    pools["key_pool"], lead, phys, row,
                    k_store.reshape(b, q_len, -1)),
                value_pool=_set_pool_rows(
                    pools["value_pool"], lead, phys, row,
                    v_store.reshape(b, q_len, -1)))
            if self.kv_cache_int8:
                pools["kv_pool_scales"] = _set_pool_rows(
                    pools["kv_pool_scales"],
                    (*lead, jnp.arange(2)[:, None, None]), phys, row,
                    jnp.stack([sk, sv]))
        for name, var in own.items():
            var.value = pools[name]

        # What the read takes: the pool as it lies.  A carried pool's
        # layers merge into its blocks (leading axes only, so the same
        # bytes) and the table is read ``block0`` further on.
        k_pool, v_pool = pools["key_pool"], pools["value_pool"]
        scales = pools.get("kv_pool_scales")
        block0 = 0
        if layer is not None:
            k_pool, v_pool = (p.reshape(-1, *p.shape[2:])
                              for p in (k_pool, v_pool))
            block0 = layer * nb
            if scales is not None:
                scales = jax.lax.dynamic_index_in_dim(
                    scales, layer, keepdims=False)
        k_scales, v_scales = scales if scales is not None else (None, None)

        # No scope from here to the kernel call: the benchmark finds the
        # kernel's device events by this method's name.
        if self._fused_paged_ok():
            # The kernel walks as many blocks as a lane's length
            # reaches.  A lane whose table starts at the scratch block
            # holds nothing (never inserted, or reset at retirement),
            # yet its index grows with every chunk it idles through:
            # it reads its one block of garbage, not a walk of scratch
            # that lengthens until the lane is used again.
            held = jnp.where(table.value[:, 0] == 0, 0, cur)
            out = pk.paged_attention(
                q, k_pool, v_pool, table.value, held,
                k_scales=k_scales, v_scales=v_scales,
                cache_len=self.cache_len, block0=block0,
                window=self.window, sink_logits=sink_logits,
                use_pallas=True, interpret=pk.fused_attn_interpret())
            return self._attn_epilogue(out, b, q_len, x.shape[-1],
                                       gate), pools

        rows = n_blk * bs if ring else self.cache_len

        def lane_view(pool):
            return pk.paged_kv_gather(
                pool, table.value + block0, rows).reshape(
                    b, rows, kv_heads, -1)

        if ring:
            # The ring, whole, under the mask of what each row holds.
            seen = pk.ring_mask(cur, q_len, rows, self.window)
            return self._cache_attend(
                q, lane_view(k_pool), lane_view(v_pool), kv_heads, b,
                q_len, x.shape[-1], mask=seen[:, None], gate=gate,
                sink_logits=sink_logits), pools
        if self.kv_cache_int8:      # else ``scales`` is None already
            scales = tuple(
                pk.paged_kv_gather(s, table.value, self.cache_len)
                for s in (k_scales, v_scales))
        return self._cache_attend(
            q, lane_view(k_pool), lane_view(v_pool), kv_heads, b, q_len,
            x.shape[-1], start=cur, scales=scales, gate=gate,
            sink_logits=sink_logits), pools

    def _fused_paged_ok(self) -> bool:
        return fused_paged_ok()

    def _cache_attend(self, q, kc, vc, kv_heads, b, q_len, features, *,
                      start=None, mask=None, scales=None, window=None,
                      gate=None, sink_logits=None):
        """Attention of q over the cache buffers [B, rows, kv_heads, D].

        A linear cache hands in ``start`` [B] (or a scalar), the
        position of each lane's first query: ``prefix_attention`` then
        walks the row tiles the longest lane holds (``ops.attention.
        prefix_tiles_walked``) with a running softmax, and repeats
        grouped heads and dequantizes int8 rows (``scales``: the keys'
        and the values', [B, rows, kv_heads]) a tile at a time, so a row
        no lane holds is not read.  A cache of one tile is the ordinary
        masked attention over all of it.  Where the rows are plain
        bf16 and the call is long enough (``flash_walk_ok``) the same
        walk is one kernel (``pallas_kernels.prefix_flash_attention``).
        A ring (the rolling window
        and its sinks, a paged window layer's gathered ring) addresses
        no prefix: it hands in its own ``mask`` over every row.
        ``window`` narrows a linear cache's walk to the tiles a sliding
        window reaches (``prefix_attention``).  ``sink_logits``
        [num_heads]: the learned sink of every row's softmax
        (``sink``)."""
        from tensorflow_train_distributed_tpu.ops import pallas_kernels as pk
        from tensorflow_train_distributed_tpu.ops.attention import (
            dot_product_attention, prefix_attention,
        )

        # Same logical sharding as the training path: under a tensor/fsdp
        # mesh the cache reads and attention activations shard over heads
        # rather than replicating (B, cache_len, H, D) per device.
        kv_ax = self._head_ax(kv_heads)
        rep = self.num_heads // kv_heads

        def heads(rows):
            """(k, v) of every head, [B, H, T, D], from T cache rows."""
            kv = rows[:2]
            if rows[2] is not None:
                # Dequant at read: XLA fuses the convert+multiply into
                # the attention einsum's cache read (int8 bytes off HBM).
                kv = [c.astype(self.dtype)
                      * s[..., None].astype(self.dtype)
                      for c, s in zip(kv, rows[2])]
            kv = [nn.with_logical_constraint(
                c, ("batch", "length", kv_ax, "kv")) for c in kv]
            if rep != 1:
                kv = [jnp.repeat(c, rep, axis=2) for c in kv]
            return [c.transpose(0, 2, 1, 3) for c in kv]

        qh = q.transpose(0, 2, 1, 3)        # [B, S, H, D] → [B, H, S, D]
        if start is None:
            out = dot_product_attention(qh, *heads((kc, vc, scales)),
                                        mask=mask,
                                        sink_logits=sink_logits)
        elif flash_walk_ok(q_len, kc, vc, scales):
            # The same walk as one kernel: each of its query blocks
            # walks its own tiles, so ``query_block`` has nothing to
            # add.
            out = pk.prefix_flash_attention(
                qh, kc, vc, start, window=window, sink_logits=sink_logits,
                interpret=pk.fused_attn_interpret())
        else:
            out = prefix_attention(qh, (kc, vc, scales), start, heads,
                                   window=window,
                                   block=self.query_block,
                                   sink_logits=sink_logits)
        out = out.transpose(0, 2, 1, 3)
        return self._attn_epilogue(out, b, q_len, features, gate)

    def _attn_epilogue(self, out, b, q_len, features, gate=None):
        """Shared decode tail — constraint, the heads' gate
        (``out_gate``), head-merge, out-proj — for the gathered-attend
        path and the fused paged-attention kernel (one epilogue keeps
        the two paths' param use identical)."""
        out = nn.with_logical_constraint(
            out, ("batch", "length", self._head_ax(self.num_heads), "kv"))
        out = self._gated(out, gate)
        out = out.reshape(b, q_len, self.num_heads * self._v_dim)
        y = self._out_proj(out, features)
        return nn.with_logical_constraint(y, ("batch", "length", "embed"))

    def _rolling_block(self, x, q, k, v, cache_k, cache_v, cur, kv_heads,
                       b, q_len, fresh, gate=None):
        """Multi-token call under the rolling cache, correct at ANY
        ``cur`` (first prefill, chunked prefill, speculative blocks).

        Ring invariant BEFORE the block: slot j holds position
        ``cur - w + ((j - cur) %% w)`` — the last w positions
        ``cur-w .. cur-1``, so rolling by ``-cur`` sorts the ring into
        positional order.  The block concatenates its fresh k/v after
        the unrolled ring, each query applies the causal+window+validity
        band over the w+q_len keys, and the last w rows of that concat
        re-roll into slot order as the new ring state."""
        w = self.window
        kdt = cache_k.value.dtype
        sinks = self.sinks
        if sinks:
            sink_k, sink_v = self._sink_buffers(b, kv_heads)
            # Merge this block's rows that land in the sink range first:
            # the sink COLUMNS below read the post-merge buffer, so a
            # block that decodes across the sink boundary sees its own
            # sink keys (trace-safe at any cur).
            self._write_sinks(sink_k, sink_v, k, v, cur, q_len, kdt)
        # First prefill (`fresh`: the cache collection was created THIS
        # call): the ring is knowably empty — skip the unroll/concat and
        # attend the block alone (a 128-token prompt must not pay a
        # w+128-key attention against w masked zeros).
        if fresh:
            kcat, vcat = k.astype(kdt), v.astype(kdt)
            kv_pos = jnp.arange(q_len)
            q_pos = jnp.arange(q_len)
            sink_cols = 0
        else:
            shift = jnp.mod(cur, w)
            ordered_k = jnp.roll(cache_k.value, -shift, axis=1)
            ordered_v = jnp.roll(cache_v.value, -shift, axis=1)
            kcat = jnp.concatenate([ordered_k, k.astype(kdt)], axis=1)
            vcat = jnp.concatenate([ordered_v, v.astype(kdt)], axis=1)
            kv_pos = cur - w + jnp.arange(w + q_len)      # global positions
            q_pos = cur + jnp.arange(q_len)
            sink_cols = sinks
            if sinks:
                kcat = jnp.concatenate([sink_k.value, kcat], axis=1)
                vcat = jnp.concatenate([sink_v.value, vcat], axis=1)
        band = ((kv_pos[None, :] >= 0)
                & (kv_pos[None, :] <= q_pos[:, None])
                & (q_pos[:, None] - kv_pos[None, :] < w))
        if fresh and sinks:
            # StreamingLLM keep-set during the first block: band OR sink
            # prefix (the block holds its own sink keys — no columns).
            band = band | ((kv_pos[None, :] < sinks)
                           & (kv_pos[None, :] <= q_pos[:, None]))
        if sink_cols:
            # Exclusivity at any cur: sink columns serve positions
            # < sinks (causally: si <= q_pos; unwritten rows are beyond
            # every q_pos), ring/block entries serve >= sinks.
            band = band & (kv_pos[None, :] >= sinks)
            sink_keep = (jnp.arange(sinks)[None, :] <= q_pos[:, None])
            keep = jnp.concatenate([sink_keep, band], axis=1)
        else:
            keep = band
        # New ring = last w positions written so far, re-packed so each
        # row with position p sits at slot p % w.  A fresh block shorter
        # than w writes positions 0..q_len-1 straight to slots 0..q_len-1
        # (untouched tail slots read as position < 0 → masked later).
        if fresh and q_len < w:
            cache_k.value = jax.lax.dynamic_update_slice(
                cache_k.value, kcat, (0, 0, 0, 0))
            cache_v.value = jax.lax.dynamic_update_slice(
                cache_v.value, vcat, (0, 0, 0, 0))
        else:
            end = jnp.mod(cur + q_len, w)
            cache_k.value = jnp.roll(kcat[:, -w:], end, axis=1)
            cache_v.value = jnp.roll(vcat[:, -w:], end, axis=1)
        return self._cache_attend(q, kcat, vcat, kv_heads, b, q_len,
                                  x.shape[-1], mask=keep[None, None],
                                  gate=gate)


def _pad_last(x, width: int):
    """``x`` with zeros appended to its last axis up to ``width``."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1)
                   + [(0, width - x.shape[-1])])


class KernelParam(nn.Module):
    """One ``kernel`` parameter read as an array instead of through
    ``nn.Dense``: a kernel used in more than one contraction (the latent
    attention's ``kv_b``, whose halves are absorbed into the query and
    the output at decode) or stacked over experts (``models.moe``).
    ``batch_axis`` are leading axes the initializer leaves out of its
    fan-in (the expert axis of a stacked kernel)."""

    shape: tuple
    logical_axes: tuple
    batch_axis: tuple = ()

    @nn.compact
    def __call__(self):
        if self.has_variable("quant", "scale"):
            # The int8 serving path rewrites nn.Dense call sites via a
            # method interceptor (models/quant.py) — this raw-param read
            # would cast int8 CODES to bf16 with no scale applied and
            # produce garbage silently.
            raise NotImplementedError(
                "int8 weight-only serving is not wired for kernels read "
                "as arrays (the gmm dispatch path's stacked experts, "
                "latent attention's kv_b) — serve quantized MoE "
                "checkpoints with dispatch='dense', or "
                "dequantize_params() first")
        return self.param(
            "kernel",
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(batch_axis=self.batch_axis),
                self.logical_axes),
            self.shape)


def _scope_when(named: bool, scope: str):
    """``jax.named_scope(scope)`` where ``named``, else no scope."""
    return jax.named_scope(scope) if named else contextlib.nullcontext()


def _head_gate(x, heads: int, dtype):
    """``sigmoid(x W_g)`` [B, S, heads]: the per-head output gate of the
    module whose compact method calls this (its bias-free ``gate``
    kernel, as ``MultiHeadAttention._gate``'s)."""
    with jax.named_scope("attn/gate"):
        return jax.nn.sigmoid(nn.Dense(
            heads, use_bias=False, dtype=dtype, name="gate",
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "heads")))(x))


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2; GLM-4.7-Flash).

    Queries and keys/values go through low-rank latents::

        c_q = norm(x Wq_a)              [q_lora_rank]
        q   = c_q Wq_b  -> H heads of  q_nope [nope] ‖ q_rope [rope]
        [c_kv ‖ k_r] = x Wkv_a          [kv_lora_rank ‖ rope]
        [k_nope ‖ v] = norm(c_kv) Wkv_b -> H heads of [nope] ‖ [v_head]

    with one rotary key ``k_r`` shared by every head, so a token's whole
    attention state is ONE row ``[norm(c_kv) ‖ rope(k_r)]`` of
    ``kv_lora_rank + rope`` values: that row is what the cache holds
    (``latent_cache`` [B, C, row] linear, ``latent_pool`` [blocks, bs,
    row] paged; tables and indices as ``MultiHeadAttention``'s).  A
    stored row is as wide as the 128-lane tiles it lies in (576 values
    in 640; device memory pads the minor dimension so whatever the
    shape says, and a kernel's copy may only cut it at a tile), the
    tail zero.

    Two ways to attend, the same mathematics:

    - **up-projected** (training forward, and every call on the linear
      cache, so the engine's prefill pieces): keys and values of all
      heads are made from the rows (``Wkv_b``) and attention is the
      ordinary one at head size nope + rope; over the linear cache it
      walks, tile by tile with a running softmax, the rows the call's
      lanes hold and up-projects no others (``_linear_step``).  Per
      cached row and
      query this costs 2·H·(nope+rope+v) operations against the
      absorbed form's 2·H·(2·rank+rope), so it is the cheaper one
      wherever many queries share the up-projection;
    - **absorbed** (the paged decode step): ``Wkv_b``'s key half is
      folded into the query (``q_lat = q_nope · W_uk``), scores are
      taken against the rows themselves (``q_lat·c_kv + q_rope·k_r``),
      the probabilities average the rows, and the value half brings the
      result back (``o = (P·c_kv) · W_uv``).  The rows are read once
      and serve as key and as value, which is the point of the cache:
      a decode step is bound by the bytes of the rows it reads.

    A layer may see a sliding ``window`` of its rows (a model whose
    latent layers are of two kinds: ``models.moe.OwnLatentKind``): the
    forward masks the band, a piece's walk starts at the window's first
    tile (``prefix_attention(window=)``: the XLA walk; the prefill
    kernel knows no window), and the paged step keeps the rows in a
    ring of ``ring_blocks`` blocks a lane under ``window_table``, as
    ``MultiHeadAttention`` does, read by the same absorbed kernel from
    the window's first block.  ``lora_rescale`` multiplies both
    normalised latents by ``sqrt(d_model / rank)`` before anything reads
    them (the cached row holds the rescaled latent).
    """

    num_heads: int
    # None: the queries are one plain projection of the input (no query
    # latent, no ``q_norm``); the learned selection needs the latent.
    q_lora_rank: Optional[int]
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    dtype: Dtype = jnp.float32
    rope_base: float = 10000.0
    rope_scaling: Optional[tuple] = None
    rms_epsilon: float = 1e-5
    decode: bool = False
    cache_len: int = 0
    slot_decode: bool = False
    paged_kv_blocks: int = 0
    kv_block_size: int = 0
    query_block: int = 0    # see MultiHeadAttention
    # The learned selection (class docstring): ``index_heads`` heads of
    # ``index_dim`` score every cached row for a query, and attention
    # sees the ``index_topk`` best.  0 = attention over every row.
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    # Per-head output gate, as ``MultiHeadAttention.out_gate``.
    out_gate: bool = False
    # A sliding window (None: every row): a query at position p sees
    # ``p - window < row <= p``.  The paged step then keeps its rows in
    # a RING of ``ring_blocks`` blocks a lane under ``window_table``
    # (``MultiHeadAttention._paged_decode_step``'s rule); a window
    # layer chooses no rows.
    window: Optional[int] = None
    ring_blocks: int = 0
    # The normalised latents times ``sqrt(d_model / rank)``.
    lora_rescale: bool = False

    def _rescaled(self, c, d_model: int):
        """The normalised latent ``c`` at the hidden size's scale."""
        if not self.lora_rescale:
            return c
        return c * jnp.asarray((d_model / c.shape[-1]) ** 0.5, c.dtype)

    @property
    def row_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def softmax_scale(self) -> float:
        """``(nope + rope) ** -0.5``, times YaRN's ``mscale`` squared
        where the frequencies are YaRN's."""
        scale = (self.qk_nope_dim + self.qk_rope_dim) ** -0.5
        if self.rope_scaling and self.rope_scaling[0] == "yarn":
            scale *= yarn_mscale(self.rope_scaling[1]) ** 2
        return scale

    def _selects(self, rows: int) -> bool:
        """Whether attention over ``rows`` positions can leave any out
        (statically: up to ``index_topk`` rows the choice is all)."""
        return bool(self.index_topk) and rows > self.index_topk

    @property
    def row_store(self) -> int:
        return -(-self.row_dim // 128) * 128

    def _dense(self, features, axes, name):
        return nn.Dense(
            features, use_bias=False, dtype=self.dtype, name=name,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), axes))

    def _rope(self, x, positions):
        return apply_rope(x, positions, base=self.rope_base,
                          scaling=self.rope_scaling)

    @jax.named_scope("attn/q_latent")
    def _queries(self, x, positions):
        """(q_nope [B,S,H,nope], rope(q_rope) [B,S,H,rope], the query
        latent c_q [B,S,q_lora_rank] the indexer shares, or None)."""
        width = self.num_heads * (self.qk_nope_dim + self.qk_rope_dim)
        if self.q_lora_rank is None:
            if self.index_topk:
                raise ValueError("the learned selection reads the query "
                                 "latent: it needs q_lora_rank")
            c_q = None
            q = self._dense(width, ("embed", "heads"), "query")(x)
        else:
            c_q = self._rescaled(
                RMSNorm(epsilon=self.rms_epsilon, dtype=self.dtype,
                        name="q_norm")(
                    self._dense(self.q_lora_rank, ("embed", None),
                                "q_a")(x)), x.shape[-1])
            q = self._dense(width, (None, "heads"), "q_b")(c_q)
        q = q.reshape(*x.shape[:-1], self.num_heads, -1)
        q = nn.with_logical_constraint(
            q, ("batch", "length", "heads", "kv"))
        return (q[..., :self.qk_nope_dim],
                self._rope(q[..., self.qk_nope_dim:], positions), c_q)

    @jax.named_scope("attn/kv_latent")
    def _rows(self, x, positions):
        """This call's cache rows [B, S, row_store]."""
        kv = self._dense(self.row_dim, ("embed", None), "kv_a")(x)
        c_kv = self._rescaled(
            RMSNorm(epsilon=self.rms_epsilon, dtype=self.dtype,
                    name="kv_norm")(kv[..., :self.kv_lora_rank]),
            x.shape[-1])
        k_r = self._rope(kv[..., None, self.kv_lora_rank:],
                         positions)[..., 0, :]
        return _pad_last(jnp.concatenate([c_kv, k_r], axis=-1),
                         self.row_store)

    def _rope_head(self, t, positions):
        """The indexer's rotation: the leading ``qk_rope_dim`` dims of
        every head of ``t`` [B, S, H, index_dim], the attention's own
        frequency table."""
        r = self.qk_rope_dim
        return jnp.concatenate(
            [self._rope(t[..., :r], positions), t[..., r:]], axis=-1)

    @jax.named_scope("attn/index_q")
    def _index_queries(self, c_q, x, positions):
        """(q_I [B,S,Hi,Di] in the cache's type, w [B,S,Hi] float32):
        the indexer's queries from the query latent, and the weight of
        each of its heads from the layer's input, scaled by
        ``Hi ** -0.5 * Di ** -0.5``."""
        q = self._dense(self.index_heads * self.index_dim,
                        (None, "heads"), "index_q")(c_q)
        q = self._rope_head(
            q.reshape(*x.shape[:-1], self.index_heads, self.index_dim),
            positions)
        w = dense(self.index_heads, ("embed", None), use_bias=False,
                  dtype=jnp.float32, name="index_w")(
                      x.astype(jnp.float32))
        return q, w * (self.index_heads ** -0.5 * self.index_dim ** -0.5)

    @jax.named_scope("attn/index_k")
    def _index_keys(self, x, positions):
        """This call's index keys [B, S, Di]: one a token, LayerNorm
        (scale and bias) of a projection of the layer's input."""
        k = nn.LayerNorm(epsilon=1e-6, dtype=self.dtype,
                         name="index_k_norm")(
            self._dense(self.index_dim, ("embed", None), "index_k")(x))
        return self._rope_head(k[..., None, :], positions)[..., 0, :]

    def _chosen_rows(self, q_i, w_i, keys, start):
        """Bool [B, Q, C]: the ``index_topk`` rows of the linear
        ``keys`` [B, C, Di] that each query (lane ``b``'s at ``start[b]
        + arange(Q)``) attends."""
        from tensorflow_train_distributed_tpu.ops.attention import (
            prefix_index_scores, select_top_rows,
        )

        with jax.named_scope("attn/index_score"):
            scores = prefix_index_scores(q_i, w_i, keys, start,
                                         block=self.query_block)
        with jax.named_scope("attn/select"):
            return select_top_rows(scores, self.index_topk, start,
                                   block=self.query_block)

    def _kv_b(self):
        """``Wkv_b`` as [rank, H, nope + v_head]."""
        per_head = self.qk_nope_dim + self.v_head_dim
        return KernelParam(
            (self.kv_lora_rank, self.num_heads * per_head),
            (None, "heads"), name="kv_b")().astype(self.dtype).reshape(
                self.kv_lora_rank, self.num_heads, per_head)

    @jax.named_scope("attn/kv_latent")
    def _up_project(self, rows, kv_b):
        """(k, v) of every head from rows [B, T, row_store] through
        ``kv_b`` (``_kv_b()``, read by the caller: this runs inside the
        tile loop of ``_linear_step``, where no parameter is read)."""
        kv = jnp.einsum("btc,chd->bthd", rows[..., :self.kv_lora_rank],
                        kv_b)
        k_r = jnp.broadcast_to(
            rows[..., None, self.kv_lora_rank:self.row_dim],
            (*rows.shape[:2], self.num_heads, self.qk_rope_dim))
        k = jnp.concatenate([kv[..., :self.qk_nope_dim], k_r], axis=-1)
        return k, kv[..., self.qk_nope_dim:]

    def _gate(self, x):
        """[B, S, H] gate of the heads' outputs, or None."""
        return (_head_gate(x, self.num_heads, self.dtype)
                if self.out_gate else None)

    @jax.named_scope("attn/out")
    def _out(self, o, features, gate=None):
        o = MultiHeadAttention._gated(o, gate)
        o = nn.with_logical_constraint(
            o, ("batch", "length", "heads", "kv"))
        y = self._dense(features, ("heads", "embed"), "out")(
            o.reshape(*o.shape[:2], self.num_heads * self.v_head_dim))
        return nn.with_logical_constraint(y, ("batch", "length", "embed"))

    @nn.compact
    def __call__(self, x, *, positions=None, segment_ids=None):
        if self.decode:
            if positions is not None or segment_ids is not None:
                raise ValueError(
                    "decode=True takes positions from the cache index; "
                    "explicit positions and segment ids are not "
                    "supported in decode mode")
            if self.cache_len <= 0:
                raise ValueError("decode=True needs cache_len > 0")
            if self.window is not None and self.index_topk:
                raise ValueError(
                    "a window layer chooses no rows: window="
                    f"{self.window} beside index_topk={self.index_topk}")
            if self.paged_kv_blocks:
                if not self.slot_decode:
                    raise ValueError(
                        "paged_kv_blocks requires slot_decode=True")
                if self.paged_kv_blocks < 2 or self.kv_block_size < 1:
                    raise ValueError(
                        "the paged latent pool needs >= 2 blocks (block "
                        "0 is scratch) of >= 1 rows, got "
                        f"{self.paged_kv_blocks} x {self.kv_block_size}")
                return self._paged_step(x)
            return self._linear_step(x)
        if self.slot_decode or self.paged_kv_blocks:
            raise ValueError("slot_decode / paged_kv_blocks are KV-cache "
                             "modes: they require decode=True")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(x.shape[1]),
                                         x.shape[:2])
        q_nope, q_rope, c_q = self._queries(x, positions)
        k, v = self._up_project(self._rows(x, positions), self._kv_b())
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        mask = None
        if self.index_topk:
            # The indexer's parameters exist whatever the length; its
            # choice is read only where it can leave a row out, as one
            # dense [B, S, S] mask (the training forward: no cache).
            q_i, w_i = self._index_queries(c_q, x, positions)
            k_i = self._index_keys(x, positions)
            if self._selects(x.shape[1]):
                if segment_ids is not None:
                    raise ValueError("the learned selection does not "
                                     "take packed segments")
                mask = self._chosen_rows(
                    q_i, w_i, k_i,
                    jnp.zeros((x.shape[0],), jnp.int32))[:, None]
        with _scope_when(mask is not None, "attn/sparse"):
            o = multihead_attention_kernel(
                *(t.transpose(0, 2, 1, 3) for t in (q, k, v)),
                causal=mask is None, mask=mask, segment_ids=segment_ids,
                window=self.window,
                softmax_scale=self.softmax_scale).transpose(0, 2, 1, 3)
        return self._out(o, x.shape[-1], self._gate(x))

    def _linear_step(self, x):
        """Append this call's rows to the linear cache and attend over
        the rows the lanes hold, up-projected a tile at a time
        (``ops.attention.prefix_attention``: a row past the longest
        lane's last query is neither read nor up-projected; a cache of
        one tile is the ordinary masked attention over all of it).
        Where the rows are bf16 and the call is long enough
        (``latent_walk_ok``) the same walk is one kernel
        (``pallas_kernels.prefix_flash_latent``); a layer with a
        ``window`` keeps the XLA walk, which starts at the window's
        first tile.
        ``index`` is a scalar (``models.generate``) or, under
        ``slot_decode``, one per batch row (the engine's batch-1
        prefill cache)."""
        from tensorflow_train_distributed_tpu.ops import pallas_kernels as pk
        from tensorflow_train_distributed_tpu.ops.attention import (
            prefix_attention,
        )

        b, q_len, _ = x.shape
        cache = self.variable(
            "cache", "latent_cache", jnp.zeros,
            (b, self.cache_len, self.row_store), self.dtype)
        index = self.variable(
            "cache", "index", lambda: jnp.zeros(
                (b,) if self.slot_decode else (), jnp.int32))
        cur = jnp.broadcast_to(index.value, (b,))
        positions = cur[:, None] + jnp.arange(q_len)            # [B, q]
        index.value = index.value + q_len
        q_nope, q_rope, c_q = self._queries(x, positions)
        rows = self._rows(x, positions)

        def write(cache, rows):
            # Out-of-range positions are dropped: an overrun row goes
            # inert, as in MultiHeadAttention._slot_decode_step.
            cache.value = cache.value.at[
                jnp.arange(b)[:, None], positions].set(
                    rows.astype(cache.value.dtype), mode="drop")

        with jax.named_scope("kv_pool/write"):
            write(cache, rows)
        keep = None
        if self._selects(self.cache_len):
            keys = self.variable(
                "cache", "index_cache", jnp.zeros,
                (b, self.cache_len, self.index_dim), self.dtype)
            q_i, w_i = self._index_queries(c_q, x, positions)
            k_i = self._index_keys(x, positions)
            with jax.named_scope("index_pool/write"):
                write(keys, k_i)
            keep = self._chosen_rows(q_i, w_i, keys.value, cur)
        kv_b = self._kv_b()
        with _scope_when(keep is not None, "attn/sparse"):
            if self.window is None and latent_walk_ok(
                    q_len, cache.value, **latent_walk_sizes(self)):
                # The same walk as one kernel: each of its query
                # blocks walks its own tiles, so ``query_block`` has
                # nothing to add.
                o = pk.prefix_flash_latent(
                    q_nope.transpose(0, 2, 1, 3),
                    q_rope.transpose(0, 2, 1, 3), cache.value, kv_b, cur,
                    keep=keep, softmax_scale=self.softmax_scale,
                    interpret=pk.fused_attn_interpret())
            else:
                q = jnp.concatenate([q_nope, q_rope], axis=-1)
                o = prefix_attention(
                    q.transpose(0, 2, 1, 3), cache.value, cur,
                    lambda rows: [t.transpose(0, 2, 1, 3)
                                  for t in self._up_project(rows, kv_b)],
                    keep=keep, window=self.window,
                    softmax_scale=self.softmax_scale,
                    block=self.query_block)
        return self._out(o.transpose(0, 2, 1, 3), x.shape[-1],
                         self._gate(x))

    def _paged_step(self, x):
        """Per-slot decode over the paged latent pool, absorbed: the
        append-and-attend contract of
        ``MultiHeadAttention._paged_decode_step`` (block table, scratch
        block 0, dropped overrun rows, an empty lane told length 0)
        with ONE pool of rows a layer.  With a ``window`` the pool is
        the lanes' RINGS (entry ``(p // block) % ring_blocks`` of
        ``window_table``) and the kernel walks from the window's first
        block (``paged_latent_attention(window=)``)."""
        from tensorflow_train_distributed_tpu.ops import pallas_kernels \
            as pk

        b, q_len, _ = x.shape
        bs, nb = self.kv_block_size, self.paged_kv_blocks
        n_blk = -(-self.cache_len // bs)
        rank = self.kv_lora_rank
        ring = self.window is not None
        if ring:
            # A window layer's own pool: a ring of blocks a lane and the
            # scratch block, its table under a name of its own
            # (``MultiHeadAttention._paged_decode_step``).
            if self.ring_blocks * bs < self.window + q_len - 1:
                raise ValueError(
                    f"a ring of {self.ring_blocks} blocks of {bs} rows "
                    f"cannot hold a window of {self.window} and "
                    f"{q_len} new rows")
            nb, n_blk = 1 + b * self.ring_blocks, self.ring_blocks
        pool = self.variable("cache", "latent_pool", jnp.zeros,
                             (nb, bs, self.row_store), self.dtype)
        table = self.variable(
            "cache", "window_table" if ring else "block_table", jnp.zeros,
            (b, n_blk), jnp.int32)
        index = self.variable(
            "cache", "index", lambda: jnp.zeros((b,), jnp.int32))
        cur = index.value
        positions = cur[:, None] + jnp.arange(q_len)            # [B, q]
        index.value = cur + q_len
        q_nope, q_rope, c_q = self._queries(x, positions)
        rows = self._rows(x, positions)
        with jax.named_scope("kv_pool/write/window" if ring
                             else "kv_pool/write"):
            dest = _paged_dest(table.value, positions, bs, nb,
                               ring_of=self.cache_len if ring else None)
            pool.value = _set_pool_rows(pool.value, (), *dest, rows)

        w = self._kv_b()
        with jax.named_scope("attn/absorb"):
            q_lat = jnp.einsum("bqhd,chd->bqhc", q_nope,
                               w[..., :self.qk_nope_dim])
            q_cat = _pad_last(jnp.concatenate([q_lat, q_rope], axis=-1),
                              self.row_store)
        # An empty lane (table starts at the scratch block) holds
        # nothing, whatever its index has grown to while it idled.
        held = jnp.where(table.value[:, 0] == 0, 0, cur)
        kernel = dict(value_dim=rank, scale=self.softmax_scale,
                      use_pallas=fused_paged_ok(),
                      interpret=pk.fused_attn_interpret())
        if self._selects(self.cache_len):
            o_lat = self._paged_selected(
                x, c_q, positions, dest, q_cat, pool.value, table.value,
                held, kernel)
        else:
            o_lat = pk.paged_latent_attention(
                q_cat, pool.value, table.value, held,
                cache_len=self.cache_len, window=self.window, **kernel)
        with jax.named_scope("attn/absorb"):
            o = jnp.einsum("bqhc,chd->bqhd", o_lat.astype(self.dtype),
                           w[..., self.qk_nope_dim:])
        return self._out(o, x.shape[-1], self._gate(x))

    def _paged_selected(self, x, c_q, positions, dest, q_cat, pool, table,
                        held, kernel):
        """The absorbed attention of a paged step over the rows the
        indexer chooses: append this step's index keys to
        ``index_pool`` (one key a token, the latent pool's blocks and
        table), score every row a lane holds through its table
        (``paged_index_scores``: the walk rule of the attention
        kernel, 256 B a row in bf16), take the ``index_topk`` best of
        each query, gather their latent rows, and run
        ``paged_latent_attention`` over the gathered rows laid out as a
        pool of their own: a query reads ``index_topk`` latent rows, not
        its lane's history.  A lane with no more rows than that chooses
        all of them.  The choice is ``lax.top_k`` here (ties to the
        lower position, the set ``select_top_rows`` marks for a prefill
        piece): a step's scores are 2 MB, and on the chip the sort beat
        the counted threshold with the positions read off by block
        counts, a few dozen small operations (PERF.md section 6,
        PR 33)."""
        from tensorflow_train_distributed_tpu.ops import pallas_kernels \
            as pk

        b, q_len = positions.shape
        bs, nb, top = self.kv_block_size, self.paged_kv_blocks, \
            self.index_topk
        if top % bs:
            raise ValueError(f"index_topk={top} is no multiple of the "
                             f"block size {bs}")
        keys = self.variable("cache", "index_pool", jnp.zeros,
                             (nb, bs, self.index_dim), self.dtype)
        q_i, w_i = self._index_queries(c_q, x, positions)
        k_i = self._index_keys(x, positions)
        with jax.named_scope("index_pool/write"):
            keys.value = _set_pool_rows(keys.value, (), *dest, k_i)
        with jax.named_scope("attn/index_score"):
            scores = pk.paged_index_scores(
                q_i, w_i, keys.value, table, held,
                cache_len=self.cache_len, use_pallas=kernel["use_pallas"],
                interpret=kernel["interpret"])            # [B, q, C]
        seen = jnp.minimum(held[:, None] + 1 + jnp.arange(q_len),
                           self.cache_len)                 # rows a query sees
        chosen = jnp.minimum(seen, top)
        with jax.named_scope("attn/select"):
            _, at = jax.lax.top_k(scores, top)             # [B, q, top]
            at = at.reshape(b, q_len * top)
            phys = jnp.take_along_axis(table, at // bs, axis=1)
            picked = jnp.take(pool.reshape(nb * bs, -1),
                              phys * bs + at % bs, axis=0)
        # Rows a step scored and attended, idle lanes left out: the
        # engine's ``rows_scored`` / ``rows_selected`` (kept only where
        # the caller makes ``attn_stats`` mutable).
        self.sow("attn_stats", "rows", jnp.stack(
            [jnp.sum(jnp.where(table[:, :1] != 0, n, 0))
             for n in (seen, chosen)]).astype(jnp.int32))
        with jax.named_scope("attn/sparse"):
            lanes = b * q_len
            o = pk.paged_latent_attention(
                q_cat.reshape(lanes, 1, *q_cat.shape[2:]),
                picked.reshape(lanes * top // bs, bs, -1),
                jnp.arange(lanes * top // bs,
                           dtype=jnp.int32).reshape(lanes, top // bs),
                chosen.reshape(lanes) - 1, cache_len=top, **kernel)
        return o.reshape(b, q_len, *o.shape[2:])


def delta_log_decay(raw, rate, floor: float):
    """The log of one step's decay of the delta rule's state, a value a
    key channel, in ``(floor, 0)``: the bounded form ``floor *
    sigmoid(rate * raw)`` (``raw`` the decay projection with its bias,
    ``rate = exp(A_log)`` a head).  ``ops.attention.delta_rule_scan``
    counts on the bound: a chunk's decays multiply inside float32's
    range."""
    return floor * jax.nn.sigmoid(rate * raw)


class BiasParam(nn.Module):
    """One ``bias`` parameter read as an array (``KernelParam``'s
    sibling): a vector that is no Dense layer's, zero at init."""

    shape: tuple
    logical_axes: tuple

    @nn.compact
    def __call__(self):
        return self.param(
            "bias", nn.with_logical_partitioning(nn.initializers.zeros,
                                                 self.logical_axes),
            self.shape)


class DeltaAttention(nn.Module):
    """Linear attention by the gated delta rule with a decay per key
    channel (Kimi Delta Attention, arXiv:2510.26692): a layer whose
    memory of the sequence is one ``[head_dim, head_dim]`` float32
    state a head, whatever the context, and no positions.

    Per token, with ``H`` heads of ``d = head_dim`` key and value
    channels::

        q~, k~, v~ = x Wq, x Wk, x Wv                  [H d] each
        q, k, v = silu(conv4(q~)), silu(conv4(k~)), silu(conv4(v~))
        q = l2norm_h(q) d^-1/2;  k = l2norm_h(k)
        g = decay_floor * sigmoid(exp(A_h) (x Wf + b))  [H d], in (floor, 0)
        beta = sigmoid(x Wb)                            [H]
        S_ = diag(exp g) S;  S = S_ + beta k (v - S_^T k)^T;  o = S^T q
        y = concat_h(gamma_h rmsnorm(o_h)) Wo,  gamma = sigmoid(x Wg)

    ``conv4`` is a depthwise causal convolution over the last
    ``conv_size`` rows (zeros before the sequence, no bias).  A call of
    several rows runs the recurrence a chunk at a time
    (``ops.attention.delta_rule_scan``), a call of one row as one step
    (``ops.pallas_kernels.delta_state_step``: the kernel under a paged
    engine's slot grid, its reference elsewhere).

    ``decode=True`` keeps, in the "cache" collection, what the next
    call needs: ``delta_state`` [B, H, d, d] float32, ``conv_tail`` [B,
    conv_size - 1, 3 H d] (the last rows of q~|k~|v~ before the
    convolution) and ``pad_rows`` [B], which the CALLER sets: how many
    of this call's trailing rows are padding.  A padded row leaves the
    state as it was (``g = 0``, ``beta = 0``) and the tail is the last
    REAL rows: a positional cache forgives a row run twice or a pad row
    written, a state does not.  Zero (a fresh cache, ``generate()``,
    a decode step) says every row is real.
    """

    num_heads: int
    head_dim: int
    conv_size: int = 4
    decay_floor: float = -5.0
    dtype: Dtype = jnp.float32
    rms_epsilon: float = 1e-6
    out_gate: bool = False
    decode: bool = False
    # The engine's slot grid (``paged_kv_blocks`` set): a decode step
    # runs the fused state kernel where the paged kernels run fused.
    paged_kv_blocks: int = 0

    def _dense(self, features, axes, name, dtype=None, use_bias=False):
        return nn.Dense(
            features, use_bias=use_bias, dtype=dtype or self.dtype,
            name=name, kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), axes))

    @nn.compact
    def __call__(self, x, *, positions=None, segment_ids=None):
        if segment_ids is not None:
            raise ValueError("the delta rule's state runs through a whole "
                             "row: it does not take packed segments")
        del positions                      # the layer has none
        b, t, _ = x.shape
        h, d, taps = self.num_heads, self.head_dim, self.conv_size
        width = h * d
        f32 = jnp.float32
        if self.decode:
            state = self.variable("cache", "delta_state", jnp.zeros,
                                  (b, h, d, d), f32)
            tail = self.variable("cache", "conv_tail", jnp.zeros,
                                 (b, taps - 1, 3 * width), self.dtype)
            pad = self.variable("cache", "pad_rows", jnp.zeros, (b,),
                                jnp.int32)
            s0, tail0, real = state.value, tail.value, t - pad.value
        else:
            s0 = jnp.zeros((b, h, d, d), f32)
            tail0 = jnp.zeros((b, taps - 1, 3 * width), self.dtype)
            real = jnp.full((b,), t, jnp.int32)

        qkv = jnp.concatenate(
            [self._dense(width, ("embed", "heads"), name)(x)
             for name in ("query", "key", "value")], axis=-1)
        with jax.named_scope("attn/linear/conv"):
            taps_w = jnp.concatenate(
                [KernelParam((taps, width), (None, "heads"),
                             name=name)() for name in
                 ("conv_q", "conv_k", "conv_v")], axis=-1).astype(f32)
            ext = jnp.concatenate([tail0.astype(qkv.dtype), qkv], axis=1)
            mixed = sum(taps_w[i] * ext[:, i:i + t].astype(f32)
                        for i in range(taps))
            mixed = nn.silu(mixed).reshape(b, t, 3, h, d)
            # The rows the next call's convolution reaches back to: the
            # last ``taps - 1`` REAL ones.
            tail1 = jax.vmap(lambda e, r: jax.lax.dynamic_slice_in_dim(
                e, r, taps - 1, axis=0))(ext, real)
            q, k, v = mixed[:, :, 0], mixed[:, :, 1], mixed[:, :, 2]

            def l2norm(u):
                return u * jax.lax.rsqrt(
                    jnp.sum(u * u, axis=-1, keepdims=True) + 1e-6)

            q, k = l2norm(q) * d ** -0.5, l2norm(k)
        with jax.named_scope("attn/linear/gates"):
            # Float32 from the projection on: a step's decay is
            # multiplied into the state for as long as the sequence is.
            a = self._dense(width, ("embed", "heads"), "decay", f32,
                            use_bias=True)(x.astype(f32)).reshape(b, t, h, d)
            rate = jnp.exp(BiasParam((h,), ("heads",),
                                     name="a_log")().astype(f32))
            g = delta_log_decay(a, rate[:, None], self.decay_floor)
            beta = jax.nn.sigmoid(self._dense(
                h, ("embed", "heads"), "beta", f32)(x.astype(f32)))
            live = jnp.arange(t)[None, :] < real[:, None]       # [B, T]
            g = jnp.where(live[..., None, None], g, 0.0)
            beta = jnp.where(live[..., None], beta, 0.0)
        if t == 1:
            from tensorflow_train_distributed_tpu.ops import (
                pallas_kernels as pk,
            )

            with jax.named_scope("attn/linear/step"):
                fused = bool(self.paged_kv_blocks) and fused_paged_ok()
                s1, o = pk.delta_state_step(
                    s0, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                    use_pallas=fused,
                    interpret=fused and pk.fused_attn_interpret())
                o = o[:, None]
        else:
            from tensorflow_train_distributed_tpu.ops.attention import (
                delta_rule_scan,
            )

            with jax.named_scope("attn/linear/scan"):
                o, s1 = delta_rule_scan(q, k, v, g, beta, s0,
                                        dtype=self.dtype)
        if self.decode:
            with jax.named_scope("state_pool/write"):
                state.value = s1
                tail.value = tail1.astype(self.dtype)
        o = RMSNorm(epsilon=self.rms_epsilon, dtype=self.dtype,
                    name="out_norm")(o)
        if self.out_gate:
            o = MultiHeadAttention._gated(o, _head_gate(x, h, self.dtype))
        with jax.named_scope("attn/out"):
            y = self._dense(x.shape[-1], ("heads", "embed"), "out")(
                o.reshape(b, t, width))
        return nn.with_logical_constraint(y, ("batch", "length", "embed"))


class MlpBlock(nn.Module):
    """Transformer FFN; gated (SwiGLU) when ``gated`` — Llama convention."""

    hidden: int
    dtype: Dtype = jnp.float32
    activation: Callable = nn.gelu
    gated: bool = False
    dropout_rate: float = 0.0

    @nn.compact
    @jax.named_scope("mlp")
    def __call__(self, x, *, deterministic: bool = True):
        # "mlp_hidden" checkpoint_name tags document the [B,S,ffn]
        # intermediates (identity unless a policy names them).  NOTE:
        # name-based EXCLUSION policies (save_anything_except_these_names)
        # do not work here — the pre-tag producer value stays saveable, so
        # the hiddens get saved anyway (measured: 6 stacked [L,B,S,ffn]
        # buffers in the v5e OOM dump).  The "no_ffn" remat policy
        # therefore wraps this whole module in an inner nothing-saveable
        # nn.remat at the call site (llama.DecoderBlock) instead.
        from jax.ad_checkpoint import checkpoint_name

        d = x.shape[-1]
        if self.gated:
            gate = checkpoint_name(
                dense(self.hidden, ("embed", "mlp"), use_bias=False,
                      dtype=self.dtype, name="wi_gate")(x), "mlp_hidden")
            up = checkpoint_name(
                dense(self.hidden, ("embed", "mlp"), use_bias=False,
                      dtype=self.dtype, name="wi_up")(x), "mlp_hidden")
            h = checkpoint_name(self.activation(gate) * up, "mlp_hidden")
        else:
            h = checkpoint_name(
                dense(self.hidden, ("embed", "mlp"), dtype=self.dtype,
                      name="wi")(x), "mlp_hidden")
            h = checkpoint_name(self.activation(h), "mlp_hidden")
        h = checkpoint_name(
            nn.with_logical_constraint(h, ("batch", "length", "mlp")),
            "mlp_hidden")
        if self.dropout_rate > 0 and not deterministic:
            h = nn.Dropout(self.dropout_rate)(h, deterministic=deterministic)
        y = dense(d, ("mlp", "embed"), use_bias=not self.gated,
                  dtype=self.dtype, name="wo")(h)
        return nn.with_logical_constraint(y, ("batch", "length", "embed"))
