"""Llama-2 decoder for SFT — reference config[4] (DTensor 2-D mesh stretch).

The reference's stretch goal shards Llama-2-7B over a data×model DTensor
mesh (``dtensor/python/layout.py``).  Here the same 2-D (or 3-D, with seq)
layout is just the rules table: embed/mlp/heads on ``tensor``, batch on
``data``/``fsdp``, length on ``seq`` — one model definition covers dp_tp,
fsdp_tp and dp_tp_sp presets.

TPU-first scale choices:
- ``scan_layers``: one compiled block scanned over the depth axis — compile
  time stays O(1) in layers (32 layers of 7B would otherwise take minutes).
- ``remat``: per-block rematerialization trades FLOPs for HBM, the standard
  recipe for 7B on small chips.
- attention runs the pallas flash kernel on TPU (``ops.attention``).

Architecture per Llama-2: RMSNorm pre-norm, RoPE, SwiGLU FFN, untied LM
head, optional GQA (num_kv_heads < num_heads).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensorflow_train_distributed_tpu.runtime import compat
from tensorflow_train_distributed_tpu.models import layers as L
from tensorflow_train_distributed_tpu.ops.losses import (
    fold_sample_weight, softmax_cross_entropy,
)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32_000
    d_model: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: Optional[int] = None  # None → MHA (llama-2-7b)
    ffn_size: int = 11_008
    max_positions: int = 4096
    rope_base: float = 10_000.0
    rms_epsilon: float = 1e-5
    dtype: object = jnp.bfloat16
    scan_layers: bool = True
    remat: bool = True
    # What remat saves (only meaningful with remat=True):
    #   "full" — save only layer boundaries, recompute everything (max
    #            memory savings, ~1.3x recompute FLOPs; the 7B default);
    #   "dots" — save matmul/einsum outputs, recompute elementwise chains
    #            (jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    #            — the standard LLM policy: most of full-remat's memory win
    #            at a fraction of the recompute, so higher MFU when HBM
    #            allows; attention internals still stream via the flash
    #            kernel, which saves only q/k/v + LSE regardless);
    #   "no_ffn" — save everything EXCEPT the [B,S,ffn] SwiGLU hiddens
    #            (the dominant no-remat buffers): backward re-runs only
    #            the two FFN input matmuls + activation — near-no-remat
    #            speed at a fraction of its memory.
    remat_policy: str = "full"
    # "ring" | "ulysses" | None — context parallelism over the seq mesh axis.
    seq_parallel: object = None
    # Sliding-window causal attention (Mistral-7B convention): each token
    # attends to the last ``sliding_window`` positions including itself.
    # Long sequences take the O(S·window) chunked attention path — the
    # long-context lever when full attention's S² won't fit; None = full
    # causal attention.  Composes with ring/Ulysses seq_parallel (the
    # ring skips out-of-window hops) and with packing.
    sliding_window: Optional[int] = None
    # StreamingLLM attention sinks (needs sliding_window): the first N
    # positions stay attendable past the window; decode keeps them in a
    # small buffer beside the rolling KV ring, so unbounded streaming
    # generation stays stable.  Composes with ring AND Ulysses SP.
    attention_sinks: int = 0
    # GPipe microbatch count: when set AND the ambient mesh has a
    # ``pipeline`` axis > 1, the depth scan is replaced by the
    # ``parallel.pipeline`` schedule (each stage holds a contiguous layer
    # group; same stacked params, same math, pipelined execution).  The
    # schedule needs scan_layers (the stacked-parameter layout).
    pipeline_microbatches: Optional[int] = None
    # LoRA fine-tuning (models.lora.LoraSpec): frozen base + trainable
    # low-rank adapters on the targeted projections.  The task applies
    # the model under lora_scope; pair the optimizer with
    # lora.freeze_base.  None = full fine-tuning.
    lora: object = None
    # int8 KV cache for decode (linear cache only): halves cache HBM
    # traffic/footprint — the large-batch/long-context serving lever;
    # per-(position, kv_head) scales, dequant fused into the attention
    # read.  Training is unaffected (no cache there).
    kv_cache_int8: bool = False
    # One fused qkv gemm instead of three (layers.MultiHeadAttention
    # fused_qkv): an MFU lever for small decoders where three
    # launch-bound projections under-fill the MXU.  The param tree
    # differs from the split layout — pick before training; single-chip
    # / dp meshes (the fused-dim slices fight a tensor axis).
    fused_qkv: bool = False
    # q/k/v projection biases, out-proj unbiased (the Qwen2/Qwen2.5
    # dense-family convention — layers.MultiHeadAttention.qkv_bias);
    # Llama/Mistral stay bias-free.
    qkv_bias: bool = False
    # Gemma-family knobs.  head_dim decouples the attention width from
    # d_model/num_heads (gemma-2b: d=2048, 8 heads, head_dim 256);
    # None = the Llama derivation.  embed_scale multiplies token
    # embeddings by sqrt(d_model) at input.  mlp_activation "gelu"
    # makes the gated MLP GeGLU (tanh-approx, HF gelu_pytorch_tanh);
    # "silu" is SwiGLU.  norm_zero_centered stores RMSNorm scales as
    # deviations from identity (output x̂·(1+w)) so Gemma checkpoints
    # map verbatim.
    head_dim: Optional[int] = None
    embed_scale: bool = False
    mlp_activation: str = "silu"
    norm_zero_centered: bool = False
    # Llama-3.x frequency-dependent RoPE scaling: (factor,
    # low_freq_factor, high_freq_factor, original_max_positions) —
    # layers.llama3_scaled_freqs; None = plain RoPE.  A tuple (not a
    # dict) so the frozen config stays hashable for jit static args.
    rope_scaling: Optional[tuple] = None

    def __post_init__(self):
        if self.mlp_activation not in ("silu", "gelu"):
            # Config-time, not a KeyError deep inside the first trace.
            raise ValueError(
                f"mlp_activation must be 'silu' (SwiGLU) or 'gelu' "
                f"(GeGLU, tanh approximation), got "
                f"{self.mlp_activation!r}")
        if self.fused_qkv and self.lora is not None:
            attn = ({"query", "key", "value"}
                    & set(getattr(self.lora, "targets", ())))
            if attn:
                # The q/k/v Dense modules become one "qkv" module, so
                # name-based LoRA targeting of them matches NOTHING —
                # and if any non-attention target still matches, the
                # n_lora==0 structural guard passes and a frozen-base
                # run silently trains without attention adapters.
                raise ValueError(
                    f"fused_qkv replaces the q/k/v projections with one "
                    f"'qkv' module; LoRA targets {sorted(attn)} would "
                    "match nothing — fine-tune attention with "
                    "fused_qkv=False")


LLAMA_PRESETS = {
    "llama2_7b": LlamaConfig(),
    # Mistral-7B shape: GQA(8) + sliding-window 4096 over 32k positions —
    # the long-context config where chunked local attention replaces the
    # S² score matrix.
    "mistral_7b": LlamaConfig(num_kv_heads=8, ffn_size=14_336,
                              max_positions=32_768, rope_base=1e6,
                              sliding_window=4096),
    # Qwen2.5-7B shape (qkv-bias convention; --init-from-hf a local
    # checkpoint imports it exactly).
    "qwen25_7b": LlamaConfig(vocab_size=152_064, d_model=3584,
                             num_layers=28, num_heads=28,
                             num_kv_heads=4, ffn_size=18_944,
                             max_positions=32_768, rope_base=1e6,
                             rms_epsilon=1e-6, qkv_bias=True),
    # Gemma-1 shapes: decoupled 256-wide heads, sqrt(d) embed scale,
    # GeGLU, zero-centered norms, tied embeddings (import maps the tied
    # head automatically).  2b is MQA (kv=1).
    "gemma_2b": LlamaConfig(vocab_size=256_000, d_model=2048,
                            num_layers=18, num_heads=8, num_kv_heads=1,
                            head_dim=256, ffn_size=16_384,
                            max_positions=8192, rms_epsilon=1e-6,
                            embed_scale=True, mlp_activation="gelu",
                            norm_zero_centered=True),
    "gemma_7b": LlamaConfig(vocab_size=256_000, d_model=3072,
                            num_layers=28, num_heads=16,
                            num_kv_heads=16, head_dim=256,
                            ffn_size=24_576, max_positions=8192,
                            rms_epsilon=1e-6, embed_scale=True,
                            mlp_activation="gelu",
                            norm_zero_centered=True),
    # Llama-3.1-8B shape: GQA(8), 128k vocab, 500k rope base with the
    # llama3 frequency-scaling tuple (factor 8, low 1, high 4, original
    # context 8192) — --init-from-hf maps checkpoints exactly.
    "llama31_8b": LlamaConfig(vocab_size=128_256, num_layers=32,
                              num_heads=32, num_kv_heads=8,
                              ffn_size=14_336, max_positions=131_072,
                              rope_base=500_000.0,
                              rope_scaling=(8.0, 1.0, 4.0, 8192)),
    "llama2_13b": LlamaConfig(d_model=5120, num_layers=40, num_heads=40,
                              ffn_size=13_824),
    "llama_1b": LlamaConfig(d_model=2048, num_layers=16, num_heads=16,
                            ffn_size=5504),
    # ~350M-param GPT-medium-class decoder: the mid-size MFU point — big
    # enough that matmuls dominate per-op overheads (the measured 125m
    # ceiling), small enough to train on one 16 GiB chip with no_ffn.
    "llama_350m": LlamaConfig(d_model=1024, num_layers=24, num_heads=16,
                              ffn_size=2816, max_positions=2048),
    # ~125M-param GPT-2-small-class decoder: the flagship fwd path at a
    # size that compiles fast everywhere (same code path as llama2_7b;
    # also the __graft_entry__ flagship and the LM benchmark default).
    "llama_125m": LlamaConfig(d_model=768, num_layers=12, num_heads=12,
                              ffn_size=2048, max_positions=2048),
    "llama_tiny": LlamaConfig(vocab_size=256, d_model=64, num_layers=2,
                              num_heads=4, num_kv_heads=2, ffn_size=128,
                              max_positions=128, dtype=jnp.float32,
                              scan_layers=False, remat=False),
    "llama_tiny_scan": LlamaConfig(vocab_size=256, d_model=64, num_layers=2,
                                   num_heads=4, num_kv_heads=2, ffn_size=128,
                                   max_positions=128, dtype=jnp.float32,
                                   scan_layers=True, remat=True),
    # Pipeline-parallel CI variant: 4 layers so a 2-stage mesh holds 2
    # layers/stage, exercising the grouped gpipe schedule.
    "llama_tiny_pp": LlamaConfig(vocab_size=256, d_model=64, num_layers=4,
                                 num_heads=4, num_kv_heads=2, ffn_size=128,
                                 max_positions=128, dtype=jnp.float32,
                                 scan_layers=True, remat=True,
                                 pipeline_microbatches=4),
}


def _checkpoint_policy(cfg: LlamaConfig):
    """jax.checkpoint policy for the config's ``remat_policy`` name."""
    if cfg.remat_policy == "full":
        return None  # save nothing beyond layer boundaries
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    if cfg.remat_policy == "no_ffn":
        # "no_ffn" has NO outer block checkpoint (callers must not wrap;
        # gate on wants_outer_remat below).  The exclusion of the [B,S,ffn] SwiGLU
        # hiddens — the buffers that dominate the no-remat footprint
        # (measured on v5e) — is STRUCTURAL: DecoderBlock wraps the MlpBlock
        # in an inner nothing-saveable nn.remat, and everything outside
        # it is saved scan-normally.  Two approaches that do NOT work,
        # both verified empirically: (a) save_anything_except_these_names
        # leaves the pre-tag producer values saveable (6 stacked
        # [L,B,S,ffn] buffers in the v5e OOM dump); (b) an outer
        # everything_saveable checkpoint DISSOLVES inner nothing-saveable
        # regions (their internals become the outer's residuals).
        raise AssertionError(
            "no_ffn takes no outer checkpoint; gate on wants_outer_remat")
    raise ValueError(
        f"Unknown remat_policy {cfg.remat_policy!r}; expected 'full', "
        "'dots' or 'no_ffn'")


def wants_outer_remat(cfg: LlamaConfig) -> bool:
    """Whether the per-block (outer) nn.remat wrap applies.  False for
    remat=False and for the "no_ffn" policy, whose only checkpoint is the
    inner FFN region (an outer wrap would either re-introduce full
    recompute or dissolve the inner region — see _checkpoint_policy)."""
    return cfg.remat and cfg.remat_policy != "no_ffn"


class DecoderBlock(nn.Module):
    config: LlamaConfig
    decode: bool = False
    cache_len: int = 0
    slot_decode: bool = False
    # Paged serving KV cache (serving.ServingEngine paged mode) — see
    # layers.MultiHeadAttention.paged_kv_blocks.
    paged_kv_blocks: int = 0
    kv_block_size: int = 0
    query_block: int = 0    # see layers.MultiHeadAttention

    @nn.compact
    def __call__(self, x, segment_ids=None, positions=None, kv_pools=None):
        """``kv_pools``: the depth scan's carried paged pools, ``(layer,
        pools)`` (``layers.MultiHeadAttention._paged_decode_step``); the
        block then returns ``(x, pools)``."""
        cfg = self.config
        h = L.RMSNorm(epsilon=cfg.rms_epsilon, dtype=cfg.dtype,
                      zero_centered=cfg.norm_zero_centered,
                      name="attn_norm")(x)
        attn = L.MultiHeadAttention(
            num_heads=cfg.num_heads,
            head_dim=cfg.head_dim or cfg.d_model // cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads,
            dtype=cfg.dtype, causal=True, use_rope=True,
            rope_base=cfg.rope_base, rope_scaling=cfg.rope_scaling,
            seq_parallel=cfg.seq_parallel,
            window=cfg.sliding_window, sinks=cfg.attention_sinks,
            decode=self.decode,
            cache_len=self.cache_len or cfg.max_positions,
            kv_cache_int8=cfg.kv_cache_int8,
            slot_decode=self.slot_decode,
            paged_kv_blocks=self.paged_kv_blocks,
            kv_block_size=self.kv_block_size,
            query_block=self.query_block,
            fused_qkv=cfg.fused_qkv,
            qkv_bias=cfg.qkv_bias,
            name="attention",
        )(h, segment_ids=segment_ids, positions=positions,
          kv_pools=kv_pools)
        if kv_pools is not None:
            attn, pools = attn
        x = x + attn
        h = L.RMSNorm(epsilon=cfg.rms_epsilon, dtype=cfg.dtype,
                      zero_centered=cfg.norm_zero_centered,
                      name="mlp_norm")(x)
        mlp_cls = L.MlpBlock
        if cfg.remat and cfg.remat_policy == "no_ffn" and not self.decode:
            # "no_ffn": the FFN runs inside an inner nothing-saveable
            # remat region, so no [B,S,ffn] intermediate can be saved —
            # backward re-runs the FFN from its (saved) input.  nn.remat
            # on the module class is param-path-transparent, so
            # checkpoints load unchanged.  The outer block policy is
            # everything_saveable (see _checkpoint_policy): name-based
            # exclusion does NOT drop the hiddens (the pre-tag producer
            # value stays saveable — verified in a v5e OOM dump).
            mlp_cls = nn.remat(
                L.MlpBlock, prevent_cse=False,
                policy=jax.checkpoint_policies.nothing_saveable)
        x = x + mlp_cls(
            hidden=cfg.ffn_size, dtype=cfg.dtype,
            activation={"silu": nn.silu, "gelu": nn.gelu}[
                cfg.mlp_activation],
            gated=True, name="mlp")(h)
        return x if kv_pools is None else (x, pools)


def segment_relative_positions(segment_ids: jax.Array) -> jax.Array:
    """[B, S] segment ids → [B, S] positions restarting at each segment.

    Positions are what RoPE sees: in a packed row each document must be
    encoded at 0..len-1, not at its offset in the row.  Padding (its own
    segment id) restarts too — harmless, those positions are loss-masked.
    """
    s = segment_ids.shape[-1]
    idx = jnp.arange(s)
    restart = jnp.concatenate(
        [jnp.ones_like(segment_ids[..., :1], bool),
         segment_ids[..., 1:] != segment_ids[..., :-1]], axis=-1)
    last_restart = jax.lax.associative_scan(
        jnp.maximum, jnp.where(restart, idx, 0), axis=-1)
    return idx - last_restart


class _BlockStep(nn.Module):
    """scan-compatible adapter: (carry, aux, layer) → (carry, None).
    ``carry`` is ``(x, pools)``: the activations and, in paged decode,
    every layer's KV pools (otherwise none: ``{}``); ``aux`` is the
    nn.broadcast (segment_ids, positions) pair shared by all layers;
    ``layer`` is this step's index, by which the block addresses its
    part of the carried pools."""

    config: LlamaConfig
    decode: bool = False
    cache_len: int = 0
    slot_decode: bool = False
    paged_kv_blocks: int = 0
    kv_block_size: int = 0
    query_block: int = 0    # see layers.MultiHeadAttention

    @nn.compact
    def __call__(self, carry, aux, layer):
        x, pools = carry
        segment_ids, positions = aux if aux is not None else (None, None)
        block = DecoderBlock(self.config, decode=self.decode,
                             cache_len=self.cache_len,
                             slot_decode=self.slot_decode,
                             paged_kv_blocks=self.paged_kv_blocks,
                             kv_block_size=self.kv_block_size,
                             query_block=self.query_block,
                             name="block")
        if not pools:
            return (block(x, segment_ids, positions), pools), None
        return block(x, segment_ids, positions,
                     kv_pools=(layer, pools)), None


class _ScannedBlock(nn.Module):
    """Depth-scanned stack: params get a leading ``stage`` axis, so compile
    time is O(1) in depth and the pipeline axis can shard layers.

    Cache variables are scanned with the params (a layer's linear cache,
    index and block table are its slice of a stacked leaf) — but NOT the
    paged KV pools of a decode step.  Those this module owns whole,
    ``[layers, blocks, block_size, row]`` a pool
    (``layers.paged_pool_leaves``), and the scan CARRIES them: a layer
    writes its rows into the one buffer and its kernel reads that
    buffer, so no 128 MiB slab is sliced out of a stack and put back
    around a write of 32 rows, and the buffer the program was given is
    the buffer its result lives in."""

    config: LlamaConfig
    decode: bool = False
    cache_len: int = 0
    slot_decode: bool = False
    paged_kv_blocks: int = 0
    kv_block_size: int = 0
    query_block: int = 0    # see layers.MultiHeadAttention

    @nn.compact
    def __call__(self, x, segment_ids=None, positions=None):
        from functools import partial as _partial

        # slot_decode (and the paged-pool knobs) thread through BOTH
        # branches so the layer guards ("slot_decode requires
        # decode=True", ditto paged_kv_blocks) fire under scan_layers
        # exactly as they do on the unscanned path.
        step = (_partial(_BlockStep, decode=True,
                         cache_len=self.cache_len,
                         slot_decode=self.slot_decode,
                         paged_kv_blocks=self.paged_kv_blocks,
                         kv_block_size=self.kv_block_size,
                         query_block=self.query_block)
                if self.decode
                else _partial(_BlockStep,
                              slot_decode=self.slot_decode,
                              paged_kv_blocks=self.paged_kv_blocks,
                              kv_block_size=self.kv_block_size,
                              query_block=self.query_block))
        # No remat in decode mode: there is no backward pass to save memory
        # for, and the KV-cache writes must not replay under a checkpoint.
        if wants_outer_remat(self.config) and not self.decode:
            step = nn.remat(step, prevent_cse=False,
                            policy=_checkpoint_policy(self.config))
        cfg = self.config
        pools = {}
        if self.decode and self.paged_kv_blocks:
            pools = {
                name: self.variable("cache", name, jnp.zeros,
                                    (cfg.num_layers, *shape), dtype)
                for name, (shape, dtype) in L.paged_pool_leaves(
                    self.paged_kv_blocks, self.kv_block_size,
                    cfg.num_kv_heads or cfg.num_heads,
                    cfg.head_dim or cfg.d_model // cfg.num_heads,
                    cfg.dtype, cfg.kv_cache_int8).items()}
        scanned = nn.scan(
            step,
            # "quant": stacked int8 serving scales (models.quant) slice
            # per-layer exactly like the stacked params they mirror;
            # absent collections are ignored by nn.scan.
            variable_axes={"params": 0, "cache": 0, "quant": 0},
            split_rngs={"params": True},
            # (segment_ids, positions): all layers; the layer's index.
            in_axes=(nn.broadcast, 0),
            length=cfg.num_layers,
            metadata_params={nn.PARTITION_NAME: "stage"},
        )
        (x, carried), _ = scanned(cfg, name="stack")(
            (x, {name: var.value for name, var in pools.items()}),
            (segment_ids, positions), jnp.arange(cfg.num_layers))
        for name, var in pools.items():
            var.value = carried[name]
        return x


def _pipeline_mesh(cfg: LlamaConfig):
    """The ambient mesh when the gpipe path is requested and usable."""
    if not (cfg.pipeline_microbatches and cfg.scan_layers):
        return None
    mesh = compat.get_abstract_mesh()
    if mesh is None or mesh.empty or mesh.shape.get("pipeline", 1) <= 1:
        return None
    return mesh


def _pipelined_blocks(cfg: LlamaConfig, block_params, x, mesh,
                      segment_ids=None, positions=None):
    """Decoder stack as a GPipe schedule over the ``pipeline`` mesh axis.

    ``block_params`` is the nn.scan-stacked DecoderBlock tree (leading dim
    ``num_layers``, sharded over ``pipeline`` by the ``stage`` rule) — the
    SAME parameters the depth scan uses, so dp and dp_pp runs of one
    checkpoint are numerically identical.

    Packed rows: segment ids / positions ride the pipeline carry WITH the
    activation — at tick t, stage s is processing microbatch t−s, so
    side inputs cannot be indexed by tick at later stages; shipping them
    through the same ppermute hop keeps each microbatch's metadata
    aligned with its activation (int [mb,S] hops are <1% of the [mb,S,D]
    activation bytes at real widths).
    """
    from tensorflow_train_distributed_tpu.parallel.pipeline import (
        gpipe_layers,
    )

    # Constructed OUTSIDE layer_fn: flax forbids Module CONSTRUCTION at
    # a deeper trace level than the enclosing module context (layer_fn
    # runs inside scan-in-shard_map), while ``.apply`` opens a fresh
    # context and is legal anywhere.
    block = DecoderBlock(cfg)

    def layer_fn(p, carry):
        h, seg, pos = carry
        # Inside shard_map every mesh axis is manual: logical sharding
        # constraints are meaningless there (and illegal to apply), so the
        # block runs under empty rules — pure per-shard compute.
        with nn.logical_axis_rules(()):
            h = block.apply({"params": p}, h, seg, pos)
        return (h, seg, pos)

    if wants_outer_remat(cfg):
        layer_fn = jax.checkpoint(layer_fn, prevent_cse=False,
                                  policy=_checkpoint_policy(cfg))
    data_axes = tuple(a for a in ("data", "fsdp")
                      if mesh.shape.get(a, 1) > 1)
    out, _, _ = gpipe_layers(
        layer_fn, block_params, (x, segment_ids, positions), mesh=mesh,
        num_microbatches=cfg.pipeline_microbatches,
        batch_axes=data_axes,
    )
    return out


class LlamaModel(nn.Module):
    # ``decode=True``: autoregressive KV-cache mode (models.generate) —
    # same params, plus a mutable "cache" collection sized max_positions.
    config: LlamaConfig = LlamaConfig()
    decode: bool = False
    # Decode-mode KV cache size; 0 → config.max_positions.  generate()
    # passes the statically-known prompt_len + max_new_tokens so short
    # generations from a long-context config don't allocate (and attend
    # over) the full max_positions cache.
    cache_len: int = 0
    # Per-slot cache positions (continuous-batching serving,
    # serving.ServingEngine): the cache "index" is a [B] vector, one position
    # per slot.  Linear full-precision cache only — see
    # layers.MultiHeadAttention.slot_decode.
    slot_decode: bool = False
    # Paged serving KV cache: >0 turns the per-lane contiguous cache
    # into a fixed physical block pool + per-lane block table — see
    # layers.MultiHeadAttention.paged_kv_blocks.
    paged_kv_blocks: int = 0
    kv_block_size: int = 0
    query_block: int = 0    # see layers.MultiHeadAttention

    @nn.compact
    def __call__(self, tokens, *, segment_ids=None, positions=None):
        cfg = self.config
        if segment_ids is not None and self.decode:
            raise ValueError("decode mode does not take packed segments")
        if segment_ids is not None and positions is None:
            # Packed rows: RoPE positions restart at each segment
            # boundary (each document sees itself at positions 0..len-1,
            # exactly as if it were alone in the row).
            positions = segment_relative_positions(segment_ids)
        x = L.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                    name="token_embed")(tokens)
        if cfg.embed_scale:
            # Gemma input normalizer; the cast mirrors HF (the constant
            # is materialized in the activation dtype).
            x = x * jnp.asarray(
                cfg.d_model ** 0.5, x.dtype)
        pp_mesh = None if self.is_initializing() else _pipeline_mesh(cfg)
        if pp_mesh is not None and self.decode:
            raise ValueError(
                "decode mode does not run under a pipeline mesh; generate "
                "outside the pipeline strategy")
        if pp_mesh is not None:
            # Params were created by the scan path (init always takes it);
            # read the stacked block tree and drive the pipeline schedule.
            # Packed segment ids / positions ride the pipeline carry.
            block_params = (
                self.variables["params"]["layers"]["stack"]["block"])
            x = _pipelined_blocks(cfg, block_params, x, pp_mesh,
                                  segment_ids, positions)
        elif cfg.scan_layers:
            x = _ScannedBlock(cfg, decode=self.decode,
                              cache_len=self.cache_len,
                              slot_decode=self.slot_decode,
                              paged_kv_blocks=self.paged_kv_blocks,
                              kv_block_size=self.kv_block_size,
                              query_block=self.query_block,
                              name="layers")(
                x, segment_ids, positions)
        else:
            for i in range(cfg.num_layers):
                blk = DecoderBlock
                if wants_outer_remat(cfg) and not self.decode:
                    blk = nn.remat(blk, prevent_cse=False,
                                   policy=_checkpoint_policy(cfg))
                x = blk(cfg, decode=self.decode,
                        cache_len=self.cache_len,
                        slot_decode=self.slot_decode,
                        paged_kv_blocks=self.paged_kv_blocks,
                        kv_block_size=self.kv_block_size,
                        query_block=self.query_block,
                        name=f"layer_{i}")(
                    x, segment_ids, positions)
        x = L.RMSNorm(epsilon=cfg.rms_epsilon, dtype=cfg.dtype,
                      zero_centered=cfg.norm_zero_centered,
                      name="final_norm")(x)
        with jax.named_scope("head"):
            logits = L.dense(cfg.vocab_size, ("embed", "vocab"),
                             use_bias=False, dtype=cfg.dtype,
                             name="lm_head")(x)
        return nn.with_logical_constraint(
            logits, ("batch", "length", "vocab"))


class CausalLmTask:
    """Next-token objective over ``SyntheticLM`` batches (SFT-shaped)."""

    report_perplexity = True  # evaluate() adds exp(mean loss)

    def __init__(self, config: LlamaConfig = LlamaConfig()):
        self.config = config
        self.model = LlamaModel(config)

    def _scope(self):
        """LoRA interception context when the config asks for it."""
        from tensorflow_train_distributed_tpu.models.lora import (
            maybe_lora_scope,
        )

        return maybe_lora_scope(self.config.lora)

    def init_variables(self, rng, batch):
        with self._scope():
            variables = self.model.init(rng, batch["tokens"])
        if self.config.lora is not None:
            # Structural check at the right altitude: a target list that
            # matches no module (beyond what name validation can know)
            # would freeze everything and silently train nothing.
            from tensorflow_train_distributed_tpu.models.lora import (
                count_lora_params,
            )

            n_lora, _ = count_lora_params(variables["params"])
            if n_lora == 0:
                raise ValueError(
                    f"LoRA targets {self.config.lora.targets} matched no "
                    "module in this model — no adapters were created, so "
                    "a frozen-base run would train nothing")
        return variables

    def loss_fn(self, params, model_state, batch, rng, train):
        del rng, train  # no dropout in llama pretraining/SFT
        with self._scope():
            logits = self.model.apply(
                {"params": params}, batch["tokens"],
                segment_ids=batch.get("segment_ids")).astype(jnp.float32)
        weights = fold_sample_weight(batch, batch["targets"].shape,
                                     batch.get("loss_weights"))
        loss, acc = softmax_cross_entropy(logits, batch["targets"],
                                          weights=weights)
        metrics = {"accuracy": acc}
        if weights is not None:
            # Grad-accum recombination contract (Task docstring): weighted
            # losses report their total weight, unclamped per
            # fold_sample_weight's contract.
            metrics["loss_weight"] = weights.sum()
        return loss, (metrics, model_state)

    def predict_fn(self, params, model_state, batch):
        """Next-token logits (Trainer.predict contract)."""
        del model_state
        with self._scope():
            return self.model.apply({"params": params}, batch["tokens"],
                                    segment_ids=batch.get("segment_ids"))


def make_task(config: LlamaConfig = LLAMA_PRESETS["llama2_7b"]
              ) -> CausalLmTask:
    return CausalLmTask(config)
