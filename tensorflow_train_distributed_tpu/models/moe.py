"""Mixture-of-Experts decoder with expert parallelism.

NEW capability relative to the reference — it has no MoE anywhere
(SURVEY.md §2.4: EP absent; its nearest artifact is TPU embedding-table
sharding, ``tpu_embedding_v3.py:498``).  Included so the framework covers
the full dp/fsdp/tp/sp/ep/pp axis set.

TPU-native design — the GShard/Switch dense-dispatch formulation rather
than scatter/gather: tokens are routed per group g (one group per
sequence, riding the batch sharding), and moved with two einsums,

    expert_in[e,g,c,d] = Σ_s dispatch[g,s,e,c] · x[g,s,d]
    y[g,s,d]           = Σ_{e,c} combine[g,s,e,c] · out[e,g,c,d]

with per-group capacity c ≈ S·top_k·cf/E — cost linear in total tokens —
so the whole layer is static-shaped MXU work.  Expert weights carry the
``expert`` logical axis; under an ``expert``-sharded mesh GSPMD turns
those einsums into the all-to-all dispatch/return pattern automatically —
no hand-written collectives, and the same model runs unsharded on one
chip.  Capacity (``capacity_factor``) bounds per-expert token count, the
standard trick that keeps shapes static under jit (over-capacity tokens
fall through the residual connection).

Aux objectives follow Switch/GShard: load-balance loss (makes routing
uniform so EP shards stay busy) and router z-loss (keeps logits small for
bf16 stability); both are sown into an ``aux_loss`` collection that
``MoeLmTask`` folds into the training loss.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import ClassVar, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensorflow_train_distributed_tpu.runtime import compat
from tensorflow_train_distributed_tpu.models import layers as L
from tensorflow_train_distributed_tpu.ops import pallas_kernels as pk
from tensorflow_train_distributed_tpu.ops.losses import (
    fold_sample_weight, softmax_cross_entropy,
)


@dataclasses.dataclass(frozen=True)
class AttnKind:
    """One kind of attention layer in a model whose layers differ
    (``MoeConfig.attn_period``): its query heads (the KV heads and the
    head size are the model's), its sliding window (None: every row),
    its rotary rule (``layers.apply_rope``): base, the share of a
    head that is rotated, the scaling tuple.  What KIND of attention
    it is, is the class's own ``kind``: ``"softmax"`` here (MHA/GQA
    over cached keys and values), ``"latent"`` (``LatentKind``) or
    ``"linear"`` (``LinearKind``); a class attribute and no field, so
    that the five fields stay what readers of ``dataclasses.astuple``
    compare (``benchmark/harness/serve_pattern.py``); what a softmax
    layer has beyond the five is ``KvKind``'s."""

    num_heads: int
    window: Optional[int] = None
    rope_base: float = 10_000.0
    rotary_share: float = 1.0
    rope_scaling: Optional[tuple] = None
    kind: ClassVar[str] = "softmax"


@dataclasses.dataclass(frozen=True)
class KvKind(AttnKind):
    """A softmax layer whose KV heads are its own (None: the model's
    ``num_kv_heads``) and whose softmax may carry a learned ``sink``: one
    logit a query head in the denominator, with no row and no value
    behind it (``layers.MultiHeadAttention.sink``).  A subclass, so that
    ``AttnKind`` keeps its five fields."""

    num_kv_heads: Optional[int] = None
    sink: bool = False


@dataclasses.dataclass(frozen=True)
class LatentKind(AttnKind):
    """A latent-attention layer of a period (``layers.LatentAttention``
    at the model's latent sizes: ``kv_lora_rank`` and the rest).
    ``window`` and ``rope_base`` mean what they mean on a softmax kind
    (a window layer's latent rows live in a ring); ``rotary_share``
    says nothing: a head's rotary part is ``qk_rope_dim`` wide."""

    kind: ClassVar[str] = "latent"


@dataclasses.dataclass(frozen=True)
class OwnLatentKind(LatentKind):
    """A latent layer whose latent sizes are its own (None: the
    model's), beside latent layers of another kind in one model, and
    which says whether it chooses its rows (``index_topk``; 0: it
    attends every row it sees, and has no indexer and no index keys).
    A subclass, so that ``LatentKind`` keeps ``AttnKind``'s five
    fields; ``MoeConfig.latent_sizes`` resolves a layer's."""

    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_nope_dim: Optional[int] = None
    qk_rope_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    index_topk: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class LatentSizes:
    """What ONE latent layer runs at (``MoeConfig.latent_sizes``): its
    kind's own sizes where it has them, else the model's."""

    num_heads: int
    q_lora_rank: Optional[int]
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    window: Optional[int]
    rope_base: float
    rope_scaling: Optional[tuple]
    index_heads: int
    index_dim: int
    index_topk: int


@dataclasses.dataclass(frozen=True)
class LinearKind(AttnKind):
    """A linear-attention layer of a period (``layers.DeltaAttention``):
    a recurrent state a head and no positions; of the fields only
    ``num_heads`` says anything."""

    kind: ClassVar[str] = "linear"


@dataclasses.dataclass(frozen=True)
class MoeConfig:
    vocab_size: int = 32_000
    d_model: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: Optional[int] = 8
    ffn_size: int = 14_336
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    moe_every: int = 1          # 1 = every layer MoE (Mixtral); 2 = alternate
    max_positions: int = 4096
    rope_base: float = 10_000.0
    rms_epsilon: float = 1e-5
    aux_loss_weight: float = 0.01
    z_loss_weight: float = 1e-3
    dtype: object = jnp.bfloat16
    remat: bool = True
    # Expert-compute formulation.  "dense": GShard dispatch/combine
    # einsums — capacity-bounded, static-shaped, and the EP-sharded path
    # (GSPMD turns the einsums into all-to-alls under an ``expert``
    # mesh axis).  "gmm": MegaBlocks-style DROPLESS dispatch — tokens
    # are sorted by expert and the three FFN matmuls run as megablox
    # grouped matmuls (``jax.experimental.pallas.ops.tpu.megablox``),
    # skipping the dispatch-einsum FLOPs and the capacity padding
    # entirely (capacity_factor is ignored; nothing is ever dropped).
    # Same parameter tree either way, so checkpoints transfer between
    # formulations.  Under an ``expert``-sharded mesh the gmm path runs
    # the shard_map expert-parallel formulation (local sort +
    # group_offset gmm + one psum); unsharded it is the single-chip
    # throughput path.
    dispatch: str = "dense"
    # DeepSeek/Qwen-MoE-style shared expert: a dense SwiGLU FFN of this
    # hidden size runs on EVERY token beside the routed experts, outputs
    # summed.  Routing pressure drops (common knowledge lives in the
    # shared path; routed experts specialize) at a fixed dense-FLOP
    # cost.  Orthogonal to dispatch ("dense"/"gmm"), decode, serving and
    # EP sharding — the branch is an ordinary tensor-shardable MLP.
    # None = plain Mixtral-style (no shared expert).
    shared_expert_size: Optional[int] = None
    # Qwen-MoE-style scalar gate on the shared branch:
    # sigmoid(x @ w_gate) per token multiplies the shared output
    # (needs shared_expert_size).
    shared_expert_gate: bool = False
    # Renormalize the top-k gates over the chosen experts (GShard /
    # Mixtral rule).  False = raw softmax probabilities as gates —
    # the Qwen2-MoE default (norm_topk_prob=False).
    norm_topk_prob: bool = True
    # q/k/v projection biases (Qwen attention convention; out stays
    # unbiased) — layers.MultiHeadAttention.qkv_bias.
    qkv_bias: bool = False
    # Leading dense layers (DeepSeek / GLM ``first_k_dense_replace``):
    # the first ``dense_layers`` blocks carry a plain SwiGLU of width
    # ``dense_ffn_size`` (None = ``ffn_size``) and ``moe_every`` counts
    # from the first block after them.
    dense_layers: int = 0
    dense_ffn_size: Optional[int] = None
    # How the router scores and picks.  "softmax": probabilities over
    # the experts, top-k of them (Mixtral, Qwen-MoE).  "sigmoid"
    # (DeepSeek-V3 / GLM ``topk_method: noaux_tc`` with one group): an
    # independent sigmoid score an expert, the top-k of score + a
    # learned correction ``bias`` (a parameter: it steers the CHOICE
    # toward idle experts and never enters the gate), gates the chosen
    # scores themselves, renormalized under ``norm_topk_prob``.  Either
    # way the gates are multiplied by ``routed_scaling``.  The sigmoid
    # router runs under dispatch="gmm" only.
    router: str = "softmax"
    routed_scaling: float = 1.0
    # Multi-head latent attention (layers.LatentAttention) instead of
    # MHA/GQA when ``kv_lora_rank`` is set: q/kv low-rank sizes, the
    # per-head split of a query/key into a position-free and a rotary
    # part, and the value head size.  ``num_kv_heads``/``qkv_bias`` are
    # then unused.
    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    # The value head's size: the latent layers', and an MHA/GQA layer's
    # where it is not its key head's (0: ``head_dim`` there).
    v_head_dim: int = 0
    # An MHA/GQA layer's projected values are multiplied by this
    # (``attention_value_scale``).
    value_scale: float = 1.0
    # Rotary scaling of the attention (``layers.apply_rope``'s tagged
    # tuple, e.g. ("yarn", factor, beta_fast, beta_slow, original_max)).
    rope_scaling: Optional[tuple] = None
    # Latent attention's learned selection (DeepSeek-V3.2): an indexer
    # of ``index_heads`` heads of ``index_dim`` scores every cached row
    # and a query attends over the ``index_topk`` best
    # (layers.LatentAttention).  0 = attention over every row.
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    # Group-limited choice of the sigmoid router (``noaux_tc`` with
    # ``n_group`` > 1): the experts lie in ``n_group`` equal groups, a
    # group scores the sum of its two best (score + bias), the
    # ``topk_group`` best groups stay and the top-k is taken among
    # their experts.  One group is the plain top-k.
    n_group: int = 1
    topk_group: int = 1
    # The experts that live here, of ``num_experts``: a chip's share of
    # an expert-parallel deployment.  The router keeps its width and
    # its ``top_k``; the layer computes what experts [offset, offset +
    # held) add for the tokens routed to them and leaves the rest out
    # (their chips would add it: nothing here stands in for them).  The
    # shared expert and the residual are whole.  None = all of them.
    experts_held: Optional[int] = None
    experts_offset: int = 0
    # A head's size where it is not ``d_model // num_heads``.
    head_dim: Optional[int] = None
    # Attention kinds that differ by layer: a period of ``AttnKind``s
    # counted from the first layer after ``attn_lead`` (layer i is
    # ``attn_period[(i - len(attn_lead)) % len]``), each with its own
    # query heads, window and rotary rule; ``num_heads``, ``rope_base``
    # and ``rope_scaling`` above then say nothing.  The layers of such
    # a model share no parameter shape (they are unrolled here anyway).
    # None: every layer alike, from the fields above.  With
    # ``kv_lora_rank`` set the kinds are "latent" and "linear" alone:
    # the latent sizes above are those of every latent layer whose kind
    # has none of its own (``OwnLatentKind``; ``latent_sizes`` is where
    # a layer's are resolved).
    attn_period: Optional[tuple] = None
    # The kinds of the layers BEFORE the period starts, one a layer (a
    # pattern that is no period from layer 0: full at 0, 5, 11, ... is
    # a lead of one full layer and a period of six).
    attn_lead: tuple = ()
    # Per-head output gate of the attention (``out_gate`` of the
    # layer's module), every layer.
    attn_gate: bool = False
    # A "linear" layer's short convolution and the floor of its log
    # decay (``layers.DeltaAttention``).
    linear_conv: int = 4
    linear_decay_floor: float = -5.0
    # Latent attention's two normalised latents are multiplied by
    # ``sqrt(d_model / rank)`` (``apply_mla_qkv_lora_rescale``): a
    # latent narrower than the hidden size is brought back to the
    # hidden size's scale (``layers.LatentAttention.lora_rescale``).
    lora_rescale: bool = False

    def attn_kind(self, layer: int) -> Optional[AttnKind]:
        """Layer ``layer``'s kind, or None where layers do not differ."""
        if not self.attn_period:
            return None
        lead = len(self.attn_lead)
        if layer < lead:
            return self.attn_lead[layer]
        return self.attn_period[(layer - lead) % len(self.attn_period)]

    def latent_sizes(self, layer: int) -> LatentSizes:
        """What latent layer ``layer`` runs at: THE place where a
        kind's own sizes (``OwnLatentKind``) stand before the model's;
        the block, the engine and the benchmark's harness read it."""
        kind = self.attn_kind(layer)

        def own(name):
            had = getattr(kind, name, None)
            return getattr(self, name) if had is None else had

        return LatentSizes(
            num_heads=kind.num_heads if kind else self.num_heads,
            q_lora_rank=own("q_lora_rank"),
            kv_lora_rank=own("kv_lora_rank"),
            qk_nope_dim=own("qk_nope_dim"), qk_rope_dim=own("qk_rope_dim"),
            v_head_dim=own("v_head_dim"),
            window=kind.window if kind else None,
            rope_base=kind.rope_base if kind else self.rope_base,
            rope_scaling=kind.rope_scaling if kind else self.rope_scaling,
            index_heads=self.index_heads, index_dim=self.index_dim,
            index_topk=own("index_topk"))

    @property
    def attn_kinds(self) -> tuple:
        """Every kind a layer of the model may be (lead and period)."""
        return self.attn_lead + (self.attn_period or ())

    @property
    def recurrent_layers(self) -> int:
        """How many of the model's layers keep a recurrent state
        instead of rows (kind "linear")."""
        return sum(self.attn_kind(i).kind == "linear"
                   for i in range(self.num_layers)) if self.attn_period \
            else 0

    @property
    def attn_window(self) -> Optional[int]:
        """The sliding window of the model's window layers (None: it has
        none).  One size a model: the serving engine sizes one ring."""
        sizes = {k.window for k in self.attn_kinds
                 if k.window is not None}
        if len(sizes) > 1:
            raise ValueError(f"window layers of several sizes {sizes}")
        return sizes.pop() if sizes else None


_LAGUNA_KINDS = (
    # [full, sliding, sliding, sliding]: a full layer rotates half of
    # each head under YaRN (cos and sin x attention_factor), a sliding
    # layer the whole head, unscaled, and sees the last 512 rows.
    AttnKind(num_heads=48, rope_base=500_000.0, rotary_share=0.5,
             rope_scaling=("yarn", 128.0, 32.0, 1.0, 8192,
                           1.4852030263919618)),
) + (AttnKind(num_heads=72, window=512),) * 3


#: Five linear layers to one latent one, counted from layer 0 (layer i
#: is latent where (i + 1) % 6 == 0).
_LING_KINDS = (LinearKind(num_heads=32),) * 5 + (
    LatentKind(num_heads=32, rope_base=6_000_000.0),)


#: dots3-note-prev's two kinds of LATENT layer: a full one at the
#: model's latent sizes, over the rows its indexer picks, and a window
#: one (513 keys, the token's own among them) with ranks, heads and a
#: rotary base of its own and no indexer.
_DOTS3_FULL = LatentKind(num_heads=128, rope_base=80_000_000.0)
_DOTS3_WINDOW = OwnLatentKind(
    num_heads=64, window=513, rope_base=50_000.0, q_lora_rank=1024,
    kv_lora_rank=1024, qk_nope_dim=192, qk_rope_dim=64, v_head_dim=128,
    index_topk=0)
#: The same two at test size: a window of 9, and a window row of two
#: lane tiles (136 + 8 values) beside a full row of one (32 + 8).
_DOTS3_TINY_FULL = LatentKind(num_heads=4, rope_base=80_000_000.0)
_DOTS3_TINY_WINDOW = OwnLatentKind(
    num_heads=2, window=9, rope_base=50_000.0, q_lora_rank=20,
    kv_lora_rank=136, qk_nope_dim=20, qk_rope_dim=8, v_head_dim=16,
    index_topk=0)


#: MiMo-V2.5's two kinds of layer: the first int(0.334 x 192) = 64
#: values of a head are rotated in both.
_MIMO_FULL = KvKind(num_heads=64, rope_base=10_000_000.0,
                    rotary_share=0.334)
_MIMO_WINDOW = KvKind(num_heads=64, window=128, rope_base=10_000.0,
                      rotary_share=0.334, num_kv_heads=8, sink=True)
#: The same two at test size: 4 query heads, a window of 8.
_MIMO_TINY_FULL = dataclasses.replace(_MIMO_FULL, num_heads=4)
_MIMO_TINY_WINDOW = dataclasses.replace(_MIMO_WINDOW, num_heads=4,
                                        window=8, num_kv_heads=2)


MOE_PRESETS = {
    # Mixtral-8x7B-shaped flagship EP config.
    "mixtral_8x7b": MoeConfig(),
    "moe_1b": MoeConfig(d_model=1024, num_layers=8, num_heads=16,
                        num_kv_heads=4, ffn_size=4096, num_experts=8),
    # Single-16GiB-chip bench point (~370M total / ~135M active params):
    # the EP family's silicon number (tools/bench_moe.py).
    "moe_370m": MoeConfig(d_model=768, num_layers=8, num_heads=12,
                          num_kv_heads=4, ffn_size=2048, num_experts=8,
                          top_k=2, max_positions=2048),
    "moe_tiny": MoeConfig(vocab_size=256, d_model=64, num_layers=2,
                          num_heads=4, num_kv_heads=2, ffn_size=128,
                          num_experts=4, top_k=2, max_positions=128,
                          dtype=jnp.float32, remat=False),
    # Qwen1.5-MoE-A2.7B shape (14.3B total / 2.7B active): the gated-
    # shared-expert flagship — fine-grained 60-expert top-4 routing,
    # raw softmax gates, qkv biases; --init-from-hf a local checkpoint.
    "qwen15_moe_a27b": MoeConfig(
        vocab_size=151_936, d_model=2048, num_layers=24, num_heads=16,
        num_kv_heads=16, ffn_size=1408, num_experts=60, top_k=4,
        capacity_factor=15.0,  # E/k — the no-drop HF-parity setting
        max_positions=8192, rope_base=1_000_000.0,
        rms_epsilon=1e-6,
        shared_expert_size=5632, shared_expert_gate=True,
        norm_topk_prob=False, qkv_bias=True),
    # GLM-4.7-Flash (zai-org, ``glm4_moe_lite``) at its published
    # widths: latent attention, one leading dense layer of its own
    # width, 64 sigmoid-routed experts (top 4, gates x 1.8) beside one
    # shared expert, dropless dispatch.  47 layers as published;
    # deployments cut the depth to their chip (benchmark/configs).
    "glm47_flash": MoeConfig(
        vocab_size=154_880, d_model=2048, num_layers=47, num_heads=20,
        num_kv_heads=None, ffn_size=1536, num_experts=64, top_k=4,
        max_positions=202_752, rope_base=1_000_000.0, rms_epsilon=1e-5,
        dispatch="gmm", shared_expert_size=1536, norm_topk_prob=True,
        dense_layers=1, dense_ffn_size=10_240, router="sigmoid",
        routed_scaling=1.8, q_lora_rank=768, kv_lora_rank=512,
        qk_nope_dim=192, qk_rope_dim=64, v_head_dim=256),
    # The same block at test size (float32, two expert layers).
    "glm_lite_tiny": MoeConfig(
        vocab_size=256, d_model=64, num_layers=3, num_heads=4,
        num_kv_heads=None, ffn_size=48, num_experts=8, top_k=2,
        max_positions=128, dtype=jnp.float32, remat=False,
        dispatch="gmm", shared_expert_size=48, dense_layers=1,
        dense_ffn_size=160, router="sigmoid", routed_scaling=1.8,
        q_lora_rank=24, kv_lora_rank=32, qk_nope_dim=12, qk_rope_dim=8,
        v_head_dim=16),
    # DeepSeek-V3.2-Exp (``deepseek_v32``) at its published widths:
    # latent attention with the learned selection of 2048 rows a query,
    # YaRN, three leading dense layers, 256 sigmoid-routed experts in 8
    # groups (4 stay, top 8, gates x 2.5) beside one shared expert.  No
    # chip holds it whole: deployments give ``experts_held`` and cut the
    # depth and the vocabulary to their share (benchmark/configs).
    "deepseek_v32": MoeConfig(
        vocab_size=129_280, d_model=7168, num_layers=61, num_heads=128,
        num_kv_heads=None, ffn_size=2048, num_experts=256, top_k=8,
        max_positions=163_840, rope_base=10_000.0, rms_epsilon=1e-6,
        dispatch="gmm", shared_expert_size=2048, norm_topk_prob=True,
        dense_layers=3, dense_ffn_size=18_432, router="sigmoid",
        routed_scaling=2.5, n_group=8, topk_group=4, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
        rope_scaling=("yarn", 40.0, 32.0, 1.0, 4096),
        index_heads=64, index_dim=128, index_topk=2048),
    # The same block at test size (float32): a query chooses 16 rows,
    # 8 experts in 4 groups of which 2 stay, all of them held.
    "deepseek_v32_tiny": MoeConfig(
        vocab_size=256, d_model=64, num_layers=3, num_heads=4,
        num_kv_heads=None, ffn_size=48, num_experts=8, top_k=2,
        max_positions=128, dtype=jnp.float32, remat=False,
        dispatch="gmm", shared_expert_size=48, dense_layers=1,
        dense_ffn_size=160, router="sigmoid", routed_scaling=2.5,
        n_group=4, topk_group=2, q_lora_rank=24, kv_lora_rank=32,
        qk_nope_dim=12, qk_rope_dim=8, v_head_dim=16,
        rope_scaling=("yarn", 40.0, 32.0, 1.0, 16),
        index_heads=4, index_dim=16, index_topk=16, rms_epsilon=1e-6),
    # Laguna-S-2.1 (poolside, ``laguna``) at its published widths: full
    # and sliding-window (512) GQA layers 1 : 3 with their own query
    # heads (48 / 72 over 8 KV heads of 128) and rotary rules, a
    # per-head output gate, one leading SwiGLU layer, then 256
    # sigmoid-routed experts (top 10, gates x 2.5) beside one shared.
    # Deployments give ``experts_held`` and cut depth and vocabulary
    # (benchmark/configs).
    "laguna_s21": MoeConfig(
        vocab_size=100_352, d_model=3072, num_layers=48, num_heads=48,
        num_kv_heads=8, head_dim=128, ffn_size=1024, num_experts=256,
        top_k=10, max_positions=1_048_576, rms_epsilon=1e-6,
        dispatch="gmm", shared_expert_size=1024, norm_topk_prob=True,
        dense_layers=1, dense_ffn_size=12_288, router="sigmoid",
        routed_scaling=2.5, attn_period=_LAGUNA_KINDS, attn_gate=True),
    # The same block at test size (float32): 4 and 6 query heads over
    # 2 KV heads of 16, a window of 8.
    "laguna_tiny": MoeConfig(
        vocab_size=256, d_model=64, num_layers=5, num_heads=4,
        num_kv_heads=2, head_dim=16, ffn_size=48, num_experts=8, top_k=2,
        max_positions=128, dtype=jnp.float32, remat=False,
        dispatch="gmm", shared_expert_size=48, dense_layers=1,
        dense_ffn_size=160, router="sigmoid", routed_scaling=2.5,
        rms_epsilon=1e-6, attn_gate=True,
        attn_period=(
            AttnKind(num_heads=4, rope_base=500_000.0, rotary_share=0.5,
                     rope_scaling=("yarn", 8.0, 32.0, 1.0, 16,
                                   1.2079441541679836)),
        ) + (AttnKind(num_heads=6, window=8),) * 3),
    # Ling-3.0-flash (inclusionAI, ``bailing_hybrid``) at its published
    # widths: delta-rule linear-attention layers (32 heads of 128, a
    # decay per key channel, a convolution of 4) 5 : 1 with latent
    # attention that has no query latent, per-head output gates on
    # both, two leading SwiGLU layers, then 512 sigmoid-routed experts
    # in 8 groups (4 stay, top 8, gates x 2.5) beside one shared.
    # Deployments give ``experts_held`` and cut depth and vocabulary
    # (benchmark/configs).
    "ling3_flash": MoeConfig(
        vocab_size=157_184, d_model=2560, num_layers=42, num_heads=32,
        num_kv_heads=None, head_dim=128, ffn_size=768, num_experts=512,
        top_k=8, max_positions=262_144, rope_base=6_000_000.0,
        rms_epsilon=1e-6, dispatch="gmm", shared_expert_size=768,
        norm_topk_prob=True, dense_layers=2, dense_ffn_size=6144,
        router="sigmoid", routed_scaling=2.5, n_group=8, topk_group=4,
        q_lora_rank=None, kv_lora_rank=512, qk_nope_dim=128,
        qk_rope_dim=64, v_head_dim=128, attn_period=_LING_KINDS,
        attn_gate=True),
    # The same block at test size (float32): 4 heads of 16, one dense
    # layer, then linear x 4, latent, linear; 8 experts in 4 groups.
    "ling_tiny": MoeConfig(
        vocab_size=256, d_model=64, num_layers=7, num_heads=4,
        num_kv_heads=None, head_dim=16, ffn_size=48, num_experts=8,
        top_k=2, max_positions=128, dtype=jnp.float32, remat=False,
        dispatch="gmm", shared_expert_size=48, dense_layers=1,
        dense_ffn_size=160, router="sigmoid", routed_scaling=2.5,
        n_group=4, topk_group=2, rms_epsilon=1e-6, q_lora_rank=None,
        kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
        attn_gate=True,
        attn_period=(LinearKind(num_heads=4),) * 5 + (
            LatentKind(num_heads=4, rope_base=6_000_000.0),)),
    # MiMo-V2.5's language model (XiaomiMiMo, ``mimo_v2``; the model of
    # MiMo-V2-Flash) at its published widths: full and sliding-window
    # (128) GQA layers with keys of 192 beside values of 128 (values x
    # 0.707), 4 KV heads in a full layer and 8 in a window layer, a
    # learned sink logit a head in a window layer's softmax, the first
    # 64 values of a head rotated; full at layers 0, 5, 11, ..., 47 (a
    # lead of one and a period of six); one leading SwiGLU layer, then
    # 256 sigmoid-routed experts (top 8, gates unscaled) and no shared
    # one.  Deployments give ``experts_held`` and cut depth and
    # vocabulary (benchmark/configs).  Served by the engine's tiled
    # paths; a forward outside it (training, a whole prompt) takes the
    # dense S x S oracle for the sink and the value width and warns
    # from 2,048 rows on (``ops.attention.DENSE_WARN_ROWS``; ROADMAP R2).
    "mimo_v25": MoeConfig(
        vocab_size=152_576, d_model=4096, num_layers=48, num_heads=64,
        num_kv_heads=4, head_dim=192, v_head_dim=128, value_scale=0.707,
        ffn_size=2048, num_experts=256, top_k=8,
        max_positions=1_048_576, rope_base=10_000_000.0,
        rms_epsilon=1e-5, dispatch="gmm", norm_topk_prob=True,
        dense_layers=1, dense_ffn_size=16_384, router="sigmoid",
        routed_scaling=1.0, attn_lead=(_MIMO_FULL,),
        attn_period=(_MIMO_WINDOW,) * 4 + (_MIMO_FULL, _MIMO_WINDOW)),
    # The same block at test size (float32): keys of 24 beside values
    # of 16, 1 KV head in a full layer and 2 in a window layer under 4
    # query heads, a window of 8, the first 8 values of a head rotated.
    "mimo_v25_tiny": MoeConfig(
        vocab_size=256, d_model=64, num_layers=7, num_heads=4,
        num_kv_heads=1, head_dim=24, v_head_dim=16, value_scale=0.707,
        ffn_size=48, num_experts=8, top_k=2, max_positions=128,
        rope_base=10_000_000.0, dtype=jnp.float32, remat=False,
        dispatch="gmm", dense_layers=1, dense_ffn_size=160,
        router="sigmoid", rms_epsilon=1e-5,
        attn_lead=(_MIMO_TINY_FULL,),
        attn_period=(_MIMO_TINY_WINDOW,) * 4 + (_MIMO_TINY_FULL,
                                                _MIMO_TINY_WINDOW)),
    # dots3-note-prev's language model (dots-studio, ``dots3_note``) at
    # its published widths: latent attention of TWO kinds, full layers
    # (128 heads, ranks 1024 / 512, keys of 128 + 64) over the 2048
    # rows a learned indexer picks and window layers (513; 64 heads,
    # ranks 1024 / 1024, keys of 192 + 64) with no indexer, full at
    # layers 0, 1, 5, 9, ..., 45 (a lead of one and a period of four);
    # both latents rescaled, a per-head output gate on both kinds; one
    # leading SwiGLU layer, then 256 sigmoid-routed experts (top 8,
    # gates unscaled) beside one shared.  Deployments give
    # ``experts_held`` and cut depth and vocabulary (benchmark/configs).
    "dots3_note": MoeConfig(
        vocab_size=152_064, d_model=5120, num_layers=46, num_heads=128,
        num_kv_heads=None, ffn_size=1536, num_experts=256, top_k=8,
        max_positions=524_288, rope_base=80_000_000.0, rms_epsilon=1e-5,
        dispatch="gmm", shared_expert_size=1536, norm_topk_prob=True,
        dense_layers=1, dense_ffn_size=13_824, router="sigmoid",
        routed_scaling=1.0, q_lora_rank=1024, kv_lora_rank=512,
        qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
        index_heads=64, index_dim=128, index_topk=2048,
        attn_lead=(_DOTS3_FULL,),
        attn_period=(_DOTS3_FULL,) + (_DOTS3_WINDOW,) * 3,
        attn_gate=True, lora_rescale=True),
    # The same block at test size (float32): a full layer chooses 16
    # rows, a window layer sees 9; one dense layer, then full, window
    # x 3.
    "dots3_note_tiny": MoeConfig(
        vocab_size=256, d_model=64, num_layers=5, num_heads=4,
        num_kv_heads=None, ffn_size=48, num_experts=8, top_k=2,
        max_positions=128, rope_base=80_000_000.0, dtype=jnp.float32,
        remat=False, dispatch="gmm", shared_expert_size=48,
        dense_layers=1, dense_ffn_size=160, router="sigmoid",
        rms_epsilon=1e-5, q_lora_rank=24, kv_lora_rank=32,
        qk_nope_dim=12, qk_rope_dim=8, v_head_dim=16, index_heads=4,
        index_dim=16, index_topk=16, attn_lead=(_DOTS3_TINY_FULL,),
        attn_period=(_DOTS3_TINY_FULL,) + (_DOTS3_TINY_WINDOW,) * 3,
        attn_gate=True, lora_rescale=True),
    # DeepSeek/Qwen-MoE-style: always-on shared expert beside the
    # routed ones (tiny test shape).
    "moe_tiny_shared": MoeConfig(vocab_size=256, d_model=64,
                                 num_layers=2, num_heads=4,
                                 num_kv_heads=2, ffn_size=128,
                                 num_experts=4, top_k=2,
                                 max_positions=128, dtype=jnp.float32,
                                 remat=False, shared_expert_size=96),
    # Full Qwen-convention tiny shape (gated shared expert, qkv biases,
    # raw top-k gates) — matches the test HF fixture for the CLI
    # --init-from-hf path.
    "qwen_moe_tiny": MoeConfig(vocab_size=256, d_model=64,
                               num_layers=2, num_heads=4,
                               num_kv_heads=2, ffn_size=96,
                               num_experts=4, top_k=2,
                               capacity_factor=2.0,
                               max_positions=128, dtype=jnp.float32,
                               remat=False, shared_expert_size=112,
                               shared_expert_gate=True,
                               norm_topk_prob=False, qkv_bias=True),
}


def _router_one_hot(probs: jax.Array, top_k: int, capacity: int,
                    normalize: bool = True):
    """Top-k dispatch/combine tensors with per-expert capacity.

    ``probs`` [T, E] float32.  Returns ``dispatch`` [T, E, C] one-hot and
    ``combine`` [T, E, C] gate-weighted, plus the [T, E] routed mask for
    the load-balance loss.  Tokens beyond an expert's capacity are dropped
    (their combine weight is zero → they ride the residual path).
    ``normalize=False`` keeps raw softmax probabilities as gates (the
    Qwen2-MoE ``norm_topk_prob=False`` convention) instead of the GShard
    renormalize-over-chosen rule.
    """
    tokens, num_experts = probs.shape
    remaining = probs
    fill = jnp.zeros((num_experts,), jnp.int32)  # tokens already assigned
    dispatch = jnp.zeros((tokens, num_experts, capacity), probs.dtype)
    combine = jnp.zeros((tokens, num_experts, capacity), probs.dtype)
    routed = jnp.zeros((tokens, num_experts), probs.dtype)
    gate_sum = jnp.zeros((tokens, 1), probs.dtype)
    for _ in range(top_k):  # static, small
        idx = jnp.argmax(remaining, axis=-1)                      # [T]
        onehot = jax.nn.one_hot(idx, num_experts, dtype=probs.dtype)
        gate = jnp.sum(remaining * onehot, axis=-1, keepdims=True)  # [T,1]
        # Position of each token within its expert's buffer this round,
        # offset by what previous rounds already filled.
        pos = jnp.cumsum(onehot, axis=0) - onehot + fill[None, :]   # [T,E]
        pos_tok = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)  # [T]
        keep = (pos_tok < capacity).astype(probs.dtype)             # [T]
        slot = jax.nn.one_hot(pos_tok, capacity, dtype=probs.dtype)
        hot = onehot[:, :, None] * slot[:, None, :] * keep[:, None, None]
        dispatch = dispatch + hot
        combine = combine + hot * gate[:, :, None]
        routed = routed + onehot * keep[:, None]
        gate_sum = gate_sum + gate * keep[:, None]
        fill = fill + jnp.sum(onehot * keep[:, None], axis=0).astype(
            jnp.int32)
        remaining = remaining * (1.0 - onehot)
    if normalize:
        # Over the chosen experts (GShard top-2 rule).
        combine = combine / jnp.maximum(gate_sum[:, :, None], 1e-9)
    return dispatch, combine, routed


class _ExpertFfn(nn.Module):
    """One expert's SwiGLU FFN over its [groups, capacity, d_model] buffer.

    Separate from ``layers.MlpBlock`` because expert buffers carry
    (group, capacity, embed) dims — the shared block's (batch, length, ·)
    activation constraints don't apply.  ``nn.vmap`` stacks this over the expert axis,
    tagging params with the ``expert`` logical name.
    """

    hidden: int
    dtype: object

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        gate = L.dense(self.hidden, ("embed", "mlp"), use_bias=False,
                       dtype=self.dtype, name="wi_gate")(x)
        up = L.dense(self.hidden, ("embed", "mlp"), use_bias=False,
                     dtype=self.dtype, name="wi_up")(x)
        h = nn.silu(gate) * up
        return L.dense(d, ("mlp", "embed"), use_bias=False,
                       dtype=self.dtype, name="wo")(h)


# Pallas interpret mode for the grouped matmul is a TEST seam, not a
# fallback: the megablox kernel lowers only for TPU, so the CPU suite
# (tests/conftest.py), the CPU dry run (__graft_entry__) and bench_moe's
# ``--platform cpu`` smoke switch it on, each explicitly.  Nothing else
# does — on any other backend dispatch="gmm" gets the compiler's error,
# never a silently interpreted kernel.
GMM_INTERPRET = False


#: Values of one ``[tk, tn]`` slice of an expert's kernel in a serving
#: call: 2 MB in bf16, 4 MB double buffered beside the rows' block and
#: the float32 result's under the default 16 MiB of scoped VMEM.
_GMM_SLICE = 2 ** 20


def _dividing_tile(size: int, most: int) -> int:
    """The largest multiple of 128 that divides ``size`` (itself one)
    and is at most ``most``; 128 where none is."""
    units = size // 128
    return 128 * max(d for d in range(1, units + 1)
                     if units % d == 0 and (d == 1 or 128 * d <= most))


def _gmm_tiling(rows: int, groups: int, k: int, n: int) -> tuple:
    """Tiles (rows, k, n) of a grouped matmul of ``rows`` rows over
    ``groups`` groups.  A grid step is one [128, tk] x [tk, tn] product
    of one expert's rows.  With a few row tiles or less an expert
    (serving: lanes x top_k rows of a decode step, or those of a
    prefill call of one to four pieces, over all experts) a step is
    bound by its slice of the expert's kernel coming in, so the slices
    are as large as VMEM carries double buffered (``_GMM_SLICE``) and
    they DIVIDE the kernel:

    - ``tk`` is ``k`` whole wherever 128 columns of it fit a slice (to
      8192), else ``k``'s largest divisor that does.  megablox indexes
      the rows' block by (row tile, k tile): with one k tile it stays
      put from one expert to the next and is fetched once a row tile,
      with more it rides every slice (a [128, 2048] block beside 2 MB
      of kernel is a quarter more bytes).  And a ``tk`` that does not
      divide ``k`` makes the last k tile a masked one: megablox then
      converts that whole slice and the rows' block to float32,
      selects against an iota and converts back, on the vector unit,
      at every visit.  A ``k`` that is no multiple of 128 (tests' tiny
      models) is one rounded-up tile, masked as it must be.
    - ``tn`` is the largest divisor of ``n`` (rounded up to a multiple
      of 128) whose slice is within ``_GMM_SLICE``: no half-empty last
      tile, and a narrow expert's down product gets wide slices.

    At 2048 x 1536 that is 2048 x 512, three steps a group where 128 x
    128 tiles make 192.  Measured up to 160 rows an expert (PERF.md, PR
    26, PR 38 and PR 40: at 40,960 rows over 256 experts megablox's
    own tiles took four times as long as large ones; at 2560 x 768
    tiles of 2048 x 512, a masked remainder in ``k`` and a half-empty
    tile in ``n``, took 0.78 ms for a decode step's call where
    2560 x 384 take 0.48, and two exact tiles of 3584 took 7168 x 2048
    half as long again as ``k`` whole); the rule holds to four row
    tiles an expert.  With more (training) the tiles stay megablox's
    own, which nothing here has measured, and the backward's
    transposed products take the same tuple.  ``rhs`` may hold a share
    of the groups (``group_offset``): the rows are then all groups'
    and a held group still gets its share."""
    if rows > 4 * 128 * groups:
        return (128, 128, 128)
    tk = (-(-k // 128) * 128 if k % 128
          else _dividing_tile(k, _GMM_SLICE // 128))
    return (128, tk, _dividing_tile(-(-n // 128) * 128, _GMM_SLICE // tk))


def _gmm(lhs, rhs, group_sizes, interpret, group_offset=None):
    """Megablox grouped matmul: rows of ``lhs`` hit the ``rhs`` slice of
    their group (``group_sizes`` [E] row counts, summing to lhs rows).

    ``ops.gmm`` is the differentiable (custom-VJP) wrapper — the
    backward pass runs as grouped matmuls too.  ``interpret`` runs the
    kernel in pallas interpret mode for CPU tests.  ``group_offset``
    (expert parallelism): ``rhs`` holds only groups
    [offset, offset + rhs.shape[0]) and rows outside them come back
    ZERO — verified: per-shard outputs sum exactly to the full gmm, and
    grads flow only through the shard's own rows.
    """
    from jax.experimental.pallas.ops.tpu.megablox import ops as _mb

    _, k, n = rhs.shape
    tiling = _gmm_tiling(lhs.shape[0], group_sizes.shape[0], k, n)
    return _mb.gmm(lhs, rhs, group_sizes,
                   preferred_element_type=jnp.float32, interpret=interpret,
                   tiling=tiling,
                   group_offset=None if group_offset is None
                   else jnp.asarray(group_offset, jnp.int32))


def _within_best_groups(choice, n_group: int, topk_group: int):
    """``choice`` [T, E] (score + bias) with every expert outside its
    token's ``topk_group`` best groups at ``-inf``: the experts lie in
    ``n_group`` equal runs, and a group scores the sum of its two
    largest entries (DeepSeek-V3's ``noaux_tc``)."""
    t, e = choice.shape
    if e % n_group or e // n_group < 2 or not 1 <= topk_group <= n_group:
        raise ValueError(
            f"{e} experts do not lie in {n_group} groups of two or more "
            f"of which {topk_group} stay")
    grouped = choice.reshape(t, n_group, e // n_group)
    best_two = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)    # [T, G]
    _, stay = jax.lax.top_k(best_two, topk_group)
    stays = jnp.any(jax.nn.one_hot(stay, n_group, dtype=jnp.bool_), axis=1)
    return jnp.where(stays[:, :, None], grouped, -jnp.inf).reshape(t, e)


@partial(jax.jit, static_argnames=("num_experts", "dtype", "interpret",
                                    "psum_axis"))
def _routed_ffn_rows(  # ttd-lint: disable=compilecheck -- no dispatch site: traced inside the programs of declared sites (the engine's, the trainer's)
        flat, top_e, gate_w, num_experts, wi_gate, wi_up, wo, *, dtype,
        interpret, group_offset=None, psum_axis=None):
    """The dropless routed FFN over a block of tokens.  Jitted on its
    own, so the expert layers of one program, alike in every shape,
    are traced and lowered once and not once a layer (set-up seconds
    of every serving program: PERF.md, PR 38).

    ``flat`` [T, D] tokens; ``top_e``/``gate_w`` [T, k] the router's
    expert choices and normalized gates (computed ONCE by the caller —
    under EP they ride into the shard_map rather than being recomputed
    per expert shard).  The (token, choice) pairs go to expert order
    and come back in one pass each: one stable sort carries a pair's
    number and its gate along with its expert, the tokens' rows are
    gathered by indices promised in bounds (no fill pass), the SwiGLU
    runs as grouped matmuls, and ``ops.pallas_kernels.moe_combine``
    adds each float32 row the third matmul returns, times its gate, to
    its token's sum as the rows lie — no row is moved back to token
    order, and of a held share (``group_offset`` without a psum) only
    the rows of the experts held are read, the others being zero by
    ``gmm``'s mask.  With ``group_offset``/``psum_axis`` set this is
    the per-shard body of the expert-parallel formulation: each expert
    shard computes ONLY its experts' rows (zeros elsewhere) and the
    psum over the expert axis assembles the full row set — every row
    is computed by exactly one shard, so the sum is exact, not
    averaged.
    """
    t, d = flat.shape
    top_k = top_e.shape[-1]
    e_total = num_experts
    m = t * top_k
    with jax.named_scope("moe/sort"):
        e_flat = top_e.reshape(-1)                      # [T*k] token-major
        # One stable sort carries each pair's number and its gate to
        # expert order; a row's token is its pair's.
        _, order, gates = jax.lax.sort(
            (e_flat, jnp.arange(m, dtype=jnp.int32),
             gate_w.reshape(-1).astype(jnp.float32)), num_keys=1)
        tok = order // top_k
        xs = flat.at[tok].get(mode="promise_in_bounds").astype(dtype)
        experts = jnp.arange(e_total, dtype=e_flat.dtype)
        sizes = jnp.sum(e_flat[None, :] == experts[:, None], axis=1,
                        dtype=jnp.int32)       # no scatter of T*k scalars
        m_pad = -(-m // 128) * 128                      # kernel row tile
        if m_pad != m:
            # Zero rows appended to the LAST expert's range: zero inputs
            # produce zero outputs (silu(0)*0 = 0), and no pair's row
            # lies among them — never observable, under EP included
            # (the last shard computes them as zeros; psum adds zeros).
            xs = jnp.pad(xs, ((0, m_pad - m), (0, 0)))
            sizes = sizes.at[e_total - 1].add(m_pad - m)
    with jax.named_scope("moe/experts"):
        gate = _gmm(xs, wi_gate, sizes, interpret, group_offset)
        up = _gmm(xs, wi_up, sizes, interpret, group_offset)
        h = (nn.silu(gate) * up).astype(dtype)
        out = _gmm(h, wo, sizes, interpret, group_offset)  # [m_pad, D] f32
    if psum_axis is not None:
        out = jax.lax.psum(out, psum_axis)
    with jax.named_scope("moe/combine"):
        # Expert order keeps the rows of the experts held together, and
        # every other row is zero (``gmm``'s mask) until a psum fills it.
        span = (0, m)
        if group_offset is not None and psum_axis is None:
            held = jax.lax.dynamic_slice_in_dim(
                sizes, group_offset, wi_gate.shape[0])
            lo = (jnp.cumsum(sizes) - sizes)[group_offset]
            span = (lo, lo + jnp.sum(held))
        return pk.moe_combine(out, tok, gates, jnp.stack(span), t, dtype,
                              interpret)


class _GmmExperts(nn.Module):
    """Dropless expert FFN: grouped matmuls over expert-sorted rows.

    ``flat`` [T, d_model] tokens, ``p2`` [T, E] router probs; same
    SwiGLU math as ``_ExpertFfn``, with the three matmuls as
    ``megablox.gmm`` so each expert's rows hit its own kernel slice
    without ``[E, capacity]`` buffers or dispatch one-hots.

    With ``ep_mesh`` (an ambient mesh whose ``expert`` axis > 1) the
    compute runs as a ``shard_map``: tokens stay sharded over the data
    axes (each data shard sorts ITS tokens locally), expert kernels
    shard over ``expert``, each expert shard computes only its experts'
    rows via ``group_offset``, and one psum over ``expert`` assembles
    the rows — dropless expert parallelism with exactly one collective
    pair (tokens broadcast over the expert axis on the way in, psum on
    the way out).
    """

    num_experts: int
    hidden: int
    dtype: object
    # A share of the experts (``MoeConfig.experts_held``): the kernels
    # are [held, ...] and the rows of every other expert come back
    # zero, which is what one shard's body below computes by
    # ``group_offset`` under an ``expert`` mesh: the same call.
    held: Optional[int] = None
    offset: int = 0

    @nn.compact
    def __call__(self, flat, top_e, gate_w, *, interpret, ep_mesh=None):
        d = flat.shape[-1]
        e, f = self.num_experts, self.hidden
        n_here = e if self.held is None else self.held
        if not 0 <= self.offset <= e - n_here:
            raise ValueError(
                f"experts [{self.offset}, {self.offset + n_here}) are not "
                f"among the router's {e}")
        # The dense path's ``nn.vmap(_ExpertFfn)`` tree
        # (``experts/<name>/kernel``, expert-stacked, per-expert init
        # statistics), so checkpoints transfer between formulations.
        def stacked(shape, axes, name):
            return L.KernelParam(shape, axes, batch_axis=(0,),
                                 name=name)().astype(self.dtype)

        wi_gate = stacked((n_here, d, f), ("expert", "embed", "mlp"),
                          "wi_gate")
        wi_up = stacked((n_here, d, f), ("expert", "embed", "mlp"),
                        "wi_up")
        wo = stacked((n_here, f, d), ("expert", "mlp", "embed"), "wo")
        if ep_mesh is None:
            return _routed_ffn_rows(
                flat, top_e, gate_w, e, wi_gate, wi_up, wo,
                dtype=self.dtype, interpret=interpret,
                group_offset=None if self.held is None else self.offset)
        if self.held is not None:
            raise ValueError(
                "experts_held is one chip's share; an expert mesh divides "
                "all num_experts over its own shards")

        from tensorflow_train_distributed_tpu.runtime.compat import (
            shard_map,
        )
        from jax.sharding import PartitionSpec as P

        from tensorflow_train_distributed_tpu.runtime.mesh import (
            batch_axes,
        )

        local_e = e // ep_mesh.shape["expert"]
        bspec = batch_axes(ep_mesh)
        dtype_, interp_ = self.dtype, interpret

        def body(flat_b, te_b, gw_b, wg_b, wu_b, wo_b):
            e0 = jax.lax.axis_index("expert") * local_e
            return _routed_ffn_rows(
                flat_b, te_b, gw_b, e, wg_b, wu_b, wo_b,
                dtype=dtype_, interpret=interp_, group_offset=e0,
                psum_axis="expert")

        return shard_map(
            body, mesh=ep_mesh,
            in_specs=(P(bspec, None), P(bspec, None), P(bspec, None),
                      P("expert", None, None), P("expert", None, None),
                      P("expert", None, None)),
            out_specs=P(bspec, None), check_vma=False,
        )(flat, top_e, gate_w, wi_gate, wi_up, wo)


class MoEMlpBlock(nn.Module):
    """Routed expert FFN, a drop-in for ``layers.MlpBlock``."""

    config: MoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        # GShard grouping: each sequence is a routing group, so dispatch
        # tensors are [G, S, E, C] with per-group capacity C ≈ S·k·cf/E —
        # cost linear in total tokens (an ungrouped [T, E, C] formulation
        # would be O(T²) and serialize the position cumsum across data
        # shards).  Groups ride the batch sharding; routing is per-group
        # independent, so no cross-shard bookkeeping exists at all.
        x = nn.with_logical_constraint(x, ("batch", "length", "embed"))
        groups, group_size, d_model = x.shape

        # Router in float32: small matmul, numerically load-bearing.
        with jax.named_scope("moe/router"):
            logits = L.dense(cfg.num_experts, ("embed", "expert"),
                             use_bias=False, dtype=jnp.float32,
                             name="router")(x.astype(jnp.float32))
            if cfg.router == "sigmoid":
                probs = jax.nn.sigmoid(logits)           # [G, S, E] scores
            elif cfg.router == "softmax":
                probs = jax.nn.softmax(logits, axis=-1)  # [G, S, E]
            else:
                raise ValueError(
                    f"unknown MoeConfig.router {cfg.router!r} "
                    "(expected 'softmax' or 'sigmoid')")
        if cfg.dispatch == "gmm":
            return self._add_shared(x, self._gmm_moe(x, logits, probs))
        if cfg.router != "softmax" or cfg.routed_scaling != 1.0:
            raise ValueError(
                "router='sigmoid' (a correction bias in the choice) and "
                "routed_scaling run under dispatch='gmm' only; the "
                "capacity-bounded dense dispatch routes by softmax "
                "probabilities alone")
        if cfg.dispatch != "dense":
            raise ValueError(
                f"unknown MoeConfig.dispatch {cfg.dispatch!r} "
                "(expected 'dense' or 'gmm')")
        capacity = max(
            1, int(cfg.capacity_factor * cfg.top_k * group_size
                   / cfg.num_experts))
        dispatch, combine, routed = jax.vmap(
            lambda p: _router_one_hot(p, cfg.top_k, capacity,
                                      cfg.norm_topk_prob))(probs)

        # Aux losses (Switch §4 / ST-MoE): sown, folded in by the task.
        frac_routed = jnp.mean(routed, axis=(0, 1))      # [E] token fraction
        frac_prob = jnp.mean(probs, axis=(0, 1))         # [E] router mass
        lb = cfg.num_experts * jnp.sum(frac_routed * frac_prob) / cfg.top_k
        z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
        self.sow("aux_loss", "load_balance", cfg.aux_loss_weight * lb)
        self.sow("aux_loss", "router_z", cfg.z_loss_weight * z)

        # Routing health (sown separately — diagnostics, NOT loss terms):
        # a binding capacity_factor drops tokens silently (they ride the
        # residual), which also breaks packed==lone-document parity (see
        # MoeLmModel packing note).  dropped_frac = fraction of desired
        # top_k assignments that hit a full expert; expert_load = each
        # expert's share of kept tokens (uniform = 1/E).
        desired = jnp.asarray(groups * group_size * cfg.top_k, jnp.float32)
        self.sow("router_stats", "dropped_frac",
                 1.0 - jnp.sum(routed) / desired)
        self.sow("router_stats", "expert_load",
                 jnp.sum(routed, axis=(0, 1)) / jnp.maximum(
                     jnp.sum(routed), 1.0))

        dispatch = dispatch.astype(cfg.dtype)
        expert_in = jnp.einsum("gsec,gsd->egcd", dispatch, x)
        expert_in = nn.with_logical_constraint(
            expert_in, ("expert", "batch", None, "embed"))
        experts = nn.vmap(
            _ExpertFfn,
            in_axes=0, out_axes=0,
            # "quant": expert-stacked int8 serving scales (models.quant)
            # slice per-expert like the stacked kernels they mirror, so
            # the fused int8 Dense path is exact for MoE too.
            variable_axes={"params": 0, "quant": 0},
            split_rngs={"params": True},
            metadata_params={nn.PARTITION_NAME: "expert"},
        )(hidden=cfg.ffn_size, dtype=cfg.dtype, name="experts")
        expert_out = experts(expert_in)                  # [E, G, C, D]
        expert_out = nn.with_logical_constraint(
            expert_out, ("expert", "batch", None, "embed"))
        y = jnp.einsum("gsec,egcd->gsd", combine.astype(cfg.dtype),
                       expert_out)
        y = nn.with_logical_constraint(y, ("batch", "length", "embed"))
        return self._add_shared(x, y)

    def _add_shared(self, x, routed):
        """Shared-expert branch (``shared_expert_size``): an always-on
        SwiGLU over every token, summed with the routed output.  A
        plain ``layers.MlpBlock``, so it tensor-shards/quantizes/decodes
        like any dense FFN; identity when the config leaves it None."""
        cfg = self.config
        if not cfg.shared_expert_size:
            return routed
        with jax.named_scope("moe/shared"):
            shared = L.MlpBlock(hidden=cfg.shared_expert_size,
                                dtype=cfg.dtype, gated=True,
                                activation=nn.silu,  # SwiGLU, like every
                                name="shared_mlp")(x)   # gated FFN here
        if cfg.shared_expert_gate:
            # Qwen-MoE: one sigmoid scalar per token scales the shared
            # branch (f32 like the router — small and load-bearing).
            g = jax.nn.sigmoid(L.dense(
                1, ("embed", None), use_bias=False, dtype=jnp.float32,
                name="shared_gate")(x.astype(jnp.float32)))
            shared = shared * g.astype(shared.dtype)
        return nn.with_logical_constraint(
            routed + shared, ("batch", "length", "embed"))

    def _gmm_moe(self, x, logits, probs):
        """Dropless dispatch (MegaBlocks, arXiv:2211.15841): sort token
        copies by expert, run the FFN as grouped matmuls.

        No capacity, no drops — every top-k assignment is computed, so
        ``capacity_factor`` is ignored and packed==lone-document parity
        holds unconditionally (the dense path's binding-capacity caveat
        does not exist here).  Output matches the dense path exactly
        whenever the dense path drops nothing.
        """
        cfg = self.config
        groups, group_size, d_model = x.shape
        n_tokens = groups * group_size
        k = cfg.top_k
        flat = x.reshape(n_tokens, d_model)
        p2 = probs.reshape(n_tokens, cfg.num_experts)
        with jax.named_scope("moe/router"):
            if cfg.router == "sigmoid":
                # The correction bias takes part in the choice and not
                # in the gate; the gates are the chosen scores over
                # their sum (the source's own epsilon).
                bias = self.param(
                    "bias", nn.with_logical_partitioning(
                        nn.initializers.zeros, ("expert",)),
                    (cfg.num_experts,), jnp.float32)
                choice = p2 + bias.astype(jnp.float32)
                if cfg.n_group > 1:
                    with jax.named_scope("moe/route_groups"):
                        choice = _within_best_groups(
                            choice, cfg.n_group, cfg.topk_group)
                _, top_e = jax.lax.top_k(choice, k)
                top_p = jnp.take_along_axis(p2, top_e, axis=-1)
                eps = 1e-20
                # Load-balance mass below: the scores as a distribution.
                p2 = p2 / jnp.sum(p2, axis=-1, keepdims=True)
            else:
                top_p, top_e = jax.lax.top_k(p2, k)      # [T, k]
                eps = 1e-9
            # GShard top-k gate rule: normalize over the chosen experts.
            # (The dense path normalizes over *kept* gates — identical
            # here because nothing is ever dropped.)  Computed ONCE;
            # under EP it rides into the shard_map instead of re-running
            # per shard.
            if cfg.norm_topk_prob and cfg.router == "sigmoid":
                gate_w = top_p / (jnp.sum(top_p, axis=-1, keepdims=True)
                                  + eps)
            elif cfg.norm_topk_prob:
                gate_w = top_p / jnp.maximum(
                    jnp.sum(top_p, axis=-1, keepdims=True), eps)
            else:
                gate_w = top_p    # raw gates (Qwen2-MoE rule)
            if cfg.routed_scaling != 1.0:
                gate_w = gate_w * cfg.routed_scaling
        # Rows each expert takes in this call, every lane counted (an
        # idle serving lane's rows read expert weights like any
        # other): the serving engine's ``experts_hit`` (sown; kept only
        # where the caller makes ``moe_stats`` mutable).
        rows = jnp.bincount(top_e.reshape(-1),
                            length=cfg.num_experts).astype(jnp.int32)
        if cfg.experts_held is not None:
            # Counted over the experts held; beside them the share of
            # the call's (token, choice) pairs that fell here.
            rows = jax.lax.dynamic_slice_in_dim(
                rows, cfg.experts_offset, cfg.experts_held)
            self.sow("moe_stats", "routed_here",
                     jnp.sum(rows) / float(n_tokens * k))
        self.sow("moe_stats", "expert_rows", rows)

        # Aux losses — same definitions as the dense path, with
        # routed = all top-k assignments (dropless).
        routed = jnp.sum(jax.nn.one_hot(top_e, cfg.num_experts,
                                        dtype=jnp.float32), axis=1)
        lb = cfg.num_experts * jnp.sum(
            jnp.mean(routed, axis=0) * jnp.mean(p2, axis=0)) / k
        z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
        self.sow("aux_loss", "load_balance", cfg.aux_loss_weight * lb)
        self.sow("aux_loss", "router_z", cfg.z_loss_weight * z)
        self.sow("router_stats", "dropped_frac", jnp.zeros((), jnp.float32))
        self.sow("router_stats", "expert_load",
                 jnp.sum(routed, axis=0) / float(n_tokens * k))

        # Expert parallelism: an ambient mesh with an ``expert`` axis
        # routes the compute through the shard_map formulation (each
        # data shard sorts locally, each expert shard computes its own
        # experts via group_offset, one psum assembles).
        mesh = compat.get_abstract_mesh()
        ep_mesh = None
        if (mesh is not None and not mesh.empty
                and mesh.shape.get("expert", 1) > 1):
            if cfg.num_experts % mesh.shape["expert"]:
                raise ValueError(
                    f"num_experts={cfg.num_experts} not divisible by the "
                    f"expert mesh axis ({mesh.shape['expert']})")
            if mesh.shape.get("tensor", 1) > 1:
                # The shard_map body replicates expert kernels over the
                # tensor axis (its in_specs only mention expert/data) —
                # silently undoing TP would blow per-device memory and
                # duplicate FLOPs.  The dense dispatch keeps full
                # expert×tensor GSPMD sharding; refuse loudly here.
                raise ValueError(
                    "dispatch='gmm' supports data×fsdp×expert meshes; "
                    "an expert×tensor mesh keeps dispatch='dense' "
                    "(GSPMD shards both axes there)")
            ep_mesh = mesh
        y = _GmmExperts(num_experts=cfg.num_experts, hidden=cfg.ffn_size,
                        dtype=cfg.dtype, held=cfg.experts_held,
                        offset=cfg.experts_offset, name="experts")(
            flat, top_e, gate_w,
            interpret=GMM_INTERPRET, ep_mesh=ep_mesh)
        return nn.with_logical_constraint(
            y.reshape(groups, group_size, d_model),
            ("batch", "length", "embed"))


class MoeDecoderBlock(nn.Module):
    config: MoeConfig
    use_moe: bool = True
    # Which layer of the model this is: it picks the layer's attention
    # kind where they differ (``MoeConfig.attn_period``).
    layer: int = 0
    # Autoregressive decode (models.generate): KV-cached attention; the
    # MoE dispatch needs nothing special — at q_len 1 each group holds
    # one token, capacity is >= 1 per expert, so routing never drops.
    decode: bool = False
    cache_len: int = 0
    slot_decode: bool = False
    # Paged serving KV cache — see layers.MultiHeadAttention.
    paged_kv_blocks: int = 0
    kv_block_size: int = 0
    query_block: int = 0    # see layers.MultiHeadAttention
    # Blocks of a window layer's paged ring a lane (layers.
    # MultiHeadAttention.ring_blocks).
    ring_blocks: int = 0

    @nn.compact
    def __call__(self, x, segment_ids=None, positions=None):
        cfg = self.config
        h = L.RMSNorm(epsilon=cfg.rms_epsilon, dtype=cfg.dtype,
                      name="attn_norm")(x)
        kind = cfg.attn_kind(self.layer)
        if kind is not None and kind.kind == "linear":
            with jax.named_scope("attn/linear"):
                attn = L.DeltaAttention(
                    num_heads=kind.num_heads,
                    head_dim=cfg.head_dim or cfg.d_model // kind.num_heads,
                    conv_size=cfg.linear_conv,
                    decay_floor=cfg.linear_decay_floor, dtype=cfg.dtype,
                    rms_epsilon=cfg.rms_epsilon, out_gate=cfg.attn_gate,
                    name="attention", decode=self.decode,
                    paged_kv_blocks=self.paged_kv_blocks,
                )(h, segment_ids=segment_ids, positions=positions)
        elif cfg.kv_lora_rank:
            # Every layer alike from the model's fields, or the latent
            # layers of a period, whose scope names them for the trace:
            # ``attn/latent`` the kind without a window,
            # ``attn/latent_window`` the kind with one.
            own = cfg.latent_sizes(self.layer)
            with L._scope_when(
                    kind is not None,
                    "attn/latent" if own.window is None
                    else "attn/latent_window"):
                attn = L.LatentAttention(
                    **{f.name: getattr(own, f.name)
                       for f in dataclasses.fields(own)},
                    dtype=cfg.dtype, rms_epsilon=cfg.rms_epsilon,
                    out_gate=cfg.attn_gate,
                    lora_rescale=cfg.lora_rescale,
                    name="attention", decode=self.decode,
                    cache_len=self.cache_len or cfg.max_positions,
                    slot_decode=self.slot_decode,
                    paged_kv_blocks=self.paged_kv_blocks,
                    kv_block_size=self.kv_block_size,
                    query_block=self.query_block,
                    ring_blocks=(self.ring_blocks
                                 if own.window is not None else 0),
                )(h, segment_ids=segment_ids, positions=positions)
        else:
            attn = self._mha(h, segment_ids, positions)
        x = x + attn
        h = L.RMSNorm(epsilon=cfg.rms_epsilon, dtype=cfg.dtype,
                      name="mlp_norm")(x)
        if self.use_moe:
            x = x + MoEMlpBlock(cfg, name="moe")(h)
        else:
            x = x + L.MlpBlock(hidden=cfg.dense_ffn_size or cfg.ffn_size,
                               dtype=cfg.dtype,
                               activation=nn.silu, gated=True,
                               name="mlp")(h)
        return x

    def _mha(self, h, segment_ids, positions):
        cfg = self.config
        head_dim = cfg.head_dim or cfg.d_model // cfg.num_heads
        kind = cfg.attn_kind(self.layer)
        # The KV heads and the sink of a kind are ``KvKind``'s fields;
        # every other kind runs the model's and no sink.
        own = isinstance(kind, KvKind)
        common = dict(
            qkv_bias=cfg.qkv_bias, head_dim=head_dim,
            v_head_dim=cfg.v_head_dim or None,
            value_scale=cfg.value_scale,
            num_kv_heads=(own and kind.num_kv_heads) or cfg.num_kv_heads,
            sink=own and kind.sink,
            dtype=cfg.dtype, causal=True, use_rope=True,
            name="attention", decode=self.decode,
            cache_len=self.cache_len or cfg.max_positions,
            slot_decode=self.slot_decode,
            paged_kv_blocks=self.paged_kv_blocks,
            kv_block_size=self.kv_block_size,
            query_block=self.query_block)
        if kind is None:
            return L.MultiHeadAttention(
                num_heads=cfg.num_heads, rope_base=cfg.rope_base,
                out_gate=cfg.attn_gate, **common,
            )(h, segment_ids=segment_ids, positions=positions)
        # What the layer is comes from its kind's fields; the scope
        # names it for the device trace.
        with jax.named_scope("attn/full" if kind.window is None
                             else "attn/window"):
            return L.MultiHeadAttention(
                num_heads=kind.num_heads, window=kind.window,
                rope_base=kind.rope_base, rope_scaling=kind.rope_scaling,
                rotary_dim=(None if kind.rotary_share == 1.0
                            else int(head_dim * kind.rotary_share)),
                out_gate=cfg.attn_gate,
                ring_blocks=(self.ring_blocks
                             if kind.window is not None else 0),
                **common,
            )(h, segment_ids=segment_ids, positions=positions)


class MoeLmModel(nn.Module):
    """Decoder LM with MoE FFNs every ``moe_every``-th layer after
    ``dense_layers`` leading dense ones.

    Layers are a Python loop (not depth-scan): MoE layers interleave with
    dense ones, so blocks are not homogeneous when ``moe_every > 1``.
    """

    config: MoeConfig = MoeConfig()
    # models.generate contract (same as LlamaModel): decode=True adds
    # the mutable "cache" collection, sized by cache_len.  Decode routes
    # each step as a one-token group, so capacity NEVER binds there —
    # cached decode equals the training-time forward exactly only while
    # the training capacity doesn't bind either (the Mixtral-import E/k
    # default guarantees that; a binding capacity_factor makes the
    # full-sequence forward drop tokens decode would not, the same
    # caveat as packed segments above).
    decode: bool = False
    cache_len: int = 0
    # Per-slot cache positions (continuous-batching serving,
    # serving.ServingEngine) — see layers.MultiHeadAttention.slot_decode.
    slot_decode: bool = False
    # Paged serving KV cache — see layers.MultiHeadAttention.
    paged_kv_blocks: int = 0
    kv_block_size: int = 0
    query_block: int = 0    # see layers.MultiHeadAttention
    ring_blocks: int = 0    # see MoeDecoderBlock

    @nn.compact
    def __call__(self, tokens, *, segment_ids=None, positions=None):
        cfg = self.config
        if segment_ids is not None and self.decode:
            raise ValueError("decode mode does not take packed segments")
        if cfg.attn_lead and not cfg.attn_period:
            raise ValueError("attn_lead comes before a period: it needs "
                             "attn_period")
        kinds = {k.kind for k in cfg.attn_kinds}
        if kinds - {"softmax", "latent", "linear"}:
            raise ValueError(f"unknown kinds of attention layer {kinds}")
        if ("latent" in kinds) != bool(cfg.attn_period
                                       and cfg.kv_lora_rank) or (
                cfg.kv_lora_rank and "softmax" in kinds):
            raise ValueError(
                "a period's \"latent\" layers take the model's latent "
                "sizes (kv_lora_rank), and a model with latent sizes has "
                "no MHA/GQA layer: its kinds are \"latent\" and "
                f"\"linear\"; got {sorted(kinds)} with "
                f"kv_lora_rank={cfg.kv_lora_rank}")
        if segment_ids is not None and positions is None:
            # Packed rows (llama-path contract): segment-masked attention
            # + RoPE positions restarting at each document boundary.
            # Routing needs no masking — it is per-token, and within a
            # group earlier tokens' dispatch slots are unaffected by later
            # ones (the capacity cumsum is causal in token order).  The
            # packed == lone-document equivalence is exact only while no
            # capacity drops occur: under a binding capacity_factor,
            # earlier documents consume a shared per-row budget, so later
            # documents may see drops (residual fallthrough) they would
            # not see alone.
            from tensorflow_train_distributed_tpu.models.llama import (
                segment_relative_positions,
            )

            positions = segment_relative_positions(segment_ids)
        x = L.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                    name="token_embed")(tokens)
        for i in range(cfg.num_layers):
            blk = MoeDecoderBlock
            if cfg.remat and not self.decode:
                # No backward in decode, and KV-cache writes must not
                # replay under a checkpoint.
                blk = nn.remat(blk, prevent_cse=False)
            x = blk(cfg, use_moe=(
                        i >= cfg.dense_layers
                        and (i - cfg.dense_layers) % cfg.moe_every == 0),
                    decode=self.decode, cache_len=self.cache_len,
                    slot_decode=self.slot_decode,
                    paged_kv_blocks=self.paged_kv_blocks,
                    kv_block_size=self.kv_block_size,
                    query_block=self.query_block,
                    layer=i, ring_blocks=self.ring_blocks,
                    name=f"layer_{i}")(x, segment_ids, positions)
        x = L.RMSNorm(epsilon=cfg.rms_epsilon, dtype=cfg.dtype,
                      name="final_norm")(x)
        with jax.named_scope("head"):
            logits = L.dense(cfg.vocab_size, ("embed", "vocab"),
                             use_bias=False, dtype=cfg.dtype,
                             name="lm_head")(x)
        return nn.with_logical_constraint(
            logits, ("batch", "length", "vocab"))


def _sown_values(collection, name: str) -> list:
    """All leaves sown under ``name`` anywhere in a (nested) flax
    collection — one entry per MoE layer.  Path-based so dict and
    FrozenDict collections (flax_return_frozendict mode) both work."""
    return [leaf for path, leaf
            in jax.tree_util.tree_leaves_with_path(collection)
            if any(getattr(p, "key", None) == name for p in path)]


def _routing_metrics(stats: dict) -> dict:
    """Scalar routing-health metrics averaged over MoE layers.

    ``dropped_frac`` > 0 means the capacity_factor is binding — tokens
    are silently falling through the residual AND packed rows are no
    longer exactly equivalent to lone documents; ``expert_load_max/min``
    bound the per-expert share of kept tokens (uniform = 1/E), exposing
    hot/cold experts that an aggregate load-balance loss value hides.
    """
    dropped = _sown_values(stats, "dropped_frac")
    load = _sown_values(stats, "expert_load")
    if not dropped:
        return {}
    mean_load = jnp.mean(jnp.stack(load), axis=0)  # [E] over layers
    return {
        "dropped_frac": jnp.mean(jnp.stack(dropped)),
        "expert_load_max": jnp.max(mean_load),
        "expert_load_min": jnp.min(mean_load),
    }


class MoeLmTask:
    """Causal LM objective + routed aux losses."""

    def __init__(self, config: MoeConfig = MoeConfig()):
        self.config = config
        self.model = MoeLmModel(config)

    def init_variables(self, rng, batch):
        variables = dict(self.model.init(rng, batch["tokens"]))
        # Ephemeral sown collections, not trainable state.
        variables.pop("aux_loss", None)
        variables.pop("router_stats", None)
        return variables

    def loss_fn(self, params, model_state, batch, rng, train):
        del rng
        logits, collections = self.model.apply(
            {"params": params}, batch["tokens"],
            segment_ids=batch.get("segment_ids"),
            mutable=["aux_loss", "router_stats"])
        logits = logits.astype(jnp.float32)
        weights = fold_sample_weight(batch, batch["targets"].shape,
                                     batch.get("loss_weights"))
        ce, acc = softmax_cross_entropy(logits, batch["targets"],
                                        weights=weights)
        aux = sum(
            jnp.sum(jnp.asarray(v))
            for v in jax.tree.leaves(collections.get("aux_loss", {})))
        # Aux terms are training regularizers computed over every routed
        # token — including eval pad rows, which fold_sample_weight cannot
        # mask (they bypass the CE weights).  Excluding them from the eval
        # loss keeps the padded-eval exactness contract: eval 'loss' is
        # the pad-exact CE, aux stays visible as a diagnostic metric.
        loss = ce + aux if train else ce
        metrics = {"accuracy": acc, "ce_loss": ce,
                   "aux_loss": jnp.asarray(aux)}
        metrics.update(_routing_metrics(collections.get("router_stats", {})))
        if weights is not None:
            metrics["loss_weight"] = weights.sum()
        return loss, (metrics, model_state)


def make_task(config: MoeConfig = MOE_PRESETS["mixtral_8x7b"]) -> MoeLmTask:
    return MoeLmTask(config)
