"""Model/task registry — name → Task factory + dataset pairing.

The lookup table behind the CLI's ``--config`` flag (the reference
launcher's per-model dispatch, SURVEY.md §2.1).  Tiny variants exist for
every family so each model's full path runs on CPU test meshes.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

_REGISTRY: dict[str, dict[str, Any]] = {}


def register(name: str, *, task_factory: Callable, dataset: str,
             dataset_kwargs: dict | None = None, strategy: str = "dp",
             global_batch_size: int = 32, learning_rate: float = 1e-3,
             lr_schedule: str = "constant", warmup_ratio: float = 0.0,
             grad_clip_norm: float | None = None):
    _REGISTRY[name] = dict(
        task_factory=task_factory, dataset=dataset,
        dataset_kwargs=dataset_kwargs or {}, strategy=strategy,
        global_batch_size=global_batch_size, learning_rate=learning_rate,
        lr_schedule=lr_schedule, warmup_ratio=warmup_ratio,
        grad_clip_norm=grad_clip_norm,
    )


def get_task(name: str):
    return get_entry(name)["task_factory"]()


def get_entry(name: str) -> dict[str, Any]:
    if name not in _REGISTRY:
        raise ValueError(
            f"Unknown config {name!r}; available: {sorted(_REGISTRY)}")
    return dict(_REGISTRY[name])


def available() -> list[str]:
    return sorted(_REGISTRY)


def _setup():
    from tensorflow_train_distributed_tpu.models import (
        bert, lenet, llama, moe, resnet, transformer, vit,
    )

    # Reference config[0]: MNIST LeNet (MirroredStrategy smoke test).
    register("mnist", task_factory=lenet.make_task, dataset="mnist",
             strategy="dp", global_batch_size=128, learning_rate=1e-3)
    # Reference config[1]: ResNet-50 / ImageNet (MWMS + NCCL → dp over ICI).
    register("resnet50_imagenet",
             task_factory=lambda: resnet.make_task(
                 resnet.RESNET_PRESETS["resnet50"]),
             dataset="imagenet", strategy="dp", global_batch_size=1024,
             learning_rate=0.4, lr_schedule="resnet_steps",
             warmup_ratio=0.05)
    # MXU-optimized variant: 2x2 space-to-depth stem (host-side transform
    # in the dataset, stride-1 4x4 stem conv in the model).
    register("resnet50_imagenet_s2d",
             task_factory=lambda: resnet.make_task(
                 resnet.RESNET_PRESETS["resnet50_s2d"]),
             dataset="imagenet", dataset_kwargs=dict(space_to_depth=True),
             strategy="dp", global_batch_size=1024,
             learning_rate=0.4, lr_schedule="resnet_steps",
             warmup_ratio=0.05)
    # s2d + 2-strided BN statistics (the BN-HBM-traffic attack
    # variant): CLI-trainable so its convergence can be certified
    # against resnet50_imagenet_s2d before it claims the headline.
    register("resnet50_imagenet_s2d_bnsub",
             task_factory=lambda: resnet.make_task(
                 resnet.RESNET_PRESETS["resnet50_s2d_bnsub"]),
             dataset="imagenet", dataset_kwargs=dict(space_to_depth=True),
             strategy="dp", global_batch_size=1024,
             learning_rate=0.4, lr_schedule="resnet_steps",
             warmup_ratio=0.05)
    register("resnet_tiny",
             task_factory=lambda: resnet.make_task(
                 resnet.RESNET_PRESETS["resnet_tiny"],
                 label_smoothing=0.0, weight_decay=0.0),
             dataset="imagenet",
             dataset_kwargs=dict(num_classes=10, image_size=32),
             strategy="dp", global_batch_size=64, learning_rate=1e-3)
    # ViT (beyond the reference's vision list): same ImageNet pipeline
    # as ResNet, transformer encoder stack; AdamW-style training
    # (warmup+cosine, grad clip 1.0 — the AugReg recipe shape).
    register("vit_b16_imagenet",
             task_factory=lambda: vit.make_task(
                 vit.VIT_PRESETS["vit_b16"]),
             dataset="imagenet", strategy="dp", global_batch_size=1024,
             learning_rate=3e-3, lr_schedule="warmup_cosine",
             warmup_ratio=0.03, grad_clip_norm=1.0)
    register("vit_tiny",
             task_factory=lambda: vit.make_task(
                 vit.VIT_PRESETS["vit_tiny"],
                 label_smoothing=0.0),
             dataset="imagenet",
             dataset_kwargs=dict(num_classes=10, image_size=32),
             strategy="dp", global_batch_size=64, learning_rate=1e-3)
    # Reference config[2]: BERT-base MLM (PS strategy → SPMD dp_tp).
    register("bert_base_mlm",
             task_factory=lambda: bert.make_task(
                 bert.BERT_PRESETS["bert_base"]),
             dataset="mlm", strategy="dp", global_batch_size=256,
             learning_rate=1e-4, lr_schedule="warmup_linear",
             warmup_ratio=0.1,
             # BERT pretrain convention (Devlin et al. / NVIDIA refs):
             # global-norm clip 1.0.
             grad_clip_norm=1.0)
    register("bert_tiny_mlm",
             task_factory=lambda: bert.make_task(
                 bert.BERT_PRESETS["bert_tiny"]),
             dataset="mlm",
             dataset_kwargs=dict(vocab_size=256, seq_len=64),
             strategy="dp", global_batch_size=32, learning_rate=1e-3)
    # Reference config[3]: Transformer-big WMT (Horovod hook → dp).
    register("transformer_big_wmt",
             task_factory=lambda: transformer.make_task(
                 transformer.TRANSFORMER_PRESETS["transformer_big"]),
             dataset="wmt", strategy="dp", global_batch_size=512,
             learning_rate=2.0, lr_schedule="noam", warmup_ratio=0.0)
    register("transformer_tiny_wmt",
             task_factory=lambda: transformer.make_task(
                 transformer.TRANSFORMER_PRESETS["transformer_tiny"]),
             dataset="wmt",
             dataset_kwargs=dict(vocab_size=256, seq_len=32),
             strategy="dp", global_batch_size=32, learning_rate=1e-3)
    # Reference config[4]: Llama-2-7B SFT (DTensor 2-D mesh).  fsdp_tp,
    # not dp_tp: pure dp×tp replicates the ~79 GiB params+adam state over
    # the data axis (~19 GiB/device at tensor=4 — over v5e HBM), while
    # fsdp shards it (AOT-validated in
    # tests/test_models.py::TestLlama7bMemoryBudget).
    register("llama2_7b_sft",
             task_factory=lambda: llama.make_task(
                 llama.LLAMA_PRESETS["llama2_7b"]),
             dataset="lm", strategy="fsdp_tp", global_batch_size=64,
             learning_rate=2e-5, lr_schedule="warmup_cosine",
             warmup_ratio=0.03,
             # Llama-2 training convention: global-norm clip 1.0.
             grad_clip_norm=1.0)
    # Llama-3.1-8B SFT (GQA + llama3 rope scaling; --init-from-hf).
    register("llama31_8b_sft",
             task_factory=lambda: llama.make_task(
                 llama.LLAMA_PRESETS["llama31_8b"]),
             dataset="lm", strategy="fsdp_tp", global_batch_size=64,
             learning_rate=2e-5, lr_schedule="warmup_cosine",
             warmup_ratio=0.03, grad_clip_norm=1.0)
    # Gemma-1 SFT entries (decoupled head_dim, embed scaling, GeGLU,
    # zero-centered norms — import_hf maps checkpoints exactly).
    register("gemma_2b_sft",
             task_factory=lambda: llama.make_task(
                 llama.LLAMA_PRESETS["gemma_2b"]),
             dataset="lm", strategy="dp", global_batch_size=64,
             learning_rate=2e-5, lr_schedule="warmup_cosine",
             warmup_ratio=0.03, grad_clip_norm=1.0)
    register("gemma_7b_sft",
             task_factory=lambda: llama.make_task(
                 llama.LLAMA_PRESETS["gemma_7b"]),
             dataset="lm", strategy="fsdp_tp", global_batch_size=64,
             learning_rate=2e-5, lr_schedule="warmup_cosine",
             warmup_ratio=0.03, grad_clip_norm=1.0)
    # Qwen2.5-7B SFT (qkv-bias dense family; import_hf maps the
    # checkpoints exactly — model_type "qwen2").
    register("qwen25_7b_sft",
             task_factory=lambda: llama.make_task(
                 llama.LLAMA_PRESETS["qwen25_7b"]),
             dataset="lm", strategy="fsdp_tp", global_batch_size=64,
             learning_rate=2e-5, lr_schedule="warmup_cosine",
             warmup_ratio=0.03, grad_clip_norm=1.0)
    # The single-chip benchmark flagship (bench_lm / __graft_entry__):
    # GPT-2-small-class decoder, trainable through the CLI on one chip.
    register("llama_125m_lm",
             task_factory=lambda: llama.make_task(
                 llama.LLAMA_PRESETS["llama_125m"]),
             dataset="lm",
             dataset_kwargs=dict(vocab_size=32_000, seq_len=2048),
             strategy="dp", global_batch_size=8,
             learning_rate=3e-4, lr_schedule="warmup_cosine",
             warmup_ratio=0.01, grad_clip_norm=1.0)
    # Mid-size decoder (GPT-medium-class): the single-chip MFU point
    # above 125m; no_ffn remat is what makes b4×2048 fit 16 GiB.
    register("llama_350m_lm",
             task_factory=lambda: llama.make_task(dataclasses.replace(
                 llama.LLAMA_PRESETS["llama_350m"],
                 remat=True, remat_policy="no_ffn")),
             dataset="lm",
             dataset_kwargs=dict(vocab_size=32_000, seq_len=2048),
             strategy="dp", global_batch_size=4,
             learning_rate=3e-4, lr_schedule="warmup_cosine",
             warmup_ratio=0.01, grad_clip_norm=1.0)
    # Mistral-family flagship: GQA + sliding-window attention (O(S·w)
    # chunked path) over 32k positions; same weight layout as llama so
    # --init-from-hf imports real Mistral checkpoints.
    register("mistral_7b_lm",
             task_factory=lambda: llama.make_task(
                 llama.LLAMA_PRESETS["mistral_7b"]),
             dataset="lm",
             dataset_kwargs=dict(vocab_size=32_000, seq_len=8192),
             strategy="fsdp_tp", global_batch_size=8,
             learning_rate=3e-4, lr_schedule="warmup_cosine",
             warmup_ratio=0.01, grad_clip_norm=1.0)
    # CPU-trainable windowed-family canary (CI-sized mistral shape).
    register("mistral_tiny_lm",
             task_factory=lambda: llama.make_task(
                 dataclasses.replace(
                     llama.LLAMA_PRESETS["llama_tiny"],
                     sliding_window=16, attention_sinks=4)),
             dataset="lm",
             dataset_kwargs=dict(vocab_size=256, seq_len=64),
             strategy="dp", global_batch_size=16, learning_rate=1e-3)
    # Beyond the reference (it has no MoE): expert-parallel decoder LM.
    register("mixtral_8x7b",
             task_factory=lambda: moe.make_task(
                 moe.MOE_PRESETS["mixtral_8x7b"]),
             dataset="lm", strategy="dp_ep", global_batch_size=64,
             learning_rate=1e-4)
    register("moe_tiny_lm",
             task_factory=lambda: moe.make_task(
                 moe.MOE_PRESETS["moe_tiny"]),
             dataset="lm",
             dataset_kwargs=dict(vocab_size=256, seq_len=32),
             strategy="dp_ep", global_batch_size=16, learning_rate=1e-3)
    # Qwen1.5-MoE-A2.7B flagship (gated shared expert + 60-expert
    # fine-grained routing): --init-from-hf a local checkpoint.
    register("qwen15_moe_a27b",
             task_factory=lambda: moe.make_task(
                 moe.MOE_PRESETS["qwen15_moe_a27b"]),
             dataset="lm", strategy="dp_ep", global_batch_size=64,
             learning_rate=1e-4)
    # Tiny full-Qwen-convention shape (the CLI import test fixture).
    register("qwen_moe_tiny_lm",
             task_factory=lambda: moe.make_task(
                 moe.MOE_PRESETS["qwen_moe_tiny"]),
             dataset="lm",
             dataset_kwargs=dict(vocab_size=256, seq_len=32),
             strategy="dp_ep", global_batch_size=16, learning_rate=1e-3)
    # DeepSeek/Qwen-MoE-style shared expert beside the routed ones
    # (MoeConfig.shared_expert_size) — trains/serves through every MoE
    # path; the shared branch is an ordinary dense FFN.
    register("moe_tiny_shared_lm",
             task_factory=lambda: moe.make_task(
                 moe.MOE_PRESETS["moe_tiny_shared"]),
             dataset="lm",
             dataset_kwargs=dict(vocab_size=256, seq_len=32),
             strategy="dp_ep", global_batch_size=16, learning_rate=1e-3)
    # Dropless (megablox grouped-matmul) dispatch variant: same params/
    # data/seed as moe_tiny_lm, only the expert data movement differs —
    # the convergence-certification pair for MoeConfig.dispatch="gmm"
    # (profiles/convergence/).  dp strategy: gmm is the single-shard
    # formulation; expert-sharded meshes keep the dense dispatch.
    register("moe_tiny_lm_gmm",
             task_factory=lambda: moe.make_task(
                 dataclasses.replace(moe.MOE_PRESETS["moe_tiny"],
                                     dispatch="gmm")),
             dataset="lm",
             dataset_kwargs=dict(vocab_size=256, seq_len=32),
             strategy="dp", global_batch_size=16, learning_rate=1e-3)
    register("llama_tiny_sft",
             task_factory=lambda: llama.make_task(
                 llama.LLAMA_PRESETS["llama_tiny"]),
             dataset="lm",
             dataset_kwargs=dict(vocab_size=256, seq_len=32),
             strategy="dp_tp", global_batch_size=16, learning_rate=1e-3)
    # Pipeline parallelism end-to-end: --strategy=dp_pp drives the GPipe
    # schedule (parallel.pipeline) for the scanned decoder stack; the same
    # config under --strategy=dp runs the plain depth scan with identical
    # numerics.
    register("llama_tiny_pp",
             task_factory=lambda: llama.make_task(
                 llama.LLAMA_PRESETS["llama_tiny_pp"]),
             dataset="lm",
             dataset_kwargs=dict(vocab_size=256, seq_len=32),
             strategy="dp_pp", global_batch_size=16, learning_rate=1e-3)


_setup()
