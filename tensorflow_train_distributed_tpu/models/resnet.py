"""ResNet-50 (v1.5) for ImageNet — reference config[1].

The reference trains this under MultiWorkerMirroredStrategy with NCCL
allreduce and ``Model.fit`` (SURVEY.md §3.1) — the headline benchmark config
(BASELINE.md: ≥90% of MLPerf TPU-ref images/sec/chip).  TPU-first choices:

- NHWC layout + bfloat16 compute: XLA's conv tiling onto the MXU wants NHWC
  on TPU; params stay f32 (mixed-precision policy).
- v1.5 variant (stride 2 on the 3x3, not the 1x1) — the MLPerf reference
  architecture.
- BatchNorm over the global batch (sync-BN semantics fall out of global
  arrays; see ``vision_task``).
- conv kernels carry ("conv_in", "conv_out") logical axes so the tensor
  axis can shard output channels if a preset asks for it.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import flax.linen as nn
import jax.numpy as jnp

from tensorflow_train_distributed_tpu.models.vision_task import VisionTask


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    stage_sizes: Sequence[int] = (3, 4, 6, 3)  # ResNet-50
    num_filters: int = 64
    num_classes: int = 1000
    bn_momentum: float = 0.9
    bn_epsilon: float = 1e-5
    # BN statistics over a spatially strided subset (1 = exact).  The
    # measured v5e step-time ceiling is BatchNorm HBM traffic, not conv
    # FLOPs (July v5e trace: ~half the step in BN statistics/backward
    # reductions); stride 2 reads 1/4 of each activation for the mean/var
    # passes while normalizing the full tensor — at batch 256 the
    # estimate still pools >800k samples/channel in the first stage.
    # Running-stat/param names are unchanged, so checkpoints interchange
    # with the exact-BN variants.
    bn_stats_stride: int = 1
    # MLPerf TPU trick: 2x2 space-to-depth on the input ([N,224,224,3] →
    # [N,112,112,12]) turns the stride-2 7x7 stem conv into an equivalent
    # stride-1 4x4 conv with 12 input channels — 4x better MXU lane
    # utilization on the otherwise 3-channel-starved stem (~9% of step
    # time).  Mathematically identical model family: see
    # ``stem_kernel_to_s2d`` for the exact 7x7→4x4 kernel bijection.
    space_to_depth: bool = False


RESNET_PRESETS = {
    "resnet18": ResNetConfig(stage_sizes=(2, 2, 2, 2)),
    "resnet50": ResNetConfig(stage_sizes=(3, 4, 6, 3)),
    "resnet50_s2d": ResNetConfig(stage_sizes=(3, 4, 6, 3),
                                 space_to_depth=True),
    # s2d + subsampled BN statistics: the BN-traffic attack variant
    # (bench.py --configs can pit it against the exact-stats baselines).
    "resnet50_s2d_bnsub": ResNetConfig(stage_sizes=(3, 4, 6, 3),
                                       space_to_depth=True,
                                       bn_stats_stride=2),
    "resnet101": ResNetConfig(stage_sizes=(3, 4, 23, 3)),
    "resnet_tiny": ResNetConfig(stage_sizes=(1, 1), num_filters=8,
                                num_classes=10),
}


class SubsampledStatsBN(nn.Module):
    """BatchNorm whose TRAIN statistics come from a spatially strided
    subset of the activation (``x[:, ::s, ::s]``).

    The normalize-apply is algebraically refolded to one fused
    multiply-add (``x·w + b`` with w/b precomputed per channel in f32),
    and the mean/var reduction — the HBM-bound part of BN on TPU — reads
    only 1/s² of the tensor.  The batch dim is untouched, so dp/fsdp
    sharding and the global-batch sync-BN semantics (GSPMD reduces the
    sharded jnp.mean) are identical to ``nn.BatchNorm``.  Parameter and
    running-stat names match ``nn.BatchNorm`` ("scale"/"bias",
    "mean"/"var"), so checkpoints interchange between variants.

    ``stats_stride=1`` degenerates to exact one-pass (E[x²]−E[x]²) BN;
    the resnet builder still uses ``nn.BatchNorm`` there (flax's is the
    reference implementation this one is parity-tested against).
    """

    use_running_average: bool
    momentum: float
    epsilon: float
    dtype: object
    stats_stride: int = 2
    scale_init: object = nn.initializers.ones

    @nn.compact
    def __call__(self, x):
        import jax

        feat = x.shape[-1]
        scale = self.param("scale", self.scale_init, (feat,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (feat,),
                          jnp.float32)
        ra_mean = self.variable("batch_stats", "mean",
                                lambda s: jnp.zeros(s, jnp.float32),
                                (feat,))
        ra_var = self.variable("batch_stats", "var",
                               lambda s: jnp.ones(s, jnp.float32), (feat,))
        if self.use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            s = self.stats_stride
            sub = x[:, ::s, ::s, :] if (x.ndim == 4 and s > 1) else x
            sub = sub.astype(jnp.float32)
            axes = tuple(range(sub.ndim - 1))
            mean = jnp.mean(sub, axes)
            # One-pass variance; clamped — subsampling can't make it
            # negative, but f32 cancellation can.
            var = jnp.maximum(
                jnp.mean(jnp.square(sub), axes) - jnp.square(mean), 0.0)
            if not self.is_initializing():
                m = self.momentum
                ra_mean.value = m * ra_mean.value + (1 - m) * mean
                ra_var.value = m * ra_var.value + (1 - m) * var
        inv = jax.lax.rsqrt(var + self.epsilon)
        w = (scale * inv).astype(self.dtype)
        b = (bias - mean * scale * inv).astype(self.dtype)
        return x.astype(self.dtype) * w + b


def space_to_depth(x: jnp.ndarray, block: int = 2) -> jnp.ndarray:
    """[N,H,W,C] → [N,H/b,W/b,b·b·C], channel-minor order (du, dv, c).

    Host pipelines should apply this before transfer (it is a pure data
    rearrangement); the model also applies it on the fly when handed raw
    3-channel input so both entry points work.
    """
    n, h, w, c = x.shape
    if h % block or w % block:
        raise ValueError(f"spatial dims {h}x{w} not divisible by {block}")
    x = x.reshape(n, h // block, block, w // block, block, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(n, h // block, w // block, block * block * c)


def stem_kernel_to_s2d(w: jnp.ndarray, block: int = 2) -> jnp.ndarray:
    """Map a [7,7,C,F] stem kernel to the equivalent [4,4,b·b·C,F] kernel.

    With SAME padding (pad 3) and stride 2, output pixel i reads input rows
    2i-3..2i+3; on space-to-depth input those are transformed rows i-2..i+1
    — a 4-tap window.  Zero-padding the kernel to 8x8 (one leading zero
    row/col) aligns tap k to (m=du-block offset): k+1 = 2m+du, so the
    padded kernel reshapes exactly into the 4x4x(b·b·C) layout matching
    ``space_to_depth``'s channel order.
    """
    kh, kw, c, f = w.shape
    assert kh == 7 and kw == 7 and block == 2, "stem transform is 7x7/b=2"
    padded = jnp.pad(w, ((1, 0), (1, 0), (0, 0), (0, 0)))
    padded = padded.reshape(4, 2, 4, 2, c, f)        # (m, du, n, dv, c, f)
    padded = padded.transpose(0, 2, 1, 3, 4, 5)      # (m, n, du, dv, c, f)
    return padded.reshape(4, 4, block * block * c, f)


def _conv(features, kernel, strides=1, name=None, padding="SAME"):
    return nn.Conv(
        features, (kernel, kernel), strides=(strides, strides),
        padding=padding, use_bias=False,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.variance_scaling(2.0, "fan_out", "normal"),
            (None, None, "conv_in", "conv_out"),
        ),
        name=name,
    )


def _norm_factory(cfg: ResNetConfig, train: bool, dtype):
    """The config's BN: flax's exact BatchNorm, or the strided-stats
    variant (same variable names — checkpoints interchange).

    Unnamed uses take flax's auto names for ``nn.BatchNorm``
    ("BatchNorm_0", ...) whichever implementation is active, so the tree
    structure is byte-compatible across ``bn_stats_stride`` settings.
    """
    if cfg.bn_stats_stride <= 1:
        return partial(
            nn.BatchNorm, use_running_average=not train,
            momentum=cfg.bn_momentum, epsilon=cfg.bn_epsilon, dtype=dtype)
    import itertools

    counter = itertools.count()
    base = partial(
        SubsampledStatsBN, use_running_average=not train,
        momentum=cfg.bn_momentum, epsilon=cfg.bn_epsilon,
        dtype=dtype, stats_stride=cfg.bn_stats_stride)

    def make(name: str = None, **kw):
        return base(name=name or f"BatchNorm_{next(counter)}", **kw)

    return make


class BottleneckBlock(nn.Module):
    filters: int
    strides: int
    config: ResNetConfig

    @nn.compact
    def __call__(self, x, *, train: bool):
        norm = _norm_factory(self.config, train, x.dtype)
        residual = x
        y = _conv(self.filters, 1)(x)
        y = norm()(y)
        y = nn.relu(y)
        y = _conv(self.filters, 3, self.strides)(y)  # v1.5: stride on 3x3
        y = norm()(y)
        y = nn.relu(y)
        y = _conv(self.filters * 4, 1)(y)
        # Zero-init the last BN scale (standard ResNet trick: each block
        # starts as identity, required to match reference loss curves).
        y = norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = _conv(self.filters * 4, 1, self.strides,
                             name="proj_conv")(x)
            residual = norm(name="proj_bn")(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    config: ResNetConfig = ResNetConfig()

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        cfg = self.config
        norm = _norm_factory(cfg, train, x.dtype)
        if cfg.space_to_depth:
            if x.shape[-1] == 3:  # raw input: transform on the fly
                x = space_to_depth(x)
            # Equivalent stride-1 4x4 stem on s2d input; padding (2,1)
            # from the tap-window derivation in stem_kernel_to_s2d.
            x = _conv(cfg.num_filters, 4, 1, name="stem_conv",
                      padding=((2, 1), (2, 1)))(x)
        else:
            x = _conv(cfg.num_filters, 7, 2, name="stem_conv")(x)
        x = norm(name="stem_bn")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, n_blocks in enumerate(cfg.stage_sizes):
            for j in range(n_blocks):
                x = BottleneckBlock(
                    filters=cfg.num_filters * 2**i,
                    strides=2 if j == 0 and i > 0 else 1,
                    config=cfg,
                )(x, train=train)
        x = nn.with_logical_constraint(x, ("batch", None, None, "conv_out"))
        x = x.mean(axis=(1, 2))  # global average pool
        x = nn.Dense(
            cfg.num_classes,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.zeros, ("embed", "vocab")),
            dtype=jnp.float32,
        )(x)
        return x


def make_task(config: ResNetConfig = RESNET_PRESETS["resnet50"],
              *, label_smoothing: float = 0.1,
              weight_decay: float = 1e-4) -> VisionTask:
    """MLPerf-style training task: label smoothing 0.1, weight decay 1e-4.

    ``uint8_mean_std`` enables the ship-raw-uint8 input contract
    (``imagenet_*_u8_*`` transforms): raw pixels normalize on DEVICE with
    the ImageNet constants — 4x less host→device transfer, measured +60%
    host records/sec (tools/bench_input.py) — bit-exact vs host-side
    normalization and bf16-policy-safe (VisionTask._prep_image).
    """
    from tensorflow_train_distributed_tpu.data.image import (
        MEAN_RGB, STDDEV_RGB,
    )

    return VisionTask(ResNet(config), label_smoothing=label_smoothing,
                      weight_decay=weight_decay,
                      uint8_mean_std=(MEAN_RGB * 255.0,
                                      STDDEV_RGB * 255.0))
