"""Device mesh construction and strategy presets.

The reference exposes a zoo of strategy classes — ``MirroredStrategy``
(``mirrored_strategy.py:200``), ``MultiWorkerMirroredStrategy``
(``collective_all_reduce_strategy.py:57``), ``ParameterServerStrategyV2``
(``parameter_server_strategy_v2.py:77``), a Horovod hook, and DTensor meshes
(``dtensor/python/layout.py:54``).  On TPU all of those are one thing: an SPMD
program over a named ``jax.sharding.Mesh``.  What survives of the "strategy"
concept is a *mesh preset*: a named assignment of the device grid to logical
parallelism axes.

Axes (any may be size 1):

- ``data``     — pure data parallelism (replicated params, sharded batch).
- ``fsdp``     — data parallelism with parameters/opt-state sharded over it
                 (ZeRO-3 style; batch is sharded over data×fsdp jointly).
- ``tensor``   — tensor/model parallelism (Megatron-style within-layer).
- ``seq``      — sequence/context parallelism (ring attention / Ulysses).
- ``expert``   — expert parallelism for MoE layers.
- ``pipeline`` — pipeline stages.

Presets keep the reference's ``--strategy`` CLI contract meaningful
(``mirrored`` / ``multi_worker_mirrored`` / ``tpu`` → ``dp``; ``ps`` →
rejected, see ``distributed._from_tf_config``; ``dtensor`` → ``dp_tp``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

# Canonical axis order: outermost (slowest-varying, DCN-adjacent) first.
# Data-parallel axes ride DCN across slices; tensor/seq want the fastest ICI
# links, so they sit innermost — mesh_utils assigns the last mesh dims to the
# most tightly coupled device dims.
AXES = ("pipeline", "data", "fsdp", "expert", "seq", "tensor")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Sizes per logical axis; ``-1`` on at most one axis means "infer".

    ``strategy`` may name a preset (see ``STRATEGY_PRESETS``) in which case
    unspecified axes come from the preset.
    """

    data: int = -1
    fsdp: int = 1
    tensor: int = 1
    seq: int = 1
    expert: int = 1
    pipeline: int = 1
    strategy: Optional[str] = None

    def axis_sizes(self) -> dict[str, int]:
        return {
            "pipeline": self.pipeline,
            "data": self.data,
            "fsdp": self.fsdp,
            "expert": self.expert,
            "seq": self.seq,
            "tensor": self.tensor,
        }

    def resolve(self, n_devices: int) -> dict[str, int]:
        """Concrete per-axis sizes for an ``n_devices`` mesh."""
        sizes = self.axis_sizes()
        unknown = [a for a, s in sizes.items() if s == -1]
        if len(unknown) > 1:
            raise ValueError(f"At most one axis may be -1, got {unknown}")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if unknown:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[unknown[0]] = n_devices // fixed
        if math.prod(sizes.values()) != n_devices:
            raise ValueError(
                f"Mesh {sizes} needs {math.prod(sizes.values())} devices, "
                f"have {n_devices}"
            )
        return sizes


# --strategy name → MeshConfig. Reference-strategy names map onto their SPMD
# equivalents so existing launch scripts keep working.
STRATEGY_PRESETS: dict[str, MeshConfig] = {
    "dp": MeshConfig(data=-1),
    "mirrored": MeshConfig(data=-1),                  # reference configs[0]
    "multi_worker_mirrored": MeshConfig(data=-1),     # reference configs[1]
    "horovod": MeshConfig(data=-1),                   # reference configs[3]
    "tpu": MeshConfig(data=-1),                       # reference north-star flag
    "fsdp": MeshConfig(data=1, fsdp=-1),
    "dp_fsdp": MeshConfig(data=-1, fsdp=8),
    "dp_tp": MeshConfig(data=-1, tensor=4),           # DTensor 2-D (data×model)
    "dtensor": MeshConfig(data=-1, tensor=4),         # reference configs[4]
    "dp_sp": MeshConfig(data=-1, seq=4),
    "dp_tp_sp": MeshConfig(data=-1, seq=2, tensor=4),
    "fsdp_tp": MeshConfig(data=1, fsdp=-1, tensor=4),
    "dp_ep": MeshConfig(data=-1, expert=4),
    # Pipeline axis: scanned-block models with ``pipeline_microbatches``
    # set (e.g. the llama family) run the GPipe schedule
    # (``parallel.pipeline.gpipe_layers``) over it — layer groups per
    # stage, microbatched ticks, ppermute hops.
    "dp_pp": MeshConfig(data=-1, pipeline=2),
}


def force_platform(platform: Optional[str] = None,
                   num_cpu_devices: Optional[int] = None) -> None:
    """Point this process's JAX at ``platform`` (and, on CPU, at
    ``num_cpu_devices`` virtual devices).

    Before any backend exists this is plain ``jax.config``.  Once one
    exists jax ignores a platform update and rejects a device-count
    update, so a caller that really re-targets a live process (the test
    suite, ``chaos_check``'s in-process parity read) gets the backends
    cleared first (the late-bound analog of the reference's
    logical-device split in
    ``tensorflow/python/distribute/test_util.py:131``).  A request the
    configuration already satisfies changes nothing: a process that
    holds the chip (``chip_smoke.py`` trains, then serves, both with
    ``--platform tpu``) must never build a second client.
    """
    from jax.extend import backend as jax_backend

    if num_cpu_devices and not platform:
        # A device-count override only means anything on the CPU backend.
        platform = "cpu"
    if platform and jax.config.jax_platforms != platform:
        jax_backend.clear_backends()
        jax.config.update("jax_platforms", platform)
    if num_cpu_devices:
        try:
            jax.config.update("jax_num_cpu_devices", num_cpu_devices)
        except RuntimeError:  # a live backend with another count
            jax_backend.clear_backends()
            jax.config.update("jax_num_cpu_devices", num_cpu_devices)


def strategy_preset(name: str, n_devices: Optional[int] = None) -> MeshConfig:
    """Look up a preset, shrinking fixed axes to fit small device counts.

    A preset like ``dp_tp`` (tensor=4) on a 2-device test mesh degrades to
    tensor=2 rather than failing — mirrors the reference's behavior of running
    any strategy on whatever devices exist.
    """
    if name == "ps" or name == "parameter_server":
        raise ValueError(
            "ParameterServerStrategy is not supported: this framework is "
            "SPMD-only. Use --strategy=dp_tp (the DTensor-style mesh the "
            "reference's north star prescribes for the BERT config)."
        )
    if name not in STRATEGY_PRESETS:
        raise ValueError(
            f"Unknown strategy {name!r}; available: {sorted(STRATEGY_PRESETS)}"
        )
    cfg = STRATEGY_PRESETS[name]
    if n_devices is None:
        return cfg
    return MeshConfig(strategy=name,
                      **_shrink_sizes(cfg.axis_sizes(), n_devices))


def _shrink_sizes(sizes: dict, n_devices: int) -> dict:
    """Shrink fixed (>1, non-inferred) axes until the mesh fits
    ``n_devices`` — halving non-dividers first, then the largest fixed
    axis until the fixed product divides the device count."""
    sizes = dict(sizes)
    fixed_axes = [a for a, s in sizes.items() if s not in (1, -1)]
    for axis in fixed_axes:
        while sizes[axis] > 1 and n_devices % sizes[axis]:
            sizes[axis] //= 2
        sizes[axis] = max(1, min(sizes[axis], n_devices))
    fixed = math.prod(s for s in sizes.values() if s != -1)
    while fixed > n_devices or n_devices % fixed:
        # Shrink the largest fixed axis until the mesh fits.
        big = max(fixed_axes, key=lambda a: sizes[a], default=None)
        if big is None or sizes[big] == 1:
            break
        sizes[big] //= 2
        fixed = math.prod(s for s in sizes.values() if s != -1)
    return sizes


def degrade_to_fit(config: MeshConfig, n_devices: int) -> MeshConfig:
    """Nearest valid layout for ``config`` on ``n_devices`` devices.

    The elastic-relaunch divisibility degrade: a run configured with
    explicit ``--mesh`` axis sizes that no longer fit the surviving
    device set comes back with its fixed axes shrunk (same rules as
    ``strategy_preset``'s shrink-to-fit) and any explicitly-pinned
    product mismatch absorbed by the data axis — training continues on
    the smaller mesh instead of crash-looping the relaunch.  Returns
    ``config`` unchanged when it already resolves.
    """
    try:
        config.resolve(n_devices)
        return config
    except ValueError:
        pass
    sizes = _shrink_sizes(config.axis_sizes(), n_devices)
    probe = MeshConfig(strategy=config.strategy, **sizes)
    try:
        probe.resolve(n_devices)
    except ValueError:
        # Fixed axes fit but the explicit product mismatches (e.g.
        # data pinned to the old device count): let data absorb the
        # remainder.
        sizes["data"] = -1
        probe = MeshConfig(strategy=config.strategy, **sizes)
        probe.resolve(n_devices)  # raises only if truly unsatisfiable
    return probe


def hybrid_shapes(sizes: dict[str, int],
                  dcn_axes: Optional[dict[str, int]],
                  num_slices: int) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """Split per-axis totals into (ici_shape, dcn_shape) for a multi-slice
    mesh (``mesh_utils.create_hybrid_device_mesh`` contract: per-dim totals
    = ici × dcn, product of dcn dims = number of slices).

    ``dcn_axes`` names how slices divide each logical axis (e.g.
    ``{"data": 4}`` = 4 slices data-parallel over DCN).  ``None`` infers the
    default placement: all slices on the outermost axis whose size they
    divide — DCN traffic belongs on gradient allreduce (data/fsdp), never on
    tensor/seq collectives (AXES order encodes that preference).
    """
    if dcn_axes is None:
        # Only data-like axes may be inferred: tensor/seq collectives on
        # DCN would silently destroy step time, so a mesh whose data-like
        # axes can't absorb the slices must be configured explicitly.
        for a in ("pipeline", "data", "fsdp", "expert"):
            if sizes[a] >= num_slices and sizes[a] % num_slices == 0:
                dcn_axes = {a: num_slices}
                break
        else:
            raise ValueError(
                f"cannot place {num_slices} slices on any data-like axis "
                f"of {sizes} (tensor/seq are never inferred — their "
                "collectives belong on ICI); pass dcn_axes explicitly")
    if math.prod(dcn_axes.values()) != num_slices:
        raise ValueError(
            f"dcn_axes {dcn_axes} product must equal the slice count "
            f"{num_slices}")
    for a, d in dcn_axes.items():
        if a not in sizes:
            raise ValueError(f"unknown dcn axis {a!r}")
        if d < 1:
            raise ValueError(f"dcn factor for {a!r} must be >= 1, got {d}")
        if sizes[a] % d:
            raise ValueError(
                f"axis {a!r} of size {sizes[a]} not divisible by its DCN "
                f"factor {d}")
    ici = tuple(sizes[a] // dcn_axes.get(a, 1) for a in AXES)
    dcn = tuple(dcn_axes.get(a, 1) for a in AXES)
    return ici, dcn


def build_mesh(
    config: Optional[MeshConfig] = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    allow_split_physical_axes: bool = False,
    dcn_axes: Optional[dict[str, int]] = None,
) -> Mesh:
    """Build a named ``Mesh`` over the device grid.

    On TPU, ``mesh_utils.create_device_mesh`` lays logical axes onto the
    physical torus so the innermost axes (tensor/seq) get contiguous ICI
    neighbours — the TPU-native analog of the reference's
    ``DeviceAssignment.build`` (``tpu/device_assignment.py:343``) computing
    replica→core mappings.  On CPU/test backends it falls back to a plain
    reshape.

    Multi-slice (several ICI islands joined by DCN — the topology the
    reference reaches with MultiWorkerMirroredStrategy over NCCL+gRPC):
    detected via device ``slice_index``; the hybrid mesh keeps each slice's
    devices ICI-contiguous and places the ``dcn_axes`` factors (default:
    outermost data-like axis) across slices, so XLA routes exactly those
    collectives over DCN.
    """
    if config is None:
        config = MeshConfig(data=-1)
    devices = list(devices if devices is not None else jax.devices())
    if config.strategy is not None and all(
        s == 1 for a, s in config.axis_sizes().items() if a != "data"
    ) and config.data == -1:
        # Bare MeshConfig(strategy=...) — resolve the preset against the real
        # device count so shrink-to-fit applies (e.g. dp_tp on 1 chip).
        config = strategy_preset(config.strategy, len(devices))
    sizes = config.resolve(len(devices))
    shape = tuple(sizes[a] for a in AXES)
    if devices[0].platform == "tpu":
        from jax.experimental import mesh_utils

        slice_ids = {getattr(d, "slice_index", 0) for d in devices}
        if len(slice_ids) > 1 or dcn_axes:
            ici_shape, dcn_shape = hybrid_shapes(
                sizes, dcn_axes, max(len(slice_ids), 1))
            dev_array = mesh_utils.create_hybrid_device_mesh(
                ici_shape, dcn_shape, devices=devices,
                allow_split_physical_axes=allow_split_physical_axes,
            )
        else:
            dev_array = mesh_utils.create_device_mesh(
                shape, devices=devices,
                allow_split_physical_axes=allow_split_physical_axes,
            )
    else:
        if dcn_axes:
            # No slice structure on CPU/test backends — placement is moot,
            # but the factorization is still validated so multi-slice CLI
            # invocations (--dcn) dry-run correctly on the test mesh.
            hybrid_shapes(sizes, dcn_axes,
                          math.prod(dcn_axes.values()))
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXES)


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    """Mesh axes the global batch is sharded over (data-parallel-like axes)."""
    return tuple(a for a in ("data", "fsdp") if mesh.shape[a] > 1) or ("data",)


def data_parallel_size(mesh: Mesh) -> int:
    return mesh.shape["data"] * mesh.shape["fsdp"]
