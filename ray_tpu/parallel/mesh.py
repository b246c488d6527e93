"""Device-mesh construction for every parallelism axis the framework knows.

This replaces the reference's NCCL/Gloo communicator world (its
python/ray/util/collective/collective_group/) with the TPU-native
equivalent: a named `jax.sharding.Mesh` whose axes are the parallelism
strategies themselves. All collectives then compile to ICI/DCN collectives
inside XLA programs instead of being library calls.

Axis vocabulary (sizes multiply to the device count):

  dp — data parallel: gradients psum'd over it; typically the outermost
       (slowest-varying) axis so it lands on DCN between slices.
  pp — pipeline parallel: stages; activations move via ppermute.
  ep — expert parallel: MoE experts sharded; tokens move via all_to_all.
  sp — sequence/context parallel: the sequence dimension of activations is
       sharded; ring attention rotates KV blocks around this axis.
  tp — tensor parallel: attention heads / MLP hidden sharded; innermost
       (fastest-varying) so its collectives ride nearest-neighbor ICI.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from ray_tpu.parallel.compile_watch import timed_mesh_build

# Canonical axis order, slowest- to fastest-varying. Matches
# GlobalConfig.mesh_ici_axis_order.
AXIS_ORDER = ("dp", "pp", "ep", "sp", "tp")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """How many ways to shard along each parallelism axis.

    Any axis left at -1 absorbs the remaining devices (at most one -1).
    """

    dp: int = 1
    pp: int = 1
    ep: int = 1
    sp: int = 1
    tp: int = 1

    def resolved(self, n_devices: int) -> "MeshConfig":
        sizes = {a: getattr(self, a) for a in AXIS_ORDER}
        wild = [a for a, s in sizes.items() if s == -1]
        if len(wild) > 1:
            raise ValueError("At most one mesh axis may be -1")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if wild:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {sizes}"
                )
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"Mesh {sizes} needs {fixed} devices but {n_devices} present"
            )
        return MeshConfig(**sizes)

    def axis_sizes(self) -> Dict[str, int]:
        return {a: getattr(self, a) for a in AXIS_ORDER}


@timed_mesh_build("mesh")
def create_mesh(
    config: MeshConfig | None = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    axes: Optional[Dict[str, int]] = None,
) -> Mesh:
    """Build a Mesh over `devices` (default: all).

    On real TPU slices we delegate the physical layout to
    `mesh_utils.create_device_mesh`, which maps the logical axes onto the
    ICI torus so that the fastest-varying axes are nearest-neighbor; on CPU
    (tests) a plain reshape is used.
    """
    if config is None:
        config = MeshConfig(**(axes or {"dp": -1}))
    devices = list(devices if devices is not None else jax.devices())
    config = config.resolved(len(devices))
    sizes = config.axis_sizes()
    shape = tuple(sizes[a] for a in AXIS_ORDER)
    if devices[0].platform == "tpu":
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(
            shape, devices=np.asarray(devices, dtype=object)
        )
    else:
        dev_array = np.asarray(devices, dtype=object).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)


def single_device_mesh(device: Optional[jax.Device] = None) -> Mesh:
    device = device or jax.devices()[0]
    return create_mesh(MeshConfig(), devices=[device])


def balanced_factorization(n: int, axes: Sequence[str]) -> Dict[str, int]:
    """Split n devices over `axes` as evenly as possible (used by the
    multi-chip dry run to make every requested axis non-degenerate when the
    device count allows)."""
    sizes = {a: 1 for a in axes}
    remaining = n
    # Greedily assign factors of 2 (TPU slice sizes are powers of two),
    # round-robin over the requested axes.
    i = 0
    axes = list(axes)
    while remaining % 2 == 0 and remaining > 1:
        sizes[axes[i % len(axes)]] *= 2
        remaining //= 2
        i += 1
    if remaining > 1:  # non-power-of-two leftover goes to the first axis
        sizes[axes[0]] *= remaining
    return sizes


def mesh_shape_summary(mesh: Mesh) -> str:
    return "x".join(f"{k}={v}" for k, v in mesh.shape.items())


def validate_mesh_for_model(mesh: Mesh, *, n_heads: int, n_layers: int) -> List[str]:
    """Sanity checks mirroring the reference's option validation layer
    (python/ray/_private/ray_option_utils.py): returns human-readable
    problems instead of letting XLA fail deep inside compilation."""
    problems = []
    shape = dict(mesh.shape)
    if n_heads % (shape.get("tp", 1)) != 0:
        problems.append(f"n_heads={n_heads} not divisible by tp={shape.get('tp')}")
    if n_layers % (shape.get("pp", 1)) != 0:
        problems.append(f"n_layers={n_layers} not divisible by pp={shape.get('pp')}")
    return problems


def group_devices_by_slice(devices: Sequence[jax.Device]) -> Dict[int, list]:
    """Group devices by their TPU slice (`slice_index`; single-slice and
    CPU devices all land in slice 0)."""
    groups: Dict[int, list] = {}
    for d in devices:
        groups.setdefault(getattr(d, "slice_index", 0), []).append(d)
    return groups


@timed_mesh_build("hybrid_mesh")
def create_hybrid_mesh(
    config: MeshConfig | None = None,
    *,
    dcn_dp: int = -1,
    devices: Optional[Sequence[jax.Device]] = None,
    axes: Optional[Dict[str, int]] = None,
    slice_assignments: Optional[Sequence[int]] = None,
) -> Mesh:
    """Multi-slice mesh: `dp` spans slices over DCN, every other axis stays
    inside a slice on ICI (the megascale layout; public recipe:
    jax mesh_utils.create_hybrid_device_mesh).

    `config`/`axes` describe the WITHIN-slice sharding; `dcn_dp` is the
    between-slice data-parallel degree (-1 = one dp shard per slice). The
    returned mesh's dp axis size is ``dcn_dp * config.dp``; gradient psums
    over dp then hierarchically reduce inside each slice first (ICI) and
    cross slices (DCN) once — XLA does that decomposition when the axis is
    laid out slice-major, which this function guarantees.

    `slice_assignments` forces a slice id per device — the CPU-mesh test
    hook (virtual CPU devices all report slice 0).
    """
    devices = list(devices if devices is not None else jax.devices())
    if slice_assignments is not None:
        if len(slice_assignments) != len(devices):
            raise ValueError(
                f"slice_assignments has {len(slice_assignments)} entries "
                f"for {len(devices)} devices")
        groups: Dict[int, list] = {}
        for d, s in zip(devices, slice_assignments):
            groups.setdefault(s, []).append(d)
    else:
        groups = group_devices_by_slice(devices)
    n_slices = len(groups)
    if dcn_dp == -1:
        dcn_dp = n_slices
    if dcn_dp != n_slices:
        raise ValueError(
            f"dcn_dp={dcn_dp} but {n_slices} slices present (one dp shard "
            f"per slice is the supported DCN layout)")
    sizes = sorted(len(g) for g in groups.values())
    if sizes[0] != sizes[-1]:
        raise ValueError(f"uneven slices: {sizes}")
    per_slice = sizes[0]

    if config is None:
        # default: all within-slice devices on tp (dp is the DCN axis here)
        config = MeshConfig(**(axes or {"tp": -1}))
    config = config.resolved(per_slice)

    if devices[0].platform == "tpu" and slice_assignments is None:
        from jax.experimental import mesh_utils

        inner = tuple(config.axis_sizes()[a] for a in AXIS_ORDER)
        dcn = tuple(dcn_dp if a == "dp" else 1 for a in AXIS_ORDER)
        dev_array = mesh_utils.create_hybrid_device_mesh(
            inner, dcn, devices=devices)
        return Mesh(dev_array, AXIS_ORDER)
    # Off-TPU (tests; virtual CPU devices carry no topology): slice-major
    # ordering makes dp the slowest-varying axis, so dp index = slice.
    ordered: list = []
    for s in sorted(groups):
        ordered.extend(groups[s])
    sizes_d = config.axis_sizes()
    shape = tuple((dcn_dp * sizes_d[a]) if a == "dp" else sizes_d[a]
                  for a in AXIS_ORDER)
    dev_array = np.asarray(ordered, dtype=object).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)
