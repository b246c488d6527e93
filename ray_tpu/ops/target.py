"""Where a call runs: the one question every op with a kernel asks before it
chooses between the kernel and its plain form."""
from __future__ import annotations

from typing import Tuple

import jax


def where(mesh=None, *, interpret: bool = False) -> Tuple[str, int]:
    """``(platform, devices)`` of the program a call is traced into: the
    mesh's first device's platform and the mesh's size — so a compile for a
    DESCRIBED TPU on a CPU host gets what the chip runs — and without a mesh
    the default backend, one device. ``("tpu", 1)`` under `interpret`: the
    Pallas interpreter runs a kernel wherever the process is.

    An op takes its kernel where this says "tpu" and its own tiles divide
    the shapes (and, where the kernel cannot be partitioned, on one device);
    nothing else under `ops/` or `models/` asks the backend or a device."""
    if interpret:
        return "tpu", 1
    if mesh is None:
        return jax.default_backend(), 1
    return mesh.devices.flat[0].platform, mesh.devices.size
