"""Chip detection + per-device telemetry.

A TPU chip belongs to one process at a time, and the raylet lives in the
driver process (``_private/api.py`` ``_LocalNode``), which must never own
the chips its workers will be granted. So detection runs ``jax.devices()``
in a SUBPROCESS that exits — releasing the chips — before ``probe_chips``
returns: one synchronous probe during raylet start, finished before
``ray_tpu.init()`` hands control back, and nothing afterwards. No thread or
subprocess of the raylet touches JAX once a worker can ask for the device.

- ``probe_chips()``        chip count, coords/slice when the runtime
                           exposes them, and the per-device HBM limit —
                           everything the raylet needs from the device,
                           taken in the one probe.
- ``publish_device_gauges()`` folds device records into the
                           ``ray_tpu_device_hbm_bytes`` catalog gauge.
- ``publish_local_device_gauges()`` live HBM numbers from the process
                           that OWNS the backend (train workers call it
                           on every step report).
- ``local_device_identity()`` IN-process identity for tagging train
                           step events — consults jax only if the
                           process already imported it (a train worker
                           inevitably will), so a process that never
                           imported jax is never made to take the chips.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

_CHIP_PROBE_SRC = """
import json, jax
chips = [d for d in jax.devices() if d.platform == "tpu"]
info = {}
if chips:
    info["chips"] = len(chips)
    coords = [list(getattr(d, "coords", ()) or ()) for d in chips]
    if any(coords):
        info["coords"] = coords
    si = getattr(chips[0], "slice_index", None)
    if si is not None:
        info["slice_id"] = f"slice-{si}"
    devices = []
    for d in chips:
        rec = {"id": d.id, "platform": d.platform, "kind": d.device_kind}
        limit = (d.memory_stats() or {}).get("bytes_limit")
        if limit is not None:
            rec["hbm_bytes_limit"] = int(limit)
        devices.append(rec)
    info["devices"] = devices
print(json.dumps(info))
"""


_chip_probe_cache: list = []   # [] = never probed; [result] = cached


def probed() -> bool:
    """Whether this process has made its one probe already."""
    return bool(_chip_probe_cache)


def probe_chips(timeout_s: float = 60.0) -> dict | None:
    """Chip count / coords / slice id / per-device HBM limit via a
    SUBPROCESS jax.devices() call (the raylet's process must not hold the
    chips — see the module docstring). None = no chips, or the probe
    failed or timed out. Memoized per process: detect_resources and
    detect_tpu_topology both call this during raylet init, and the chips
    are taken and released once, not twice."""
    if _chip_probe_cache:
        return _chip_probe_cache[0]
    result = _probe_chips_once(timeout_s)
    _chip_probe_cache.append(result)
    return result


def _probe_chips_once(timeout_s: float) -> dict | None:
    try:
        probe = subprocess.run(
            [sys.executable, "-c", _CHIP_PROBE_SRC],
            timeout=timeout_s, capture_output=True, text=True)
        if probe.returncode != 0:
            return None
        info = json.loads(probe.stdout.strip().splitlines()[-1])
    except (subprocess.TimeoutExpired, OSError, ValueError, IndexError):
        return None
    if not info.get("chips"):
        return None
    if "coords" in info:
        info["coords"] = [tuple(c) for c in info["coords"]]
    return info


# ------------------------------------------------ per-device telemetry

def publish_device_gauges(devices: list[dict]) -> int:
    """Fold device records into the ``ray_tpu_device_hbm_bytes`` gauge,
    one (node, device, platform, stat) series per reported stat. Returns
    the number of devices seen; 0 when telemetry is off.

    The raylet passes ``probe_chips()["devices"]``, which carries only
    ``hbm_bytes_limit``: a probe subprocess's ``bytes_in_use`` is its own
    allocator state, not the training workload's. Owner processes
    (``publish_local_device_gauges``) carry the live in-use numbers."""
    from ray_tpu._private import telemetry as _tm

    if not _tm.ENABLED or not devices:
        return 0
    node = os.uname().nodename
    for d in devices:
        # node tag: local device ids restart at 0 on every host — without
        # the hostname, multi-host gauges collide last-write-wins
        tags = {"node": node, "device": str(d.get("id")),
                "platform": str(d.get("platform", "?"))}
        if d.get("hbm_bytes_in_use") is not None:
            _tm.gauge_set("ray_tpu_device_hbm_bytes",
                          float(d["hbm_bytes_in_use"]),
                          tags={**tags, "stat": "in_use"})
        if d.get("hbm_bytes_limit") is not None:
            _tm.gauge_set("ray_tpu_device_hbm_bytes",
                          float(d["hbm_bytes_limit"]),
                          tags={**tags, "stat": "limit"})
    return len(devices)


def publish_local_device_gauges() -> int:
    """IN-process gauge publish from a process that already owns the
    jax backend (train workers): ``memory_stats()`` on the live runtime
    costs microseconds and cannot contend with anyone for chip
    ownership — the right source for live HBM while training runs.
    Consults jax only if this process already imported it (same rule as
    ``local_device_identity``)."""
    from ray_tpu._private import telemetry as _tm

    if not _tm.ENABLED:
        return 0
    jax = sys.modules.get("jax")
    if jax is None:
        return 0
    try:
        devs = jax.local_devices()
    except Exception:
        return 0
    records = []
    for d in devs:
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        if not ms:
            continue
        records.append({"id": d.id, "platform": d.platform,
                        "hbm_bytes_in_use": ms.get("bytes_in_use"),
                        "hbm_bytes_limit": ms.get("bytes_limit")})
    if not records:
        return 0
    return publish_device_gauges(devices=records)


def local_device_identity() -> dict:
    """IN-process device identity for tagging train-step events: host +
    pid always; platform/devices only when this process ALREADY imported
    jax (a train worker does before its first step) — a process that
    never imported jax is never made to initialise a backend."""
    info: dict = {"host": os.uname().nodename, "pid": os.getpid(),
                  "platform": None, "device_count": 0}
    jax = sys.modules.get("jax")
    if jax is None:
        return info
    try:
        devs = jax.local_devices()
    except Exception:
        return info
    if not devs:
        return info
    info["platform"] = devs[0].platform
    info["device_count"] = len(devs)
    info["device_kind"] = getattr(devs[0], "device_kind", "")
    info["device_ids"] = [d.id for d in devs]
    coords = [list(getattr(d, "coords", ()) or ()) for d in devs]
    if any(coords):
        info["coords"] = coords
    si = getattr(devs[0], "slice_index", None)
    if si is not None:
        info["slice_index"] = si
    return info
