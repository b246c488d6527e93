"""Crash-consistent sharded checkpointing with world-elastic restore.

PR 19 sharded optimizer state O(model/world) per rank (train/ddp.py
``ZeroOptimizer``); this module shards the CHECKPOINT the same way and
makes it survive the failures the rest of the stack already does:

- **Per-rank shard writes.** Each rank persists only its ZeRO shard —
  its ``[lo, hi)`` slice of every packed param bucket plus the
  optimizer-state slots for that slice, keyed by the deterministic
  bucket plan (``parallel/sharding.plan_buckets`` /
  ``plan_shard_map``) — as one ``.npz`` written through the sanctioned
  temp-file → fsync → rename idiom (``_private/atomic_write.py``), with
  its sha256 recorded. Numpy's lazy npz member loading means restore
  touches only the members it needs: no rank ever materializes another
  rank's optimizer state.

- **Two-phase atomic commit.** Ranks ack shard durability over the
  existing collective plane (one small ``allgather_object``), then rank
  0 ALONE writes the generation's ``MANIFEST.json`` (world size,
  bucket-plan fingerprint, per-shard digests) with the same
  write-fsync-rename discipline. A generation without a manifest is by
  definition torn and invisible to restore — a crash anywhere before
  the manifest rename loses at most one uncommitted generation, never
  the ability to restore.

- **Corruption detection + fallback.** Restore verifies the plan
  fingerprint and every shard's digest (streaming, chunked — full
  files are never held in memory); a bad/torn generation is quarantined
  (renamed ``*.quarantined``, ``CHECKPOINT_QUARANTINED`` event naming
  the shard and reason) and restore falls back to the newest complete
  one. ``prune_generations`` never deletes the last verified-complete
  generation, whatever ``num_to_keep`` says.

- **World-elastic restore.** A gang restarting at a different world
  size re-slices the saved shards onto the new shard map by pure index
  math over the plan (``parallel/sharding.reslice_spans`` — the plan
  depends only on shapes/dtypes, so old and new layouts index the same
  packed element streams). ``CHECKPOINT_RESHARDED`` marks the event;
  the result is bit-exact against a fixed-world restore (pinned in
  tests/test_zz_sharded_ckpt.py).

- **Async snapshot.** ``save_sharded(..., asynchronous=True)`` (the
  ``RAY_TPU_CHECKPOINT_ASYNC`` default) serializes the shard on the
  caller thread (cheap memcpy — the state captured is the state at
  call time) and moves the disk write to a background thread; the
  two-phase commit runs when the caller harvests the returned
  :class:`PendingSnapshot` at its next deterministic collective point.
  Both halves stamp step anatomy (kind ``checkpoint``; the background
  write lands as hidden time, the snapshot + any harvest residue as
  exposed), so a checkpoint stall is attributed, not mysterious.

Chaos: every disk write consults the fault plane's disk primitives
(``torn_write:`` / ``corrupt_file:`` / ``kill_actor:`` against the
``ckpt`` tag — see ``_private/fault_injection.py``), so every failure
mode above is a seeded, reproducible test.
"""
from __future__ import annotations

import hashlib
import io
import json
import os
import shutil
import threading
import time

from ray_tpu._private import events as _events
from ray_tpu._private import telemetry as _tm

GEN_PREFIX = "gen_"
MANIFEST = "MANIFEST.json"
QUARANTINE_SUFFIX = ".quarantined"
_DIGEST_CHUNK = 1 << 20


class CheckpointError(RuntimeError):
    pass


def _get_config(name):
    from ray_tpu._private.config import get_config

    return get_config(name)


def default_root() -> str | None:
    """The sharded-checkpoint root: the training session's directory
    (plumbed by the trainer from ``RunConfig.storage_path``) when inside
    a train worker, else the ``RAY_TPU_CHECKPOINT_DIR`` config knob."""
    try:
        from ray_tpu.air import session as _session

        d = getattr(_session._get_session(), "checkpoint_dir", None)
        if d:
            return d
    except Exception:
        pass
    d = _get_config("checkpoint_dir")
    return d or None


def shard_filename(rank: int, world: int) -> str:
    return f"shard_{int(rank):05d}_of_{int(world):05d}.npz"


def generation_dir(root: str, step: int) -> str:
    return os.path.join(root, f"{GEN_PREFIX}{int(step):08d}")


def _gen_step(dirname: str) -> int | None:
    base = os.path.basename(dirname.rstrip(os.sep))
    if not base.startswith(GEN_PREFIX) or base.endswith(QUARANTINE_SUFFIX):
        return None
    try:
        return int(base[len(GEN_PREFIX):])
    except ValueError:
        return None


def _list_generations(root: str) -> list:
    """[(step, path)] for live (non-quarantined) generations, newest
    first."""
    out = []
    try:
        names = os.listdir(root)
    except OSError:
        return out
    for name in names:
        path = os.path.join(root, name)
        step = _gen_step(path)
        if step is not None and os.path.isdir(path):
            out.append((step, path))
    out.sort(reverse=True)
    return out


def _file_sha256(path: str) -> str:
    """Streaming digest — never holds the file (i.e. a whole shard of
    optimizer state) in memory at once."""
    h = hashlib.sha256()
    with open(path, "rb") as f:
        while True:
            chunk = f.read(_DIGEST_CHUNK)
            if not chunk:
                break
            h.update(chunk)
    return h.hexdigest()


def _record_anatomy(start_m: float, end_m: float, blocking: bool, **meta):
    try:
        from ray_tpu._private import step_anatomy

        step_anatomy.record_activity("checkpoint", start_m, end_m,
                                     blocking=blocking, **meta)
    except Exception:
        pass


# ----------------------------------------------------------------- save


def _build_shard_payload(params, optimizer, bucket_bytes, world, rank,
                         step, extra):
    """This rank's shard as (npz bytes, manifest-facing meta). Param
    slices come from packing each bucket and cutting ``[lo, hi)``;
    optimizer slots come from ``ZeroOptimizer.shard_state_dict()`` —
    already O(model/world)."""
    import numpy as np

    from ray_tpu.parallel import sharding as _sh

    leaves, _ = _sh.flatten_tree(params)
    if optimizer is not None:
        optimizer._ensure_plan(leaves)
        plan = optimizer._plan
        shard_map = optimizer._shard_map
        fingerprint = optimizer.plan_fingerprint
        opt_state = optimizer.shard_state_dict()
        step = int(step if step is not None else opt_state["step"])
        slots = sorted({k for st in opt_state["buckets"] for k in st})
    else:
        if bucket_bytes is None:
            bucket_bytes = int(_get_config("train_grad_bucket_bytes"))
        plan = _sh.plan_buckets(leaves, bucket_bytes)
        shard_map = _sh.plan_shard_map(leaves, plan, world)
        fingerprint = _sh.plan_fingerprint(leaves, plan)
        opt_state = None
        step = int(step or 0)
        slots = []
    arrays = {}
    for b, indices in enumerate(plan):
        lo, hi = shard_map[b]["bounds"][rank]
        pflat = _sh.pack_bucket(leaves, indices)
        arrays[f"param_{b}"] = np.array(pflat[lo:hi])
        if opt_state is not None:
            for slot, arr in opt_state["buckets"][b].items():
                arrays[f"opt_{b}_{slot}"] = np.asarray(arr)
    meta = {"rank": int(rank), "world": int(world), "step": step,
            "plan_fingerprint": fingerprint, "buckets": len(plan),
            "slots": slots, "extra": extra if extra is not None else {}}
    arrays["meta"] = np.frombuffer(
        json.dumps(meta).encode(), dtype=np.uint8).copy()
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    return buf.getvalue(), meta


class PendingSnapshot:
    """One in-flight sharded checkpoint save. ``result(timeout)`` joins
    the background shard write (if any), runs the two-phase commit over
    the collective plane, and returns::

        {"committed": bool, "path": generation dir, "step": int,
         "manifest": dict | None, "error": str | None}

    All ranks MUST harvest at the same point in their collective
    sequence (SPMD) — the commit's durability ack is an
    ``allgather_object`` on the training group."""

    def __init__(self, root, gen_dir, step, world, rank, group_name,
                 keep, data, meta, asynchronous):
        self._root = root
        self._gen = gen_dir
        self._step = step
        self._world = world
        self._rank = rank
        self._group = group_name
        self._keep = keep
        self._data = data
        self._meta = meta
        self._write_error: str | None = None
        self._digest: str | None = None
        self._nbytes = len(data)
        self._result: dict | None = None
        self._thread: threading.Thread | None = None
        if asynchronous:
            self._thread = threading.Thread(
                target=self._write, name="rtpu-ckpt-write", daemon=True)
            self._thread.start()
        else:
            self._write()

    # ------------------------------------------------------------ write
    def _write(self):
        from ray_tpu._private.atomic_write import atomic_write

        path = os.path.join(self._gen, shard_filename(self._rank,
                                                      self._world))
        t0 = time.monotonic()
        background = self._thread is not None
        try:
            os.makedirs(self._gen, exist_ok=True)
            # digest the bytes we INTENDED to persist, not a re-read of
            # the file: a latent flip between write and read-back (the
            # corrupt_file fault) must make restore's digest check FAIL,
            # which only works if the manifest carries the clean hash
            self._digest = hashlib.sha256(self._data).hexdigest()
            atomic_write(path, self._data, tag="ckpt", name="shard")
            if _tm.ENABLED:
                _tm.observe("ray_tpu_checkpoint_write_seconds",
                            time.monotonic() - t0,
                            tags={"group": self._group or "local"})
                _tm.observe("ray_tpu_checkpoint_bytes",
                            float(self._nbytes),
                            tags={"group": self._group or "local"})
        except BaseException as e:
            self._write_error = f"{type(e).__name__}: {e}"
        finally:
            self._data = b""
            _record_anatomy(t0, time.monotonic(), blocking=not background,
                            phase="write", step=self._step)

    def done_writing(self) -> bool:
        return self._thread is None or not self._thread.is_alive()

    def _allgather_acks(self, ack):
        from ray_tpu.util import collective as col

        return col.allgather_object(ack, self._group)

    def _scan_acks(self, own_ack):
        acks = [own_ack]
        for r in range(self._world):
            if r == self._rank:
                continue
            path = os.path.join(self._gen, shard_filename(r, self._world))
            try:
                acks.append((r, _file_sha256(path),
                             os.path.getsize(path), None))
            except OSError as e:
                acks.append((r, None, 0,
                             f"shard not on disk: {type(e).__name__}"))
        return acks

    # ----------------------------------------------------------- commit
    def result(self, timeout: float | None = None) -> dict:
        if self._result is not None:
            return self._result
        if self._thread is not None:
            t0 = time.monotonic()
            self._thread.join(timeout)
            if self._thread.is_alive():
                raise TimeoutError(
                    f"sharded checkpoint shard write still in flight "
                    f"after {timeout}s ({self._gen})")
            t1 = time.monotonic()
            if t1 - t0 > 1e-4:
                # the residue the overlap window failed to hide
                _record_anatomy(t0, t1, blocking=True, phase="wait",
                                step=self._step)
        ack = (self._rank, self._digest, self._nbytes, self._write_error)
        if self._world > 1 and self._group:
            acks = self._allgather_acks(ack)
        elif self._world > 1:
            # groupless multi-rank save (driver-assembled gangs, unit
            # tests): the durability ack degrades to a directory scan —
            # rank 0's result() must run after every rank's write
            acks = self._scan_acks(ack)
        else:
            acks = [ack]
        acks = sorted(acks)
        errors = {r: err for r, _, _, err in acks if err}
        manifest = None
        if not errors and self._rank == 0:
            manifest = {
                "step": self._step, "world": self._world,
                "plan_fingerprint": self._meta["plan_fingerprint"],
                "buckets": self._meta["buckets"],
                "slots": self._meta["slots"],
                "shards": {str(r): {"file": shard_filename(r, self._world),
                                    "sha256": digest, "bytes": n}
                           for r, digest, n, _ in acks},
            }
            from ray_tpu._private.atomic_write import atomic_write

            try:
                atomic_write(os.path.join(self._gen, MANIFEST),
                             json.dumps(manifest, indent=1).encode(),
                             tag="ckpt", name="manifest")
            except BaseException as e:
                errors[0] = f"{type(e).__name__}: {e}"
                manifest = None
        if not errors:
            if self._rank == 0:
                _events.record("CHECKPOINT_COMMITTED", step=self._step,
                               world=self._world, path=self._gen,
                               shard_bytes=sum(n for _, _, n, _ in acks))
                if self._keep:
                    prune_generations(self._root, self._keep)
            self._result = {"committed": True, "path": self._gen,
                            "step": self._step, "manifest": manifest,
                            "error": None}
        else:
            # torn by definition: no manifest was (or ever will be)
            # written for this generation — restore cannot see it
            err = "; ".join(f"rank {r}: {m}" for r, m in
                            sorted(errors.items()))
            self._result = {"committed": False, "path": self._gen,
                            "step": self._step, "manifest": None,
                            "error": err}
        return self._result


def save_sharded(params, optimizer=None, *, root: str | None = None,
                 step: int | None = None, group_name: str | None = None,
                 world: int | None = None, rank: int | None = None,
                 bucket_bytes: int | None = None, extra: dict | None = None,
                 asynchronous: bool | None = None,
                 keep: int | None = None) -> PendingSnapshot:
    """Cut one sharded checkpoint generation; returns a
    :class:`PendingSnapshot` (already written in sync mode — harvest
    ``result()`` either way for the commit verdict).

    ``params`` is the full (replicated) param pytree; ``optimizer`` a
    ``train.ddp.ZeroOptimizer`` whose shard state rides along (step
    counter included). Without an optimizer the same sharded layout
    persists params only. ``world``/``rank`` default to the
    optimizer's gang (or 1/0 standalone); ``extra`` is a small
    JSON-able user dict riding every shard's meta."""
    if optimizer is not None:
        from ray_tpu.parallel import sharding as _sh

        leaves, _ = _sh.flatten_tree(params)
        optimizer._ensure_plan(leaves)
        world = optimizer._world if world is None else world
        rank = optimizer._rank if rank is None else rank
        group_name = group_name or optimizer._group
    if world is None and group_name:
        from ray_tpu.util import collective as col

        world = col.get_collective_group_size(group_name)
        rank = col.get_rank(group_name) if rank is None else rank
    world = 1 if world is None else int(world)
    rank = 0 if rank is None else int(rank)
    root = root or default_root()
    if not root:
        raise CheckpointError(
            "save_sharded: no checkpoint root — pass root=, set "
            "RAY_TPU_CHECKPOINT_DIR, or run under a trainer with a "
            "storage_path")
    if asynchronous is None:
        asynchronous = bool(_get_config("checkpoint_async"))
    t0 = time.monotonic()
    data, meta = _build_shard_payload(params, optimizer, bucket_bytes,
                                     world, rank, step, extra)
    _record_anatomy(t0, time.monotonic(), blocking=True, phase="snapshot",
                    step=meta["step"])
    gen = generation_dir(root, meta["step"])
    return PendingSnapshot(root, gen, meta["step"], world, rank,
                           group_name, keep, data, meta, asynchronous)


# -------------------------------------------------------------- verify


def _load_manifest(gen_dir: str) -> dict | None:
    try:
        with open(os.path.join(gen_dir, MANIFEST), "rb") as f:
            return json.loads(f.read().decode())
    except (OSError, ValueError):
        return None


def verify_generation(gen_dir: str, fingerprint: str | None = None,
                      digests: bool = True) -> dict:
    """Pure (no renames, no events) verification of one generation.
    Returns ``{"ok": bool, "reason": str|None, "shard": str|None,
    "manifest": dict|None}`` — reason is one of ``torn`` (no/unreadable
    manifest), ``plan_mismatch``, ``shard_missing``,
    ``digest_mismatch``, ``size_mismatch``."""
    manifest = _load_manifest(gen_dir)
    if manifest is None:
        return {"ok": False, "reason": "torn", "shard": None,
                "manifest": None}
    if fingerprint is not None and \
            manifest.get("plan_fingerprint") != fingerprint:
        return {"ok": False, "reason": "plan_mismatch", "shard": None,
                "manifest": manifest}
    for r in sorted(manifest.get("shards", {}), key=int):
        spec = manifest["shards"][r]
        path = os.path.join(gen_dir, spec["file"])
        if not os.path.isfile(path):
            return {"ok": False, "reason": "shard_missing",
                    "shard": spec["file"], "manifest": manifest}
        if os.path.getsize(path) != int(spec["bytes"]):
            return {"ok": False, "reason": "size_mismatch",
                    "shard": spec["file"], "manifest": manifest}
        if digests and _file_sha256(path) != spec["sha256"]:
            return {"ok": False, "reason": "digest_mismatch",
                    "shard": spec["file"], "manifest": manifest}
    return {"ok": True, "reason": None, "shard": None,
            "manifest": manifest}


def _quarantine(gen_dir: str, verdict: dict):
    """Rename a bad/torn generation out of restore's sight + record the
    event naming the shard and reason. Rename, not delete: the wreckage
    is evidence (the flight recorder / conftest failure hint point
    operators at it)."""
    from ray_tpu._private.atomic_write import fsync_dir

    target = gen_dir + QUARANTINE_SUFFIX
    try:
        os.rename(gen_dir, target)
        fsync_dir(os.path.dirname(gen_dir) or ".")
    except OSError:
        # every rank restores concurrently and each may see the same
        # torn generation: the losers' rename fails ENOENT because a
        # peer already moved it — the wreckage IS quarantined, do not
        # touch the target. Only when the source still exists (a
        # re-torn generation of the same step colliding with older
        # wreckage) replace the stale target and retry.
        if os.path.isdir(gen_dir):
            shutil.rmtree(target, ignore_errors=True)
            try:
                os.rename(gen_dir, target)
                fsync_dir(os.path.dirname(gen_dir) or ".")
            except OSError:
                target = gen_dir     # couldn't rename: record + skip
    _events.record("CHECKPOINT_QUARANTINED", path=gen_dir,
                   reason=verdict["reason"], shard=verdict["shard"])
    if _tm.ENABLED:
        _tm.counter_inc("ray_tpu_checkpoint_quarantined_total",
                        tags={"reason": verdict["reason"]})
    return target


# ------------------------------------------------------------- restore


def restore_sharded(params_template, optimizer=None, *,
                    root: str | None = None,
                    group_name: str | None = None,
                    world: int | None = None, rank: int | None = None,
                    bucket_bytes: int | None = None,
                    quarantine: bool = True):
    """Restore from the newest verified-complete generation under
    ``root``, re-slicing saved shards onto THIS world size when it
    differs from the saved one (pure index math — bit-exact vs a
    fixed-world restore). Bad/torn generations encountered on the way
    are quarantined (``CHECKPOINT_QUARANTINED``) and restore falls back
    to the next older one.

    Returns ``(params, meta)`` — ``params`` shaped like
    ``params_template``, ``meta`` with ``step`` / ``extra`` /
    ``world_saved`` / ``resharded`` / ``path`` — or ``None`` when no
    restorable generation exists. When ``optimizer`` is given, its
    shard state (this rank's slices only) and step counter are
    installed."""
    import numpy as np

    from ray_tpu.parallel import sharding as _sh

    if optimizer is not None and world is None:
        # the optimizer may not have a plan yet on a fresh gang; its
        # group still names the world
        group_name = group_name or optimizer._group
    if world is None:
        if group_name:
            from ray_tpu.util import collective as col

            world = col.get_collective_group_size(group_name)
            rank = col.get_rank(group_name) if rank is None else rank
        else:
            world = 1
    world = int(world)
    rank = 0 if rank is None else int(rank)
    root = root or default_root()
    if not root or not os.path.isdir(root):
        return None
    t_restore = time.monotonic()
    leaves, treedef = _sh.flatten_tree(params_template)
    if bucket_bytes is None:
        bucket_bytes = (optimizer._bucket_bytes
                        if optimizer is not None else None)
    if bucket_bytes is None:
        bucket_bytes = int(_get_config("train_grad_bucket_bytes"))
    plan = _sh.plan_buckets(leaves, bucket_bytes)
    shard_map = _sh.plan_shard_map(leaves, plan, world)
    fingerprint = _sh.plan_fingerprint(leaves, plan)
    chosen = None
    for step, gen_dir in _list_generations(root):
        verdict = verify_generation(gen_dir, fingerprint)
        if verdict["ok"]:
            chosen = (step, gen_dir, verdict["manifest"])
            break
        if quarantine:
            _quarantine(gen_dir, verdict)
    if chosen is None:
        return None
    step, gen_dir, manifest = chosen
    old_world = int(manifest["world"])
    slots = list(manifest.get("slots", ()))
    resharded = old_world != world

    payloads: dict[int, object] = {}   # old rank -> lazy npz handle

    def _payload(r: int):
        z = payloads.get(r)
        if z is None:
            z = np.load(os.path.join(
                gen_dir, manifest["shards"][str(r)]["file"]))
            payloads[r] = z
        return z

    out_leaves: list = [None] * len(leaves)
    opt_buckets: list = []
    try:
        for b, indices in enumerate(plan):
            elems = shard_map[b]["elems"]
            # full params on every rank: the rank-ordered concatenation
            # of the OLD layout's param slices IS the packed bucket
            flat = np.concatenate(
                [np.asarray(_payload(r)[f"param_{b}"])
                 for r in range(old_world)]) if old_world > 1 else \
                np.asarray(_payload(0)[f"param_{b}"])
            _sh.unpack_bucket(flat, leaves, indices, out_leaves)
            # optimizer state: ONLY this rank's [lo, hi) — assembled
            # from the overlapping spans of the old layout, touching
            # only those old shards' slot members (lazy npz access)
            if optimizer is not None and slots is not None:
                spans = _sh.reslice_spans(elems, old_world, world, rank)
                st = {}
                for slot in slots:
                    parts = [np.asarray(_payload(r)[f"opt_{b}_{slot}"]
                                        [lo:hi]) for r, lo, hi in spans]
                    st[slot] = (np.concatenate(parts) if len(parts) != 1
                                else np.array(parts[0]))
                opt_buckets.append(st)
    finally:
        for z in payloads.values():
            try:
                z.close()
            except Exception:
                pass
    for i, leaf in enumerate(leaves):
        if out_leaves[i] is None:
            out_leaves[i] = leaf
    params = _sh.unflatten_tree(treedef, out_leaves)
    if optimizer is not None:
        optimizer.load_shard_state_dict({
            "step": int(manifest["step"]),
            "plan_fingerprint": manifest["plan_fingerprint"],
            "buckets": opt_buckets})
    meta0 = _shard_meta(_payload_path(gen_dir, manifest, 0))
    if resharded:
        _events.record("CHECKPOINT_RESHARDED", path=gen_dir,
                       step=step, world_saved=old_world, world_now=world)
    if _tm.ENABLED:
        _tm.observe("ray_tpu_checkpoint_restore_seconds",
                    time.monotonic() - t_restore,
                    tags={"group": group_name or "local"})
    return params, {"step": int(manifest["step"]), "path": gen_dir,
                    "world_saved": old_world, "resharded": resharded,
                    "extra": (meta0 or {}).get("extra", {})}


def _payload_path(gen_dir: str, manifest: dict, rank: int) -> str:
    return os.path.join(gen_dir, manifest["shards"][str(rank)]["file"])


def _shard_meta(path: str) -> dict | None:
    import numpy as np

    try:
        with np.load(path) as z:
            return json.loads(bytes(z["meta"]).decode())
    except Exception:
        return None


# ------------------------------------------------------------- pruning


def prune_generations(root: str, keep: int) -> list:
    """Bound the on-disk generation count: keep the newest ``keep``
    COMMITTED generations, plus — unconditionally — the newest
    generation that verifies complete (manifest + every shard present
    at its manifested size; the cheap check, digests are restore's
    job). Torn generations older than the newest committed one are dead
    by definition and removed; quarantined wreckage is removed once it
    falls behind the kept window. Returns the removed paths."""
    keep = max(1, int(keep))
    gens = _list_generations(root)               # newest first
    committed = [(s, p) for s, p in gens
                 if _load_manifest(p) is not None]
    keep_paths = {p for _, p in committed[:keep]}
    for s, p in committed:
        if verify_generation(p, digests=False)["ok"]:
            keep_paths.add(p)                    # last verified-complete
            break
    newest_committed = committed[0][0] if committed else None
    removed = []
    for s, p in gens:
        if p in keep_paths:
            continue
        if _load_manifest(p) is None and (newest_committed is None
                                          or s >= newest_committed):
            continue    # possibly an in-flight save: not ours to judge
        shutil.rmtree(p, ignore_errors=True)
        removed.append(p)
    # quarantined wreckage: bounded the same way — drop any that is
    # older than the oldest generation we kept
    oldest_kept = min((_gen_step(p) for p in keep_paths
                       if _gen_step(p) is not None), default=None)
    try:
        names = os.listdir(root)
    except OSError:
        names = []
    for name in names:
        if not name.endswith(QUARANTINE_SUFFIX):
            continue
        step = _gen_step(os.path.join(root,
                                      name[:-len(QUARANTINE_SUFFIX)]))
        if step is None or oldest_kept is None or step < oldest_kept:
            path = os.path.join(root, name)
            shutil.rmtree(path, ignore_errors=True)
            removed.append(path)
    return removed


# ------------------------------------------------------------- summary


def summarize_checkpoints(root: str, digests: bool = True) -> list:
    """Per-generation status under ``root``, newest first — the
    ``ray-tpu checkpoints`` CLI and the conftest chaos-failure hint.
    Each entry: ``{"step", "path", "status", "world", "shards",
    "bytes", "reason", "shard"}`` with status ``committed`` / ``torn``
    / ``corrupt`` / ``quarantined``."""
    out = []
    for step, gen_dir in _list_generations(root):
        verdict = verify_generation(gen_dir, digests=digests)
        manifest = verdict["manifest"]
        status = "committed" if verdict["ok"] else (
            "torn" if verdict["reason"] == "torn" else "corrupt")
        out.append({
            "step": step, "path": gen_dir, "status": status,
            "world": manifest["world"] if manifest else None,
            "shards": len(manifest["shards"]) if manifest else
            sum(1 for n in os.listdir(gen_dir)
                if n.startswith("shard_")),
            "bytes": sum(int(s["bytes"])
                         for s in manifest["shards"].values())
            if manifest else None,
            "reason": verdict["reason"], "shard": verdict["shard"],
        })
    try:
        names = os.listdir(root)
    except OSError:
        names = []
    for name in sorted(names, reverse=True):
        if name.endswith(QUARANTINE_SUFFIX):
            path = os.path.join(root, name)
            step = _gen_step(path[:-len(QUARANTINE_SUFFIX)])
            out.append({"step": step, "path": path,
                        "status": "quarantined", "world": None,
                        "shards": None, "bytes": None, "reason": None,
                        "shard": None})
    out.sort(key=lambda e: (e["step"] is None, -(e["step"] or 0)))
    return out
