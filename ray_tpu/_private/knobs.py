"""Central catalog of every ``RAY_TPU_*`` environment knob.

The runtime grew knobs in three places — explicit ``os.environ`` reads
scattered through modules, the config table (`_private/config.py`, where
every ``_CONFIG_DEFS`` key is overridable as ``RAY_TPU_<NAME>``), and
process-spawn plumbing variables the runtime sets for its own children.
Nothing tied them together: a typo'd ``getenv`` silently read nothing,
and README drifted from reality.

This module is the single source of truth. The contract (enforced by the
``knob-registry`` static-analysis pass, ``ray_tpu/_private/analysis/``):

- every explicit ``RAY_TPU_*`` environment read in ``ray_tpu/`` must name
  a knob declared in ``KNOBS`` (or a config-table-derived name) — an
  undeclared read is finding ``RTK201``;
- every cataloged knob must appear in README (finding ``RTK202``), which
  holds by construction because README's knob tables are GENERATED from
  this catalog (``readme_knob_table()``).

Declaring a knob: add a ``Knob`` entry here, regenerate the README table
(``python -m ray_tpu.scripts.cli lint --knob-table``), paste it into
README's "Static analysis" section.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Knob:
    name: str          # full env var name, RAY_TPU_*
    default: str       # default as the env layer sees it ("" = unset)
    type: str          # bool / int / float / str / path / json
    doc: str           # one line, README-ready
    internal: bool = False   # plumbing the runtime sets for its own
    #                          child processes — cataloged (so reads
    #                          lint) but listed in README's internal
    #                          table, not the user-facing one


def _k(name, default, type_, doc, internal=False):
    return Knob("RAY_TPU_" + name, default, type_, doc, internal)


# One entry per EXPLICIT env read in ray_tpu/ (config-table-derived
# RAY_TPU_<CONFIG_KEY> names are declared implicitly by _CONFIG_DEFS and
# recognized by is_declared()). Keep alphabetical within each group.
KNOBS: dict[str, Knob] = {k.name: k for k in [
    # --- kill switches / feature gates -----------------------------------
    _k("COLLECTIVE_DEATH_POISONING", "1", "bool",
       "0 disables gang poisoning on member death; detection falls back "
       "to the collective op timeout."),
    _k("COLLECTIVE_PIPELINE", "1", "bool",
       "0 restores the legacy synchronous collective ring "
       "(bit-identical kill switch for the pipelined data path)."),
    _k("COLLECTIVE_SHM", "1", "bool",
       "0 keeps same-node collective segments off the shm object store "
       "(sockets only)."),
    _k("CHECKPOINT_ASYNC", "1", "bool",
       "0 makes sharded-checkpoint shard writes fully synchronous "
       "(train.sharded_checkpoint; default runs the disk write on a "
       "background thread and commits at the caller's harvest point)."),
    _k("CHECKPOINT_FSYNC", "1", "bool",
       "0 skips the fsync-file + fsync-dir calls in the atomic-write "
       "durability idiom — TEST-ONLY kill switch; production crash "
       "consistency requires it on."),
    _k("DATA_SHUFFLE_COLLECTIVE", "0", "bool",
       "1 routes random_shuffle's partition all-to-all over the "
       "pipelined host-collective plane (actor gang exchange) instead "
       "of object-store reduce tasks; identical rows per seed."),
    _k("COLLECTIVE_WIRE_DTYPE", "off", "str",
       "wire format for float32 sum ring segments: off = bit-exact "
       "(default), bf16 = 2x smaller wire, int8 = per-block-scaled "
       "~4x smaller (bounded error; see README Data plane)."),
    _k("INTERNAL_TELEMETRY", "1", "bool",
       "0 turns off the whole internal metrics + events plane."),
    _k("NATIVE_RPC", "1", "bool",
       "0 forces the pure-Python RPC transport (native C core off)."),
    _k("SERVE_SHAPE_BUCKETS", "1", "bool",
       "0 restores the pad-free legacy batcher (no bucketing, one "
       "compile per observed batch size)."),
    _k("TRAIN_BUCKET_DDP", "1", "bool",
       "0 restores the legacy single synchronous gradient allreduce in "
       "train.ddp.sync_gradients (no bucketing, no async overlap)."),
    _k("TRAIN_DEATH_MONITOR", "1", "bool",
       "0 disables the driver-side gang death monitor (rank death then "
       "surfaces via collective poison or the op timeout)."),
    _k("VALIDATE_SPECS", "1", "bool",
       "0 disables producer-side control-RPC shape validation (only for "
       "bisecting the validator itself)."),
    _k("TIMELINE", "1", "bool",
       "0 removes chrome-timeline span recording."),
    _k("DETECT_CHIPS", "1", "bool",
       "0 stops the raylet probing for local TPU chips at startup (one "
       "synchronous subprocess jax.devices(), finished before init() "
       "returns); the default is 0 when RAY_TPU_TESTING=1."),
    # --- tuning ----------------------------------------------------------
    _k("CHECKPOINT_DIR", "", "path",
       "sharded-checkpoint generation root for standalone (non-trainer) "
       "use; trainers plumb RunConfig.storage_path instead."),
    _k("DATA_PREFETCH_BLOCKS", "4", "int",
       "streaming data plane: blocks a consumer may have buffered or "
       "in flight at once (the bounded-memory prefetch budget; "
       "producers park when the buffer is full)."),
    _k("COLLECTIVE_QUANT_BLOCK", "1024", "int",
       "elements per int8 wire-quantization scale block (one float32 "
       "scale per block; sub-block tails travel exact)."),
    _k("EVENT_LOG_SIZE", "4096", "int",
       "bounded structured-event ring size per process (drop-oldest)."),
    _k("FLIGHT_RECORDER_WINDOW_S", "120", "float",
       "flight recorder: how far back the per-process black box reaches "
       "when a dump is cut (spans/events older than this are dropped "
       "from the dump)."),
    _k("FLIGHT_RECORDER_DIR", "", "path",
       "flight recorder: directory dump folders are written under "
       "(default <tmpdir>/ray_tpu/blackbox)."),
    _k("LEASE_SOFT_CAP", "0", "int",
       "max concurrent worker leases per node; 0 = auto (2x cluster "
       "CPUs)."),
    _k("MEMORY_RING_SIZE", "2048", "int",
       "memory anatomy: bounded provenance-op ring per process (the "
       "window the flight recorder's memory.jsonl covers)."),
    _k("MEMORY_SWEEP_GRACE_S", "5.0", "float",
       "memory anatomy: leak-sweep grace window — store objects younger "
       "than this are referenced by definition (an in-flight collective "
       "segment between put and consume must not classify as a leak)."),
    _k("MEMORY_SWEEP_INTERVAL_S", "30.0", "float",
       "memory anatomy: periodic background leak-sweep cadence per "
       "worker; 0 disables the timer (sweeps still run on demand from "
       "summarize_memory / the flight recorder)."),
    _k("STORE_FREE_RESEND", "1", "int",
       "bounded re-send of a dropped object-store free: one retry of a "
       "GCS free fan-out with no live holder connection, and of an "
       "ephemeral delete that lands while the segment is still pinned; "
       "every drop is counted either way. 0 disables the retry."),
    _k("STORE_SIZE", "268435456", "int",
       "shm object store size in bytes for a spawned node."),
    _k("TRAIN_DDP_MODE", "allreduce", "str",
       "gradient-sync shape (train.ddp): allreduce = legacy full-tree "
       "sync on every rank (bit-identical default); reducescatter = "
       "ZeRO-style sharded sync — each rank receives only its shard of "
       "every bucket (pair with ZeroOptimizer for sharded optimizer "
       "state + async param allgathers)."),
    _k("TRAIN_GRAD_BUCKET_BYTES", "4194304", "int",
       "target size of one gradient-sync bucket (train.ddp): grads are "
       "packed into buckets of about this many bytes and each bucket's "
       "allreduce is launched asynchronously as soon as it is packed."),
    # --- chaos / debugging -----------------------------------------------
    _k("FAULT_SCHEDULE", "", "str",
       "deterministic fault-injection schedule DSL; activates the "
       "injector in every process that inherits it."),
    _k("FAULT_SEED", "0", "int",
       "seed for the fault-injection schedule's probabilistic rules."),
    _k("FAULT_ROLE", "*", "str",
       "restricts which cluster role (gcs/raylet/worker/driver) the "
       "inherited schedule fires in.", internal=True),
    _k("RPC_DEBUG", "", "bool",
       "1 prints transport-level connection lifecycle diagnostics."),
    _k("WORKER_PROFILE", "", "path",
       "directory to write per-worker cProfile dumps into."),
    _k("TESTING", "", "bool",
       "set by the test harness; relaxes timing-sensitive defaults."),
    _k("SOAK_NODES", "100", "int",
       "default fleet size for the cluster-scale soak harness "
       "(_private/sim_cluster.py / benchmarks/soak_bench.py)."),
    # --- client / logging ------------------------------------------------
    _k("ADDRESS", "", "str",
       "default cluster address for ray_tpu.init() / ray://."),
    _k("LOG_TO_DRIVER", "1", "bool",
       "0 stops streaming worker stdout/stderr to the driver."),
    _k("QUIET", "", "bool",
       "1 suppresses the init() banner and log-monitor chatter."),
    _k("WORKFLOW_STORAGE", "", "path",
       "workflow checkpoint storage root (default under the session "
       "dir)."),
    # --- process-spawn plumbing (set BY the runtime for its children) ----
    _k("GCS_ADDR", "", "str",
       "host:port of the GCS, set for spawned raylets/workers.",
       internal=True),
    _k("RAYLET_ADDR", "", "str",
       "host:port of the owning raylet, set for spawned workers.",
       internal=True),
    _k("RAYLET_PORT", "", "int",
       "port a spawned raylet should bind.", internal=True),
    _k("NODE_ID", "", "str",
       "node id a spawned process belongs to.", internal=True),
    _k("WORKER_ID", "", "str",
       "worker id assigned to a spawned worker process.", internal=True),
    _k("STORE_NAME", "", "str",
       "shm store segment name a spawned process attaches to.",
       internal=True),
    _k("SPILL_DIR", "", "path",
       "object-spill directory a spawned process uses.", internal=True),
    _k("SESSION_DIR", "", "path",
       "session directory for logs/sockets of a spawned node.",
       internal=True),
    _k("RESOURCES", "", "json",
       "JSON resource map for a spawned raylet.", internal=True),
    _k("ENV_OK", "", "str",
       "marker the runtime-env builder sets inside a prepared venv.",
       internal=True),
]}


def config_knob_names() -> set[str]:
    """``RAY_TPU_<NAME>`` for every config-table entry — declared
    implicitly by ``_CONFIG_DEFS`` (each is env-overridable)."""
    from ray_tpu._private.config import _CONFIG_DEFS

    return {"RAY_TPU_" + name.upper() for name in _CONFIG_DEFS}


def is_declared(name: str) -> bool:
    """Is ``name`` (a full RAY_TPU_* env var) a declared knob?"""
    return name in KNOBS or name in config_knob_names()


def readme_knob_table(internal: bool = False) -> str:
    """The generated markdown knob table for README (user-facing by
    default; ``internal=True`` renders the plumbing table). The
    knob-registry pass asserts every cataloged name appears in README,
    which holds as long as README carries both generated tables."""
    rows = [k for k in KNOBS.values() if k.internal == internal]
    rows.sort(key=lambda k: k.name)
    head = ("| knob | default | type | what it does |\n"
            "|---|---|---|---|")
    body = "\n".join(
        f"| `{k.name}` | `{k.default or '(unset)'}` | {k.type} | {k.doc} |"
        for k in rows)
    return head + "\n" + body
