"""Task/actor spec schema — the typed contract for the dicts that cross
the control plane.

Reference: src/ray/common/task/task_spec.h (+ common.proto TaskSpec) —
the reference compiles its spec into protobuf; here the wire form stays
a plain dict (pickled by the RPC layer), and THIS module is the single
place that says which keys exist, who writes them, and what they mean.
`validate_task_spec` runs unconditionally at submission so schema drift
fails loudly at the producer, not as a KeyError deep inside a worker
(the check is set arithmetic over <=17 keys — cheap enough to always
pay; set RAY_TPU_VALIDATE_SPECS=0 only to bisect the validator itself).
"""
from __future__ import annotations

import os
from typing import Any, TypedDict


class TaskSpec(TypedDict, total=False):
    """A normal-task submission (producer: CoreWorker.submit_task)."""

    task_id: bytes               # 16-byte unique id
    func_hash: bytes             # function-table key (GCS ns=functions)
    args: bytes                  # ser.serialize((args, kwargs))
    return_ids: list             # [16-byte object id, ...]
    owner_addr: tuple            # (host, port) of the owning worker
    retries_left: int            # worker-death retry budget
    reconstructions_left: int    # lineage re-execution budget
    task_desc: str               # human-readable ("task f()")
    job_id: int
    runtime_env: dict            # normalized (content keys, not paths)
    inline_exec: bool            # pump-safe: execute on the transport pump
    inlined: dict                # {ref_id: frame bytes} for small resolved
                                 # args (executor skips the owner round trip)
    dynamic_returns: bool        # num_returns="dynamic"/"streaming": the
                                 # task yields items, each its own object
    trace_ctx: dict              # {"trace_id", "parent_span_id"} where
                                 # util.tracing is on; {"cause", "run"}:
                                 # the submitter's live timeline span
    # actor-call extension (producer: submit_actor_task)
    actor_id: bytes
    method_name: str
    caller_id: str               # submitting worker id (seq scoping)
    caller_epoch: int            # bumped per reconnect
    seq: int                     # per-caller submission order


# Keys every normal-task spec MUST carry (actor calls add their own).
REQUIRED_TASK_KEYS = frozenset({
    "task_id", "func_hash", "args", "return_ids", "owner_addr",
    "retries_left", "task_desc", "job_id",
})

REQUIRED_ACTOR_KEYS = frozenset({
    "task_id", "actor_id", "method_name", "args", "return_ids",
    "owner_addr", "caller_id",
})

# Prefix for driver-local bookkeeping that must NEVER cross the wire
# (CoreWorker._strip_spec removes these before pushing).
LOCAL_KEY_PREFIX = "_"

# Precomputed so the per-submission validator doesn't rebuild the allowed
# set from TypedDict.__annotations__ on every task (hot path).
_DECLARED_KEYS = frozenset(TaskSpec.__annotations__)


def _validation_enabled() -> bool:
    return os.environ.get("RAY_TPU_VALIDATE_SPECS", "1") != "0"


def validate_task_spec(spec: dict[str, Any], *, actor: bool = False):
    """Schema check at the PRODUCER (always on; see module docstring).
    Raises ValueError naming exactly what drifted."""
    if not _validation_enabled():
        return
    required = REQUIRED_ACTOR_KEYS if actor else REQUIRED_TASK_KEYS
    missing = required - spec.keys()
    if missing:
        raise ValueError(
            f"task spec missing required keys {sorted(missing)} "
            f"(schema: _private/task_spec.py)")
    # set-difference FIRST: the per-key startswith loop only runs over
    # leftovers, which are empty for every well-formed spec (hot path)
    unknown = spec.keys() - _DECLARED_KEYS
    if unknown:
        unknown = {k for k in unknown
                   if not k.startswith(LOCAL_KEY_PREFIX)}
    if unknown:
        raise ValueError(
            f"task spec carries undeclared keys {sorted(unknown)} — "
            f"declare them in _private/task_spec.py (the schema is the "
            f"contract both ends compile against)")
    if len(spec.get("task_id", b"")) != 16:
        raise ValueError("task_id must be 16 bytes")
    for rid in spec.get("return_ids", ()):
        if len(rid) != 16:
            raise ValueError("return ids must be 16 bytes")


# --------------------------------------------------------- control RPCs
#
# Producer-side shape checks for the top non-task control messages
# (lease request/grant, actor creation, KV put, pubsub ack). Same
# contract as validate_task_spec: a typo'd field fails AT THE PRODUCER
# with the schema location in the message, instead of a KeyError (or a
# silently-ignored kwarg) on the consumer side. Gated by the same
# RAY_TPU_VALIDATE_SPECS switch.

# strategy keys the raylet lease scheduler understands
# (raylet.rpc_request_worker_lease + the PG/spread policies)
LEASE_STRATEGY_KEYS = frozenset({
    "placement_group_id", "bundle_index", "node_id", "soft", "spread",
    "no_spill", "job",
})

# keys the lessee reads off a grant (_LeasedWorker + return_lease)
REQUIRED_GRANT_KEYS = frozenset({
    "lease_id", "worker_id", "worker_addr", "node_id",
})

# actor-creation spec keys (producer: CoreWorker.create_actor; consumers:
# GCS actor table + raylet _create_actor_locally + worker become_actor)
REQUIRED_ACTOR_SPEC_KEYS = frozenset({
    "class_hash", "class_name", "args", "resources", "max_restarts",
    "max_task_retries", "owner_addr", "job_id",
})


def _fail(what: str, detail: str):
    raise ValueError(
        f"{what}: {detail} (schema: _private/task_spec.py)")


def validate_lease_request(resources: dict, strategy: dict | None):
    if not _validation_enabled():
        return
    if not isinstance(resources, dict):
        _fail("lease request", f"resources must be a dict, "
              f"got {type(resources).__name__}")
    for k, v in resources.items():
        if not isinstance(k, str):
            _fail("lease request", f"resource name {k!r} is not a str")
        if not isinstance(v, (int, float)) or v < 0:
            _fail("lease request",
                  f"resource {k!r} amount {v!r} is not a number >= 0")
    if strategy:
        unknown = strategy.keys() - LEASE_STRATEGY_KEYS
        if unknown:
            _fail("lease request",
                  f"unknown strategy keys {sorted(unknown)} — declare "
                  f"them in LEASE_STRATEGY_KEYS")


def validate_lease_grant(grant: dict):
    if not _validation_enabled():
        return
    missing = REQUIRED_GRANT_KEYS - grant.keys()
    if missing:
        _fail("lease grant", f"missing keys {sorted(missing)}")


def validate_actor_spec(actor_id: bytes, spec: dict):
    if not _validation_enabled():
        return
    if len(actor_id) != 16:
        _fail("actor registration", "actor_id must be 16 bytes")
    if not isinstance(spec, dict):
        _fail("actor registration", "spec must be a dict")
    missing = REQUIRED_ACTOR_SPEC_KEYS - spec.keys()
    if missing:
        _fail("actor registration", f"missing spec keys {sorted(missing)}")


def validate_kv_put(ns: str, key: bytes, value: bytes):
    if not _validation_enabled():
        return
    if not isinstance(ns, str):
        _fail("kv_put", f"namespace must be str, got {type(ns).__name__}")
    if not isinstance(key, (bytes, bytearray)):
        _fail("kv_put", f"key must be bytes, got {type(key).__name__}")
    if not isinstance(value, (bytes, bytearray, memoryview)):
        _fail("kv_put",
              f"value must be bytes, got {type(value).__name__} — "
              f"serialize before the control plane, not after")


def validate_pubsub_ack(sub_id: str, after_seq: int):
    if not _validation_enabled():
        return
    if not isinstance(sub_id, str) or not sub_id:
        _fail("pubsub poll/ack", f"sub_id must be a non-empty str, "
              f"got {sub_id!r}")
    if not isinstance(after_seq, int) or after_seq < 0:
        _fail("pubsub poll/ack",
              f"after_seq must be an int >= 0, got {after_seq!r}")


# method -> kwargs validator, consulted by the GCS client boundary
# (protocol.ReconnectingRpcClient) so every producer of these messages
# is covered without per-call-site plumbing.
def _check_kv_put(kw):
    validate_kv_put(kw.get("ns"), kw.get("key"), kw.get("value"))


def _check_register_actor(kw):
    validate_actor_spec(kw.get("actor_id", b""), kw.get("spec", {}))


def _check_psub_poll(kw):
    validate_pubsub_ack(kw.get("sub_id", ""), kw.get("after_seq", -1))


def _check_lease_request(kw):
    validate_lease_request(kw.get("resources", {}), kw.get("strategy"))


CONTROL_RPC_VALIDATORS = {
    "kv_put": _check_kv_put,
    "register_actor": _check_register_actor,
    "psub_poll": _check_psub_poll,
    "request_worker_lease": _check_lease_request,
}


def validate_control_rpc(method: str, kwargs: dict):
    """Producer-boundary dispatch: validates the message shape of the
    top control RPCs; unknown methods pass through untouched."""
    fn = CONTROL_RPC_VALIDATORS.get(method)
    if fn is not None:
        fn(kwargs)
