"""Central config table with env-var overrides.

TPU-native analog of the reference's RAY_CONFIG macro table
(/root/reference/src/ray/common/ray_config_def.h:32 — 179 entries,
each overridable via a `RAY_<name>` env var and propagable cluster-wide).
Here each entry is declared once in _CONFIG_DEFS and overridable via
`RAY_TPU_<NAME>`; `system_config` overrides passed to `init()` win over env.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict

_CONFIG_DEFS: Dict[str, Any] = {
    # --- scheduling ---
    "worker_lease_timeout_ms": 30_000,
    "worker_pool_min_size": 0,
    "worker_register_timeout_s": 60.0,  # worker process spawn+import budget
    "worker_pool_idle_timeout_s": 120.0,
    "max_tasks_in_flight_per_worker": 2,  # lease pipelining depth
    "scheduler_spread_threshold": 0.5,  # hybrid policy pack→spread knob
    "scheduler_top_k_fraction": 0.2,
    "lease_soft_cap": 0,               # 0 = auto: 2x cluster CPUs
    "actor_resolution_poll_max_s": 1.0,  # backoff cap for pending actors
    # --- worker pool ---
    "prestart_workers": 4,             # warm-pool watermark per node
    "idle_worker_cap": 8,              # max idle processes kept per node
    "max_startup_concurrency": 0,      # 0 = auto: one per core
    # --- TPU probing ---
    "chip_probe_timeout_s": 60.0,      # subprocess jax.devices() budget
    # --- object store ---
    "object_store_memory_default": 256 * 1024 * 1024,
    "object_store_full_delay_ms": 10,
    "object_store_full_max_retries": 500,
    "object_spilling_threshold": 0.8,
    "min_spilling_size_bytes": 1024 * 1024,
    "max_io_workers": 2,
    "inline_object_max_size_bytes": 100 * 1024,  # small results ride the RPC reply
    "object_transfer_chunk_bytes": 4 * 1024 * 1024,
    "pull_max_inflight_bytes": 256 * 1024 * 1024,  # pull admission control
    # --- memory anatomy (_private/memory_anatomy.py) ---
    # Leak-sweep grace window: objects younger than this are referenced
    # by definition (an in-flight collective segment between put and
    # consume must not classify as a leak).
    "memory_sweep_grace_s": 5.0,
    # Periodic background sweep cadence per worker process (0 disables
    # the timer; sweeps still run on demand from summarize_memory /
    # the flight recorder / the memory-snapshot RPC).
    "memory_sweep_interval_s": 30.0,
    # Bounded provenance-op ring per process (the flight recorder's
    # memory.jsonl window).
    "memory_ring_size": 2048,
    # Bounded best-effort re-send of free fan-outs on the one-way
    # owner→GCS→raylet delete pipeline: when the GCS finds no live
    # raylet connection for a holder node, retry the push once after
    # re-resolving the connection (the counted drop otherwise strands
    # the object until the leak sweep names it). 0 disables.
    "store_free_resend": 1,
    # --- lineage / reconstruction ---
    "max_lineage_bytes": 64 * 1024 * 1024,  # retained task specs for rebuild
    # --- fault tolerance ---
    "task_max_retries_default": 3,
    "actor_max_restarts_default": 0,
    "health_check_period_ms": 1_000,
    "health_check_failure_threshold": 5,
    "gcs_rpc_timeout_s": 30.0,
    # --- unified control-plane retry policy (_private/retry.py) ---
    "rpc_retry_max_attempts": 5,        # per-call attempt cap
    "rpc_retry_base_backoff_s": 0.05,   # full-jitter backoff base
    "rpc_retry_max_backoff_s": 2.0,     # backoff cap
    "rpc_retry_deadline_s": 90.0,       # total budget across attempts
    # --- memory monitor ---
    "memory_monitor_refresh_ms": 250,
    "memory_usage_threshold": 0.95,
    "memory_monitor_kill_cooldown_s": 5.0,  # re-kill while still over
    # --- runtime envs ---
    "runtime_env_dir": "/tmp/ray_tpu/runtime_envs",
    "runtime_env_cache_max": 8,        # unreferenced envs kept (LRU)
    # --- logs ---
    "log_monitor_interval_ms": 250,    # worker-log tail cadence
    # --- serve ---
    "serve_stream_chunk_timeout_s": 300.0,  # first chunk may be a compile
    # serve-as-a-tenant (apps registered with a job): CPU bundle each
    # replica's capacity placement group reserves when the deployment's
    # ray_actor_options carry no num_cpus of their own
    "serve_replica_capacity_cpu": 1.0,
    # 0 restores the legacy direct-stop scale-down for tenant apps
    # (bit-identical kill switch: no preemption-warning round trip, no
    # draining broadcast — replicas stop the pre-tenant way)
    "serve_preempt_scale_down": 1,
    # --- collective / mesh ---
    "collective_default_backend": "xla",
    "collective_op_timeout_s": 300.0,  # dead-member detector of last resort
    # Gang fault tolerance (ray_tpu.train + util/collective): the group's
    # rendezvous actor watches the GCS actor-death feed and POISONS the
    # group when a member dies — surviving ranks' pending/future
    # collective ops raise CollectiveGroupError (naming the dead rank)
    # well under the op timeout, and members that directly observe a peer
    # connection drop poison the group themselves.
    # RAY_TPU_COLLECTIVE_DEATH_POISONING=0 falls back to timeout-only
    # detection.
    "collective_death_poisoning": True,
    # Driver-side gang death monitor (train.BackendExecutor): subscribes
    # to actor-death events for the training workers so a rank death
    # surfaces as TrainWorkerGroupError(dead_ranks=...) within seconds.
    # Kill switch: RAY_TPU_TRAIN_DEATH_MONITOR=0.
    "train_death_monitor": True,
    # Bucketed data-parallel gradient sync (train/ddp.py): partition the
    # grad pytree into size-targeted buckets and launch each bucket's
    # allreduce asynchronously so comm overlaps the rest of the backward
    # walk + pack/unpack. Kill switch RAY_TPU_TRAIN_BUCKET_DDP=0 =
    # legacy single synchronous allreduce over the whole flattened tree
    # (bit-identical at world 2 — see README "Overlapped gradient
    # sync" for the determinism contract).
    "train_bucket_ddp": True,
    "train_grad_bucket_bytes": 4 * 1024 * 1024,   # target bucket size
    # DDP sync shape (train/ddp.py): "allreduce" (legacy default —
    # every rank gets the full synced tree) or "reducescatter"
    # (ZeRO-style — each rank gets only its shard of every bucket;
    # pair with train.ddp.ZeroOptimizer for sharded optimizer state
    # and async param allgathers). The default stays bit-identical to
    # the pre-sharding behavior.
    "train_ddp_mode": "allreduce",
    # Sharded checkpointing (train/sharded_checkpoint.py). checkpoint_dir
    # is the generation root for standalone (non-trainer) use — trainers
    # plumb their storage_path instead. checkpoint_async moves the shard
    # disk write to a background thread (the two-phase commit still runs
    # at the caller's next harvest point); 0 = fully synchronous saves.
    # checkpoint_fsync=0 is a TEST-ONLY kill switch skipping the
    # fsync-file + fsync-dir calls in _private/atomic_write.py.
    "checkpoint_dir": "",
    "checkpoint_async": True,
    "checkpoint_fsync": True,
    # Pipelined host-collective data path (util/collective/host_backend):
    # one-way zero-copy segment sends, double-buffered so the reduce of
    # segment k overlaps the transfer of segment k+1. Pipeline kill
    # switch: RAY_TPU_COLLECTIVE_PIPELINE=0 restores the legacy
    # synchronous request/reply ring exactly.
    "collective_pipeline": True,
    "collective_segment_bytes": 4 * 1024 * 1024,  # ring segment size
    # Block-quantized wire formats (util/collective/wire.py): "off"
    # (default, bit-exact), "bf16" (2x smaller wire) or "int8" (per-
    # block float32 scales, ~4x smaller). Applies to float32 sum
    # allreduce/reducescatter segments on the pipelined path only;
    # everything else keeps the exact framing.
    # RAY_TPU_COLLECTIVE_WIRE_DTYPE mirrors RAY_TPU_COLLECTIVE_PIPELINE
    # as the per-group env knob.
    "collective_wire_dtype": "off",
    "collective_quant_block": 1024,   # int8 scale-block size (elements)
    # Same-node segment transport: ranks sharing a node exchange ring
    # segments as shared-memory store references (one copy in, zero-copy
    # pinned view out; forwarded hops pass the same object id) instead
    # of socket bytes. RAY_TPU_COLLECTIVE_SHM=0 forces sockets.
    "collective_shm": True,
    # Intra-host-first hierarchy: "auto" reduces within each host and
    # rings one leader per host when the membership spans >1 host with
    # co-located ranks (the DCN/ICI split); "1" forces it (tests), "0"
    # disables.
    "collective_hierarchy": "auto",
    # --- collective data-plane telemetry (util/collective/telemetry.py) ---
    "collective_timing_flush_s": 0.25,      # rank-timing flush cadence
    "collective_straggler_multiple": 3.0,   # lag > multiple * median lag
    "collective_straggler_min_lag_s": 0.05,  # floor: ignore µs jitter in
                                             # tight groups (median ~ 0)
    # --- multi-slice MPMD pipeline training (train/pipeline/) ---
    # Default wire format for inter-stage activation/grad hops: "off"
    # (exact), "bf16" (the classic half-width activation wire; ~2x
    # smaller inter-slice traffic, error <= 2^-8 * |x| per element) or
    # "int8" (per-block scales). PipelineConfig.wire_dtype overrides
    # per trainer; gradients always travel exact unless
    # pipeline_quantize_grads is also set.
    "pipeline_wire_dtype": "off",
    "pipeline_quantize_grads": False,
    # GPipe in-flight window: how many un-acked microbatch activations
    # a stage may have posted downstream before it parks for an ack
    # credit (bounds the receiver's mailbox/activation memory under
    # one-way pushes). 0 = unbounded. 1F1B ignores it — its warmup
    # depth (<= P - stage) is the inherent bound.
    "pipeline_inflight_window": 0,
    # --- step anatomy (_private/step_anatomy.py) ---
    # Rolling-baseline step-time regression detector: compare p50 of the
    # last `window` steps against p50 of the window before it; fire a
    # STEP_REGRESSION event + counter when recent > multiple * baseline.
    # window=0 disables the detector (anatomy recording stays on).
    "step_regression_multiple": 2.0,
    "step_regression_window": 20,
    "mesh_ici_axis_order": "dp,pp,ep,sp,tp",  # slowest→fastest varying axes
    # --- control plane at scale (cluster soak, _private/sim_cluster.py) ---
    # Death-feed coalescing: node deaths arriving within the window are
    # swept in ONE locked pass and (at >= gcs_death_batch_min of them)
    # fanned out as ONE `batch_dead` message + NODE_BATCH_DEAD event
    # instead of per-death broadcasts. 0 disables coalescing (every
    # death sweeps and broadcasts individually, the pre-PR-12 path).
    "gcs_death_coalesce_window_s": 0.05,
    "gcs_death_batch_min": 3,
    # Bounded admission on registration bursts: concurrent register_node
    # bodies beyond this queue on the gate (clients retry under the
    # unified policy if their wait exceeds the RPC timeout).
    "gcs_register_max_concurrent": 16,
    # Reconnect herd damping: every ReconnectingRpcClient sleeps
    # uniform(0, this) before dialing a lost endpoint, so a GCS restart
    # at 100 nodes doesn't eat one synchronized reconnect+replay storm.
    # 0 restores immediate reconnects.
    "gcs_reconnect_jitter_s": 0.2,
    # --- multi-tenant control plane (jobs/quotas/preemption, gcs.py) ---
    # Grace window between the PREEMPTION warning a victim placement
    # group receives and the GCS reclaiming its bundles: the Train
    # plane uses it to cut a checkpoint so the victim loses at most the
    # post-checkpoint steps, not the run.
    "gcs_preempt_grace_s": 5.0,
    # PlacementGroup.ready()/wait() ride the `pg_state` pubsub channel;
    # this is the cadence of the direct-RPC FALLBACK poll kept
    # underneath it (a missed transition can't hang a waiter past one
    # fallback period; PR 12's snapshot-resync covers feed gaps).
    "pg_wait_poll_fallback_s": 2.0,
    # --- misc ---
    "rpc_max_message_bytes": 512 * 1024 * 1024,
    "pubsub_poll_timeout_s": 30.0,
    "pubsub_max_mailbox": 1000,           # long-poll mailbox bound (drop-oldest)
    "pubsub_subscriber_timeout_s": 60.0,  # GC long-pollers gone this long
    "client_poll_slice_s": 60.0,          # ray:// get/wait re-poll granularity
    "actor_creation_rpc_timeout_s": 330.0,  # driver->raylet create_actor
                                          # RPC; raise when worker spawn
                                          # is slow
    "client_session_ttl_s": 60.0,         # ray:// reconnect grace: session
                                          # state survives a dropped socket
                                          # this long
    "client_chunk_bytes": 4 * 1024 * 1024,  # ray:// get/put chunk size —
                                          # bounds per-frame size on the
                                          # shared client socket
    "event_log_max_bytes": 16 * 1024 * 1024,
    "metrics_report_interval_ms": 2_000,
    "log_to_driver": True,
}


class _Config:
    def __init__(self):
        self._values = dict(_CONFIG_DEFS)
        self._system_overrides: set = set()
        for name, default in _CONFIG_DEFS.items():
            env = os.environ.get("RAY_TPU_" + name.upper())
            if env is not None:
                self._values[name] = _parse(env, default)

    def __getattr__(self, name: str):
        try:
            return self._values[name]
        except KeyError:
            raise AttributeError(name) from None

    def apply_system_config(self, overrides: Dict[str, Any] | None):
        if not overrides:
            return
        for k, v in overrides.items():
            if k not in self._values:
                raise ValueError(f"Unknown system config key: {k}")
            self._values[k] = v
            self._system_overrides.add(k)

    def system_override_env(self) -> Dict[str, str]:
        """init(system_config=...) overrides as RAY_TPU_<NAME> env vars.
        The raylet injects these into spawned worker processes so keys
        consumed worker-side (runtime_env_dir, serve stream timeout, ...)
        honor the driver's overrides — without this, system_config would
        silently apply only in the driver process."""
        out = {}
        for k in self._system_overrides:
            v = self._values[k]
            if isinstance(v, bool):
                v = "1" if v else "0"
            elif isinstance(v, (dict, list)):
                v = json.dumps(v)
            out["RAY_TPU_" + k.upper()] = str(v)
        return out

    def reset_system_config(self):
        """Drop init(system_config=...) overrides (called at shutdown so
        one driver's overrides don't leak into the next init in the same
        process — test isolation depends on this)."""
        for k in self._system_overrides:
            env = os.environ.get("RAY_TPU_" + k.upper())
            self._values[k] = (_parse(env, _CONFIG_DEFS[k])
                               if env is not None else _CONFIG_DEFS[k])
        self._system_overrides.clear()

    def snapshot(self) -> Dict[str, Any]:
        return dict(self._values)


def _parse(env: str, default: Any):
    if isinstance(default, bool):
        return env.lower() in ("1", "true", "yes")
    if isinstance(default, int):
        return int(env)
    if isinstance(default, float):
        return float(env)
    if isinstance(default, (dict, list)):
        return json.loads(env)
    return env


GlobalConfig = _Config()


def get_config(name: str):
    """Read one config value. Precedence (matching the module contract and
    the reference's RayConfig): init(system_config=...) > `RAY_TPU_<NAME>`
    env (read live, so tests/operators can set it after import) > default."""
    if name not in GlobalConfig._system_overrides:
        env = os.environ.get("RAY_TPU_" + name.upper())
        if env is not None:
            return _parse(env, _CONFIG_DEFS[name])
    return getattr(GlobalConfig, name)
