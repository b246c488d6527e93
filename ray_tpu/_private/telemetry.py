"""Internal runtime metric catalog — every core metric declared in one place.

The user-facing primitives live in ray_tpu/util/metrics.py (Counter /
Gauge / Histogram, aggregated by `metrics_summary()` and rendered at the
dashboard's /metrics). This module is the RUNTIME'S OWN use of them:
transports, scheduler, object store, retry/fault plane. Reference tier:
Ray's core "system metrics" (ray_grpc_server_*, ray_scheduler_*,
ray_object_store_*) emitted by core components into the same Prometheus
pipeline user metrics ride.

Contract (enforced by the catalog lint in tests/test_telemetry_metrics.py):

- every internal metric name is declared HERE, in ``CATALOG``;
- names are ``ray_tpu_``-prefixed and end in a unit suffix from
  ``ALLOWED_SUFFIXES`` (Prometheus naming conventions);
- call sites reference metrics through ``counter_inc`` / ``gauge_set`` /
  ``observe`` by catalog name — an undeclared name raises KeyError at
  the call site, so instrumentation can't drift from the catalog.

Overhead: the disabled path (``RAY_TPU_INTERNAL_TELEMETRY=0``) is one
module-global bool check per call site. Enabled, a recording is one
dict lookup + the util/metrics lock'd update (~1-2µs) — noise against
the RPC/store operation it measures; nothing extra happens when no
scraper reads /metrics (recording cost is the whole cost).
"""
from __future__ import annotations

import os
import threading

ENABLED = os.environ.get("RAY_TPU_INTERNAL_TELEMETRY", "1") != "0"

# Prometheus-convention unit suffixes internal metric names must end in
# (counters additionally use `_total` per convention; `_tasks` /
# `_messages` are the "unit is the thing counted" form for gauges;
# `_ratio` is the Prometheus-convention dimensionless 0..1 form).
ALLOWED_SUFFIXES = ("_total", "_seconds", "_bytes", "_tasks", "_messages",
                    "_ratio", "_blocks", "_objects")

_RPC_BOUNDARIES = [0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0]

# name -> spec. `kind` is the util/metrics class name; `tags` the label
# keys call sites pass (bounded cardinality: method names, roles,
# node ids — never task/object ids).
CATALOG: dict[str, dict] = {
    # --- transports (protocol.py / native_rpc.py) ---
    "ray_tpu_rpc_latency_seconds": {
        "kind": "Histogram", "tags": ("method", "role"),
        "boundaries": _RPC_BOUNDARIES,
        "description": "Client-observed latency of synchronous "
                       "control-plane RPC calls",
    },
    "ray_tpu_rpc_errors_total": {
        "kind": "Counter", "tags": ("method", "role", "kind"),
        "description": "Synchronous RPC calls that failed "
                       "(kind=timeout|connection_lost)",
    },
    # --- unified retry policy (retry.py) ---
    "ray_tpu_retry_attempts_total": {
        "kind": "Counter", "tags": ("method",),
        "description": "Actual retries executed under the control-plane "
                       "retry policy (first attempts are not counted)",
    },
    "ray_tpu_retry_budget_exhausted_total": {
        "kind": "Counter", "tags": (),
        "description": "Retries refused because the process-wide retry "
                       "budget was drained",
    },
    # --- fault injection (fault_injection.py) ---
    "ray_tpu_faults_injected_total": {
        "kind": "Counter", "tags": ("action", "method"),
        "description": "Fault-injection rules fired, by action "
                       "(drop/delay/dup/disconnect/slow_reply) and method",
    },
    # --- scheduler (raylet.py) ---
    "ray_tpu_scheduler_queue_tasks": {
        "kind": "Gauge", "tags": ("node_id",),
        "description": "Lease/actor-creation requests queued on this "
                       "raylet waiting for resources",
    },
    "ray_tpu_lease_grant_latency_seconds": {
        "kind": "Histogram", "tags": ("node_id",),
        "boundaries": _RPC_BOUNDARIES,
        "description": "Time from lease request arrival to local grant "
                       "(spillbacks excluded)",
    },
    # --- object store (store_client.py) ---
    "ray_tpu_object_store_put_bytes_total": {
        "kind": "Counter", "tags": (),
        "description": "Bytes written into the local shared-memory "
                       "object store (including spilled puts)",
    },
    "ray_tpu_object_store_get_total": {
        "kind": "Counter", "tags": ("result",),
        "description": "Local object-store lookups (result=hit|miss)",
    },
    # --- memory anatomy (memory_anatomy.py provenance ledger) ---
    "ray_tpu_store_bytes": {
        "kind": "Gauge", "tags": ("category", "state"),
        "description": "Live object-store bytes by provenance category "
                       "(task_arg/task_return/collective_segment/"
                       "serve_weights/data_staging/checkpoint/other), "
                       "state=live",
    },
    "ray_tpu_store_objects": {
        "kind": "Gauge", "tags": ("category",),
        "description": "Live object-store object count by provenance "
                       "category",
    },
    "ray_tpu_store_orphan_bytes": {
        "kind": "Gauge", "tags": ("category", "reason"),
        "description": "Bytes the leak sweep classified as orphaned "
                       "(reason=owner_dead|group_destroyed|epoch_stale; "
                       "category=all,reason=all carries the sum)",
    },
    "ray_tpu_store_frees_dropped_total": {
        "kind": "Counter", "tags": ("stage",),
        "description": "Deletes lost on the one-way owner→GCS→raylet "
                       "free pipeline "
                       "(stage=owner_push|gcs_fanout|raylet_delete)",
    },
    "ray_tpu_store_free_resends_total": {
        "kind": "Counter", "tags": (),
        "description": "Bounded best-effort re-sends of free fan-outs "
                       "whose first push found no raylet connection "
                       "(config store_free_resend)",
    },
    # --- train-state accounting (ddp.py / train_step.py) ---
    "ray_tpu_train_state_bytes": {
        "kind": "Gauge", "tags": ("kind", "rank"),
        "description": "Exact per-rank train-state bytes from the "
                       "deterministic flatten "
                       "(kind=params|grads|opt_state|bucket_inflight) — "
                       "the gauge the ZeRO arc diffs before/after "
                       "sharding",
    },
    # --- durable GCS store (gcs_store.py) ---
    "ray_tpu_gcs_store_ops_total": {
        "kind": "Counter", "tags": ("backend", "op"),
        "description": "Durable GCS store operations, by backend "
                       "(sqlite/log/memory) and op (put/get/delete)",
    },
    # --- pubsub (pubsub.py) ---
    "ray_tpu_pubsub_backlog_messages": {
        "kind": "Gauge", "tags": (),
        "description": "Messages parked in long-poll subscriber "
                       "mailboxes after the latest publish",
    },
    "ray_tpu_pubsub_dropped_total": {
        "kind": "Counter", "tags": (),
        "description": "Messages dropped by mailbox overflow "
                       "(slow long-poll consumers)",
    },
    "ray_tpu_pubsub_resyncs_total": {
        "kind": "Counter", "tags": (),
        "description": "Snapshot-resyncs performed by long-poll "
                       "subscribers after a feed gap (mailbox overflow "
                       "or publisher-side GC)",
    },
    # --- GCS control plane at scale (gcs.py, cluster soak) ---
    "ray_tpu_gcs_death_fanout_seconds": {
        "kind": "Histogram", "tags": (),
        "boundaries": _RPC_BOUNDARIES,
        "description": "Wall time of the off-lock death-feed broadcast "
                       "per swept node-death batch (coalesced or "
                       "single)",
    },
    "ray_tpu_gcs_register_throttled_total": {
        "kind": "Counter", "tags": (),
        "description": "register_node calls that queued on the bounded "
                       "admission gate during a registration burst",
    },
    # --- multi-tenant scheduling (gcs.py job registry) ---
    # job names are operator-chosen and bounded (one per tenant /
    # workload), the same cardinality class as Serve deployment names
    "ray_tpu_preemptions_total": {
        "kind": "Counter", "tags": ("job",),
        "description": "Placement groups preempted (bundles reclaimed "
                       "after the grace window) per victim job — the "
                       "priority plane's graceful-degradation counter",
    },
    "ray_tpu_quota_rejections_total": {
        "kind": "Counter", "tags": ("job",),
        "description": "Admissions refused because they would push a "
                       "job over its resource quota: placement groups "
                       "held PENDING at the GCS (counted once per "
                       "transition into the blocked state) and leases "
                       "throttled at raylet grant",
    },
    "ray_tpu_job_dominant_share_ratio": {
        "kind": "Gauge", "tags": ("job",),
        "description": "Each job's dominant resource share — max over "
                       "resources of usage / (quota if set, else "
                       "cluster total) — the weight the fair-share "
                       "scheduler orders pending bundles by",
    },
    # --- event log (events.py) ---
    "ray_tpu_events_dropped_total": {
        "kind": "Counter", "tags": (),
        "description": "Structured events dropped from the bounded "
                       "per-process event ring",
    },
    # --- collective data plane (util/collective/telemetry.py) ---
    # group names are operator-chosen but bounded (one per worker gang /
    # Tune trial family), same cardinality class as method names
    "ray_tpu_collective_latency_seconds": {
        "kind": "Histogram", "tags": ("op", "backend", "group"),
        "boundaries": [0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0,
                       5.0, 30.0],
        "description": "Caller-observed wall time of one collective op "
                       "on one rank (allreduce/broadcast/.../barrier, "
                       "host and xla backends)",
    },
    "ray_tpu_collective_bytes_total": {
        "kind": "Counter", "tags": ("op", "backend", "group"),
        "description": "Per-rank payload bytes moved through collective "
                       "ops (payload, not wire bytes — algorithm-"
                       "independent)",
    },
    "ray_tpu_collective_stragglers_total": {
        "kind": "Counter", "tags": ("group", "op"),
        "description": "Ranks flagged by the straggler detector (arrival "
                       "lag > configured multiple of the group median)",
    },
    "ray_tpu_collective_segments_total": {
        "kind": "Counter", "tags": ("op", "group"),
        "description": "Ring segments sent by the pipelined host "
                       "collective data path (one-way zero-copy frames; "
                       "0 when RAY_TPU_COLLECTIVE_PIPELINE=0)",
    },
    "ray_tpu_collective_wire_bytes_total": {
        "kind": "Counter", "tags": ("op", "group", "format"),
        "description": "Actual ring-segment bytes this rank put on the "
                       "wire (socket or shm), by wire format "
                       "(format=off|bf16|int8; forwarded frames count "
                       "under the op's active format). Against "
                       "ray_tpu_collective_bytes_total's payload bytes "
                       "this is the live compression ratio",
    },
    "ray_tpu_collective_quant_error_ratio": {
        "kind": "Histogram", "tags": ("op", "format"),
        "boundaries": [1e-5, 1e-4, 2.5e-4, 5e-4, 1e-3, 2e-3, 4e-3,
                       8e-3, 2e-2],
        "description": "Measured max-abs quantization error of one "
                       "sampled segment per collective op, normalized "
                       "by the segment's absmax (bf16 bound: 2^-8 ~ "
                       "0.0039 of each element; int8 bound: 1/254 ~ "
                       "0.0039 of the block absmax)",
    },
    # --- async collective plane (util/collective/async_handles.py) ---
    "ray_tpu_collective_async_inflight_tasks": {
        "kind": "Gauge", "tags": ("group",),
        "description": "Async collective ops submitted but not yet "
                       "completed on this rank (queued on the group's "
                       "issue thread + the op currently on the wire)",
    },
    # --- bucketed DDP gradient sync (train/ddp.py) ---
    "ray_tpu_train_buckets_total": {
        "kind": "Counter", "tags": ("group",),
        "description": "Gradient-sync buckets launched by "
                       "train.ddp.sync_gradients (one async allreduce "
                       "each; 0 when RAY_TPU_TRAIN_BUCKET_DDP=0)",
    },
    "ray_tpu_train_bucket_bytes": {
        "kind": "Histogram", "tags": ("group",),
        "boundaries": [65536, 262144, 1048576, 4194304, 16777216,
                       67108864, 268435456],
        "description": "Payload size of one gradient-sync bucket "
                       "(packed contiguous grads; targeted by "
                       "RAY_TPU_TRAIN_GRAD_BUCKET_BYTES)",
    },
    "ray_tpu_train_bucket_sync_seconds": {
        "kind": "Histogram", "tags": ("group",),
        "boundaries": [0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0,
                       5.0, 30.0],
        "description": "Launch-to-completion latency of one bucket's "
                       "async allreduce (background comm; compare "
                       "against _bucket_wait_seconds — the exposed "
                       "part — for the live overlap fraction)",
    },
    "ray_tpu_train_bucket_wait_seconds": {
        "kind": "Histogram", "tags": ("group",),
        "boundaries": [0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1,
                       0.5, 1.0, 5.0],
        "description": "Wall time the train loop was actually BLOCKED "
                       "in handle.wait() per bucket at the optimizer "
                       "boundary — the comm the backward pass failed "
                       "to hide",
    },
    "ray_tpu_train_param_gather_seconds": {
        "kind": "Histogram", "tags": ("group",),
        "boundaries": [0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0,
                       5.0, 30.0],
        "description": "Launch-to-completion latency of one bucket's "
                       "async param-shard allgather (ZeRO mode: the "
                       "updated shard returning to every rank; "
                       "background comm riding the issue thread)",
    },
    "ray_tpu_train_param_gather_wait_seconds": {
        "kind": "Histogram", "tags": ("group",),
        "boundaries": [0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1,
                       0.5, 1.0, 5.0],
        "description": "Wall time the train loop was actually BLOCKED "
                       "waiting a param-shard allgather at first use "
                       "of the new params (ZeRO mode) — the gather "
                       "comm the inter-step window failed to hide",
    },
    # --- gang fault tolerance (train/, util/collective) ---
    "ray_tpu_train_gang_restarts_total": {
        "kind": "Counter", "tags": ("group",),
        "description": "Training gang restarts driven by fit()'s "
                       "FailureConfig retry loop (teardown + rebuild + "
                       "checkpoint resume after a worker/rank failure)",
    },
    "ray_tpu_collective_groups_poisoned_total": {
        "kind": "Counter", "tags": ("group",),
        "description": "Collective groups poisoned in this process after "
                       "a member death (pending/future ops raise "
                       "CollectiveGroupError instead of hanging)",
    },
    "ray_tpu_collective_stale_epoch_total": {
        "kind": "Counter", "tags": ("group",),
        "description": "Collective frames / shm notifies rejected at "
                       "ingest because they carried a previous group "
                       "incarnation's epoch (plus dead-epoch mailbox "
                       "entries swept at group rejoin)",
    },
    # --- multi-slice MPMD pipeline training (train/pipeline/) ---
    # stage indices are bounded (pipeline depth, single digits in
    # practice); group names are the same cardinality class as
    # collective groups
    "ray_tpu_pipeline_bubble_seconds": {
        "kind": "Histogram", "tags": ("group", "stage"),
        "boundaries": [0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0,
                       30.0],
        "description": "Per-step wall time one pipeline stage spent "
                       "parked in schedule stalls (waiting for an "
                       "upstream activation, a downstream gradient, or "
                       "an in-flight-window credit) — the measured "
                       "bubble the (P-1)/(M+P-1) schedule theory "
                       "predicts",
    },
    "ray_tpu_pipeline_microbatches_total": {
        "kind": "Counter", "tags": ("group", "stage", "phase"),
        "description": "Microbatches processed by one pipeline stage, "
                       "split by phase (forward/backward)",
    },
    "ray_tpu_pipeline_step_seconds": {
        "kind": "Histogram", "tags": ("group", "stage"),
        "boundaries": [0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0, 120.0],
        "description": "Wall time of one optimizer step on one pipeline "
                       "stage (all microbatch forwards + backwards + "
                       "the intra-stage grad allreduce + the update)",
    },
    # --- streaming data plane (data/_internal/streaming/) ---
    # consumer names are bounded: "default", bench harness labels, or
    # train/<dataset>/rank<k> (one per gang member) — same cardinality
    # class as collective group names
    "ray_tpu_data_wait_seconds": {
        "kind": "Histogram", "tags": ("consumer",),
        "boundaries": [0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1,
                       0.5, 1.0, 5.0],
        "description": "Wall time a dataset consumer was blocked "
                       "waiting for its next batch (fetch + slice + "
                       "device transfer not yet overlapped) — the "
                       "input-gates-the-step signal; per-step data "
                       "wait / step time is the ingest health ratio",
    },
    "ray_tpu_data_prefetch_depth_blocks": {
        "kind": "Gauge", "tags": ("consumer",),
        "description": "Blocks currently buffered ahead of a streaming "
                       "dataset consumer (bounded by "
                       "RAY_TPU_DATA_PREFETCH_BLOCKS; pinned in the shm "
                       "store, not heap copies)",
    },
    "ray_tpu_data_blocks_total": {
        "kind": "Counter", "tags": ("consumer", "source"),
        "description": "Blocks fed to streaming dataset consumers by "
                       "origin (source=local|remote): locality-aware "
                       "pull ordering should keep remote pulls a "
                       "minority when blocks were produced on this node",
    },
    # --- pjit compile path (parallel/compile_watch.py) ---
    "ray_tpu_pjit_compile_seconds": {
        "kind": "Histogram", "tags": ("fn",),
        "boundaries": [0.1, 0.5, 1.0, 5.0, 15.0, 60.0, 300.0, 1200.0],
        "description": "Wall time of a compile-cache-miss call of an "
                       "instrumented jitted function (trace + XLA "
                       "compile + first run)",
    },
    "ray_tpu_pjit_cache_total": {
        "kind": "Counter", "tags": ("fn", "result"),
        "description": "Instrumented jitted-function calls by compile-"
                       "cache outcome (result=hit|miss) — a miss burst "
                       "mid-training means shape churn is recompiling "
                       "the step",
    },
    "ray_tpu_compile_cache_hits_total": {
        "kind": "Counter", "tags": ("fn",),
        "description": "Executables JAX loaded from its persistent "
                       "compilation cache, by the instrumented function "
                       "whose call asked (fn=- outside every such call)",
    },
    "ray_tpu_compile_cache_misses_total": {
        "kind": "Counter", "tags": ("fn",),
        "description": "Executables JAX compiled and wrote to its "
                       "persistent compilation cache (a cold start: the "
                       "next process loads them), by instrumented "
                       "function (fn=- outside every such call)",
    },
    "ray_tpu_mesh_build_seconds": {
        "kind": "Histogram", "tags": ("kind",),
        "boundaries": [0.001, 0.01, 0.1, 0.5, 1.0, 5.0, 30.0],
        "description": "Device-mesh construction time "
                       "(kind=mesh|hybrid_mesh)",
    },
    # --- serve data plane (serve/_private/*, serve/batching.py) ---
    # deployment names are operator-chosen and bounded (one per deployed
    # model); fn names likewise — same cardinality class as RPC methods.
    # Replica ids are NOT used as tags (they contain uuids and churn).
    "ray_tpu_serve_requests_total": {
        "kind": "Counter", "tags": ("deployment", "result"),
        "description": "Serve requests completed at the handle layer "
                       "(result=ok|error)",
    },
    "ray_tpu_serve_request_latency_seconds": {
        "kind": "Histogram", "tags": ("deployment",),
        "boundaries": _RPC_BOUNDARIES,
        "description": "End-to-end handle-observed request latency "
                       "(router queueing + replica execution)",
    },
    "ray_tpu_serve_queue_depth_tasks": {
        "kind": "Gauge", "tags": ("deployment", "role"),
        "description": "Router-side demand: callers waiting for a "
                       "replica slot plus requests in flight (the "
                       "autoscaler's primary signal). The role tag "
                       "keeps the driver handle's router and the HTTP "
                       "proxy's router as separate series — the "
                       "cross-process gauge merge keeps the last value "
                       "per tag set, so without it one idle router "
                       "masks the other's backlog; sum over roles for "
                       "total demand",
    },
    "ray_tpu_serve_shed_total": {
        "kind": "Counter", "tags": ("deployment",),
        "description": "Requests shed by admission control "
                       "(ServeOverloadedError: all replicas at "
                       "max_ongoing_requests, bounded queue full)",
    },
    "ray_tpu_serve_failovers_total": {
        "kind": "Counter", "tags": ("deployment",),
        "description": "Requests re-dispatched to a surviving replica "
                       "after their assigned replica died or started "
                       "draining mid-request",
    },
    "ray_tpu_serve_replicas_tasks": {
        "kind": "Gauge", "tags": ("deployment", "state"),
        "description": "Replica FSM occupancy per deployment "
                       "(state=starting|running|stopping|target)",
    },
    "ray_tpu_serve_replica_restarts_total": {
        "kind": "Counter", "tags": ("deployment", "reason"),
        "description": "Replicas replaced by the controller "
                       "(reason=death|health|init)",
    },
    "ray_tpu_serve_autoscale_total": {
        "kind": "Counter", "tags": ("deployment", "direction"),
        "description": "Autoscale decisions applied after hysteresis "
                       "(direction=up|down)",
    },
    # --- serve tenancy (job-plane capacity: controller.py) ---
    "ray_tpu_serve_warned_replicas_tasks": {
        "kind": "Gauge", "tags": ("deployment",),
        "description": "Replicas whose capacity gang is under a "
                       "preemption warning (already-lost capacity: the "
                       "autoscaler starts replacements before the grace "
                       "window expires) — nonzero spans are preemption "
                       "storms in flight",
    },
    "ray_tpu_serve_capacity_wait_seconds": {
        "kind": "Histogram", "tags": ("deployment",),
        "boundaries": [0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 15.0, 60.0],
        "description": "Spike-to-placed latency: time from requesting a "
                       "replica's capacity gang in the job plane to its "
                       "CREATED (includes any preemption grace window "
                       "the plane had to burn to free the capacity)",
    },
    "ray_tpu_serve_preempt_drains_total": {
        "kind": "Counter", "tags": ("deployment", "reason"),
        "description": "Replica drains begun through the preemption-"
                       "warning machinery (reason=preempted for an "
                       "external/chaos warning, scale_down for the "
                       "controller's own pg_name-narrowed self-preempt)",
    },
    "ray_tpu_serve_batch_size_tasks": {
        "kind": "Histogram", "tags": ("fn",),
        "boundaries": [1, 2, 4, 8, 16, 32, 64, 128],
        "description": "Executed @serve.batch batch sizes (after "
                       "shape-bucket padding — the batch dimension the "
                       "jitted program actually compiled for)",
    },
    "ray_tpu_serve_batch_pad_waste_tasks": {
        "kind": "Histogram", "tags": ("fn",),
        "boundaries": [1, 2, 4, 8, 16, 32, 64],
        "description": "Padded slots per executed batch (bucket size "
                       "minus real requests): the compute wasted to "
                       "keep the pjit cache at a handful of shapes",
    },
    # --- sharded checkpointing (train/sharded_checkpoint.py) ---
    "ray_tpu_checkpoint_write_seconds": {
        "kind": "Histogram", "tags": ("group",),
        "boundaries": [0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0,
                       30.0, 120.0],
        "description": "Wall time of one rank's shard write (serialize "
                       "excluded: temp-file write + fsync + rename + "
                       "dir fsync + digest) — off the step loop when "
                       "RAY_TPU_CHECKPOINT_ASYNC is on",
    },
    "ray_tpu_checkpoint_bytes": {
        "kind": "Histogram", "tags": ("group",),
        "boundaries": [65536, 262144, 1048576, 4194304, 16777216,
                       67108864, 268435456],
        "description": "Size of one rank's checkpoint shard (its ZeRO "
                       "param slices + optimizer-state slots, npz) — "
                       "O(model/world) per rank, sum over ranks for the "
                       "generation total",
    },
    "ray_tpu_checkpoint_quarantined_total": {
        "kind": "Counter", "tags": ("reason",),
        "description": "Checkpoint generations quarantined at restore "
                       "(reason=torn|digest_mismatch|size_mismatch|"
                       "shard_missing|plan_mismatch) — each one also "
                       "records a CHECKPOINT_QUARANTINED event naming "
                       "the bad shard",
    },
    "ray_tpu_checkpoint_restore_seconds": {
        "kind": "Histogram", "tags": ("group",),
        "boundaries": [0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0,
                       30.0, 120.0],
        "description": "Wall time of one rank's sharded restore (scan + "
                       "verify digests + param reassembly + elastic "
                       "opt-state re-slice)",
    },
    # --- step anatomy + flight recorder (_private/step_anatomy.py,
    # _private/flight_recorder.py) ---
    "ray_tpu_step_seconds": {
        "kind": "Histogram", "tags": (),
        "boundaries": [0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0,
                       30.0, 120.0],
        "description": "Wall time of one train-loop step on one rank "
                       "(the interval between session.report calls, "
                       "stamped by the step-anatomy plane)",
    },
    "ray_tpu_step_regressions_total": {
        "kind": "Counter", "tags": (),
        "description": "STEP_REGRESSION firings: rolling p50 step time "
                       "drifted beyond step_regression_multiple x the "
                       "prior window's p50",
    },
    "ray_tpu_flight_recorder_dumps_total": {
        "kind": "Counter", "tags": ("trigger",),
        "description": "Black-box dump directories written, by trigger "
                       "(GANG_FAILED/collective_poison/actor_death/"
                       "manual/...)",
    },
    # --- telemetry ring overflow (util/tracing.py, _private/profiling.py) ---
    "ray_tpu_trace_dropped_total": {
        "kind": "Counter", "tags": (),
        "description": "Tracing spans evicted from the bounded "
                       "per-process span ring (a non-zero rate means "
                       "fused trace windows are incomplete)",
    },
    "ray_tpu_timeline_dropped_total": {
        "kind": "Counter", "tags": (),
        "description": "Chrome-timeline spans evicted from the bounded "
                       "per-process profiling ring (merged timelines "
                       "carry a drop-marker metadata row)",
    },
    # --- per-device telemetry (_private/tpu_probe.py) ---
    # node tag is load-bearing: each host's probe subprocess numbers its
    # local devices from 0 (no jax.distributed world), so without it a
    # multi-host cluster's gauges would collide and last-write-wins
    "ray_tpu_device_hbm_bytes": {
        "kind": "Gauge", "tags": ("node", "device", "platform", "stat"),
        "description": "Per-device memory from the subprocess device "
                       "probe (stat=in_use|limit; HBM on TPU, host "
                       "allocator bytes on the CPU fallback)",
    },
}

_lock = threading.Lock()
_metrics: dict[str, object] = {}


def _get(name: str):
    """The live metric instance for a CATALOG name. KeyError for an
    undeclared name — drift from the catalog must fail loudly at the
    instrumented call site, not silently record an unlintable metric."""
    metric = _metrics.get(name)
    if metric is not None:
        return metric
    spec = CATALOG[name]
    from ray_tpu.util import metrics as um

    cls = getattr(um, spec["kind"])
    with _lock:
        metric = _metrics.get(name)
        if metric is None:
            if spec["kind"] == "Histogram":
                metric = cls(name, description=spec["description"],
                             boundaries=spec["boundaries"],
                             tag_keys=spec["tags"])
            else:
                metric = cls(name, description=spec["description"],
                             tag_keys=spec["tags"])
            _metrics[name] = metric
    return metric


def counter_inc(name: str, value: float = 1.0, tags: dict | None = None):
    if not ENABLED:
        return
    metric = _get(name)
    try:
        metric.inc(value, tags=tags)
    except Exception:
        pass   # telemetry must never take down the operation it measures


def gauge_set(name: str, value: float, tags: dict | None = None):
    if not ENABLED:
        return
    metric = _get(name)
    try:
        metric.set(value, tags=tags)
    except Exception:
        pass


def observe(name: str, value: float, tags: dict | None = None):
    if not ENABLED:
        return
    metric = _get(name)
    try:
        metric.observe(value, tags=tags)
    except Exception:
        pass


def role() -> str:
    """This process's cluster role for the {role} label — the single
    shared resolver lives in events.py so the metric label can never
    diverge from the event `role` field for the same process."""
    from ray_tpu._private.events import _role

    return _role()
