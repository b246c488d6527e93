"""Public API — ray_tpu.init / remote / get / put / wait / actors.

Analog of the reference's python/ray/_private/worker.py (ray.init at :1031,
get/put/wait at :2230,2329,2385, @ray.remote at :2709-2808),
python/ray/remote_function.py and python/ray/actor.py, re-based on the
TPU-native runtime: GCS + raylet run in-process for local mode (the
single-node quickstart), workers are real OS processes sharing the node's
shm object store.
"""
from __future__ import annotations

import atexit
import functools
import inspect
import os
import threading
import time

from ray_tpu import exceptions as exc
from ray_tpu._private.object_ref import ObjectRef, ObjectRefGenerator
from ray_tpu._private.worker_runtime import (
    CoreWorker,
    current_worker,
    set_current_worker,
)

_global_lock = threading.RLock()
_global_node = None     # _LocalNode for locally started clusters
_namespace = "default"
_log_printer = None     # DriverLogPrinter while connected as driver


class _LocalNode:
    """In-process head: GCS + raylet threads (the reference forks gcs_server
    and raylet processes, node.py:1045; in-process keeps the local quickstart
    fast — multi-node tests use cluster_utils.Cluster which adds more raylets,
    and production uses the CLI to run them standalone)."""

    def __init__(self, num_cpus=None, num_tpus=None, resources=None,
                 object_store_memory=None, session_dir=None):
        from ray_tpu._private import profiling
        from ray_tpu._private.gcs import GcsServer
        from ray_tpu._private.raylet import Raylet, detect_resources

        self.session_dir = session_dir or os.path.join(
            "/tmp/ray_tpu", f"session_{os.getpid()}_{int(time.time())}")
        os.makedirs(self.session_dir, exist_ok=True)
        with profiling.record_span("startup", "gcs_start"):
            self.gcs = GcsServer(
                snapshot_path=os.path.join(self.session_dir, "gcs_snapshot")
            ).start()
        # counting the node's resources is the raylet's start too: the
        # chip probe (`chip_probe`, its child) runs there
        with profiling.record_span("startup", "raylet_start"):
            self.raylet = Raylet(
                self.gcs.addr,
                resources=detect_resources(num_cpus, num_tpus,
                                           resources=resources),
                store_size=object_store_memory or 256 * 1024 * 1024,
                session_dir=self.session_dir,
            )

    def stop(self):
        self.raylet.stop()
        self.gcs.stop()


def init(address=None, *, num_cpus=None, num_tpus=None, num_gpus=None,
         resources=None, namespace=None, object_store_memory=None,
         ignore_reinit_error=False, **kwargs):
    """Start (or connect to) a cluster and connect this process as driver.

    address=None starts a local head; address="host:port" connects to an
    existing GCS; address="auto" reads RAY_TPU_ADDRESS.
    `num_gpus` is accepted for reference-API compatibility and maps to TPU
    chips.
    """
    from ray_tpu._private import profiling

    with profiling.record_span("startup", "init"):
        return _init(address, num_cpus, num_tpus, num_gpus, resources,
                     namespace, object_store_memory, ignore_reinit_error,
                     kwargs)


def _init(address, num_cpus, num_tpus, num_gpus, resources, namespace,
          object_store_memory, ignore_reinit_error, kwargs):
    global _global_node, _namespace
    with _global_lock:
        if current_worker() is not None:
            if ignore_reinit_error:
                return RayContext(current_worker())
            raise RuntimeError("ray_tpu.init() called twice "
                              "(pass ignore_reinit_error=True to allow)")
        if namespace:
            _namespace = namespace
        if num_tpus is None and num_gpus is not None:
            num_tpus = num_gpus
        # init(system_config=...) beats env beats defaults (config.py
        # contract; reference: ray.init(_system_config=...)). Applied
        # before any component starts so the in-process GCS/raylet (and
        # their monitors) see the overrides.
        from ray_tpu._private.config import GlobalConfig

        GlobalConfig.apply_system_config(
            kwargs.pop("system_config", None)
            or kwargs.pop("_system_config", None))
        if isinstance(address, str) and address.startswith("ray://"):
            # client mode: everything proxies through one endpoint
            # (reference: util/client/, ray.init("ray://...") at
            # worker.py:1031)
            from ray_tpu.util.client import connect

            ctx = connect(address[len("ray://"):])
            set_current_worker(ctx)
            atexit.register(shutdown)
            return RayContext(ctx)
        if address in (None, "local"):
            _global_node = _LocalNode(num_cpus, num_tpus, resources,
                                      object_store_memory)
            gcs_addr = _global_node.gcs.addr
            raylet_addr = _global_node.raylet.addr
        else:
            if address == "auto":
                address = os.environ["RAY_TPU_ADDRESS"]
            host, port = address.rsplit(":", 1)
            gcs_addr = (host, int(port))
            raylet_addr = _find_raylet(gcs_addr)
        worker = CoreWorker(gcs_addr, raylet_addr, mode="driver")
        set_current_worker(worker)
        # Stream worker stdout/stderr to this console (reference:
        # worker.py:1733 print_worker_logs; disable with
        # log_to_driver=False or RAY_TPU_LOG_TO_DRIVER=0).
        from ray_tpu._private.config import get_config

        global _log_printer
        if kwargs.get("log_to_driver", get_config("log_to_driver")) \
                and not os.environ.get("RAY_TPU_QUIET"):
            from ray_tpu._private.log_monitor import DriverLogPrinter

            try:
                _log_printer = DriverLogPrinter(gcs_addr)
            except Exception:
                _log_printer = None
        atexit.register(shutdown)
        return RayContext(worker)


def _find_raylet(gcs_addr):
    """Pick this host's raylet from the GCS node table (or any alive one)."""
    from ray_tpu._private.protocol import RpcClient

    client = RpcClient(gcs_addr)
    try:
        nodes = [n for n in client.call("get_nodes") if n["Alive"]]
    finally:
        client.close()
    if not nodes:
        raise RuntimeError("no alive nodes in cluster")
    hostname = os.uname().nodename
    for n in nodes:
        if n.get("hostname") == hostname:
            return (n["NodeManagerAddress"], n["NodeManagerPort"])
    return (nodes[0]["NodeManagerAddress"], nodes[0]["NodeManagerPort"])


def shutdown():
    global _global_node, _log_printer
    with _global_lock:
        if _log_printer is not None:
            try:
                _log_printer.stop()
            except Exception:
                pass
            _log_printer = None
        worker = current_worker()
        if worker is not None:
            worker.shutdown()
            set_current_worker(None)
        if _global_node is not None:
            _global_node.stop()
            _global_node = None
        from ray_tpu._private.config import GlobalConfig

        GlobalConfig.reset_system_config()
        try:
            atexit.unregister(shutdown)
        except Exception:
            pass


def is_initialized() -> bool:
    return current_worker() is not None


def _require_worker() -> CoreWorker:
    worker = current_worker()
    if worker is None:
        raise RuntimeError(
            "ray_tpu has not been initialized — call ray_tpu.init()")
    return worker


# --------------------------------------------------------------------- basics

def put(value) -> ObjectRef:
    if isinstance(value, ObjectRef):
        raise TypeError("put() on an ObjectRef is not allowed")
    return _require_worker().put(value)


def get(refs, *, timeout=None):
    worker = _require_worker()
    if isinstance(refs, list):
        bad = [r for r in refs if not isinstance(r, ObjectRef)]
        if bad:
            raise TypeError(f"get() takes ObjectRefs, got {type(bad[0])}")
    elif not isinstance(refs, ObjectRef):
        raise TypeError(f"get() takes an ObjectRef or list, got {type(refs)}")
    return worker.get(refs, timeout=timeout)


def wait(refs, *, num_returns=1, timeout=None, fetch_local=True):
    if not isinstance(refs, list):
        raise TypeError("wait() takes a list of ObjectRefs")
    return _require_worker().wait(refs, num_returns=num_returns,
                                  timeout=timeout, fetch_local=fetch_local)


def kill(actor, *, no_restart=True):
    if not isinstance(actor, ActorHandle):
        raise TypeError("kill() takes an ActorHandle")
    worker = _require_worker()
    if getattr(worker, "mode", None) == "client":
        # raylet addresses are cluster-internal; the proxy kills for us
        worker.kill_actor(actor._actor_id, no_restart=no_restart)
        return
    info = worker.gcs.call("get_actor", actor_id=actor._actor_id)
    if info is None:
        return
    node_id = None
    # find the actor's raylet via its node
    snap = worker.gcs.call("list_actors")
    for a in snap:
        if a["ActorID"] == actor._actor_id.hex():
            node_id = a["NodeID"]
            break
    from ray_tpu._private.protocol import RpcClient

    for n in worker.gcs.call("get_nodes"):
        if n["NodeID"] == node_id and n["Alive"]:
            c = RpcClient((n["NodeManagerAddress"], n["NodeManagerPort"]))
            try:
                c.call("kill_actor", actor_id=actor._actor_id,
                       no_restart=no_restart)
            finally:
                c.close()
            return


def cancel(ref: ObjectRef, *, force=False, recursive=True):
    """Best-effort cancellation of the task producing `ref`: a queued task
    is dropped, a running one is flagged (force interrupts the executing
    thread). get(ref) raises TaskCancelledError if the cancel won."""
    if not isinstance(ref, ObjectRef):
        raise TypeError("cancel() takes an ObjectRef")
    _require_worker().cancel_task(ref, force=force)


def get_actor(name: str, namespace: str | None = None) -> "ActorHandle":
    worker = _require_worker()
    info = worker.gcs.call("get_actor", name=name,
                           namespace=namespace or _namespace)
    if info is None or info["state"] == "DEAD":
        raise ValueError(f"actor {name!r} not found")
    meta = info.get("spec_meta") or {}
    return ActorHandle(info["actor_id"],
                       max_task_retries=meta.get("max_task_retries", 0))


def nodes():
    return _require_worker().gcs.call("get_nodes")


def cluster_resources():
    return _require_worker().gcs.call("cluster_resources")


def available_resources():
    worker = _require_worker()
    if getattr(worker, "mode", None) == "client":
        return worker.available_resources()
    from ray_tpu._private.protocol import RpcClient

    total = {}
    for n in worker.gcs.call("get_nodes"):
        if not n["Alive"]:
            continue
        try:
            c = RpcClient((n["NodeManagerAddress"], n["NodeManagerPort"]),
                          timeout=5.0)
            try:
                info = c.call("node_info")
            finally:
                c.close()
            for k, v in info["resources_available"].items():
                total[k] = total.get(k, 0) + v
        except Exception:
            continue
    return total


def get_gpu_ids():
    return []   # compatibility shim; TPU chips are addressed via jax.devices


def timeline(filename=None):
    """Cluster-wide task/actor execution spans in chrome://tracing format
    (reference: `ray timeline`, scripts.py:1757 over core-worker profiling
    events). Open the written file at chrome://tracing or Perfetto."""
    from ray_tpu._private import profiling
    from ray_tpu.experimental.state.api import _each_raylet

    worker = _require_worker()
    if getattr(worker, "mode", None) == "client":
        trace = worker._rpc.call("client_timeline")
    else:
        # drop markers ride along (ph "M" metadata rows): a ring that
        # evicted spans must say so in the merged timeline
        events = profiling.snapshot(with_drop_marker=True)  # this process
        # each raylet answers with its own process's ring and its
        # workers'; a raylet inside this process, and this process's own
        # worker, bring the rows above again
        events.extend(_each_raylet(worker.gcs.call, "profile_events"))
        trace = profiling.to_chrome_trace(profiling.merge(events))
    if filename:
        import json

        with open(filename, "w") as f:
            json.dump(trace, f)
    return trace


class RayContext:
    def __init__(self, worker):
        self._worker = worker
        self.address_info = {
            "gcs_address": f"{worker.gcs.addr[0]}:{worker.gcs.addr[1]}",
            "node_id": worker.node_id,
        }
        if _global_node is not None:
            # locally started head: worker logs live under
            # <session_dir>/logs/worker-*.{out,err}
            self.address_info["session_dir"] = _global_node.session_dir

    def __enter__(self):
        return self

    def __exit__(self, *a):
        shutdown()

    def __getitem__(self, key):
        return self.address_info[key]


class RuntimeContext:
    def __init__(self, worker: CoreWorker):
        self._worker = worker

    def get_node_id(self):
        return self._worker.node_id

    def get_job_id(self):
        return self._worker.job_id

    def get_worker_id(self):
        return self._worker.worker_id

    def get_actor_id(self):
        return self._worker.actor_id.hex() if self._worker.actor_id else None

    @property
    def namespace(self):
        return _namespace

    @property
    def was_current_actor_restarted(self):
        return False

    def get_actor_name(self):
        spec = self._worker._actor_spec
        return spec.get("name") if spec else None


def get_runtime_context() -> RuntimeContext:
    return RuntimeContext(_require_worker())


# ----------------------------------------------------------- options handling

_TASK_DEFAULTS = dict(num_cpus=1.0, num_tpus=0.0, memory=None, resources=None,
                      num_returns=1, max_retries=3, retry_exceptions=False,
                      scheduling_strategy=None, runtime_env=None,
                      # Opt-in: execute on the worker's transport pump
                      # instead of the main-thread loop — skips a queue
                      # handoff + thread wake per task. ONLY for tasks that
                      # never block (no nested get()/wait(), no runtime
                      # envs) and import no thread-hostile native libs
                      # (pyarrow). Reference analog: direct-call execution
                      # without an executor hop.
                      inline_exec=False)
_ACTOR_DEFAULTS = dict(num_cpus=1.0, num_tpus=0.0, memory=None, resources=None,
                       max_restarts=0, max_task_retries=0, max_concurrency=1,
                       concurrency_groups=None, name=None, namespace=None,
                       lifetime=None, get_if_exists=False,
                       scheduling_strategy=None, runtime_env=None)


def _build_resources(opts: dict) -> dict:
    """Pure: never mutates opts. Zero-valued entries are dropped, so
    num_cpus=0 yields {} — which the submit path must treat as 'no resource
    requirement', NOT as 'use defaults'."""
    res = dict(opts.get("resources") or {})
    if opts.get("num_cpus") is not None:
        res["CPU"] = float(opts["num_cpus"])
    if opts.get("num_gpus"):   # compat alias
        res["TPU"] = float(opts["num_gpus"])
    if opts.get("num_tpus"):
        res["TPU"] = float(opts["num_tpus"])
    if opts.get("memory"):
        res["memory"] = float(opts["memory"])
    return {k: v for k, v in res.items() if v}


def _build_strategy(opts: dict) -> dict | None:
    strategy = opts.get("scheduling_strategy")
    if strategy is None or strategy == "DEFAULT":
        pg = opts.get("placement_group")
        if pg is not None:
            return {"placement_group_id": pg.id,
                    "bundle_index":
                        opts.get("placement_group_bundle_index", -1)}
        return None
    if strategy == "SPREAD":
        return {"spread": True}
    # strategy objects (duck-typed; see ray_tpu.util.scheduling_strategies)
    if hasattr(strategy, "node_id"):
        return {"node_id": strategy.node_id,
                "soft": getattr(strategy, "soft", False)}
    if hasattr(strategy, "placement_group"):
        pg = strategy.placement_group
        return {"placement_group_id": pg.id,
                "bundle_index":
                    getattr(strategy, "placement_group_bundle_index", -1)}
    raise ValueError(f"unknown scheduling strategy {strategy!r}")


class RemoteFunction:
    """@ray_tpu.remote function wrapper (reference: remote_function.py:35)."""

    def __init__(self, fn, **options):
        self._fn = fn
        self._options = {**_TASK_DEFAULTS, **options}
        self._func_hash = None
        self._registered_with = None   # CoreWorker the hash was pushed via
        functools.update_wrapper(self, fn)

    def __call__(self, *args, **kwargs):
        raise TypeError(
            f"remote function {self._fn.__name__}() cannot be called "
            f"directly; use {self._fn.__name__}.remote()")

    def options(self, **overrides):
        return RemoteFunction(self._fn, **{**self._options, **overrides})

    def remote(self, *args, **kwargs):
        worker = _require_worker()
        if self._registered_with is not worker:
            # (re-)register against THIS runtime: a new init() means a fresh
            # GCS function table that has no copy of the function
            self._func_hash = worker.register_function(self._fn)
            self._registered_with = worker
        opts = self._options
        refs = worker.submit_task(
            self._func_hash, args, kwargs,
            num_returns=opts["num_returns"],
            resources=_build_resources(opts),
            strategy=_build_strategy(opts),
            max_retries=opts["max_retries"],
            runtime_env=opts.get("runtime_env"),
            task_desc=f"task {self._fn.__name__}()",
            inline_exec=bool(opts.get("inline_exec")),
        )
        if opts["num_returns"] == "streaming":
            return ObjectRefGenerator(refs[0].id, refs[0].owner_addr,
                                      None, worker)
        if opts["num_returns"] in (1, "dynamic"):
            return refs[0]
        return refs

    @property
    def bind(self):
        from ray_tpu.dag import FunctionNode

        def _bind(*args, **kwargs):
            return FunctionNode(self, args, kwargs)

        return _bind


class ActorMethod:
    def __init__(self, handle: "ActorHandle", name: str, num_returns=1):
        self._handle = handle
        self._name = name
        self._num_returns = num_returns

    def options(self, num_returns=1, **_):
        return ActorMethod(self._handle, self._name, num_returns)

    def remote(self, *args, **kwargs):
        worker = _require_worker()
        refs = worker.submit_actor_task(
            self._handle._actor_id, self._name, args, kwargs,
            num_returns=self._num_returns,
            max_task_retries=self._handle._max_task_retries,
            task_desc=f"actor method {self._name}()",
        )
        if self._num_returns == "streaming":
            return ObjectRefGenerator(refs[0].id, refs[0].owner_addr,
                                      None, worker)
        if self._num_returns in (1, "dynamic"):
            return refs[0]
        return refs

    @property
    def bind(self):
        from ray_tpu.dag import ClassMethodNode

        def _bind(*args, **kwargs):
            return ClassMethodNode(self, args, kwargs)

        return _bind


class ActorHandle:
    def __init__(self, actor_id: bytes, max_task_retries: int = 0):
        self._actor_id = actor_id
        self._max_task_retries = max_task_retries

    def __getattr__(self, name):
        if name.startswith("_"):
            raise AttributeError(name)
        return ActorMethod(self, name)

    def __repr__(self):
        return f"ActorHandle({self._actor_id.hex()})"

    def __reduce__(self):
        return (ActorHandle, (self._actor_id, self._max_task_retries))

    def __hash__(self):
        return hash(self._actor_id)

    def __eq__(self, other):
        return (isinstance(other, ActorHandle)
                and other._actor_id == self._actor_id)

    @property
    def __ray_terminate__(self):
        return ActorMethod(self, "__ray_terminate__")


class ActorClass:
    """@ray_tpu.remote class wrapper (reference: actor.py:377)."""

    def __init__(self, cls, **options):
        self._cls = cls
        self._options = {**_ACTOR_DEFAULTS, **options}
        self._class_hash = None
        self._registered_with = None

    def __call__(self, *args, **kwargs):
        raise TypeError(
            f"actor class {self._cls.__name__} cannot be instantiated "
            f"directly; use {self._cls.__name__}.remote()")

    def options(self, **overrides):
        out = ActorClass(self._cls, **{**self._options, **overrides})
        return out

    def remote(self, *args, **kwargs):
        worker = _require_worker()
        if self._registered_with is not worker:
            self._class_hash = worker.register_function(self._cls)
            self._registered_with = worker
        opts = dict(self._options)
        _validate_concurrency_groups(self._cls, opts["concurrency_groups"])
        resources = _build_resources(opts)   # {} = explicit zero request
        actor_id, existed = worker.create_actor(
            self._class_hash, args, kwargs,
            options={
                "class_name": self._cls.__name__,
                "resources": resources,
                "strategy": _build_strategy(opts),
                "max_restarts": opts["max_restarts"],
                "max_task_retries": opts["max_task_retries"],
                "max_concurrency": opts["max_concurrency"],
                "concurrency_groups": opts["concurrency_groups"],
                "name": opts["name"],
                "namespace": opts["namespace"] or _namespace,
                "lifetime": opts["lifetime"],
                "get_if_exists": opts["get_if_exists"],
                "runtime_env": opts.get("runtime_env"),
            })
        return ActorHandle(actor_id,
                           max_task_retries=opts["max_task_retries"])

    @property
    def bind(self):
        from ray_tpu.dag import ClassNode

        def _bind(*args, **kwargs):
            return ClassNode(self, args, kwargs)

        return _bind


def _validate_concurrency_groups(cls, groups):
    """Reject a @method(concurrency_group=...) naming an undeclared group at
    actor-creation time (reference: actor.py validates at definition time).
    Catching it here — not at dispatch — keeps a misspelled group from
    failing mid-stream after earlier calls already ran."""
    declared = set(groups or {})
    for attr_name in dir(cls):
        attr = inspect.getattr_static(cls, attr_name, None)
        group = getattr(attr, "__ray_concurrency_group__", None)
        if group is not None and group not in declared:
            raise ValueError(
                f"method {cls.__name__}.{attr_name!r} declares concurrency "
                f"group {group!r}, but the actor is being created with "
                f"groups {sorted(declared)}")


def remote(*args, **kwargs):
    """@ray_tpu.remote / @ray_tpu.remote(num_cpus=..., num_tpus=...)."""
    if len(args) == 1 and not kwargs and (
            inspect.isfunction(args[0]) or inspect.isclass(args[0])):
        target = args[0]
        if inspect.isclass(target):
            return ActorClass(target)
        return RemoteFunction(target)
    if args:
        raise TypeError("@remote takes keyword options only")

    def decorator(target):
        if inspect.isclass(target):
            return ActorClass(target, **kwargs)
        return RemoteFunction(target, **kwargs)

    return decorator


def method(**opts):
    """@ray_tpu.method(num_returns=..., concurrency_group=...) decorator
    for actor methods (reference: actor.py method + concurrency groups,
    transport/concurrency_group_manager.h)."""

    def decorator(fn):
        fn.__ray_num_returns__ = opts.get("num_returns", 1)
        if "concurrency_group" in opts:
            fn.__ray_concurrency_group__ = opts["concurrency_group"]
        return fn

    return decorator
