"""Job kind ``train_fit``: train a causal language model through
``JaxTrainer.fit()`` for a fixed window and time every step.

``run`` is the parent's side and never touches JAX: ``ray_tpu.init()``
(the chip probe), one ``TrainWorker`` holding the cell's chips, tokens
from the seed through ``ray_tpu.data``, the result back through
``session.report``. ``train_loop`` is the worker's side: state made on the
device from the seed, the program's ``make_train_step`` on the cell's mesh,
two warm-up steps, then whole steps until ``seconds`` have passed, each
ended by its loss on the host and a ``session.report``. After the window,
outside every timing: the compiled step's memory plan, the comparison with
the plain reference on freshly made parameters, and in a traced run the
reduction of the trace.

The job knows no architecture. The configuration file names two things.
Its ``entry``, ``<module>:<preset>``, is the program's model: the preset
takes no argument and returns a dataclass with the fields ``attention``,
``remat`` (both replaced by the traffic file's) and ``vocab_size`` (the
ids the check's tokens are drawn from); the module has ``init(rng, cfg)``
-> parameters, ``partition_specs(cfg)`` -> a tree of ``PartitionSpec`` like
them, and ``loss_fn(params, {"tokens": [B, S+1]}, cfg, mesh) -> (loss,
metrics)``, a mean next-token loss with ``metrics["loss"]`` a scalar. Its
``reference`` names the architecture's plain model
(``references/<reference>.py``: ``loss(params, tokens, config)`` in
float32, ``config`` being the configuration file as this cell runs it) and
its accounting (``accounting/<reference>.py``: the sizes the preset must
have, the FLOPs a token, the compared leaves).
"""
import dataclasses
import glob
import importlib
import math
import os
import shutil
import time

from chipbench import flops, generate, trace_reduce
from chipbench.catalog import ROOT

SPANS = ("data_next", "step_dispatch", "loss_fetch", "report")
WARMUP_STEPS = 2
RESULT_KEY = "chipbench_record"
# profiler files of a traced run; a fixed path inside the checkout
TRACE_DIR = os.path.join(ROOT, ".chipbench_tmp", "trace")


class JobFailed(Exception):
    """The run produced no result; the message says why."""


# ------------------------------------------------------------ parent side

def run(cell: dict, *, seed: int, seconds: float, trace: bool,
        t_start: float, require_tpu: bool = True) -> dict:
    import ray_tpu
    import ray_tpu.data
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train.trainer import JaxTrainer

    chips = cell["workload"]["chips"]
    ctx = ray_tpu.init()
    t_init = time.time()
    try:
        if require_tpu:
            found = int(ray_tpu.cluster_resources().get("TPU", 0))
            if found < chips:
                raise JobFailed(
                    f"the cell needs {chips} TPU chip(s); ray_tpu.init() "
                    f"found {found}: JAX sees no accelerator, or too few")
            scaling = ScalingConfig(num_workers=1, use_tpu=True,
                                    chips_per_worker=chips)
        else:
            scaling = ScalingConfig(num_workers=1)
        tokens = generate.token_rows(
            cell["traffic"], flops.padded_vocab(cell["model"]["vocab_size"]),
            seed)
        config = dict(cell, seed=seed, seconds=seconds, trace=trace,
                      t_start=t_start, t_init=t_init,
                      require_tpu=require_tpu)
        result = JaxTrainer(
            train_loop, train_loop_config=config, scaling_config=scaling,
            datasets={"train": ray_tpu.data.from_numpy(tokens)}).fit()
        # fit() returns a failed loop as Result(error=...); it does not raise
        if result.error is not None:
            raise JobFailed(
                f"train loop failed: {type(result.error).__name__}: "
                f"{result.error}\n{_worker_log_tail(ctx['session_dir'])}")
        record = (result.metrics or {}).get(RESULT_KEY)
        if record is None:
            raise JobFailed("train loop ended without its final report")
        return record
    finally:
        ray_tpu.shutdown()
        # the runtime keeps its logs under /tmp/ray_tpu, outside the
        # checkout: a run leaves nothing there
        shutil.rmtree(ctx["session_dir"], ignore_errors=True)


def _worker_log_tail(session_dir: str, n_bytes: int = 6000) -> str:
    out = []
    for path in sorted(glob.glob(
            os.path.join(session_dir, "logs", "worker-*.err"))):
        with open(path, "rb") as f:
            f.seek(max(0, os.path.getsize(path) - n_bytes))
            tail = f.read().decode(errors="replace").strip()
        if tail:
            out.append(f"--- {path}\n{tail}")
    return "\n".join(out)


# ------------------------------------------------------------ worker side

def _model(config: dict):
    """The program's model module and its configuration for this cell, and
    a refusal if the preset's sizes are not the configuration file's."""
    model, traffic = config["model"], config["traffic"]
    module_name, preset = model["entry"].split(":")
    module = importlib.import_module(module_name)
    cfg = dataclasses.replace(getattr(module, preset)(),
                              attention=traffic["attention"],
                              remat=traffic["remat"])
    accounting = importlib.import_module(config["accounting"])
    ran, filed = accounting.ran_sizes(cfg), accounting.filed_sizes(model)
    if ran != filed:
        raise ValueError(f"{model['entry']} runs {ran}, the configuration "
                         f"file says {filed}")
    return module, cfg


def _batches(shard, batch: int):
    """Batches for ever: the shard is iterated again when it ends."""
    while True:
        yield from shard.iter_batches(batch_size=batch, drop_last=True)


def train_loop(config: dict):
    t_loop = time.time()
    import jax

    from chipbench import compare
    from ray_tpu.air import session
    from ray_tpu.parallel.compile_watch import configure_compile_cache
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.parallel.train_step import (
        default_optimizer,
        make_train_state,
        make_train_step,
    )

    traffic, seed = config["traffic"], config["seed"]
    configure_compile_cache()
    compiles, cache = [], {"hits": 0, "misses": 0}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(time.perf_counter())

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    devices = jax.local_devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(jax.devices())}
    if config["require_tpu"]:
        if jax.default_backend() != "tpu":
            raise RuntimeError(f"the worker's backend is "
                               f"{jax.default_backend()!r}, not 'tpu'")
        peaks = flops.peaks_for(device["kind"])
    else:
        peaks = None

    module, cfg = _model(config)
    n_mesh = math.prod(traffic["mesh"].values())
    mesh = create_mesh(MeshConfig(**traffic["mesh"]),
                       devices=devices[:n_mesh])
    opt = default_optimizer(**traffic["optimizer"])
    state = make_train_state(lambda rng: module.init(rng, cfg),
                             jax.random.PRNGKey(seed), opt, mesh,
                             module.partition_specs(cfg))
    step = make_train_step(lambda p, b: module.loss_fn(p, b, cfg, mesh),
                           opt, mesh)
    batches = _batches(session.get_dataset_shard("train"), traffic["batch"])
    for _ in range(WARMUP_STEPS):
        state, metrics = step(state, {"tokens": next(batches)})
        float(metrics["loss"])
    setup_cache = dict(cache)

    # ---- the window: whole steps until `seconds` have passed
    annotate = jax.profiler.TraceAnnotation
    spans = {name: [] for name in SPANS + ("step",)}
    losses, tracing, traced_steps = [], False, 0
    first_traced = traffic["trace_from_step"] if config["trace"] else None
    t_window = time.time()
    t0 = now = time.perf_counter()
    while now - t0 < config["seconds"]:
        if len(losses) == first_traced:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # annotations, not frames
            jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
            tracing, now = True, time.perf_counter()
        begin = now
        with annotate("data_next"):
            tokens = next(batches)
        t1 = time.perf_counter()
        with annotate("step_dispatch"):
            state, metrics = step(state, {"tokens": tokens})
        t2 = time.perf_counter()
        with annotate("loss_fetch"):
            loss = float(metrics["loss"])
        t3 = time.perf_counter()
        with annotate("report"):
            session.report({"step": len(losses), "loss": loss})
        now = time.perf_counter()
        losses.append(loss)
        for name, dt in zip(SPANS + ("step",), (
                t1 - begin, t2 - t1, t3 - t2, now - t3, now - begin)):
            spans[name].append(dt)
        if tracing:
            traced_steps += 1
            # one execution more than the periods the reduction keeps
            if traced_steps > traffic["trace_steps"]:
                jax.profiler.stop_trace()
                tracing, now = False, time.perf_counter()
    window_s = now - t0
    if tracing:
        jax.profiler.stop_trace()

    # ---- after the window, outside every timing
    plan = _memory_plan(step, state, tokens)
    device["memory_peak_bytes"] = max(
        [plan["argument"] + plan["temp"] + plan["output"] - plan["alias"]]
        + [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
           for d in devices[:n_mesh]])
    del state
    check = _reference_check(config, module, cfg, mesh, devices[0])
    finite = [x for x in losses if math.isfinite(x)]
    verdicts = {
        "agrees_with_reference": check["within"],
        "every_loss_finite": len(finite) == len(losses),
        "loss_fell": losses[-1] < losses[0],
    }
    summary = _read_trace(config.get("keep_trace")) if config["trace"] \
        else None

    context = {
        "spans": spans,
        "clock": {"process_start": config["t_start"],
                  "init_done": config["t_init"], "loop_start": t_loop,
                  "window_start": t_window, "window_s": window_s},
        "counters": {
            "steps": len(losses),
            "compiles_in_window": sum(t0 <= t <= t0 + window_s
                                      for t in compiles)},
        "plan": plan, "trace": summary, "peaks": peaks, "device": device,
        "model": config["model"], "accounting": config["accounting"],
        "traffic": traffic, "chips": n_mesh,
    }
    values = {}
    for spec in config["metrics"]:
        value = importlib.import_module(spec["reader"]).read(
            context, **spec["args"])
        if value is not None:
            values[spec["name"]] = {"value": float(value),
                                    "unit": spec["unit"]}
    record = {
        "correct": all(verdicts.values()), "verdicts": verdicts,
        "attempted": len(losses), "failed": len(losses) - len(finite),
        "metrics": values, "device": device,
        "losses": {str(i): losses[i - 1] for i in (1, 8, 32)
                   if i <= len(losses)},
        "last_loss": losses[-1],
        "check": check, "compared": compare.beside_limits(check["errors"]),
        "plan": plan,
        "setup_cache": setup_cache, "clock": context["clock"],
        # [step, seconds of step, data_next, step_dispatch, loss_fetch,
        # report, seconds into the window at which it began]: where and
        # when a stall was, if there was one
        "longest_steps": [
            [i] + [spans[name][i] for name in ("step",) + SPANS]
            + [sum(spans["step"][:i])]
            for i in sorted(range(len(losses)),
                            key=lambda i: -spans["step"][i])[:6]],
    }
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        record["breakdown"] = trace_reduce.breakdown(summary)
        record["trace_notes"] = _trace_notes(summary, peaks)
    session.report({RESULT_KEY: record})


def _memory_plan(step, state, tokens) -> dict:
    """Bytes the compiled step holds on one device. The plan, not the
    backend's ``peak_bytes_in_use``, which leaves the step's temporaries
    out (it read 1.5 GB beside an 11 GB plan on the v5e, PR 21 and 23).
    The program is in the compile cache by now."""
    plan = step.lower(state, {"tokens": tokens}).compile().memory_analysis()
    return {k: getattr(plan, f"{k}_size_in_bytes")
            for k in ("argument", "output", "alias", "temp")}


def _reference_check(config, module, cfg, mesh, device) -> dict:
    """The system's loss and gradients, as the cell runs them, against the
    plain reference. On parameters freshly made from the seed, not on the
    state the window left: tokens drawn independently teach the model to
    ignore its context, and after a window of them the true gradient of
    the attention's query weights is ~1e-8 of its neighbours' (measured,
    PR 23), so nothing could be compared there."""
    import jax

    from chipbench import compare

    seed, traffic = config["seed"] + 1, config["traffic"]
    shardings = jax.tree_util.tree_map(
        lambda spec: jax.sharding.NamedSharding(mesh, spec),
        module.partition_specs(cfg))
    params = jax.jit(lambda rng: module.init(rng, cfg),
                     out_shardings=shardings)(jax.random.PRNGKey(seed))
    tokens = generate.token_rows(
        dict(traffic, batches=1, batch=traffic["check_sequences"]),
        cfg.vocab_size, seed)
    accounting = importlib.import_module(config["accounting"])
    # an architecture whose draw is ill-conditioned on some seeds brings
    # every seed's to one difficulty (`accounting.conditioned`)
    params = getattr(accounting, "conditioned", lambda p: p)(params)
    reference = importlib.import_module(config["reference"])
    return compare.compare(
        lambda p, t: module.loss_fn(p, {"tokens": t}, cfg, mesh)[0],
        lambda p, t: reference.loss(p, t, config["model"]), params, tokens,
        device, pick=accounting.pick, put=accounting.put)


def _read_trace(keep):
    """Reduce the profiler's file, copy it to ``keep`` if asked, and leave
    nothing behind. None where the trace holds no TPU plane."""
    files = glob.glob(os.path.join(
        TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb"))
    summary = None
    if files:
        summary = trace_reduce.reduce_trace(
            trace_reduce.load_xplane(files[0], SPANS), SPANS)
        if keep:
            os.makedirs(os.path.dirname(keep), exist_ok=True)
            shutil.copy(files[0], keep)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return summary


def _trace_notes(summary: dict, peaks) -> dict:
    """What the result line has no place for: which bound the flash
    kernels' roofline is, per kernel, and the collective seconds."""
    notes = {"steps": summary["steps"], "flash": {}}
    for name, seconds in summary["per_op_s"].items():
        cost = flops.flash_call_cost(name)
        if cost and peaks:
            # under remat the forward kernel is two instructions
            kind, ops, moved = cost
            least, bound = flops.least_seconds(ops, moved, peaks)
            entry = notes["flash"].setdefault(
                kind, {"calls": 0, "seconds": 0.0, "least_s": 0.0,
                       "bound": bound})
            entry["calls"] += summary["per_op_calls"][name]
            entry["seconds"] += seconds
            entry["least_s"] += least * summary["per_op_calls"][name]
    for entry in notes["flash"].values():
        entry["roofline_pct"] = 100 * entry["least_s"] / entry["seconds"]
    notes["collective_s"] = {
        plane: {"total": d["collective_ns"] / 1e9,
                "exposed": d["collective_exposed_ns"] / 1e9}
        for plane, d in summary["devices"].items()}
    return notes
