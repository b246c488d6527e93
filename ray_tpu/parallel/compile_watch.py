"""Compile-path observability for the pjit data plane.

A slow training step is either a slow step or a RECOMPILING step, and
without instrumentation the two are indistinguishable from the driver.
This module wraps the jitted callables ``make_train_step`` /
``eval_step`` / ``make_train_state`` hand out so every call is
classified against a per-signature compile cache:

- cache hit: one counter inc (``ray_tpu_pjit_cache_total{result=hit}``),
  then straight into the jitted function;
- cache miss: ``COMPILE_BEGIN``/``COMPILE_END`` cluster events, a span
  in BOTH the chrome-trace timeline (_private/profiling.py, µs) and
  util/tracing (ns — joins the surrounding task's trace), and the
  wall time into ``ray_tpu_pjit_compile_seconds``. The timeline's span
  ``compile::<fn>`` is split where the compile happens: JAX's own
  ``jax.monitoring`` durations that fired during the call become its
  children ``trace``, ``lower``, ``backend_compile`` and ``cache_load``,
  and it says whether the persistent cache gave the executable
  (``args.persistent_cache``: ``hit`` | ``miss`` | ``off``) and what was
  left for the first execution (``args.first_execute_s``).

Classification is O(1) on the hit path: jitted callables expose
``_cache_size()`` (~0.1µs), so a call that grew the cache IS a
trace+compile — no signature re-derivation duplicating jit's own C++
dispatch on every training step. Plain callables that are not jitted
(serve/batching.py wraps user batch functions) have no ``_cache_size``
and are classified by a per-signature key at jit's abstraction level
((shape, dtype) per array leaf + pytree structure). The measured duration is
trace + compile + first execution (the recompile-attribution signal
operators need), not a pure XLA compile timer; on the cache-size path
the COMPILE_BEGIN event is materialized after the fact (the miss is
only knowable once the call returns) and carries ``started_at``.

Mesh construction gets the same treatment through ``mesh_build_timer``
(``ray_tpu_mesh_build_seconds{kind}``): on a multi-slice pod,
``mesh_utils.create_device_mesh`` does real topology work worth seeing.

Device time by the program's own names: the profiler's events carry
the HLO instruction and no ``jax.named_scope``; the compiled text does
(``metadata={op_name="…"}``) and its instruction names are the trace's.
``CompiledFunction.scope_table()`` is that text reduced to ``{instruction:
(scopes, phase)}`` (rules at ``parse_op_name``), built on request from the
abstract arguments the newest compile MISS left behind; ``compiled(name)``
finds the wrapper of a name in this process, so a reader of a device trace
can ask for ``"train_step"``'s table.

Everything is behind the ``RAY_TPU_INTERNAL_TELEMETRY=0`` kill switch;
disabled, a wrapped call costs one attribute read and one bool check, no
arguments are kept and there is no table.
"""
from __future__ import annotations

import contextlib
import functools
import itertools
import os
import re
import sys
import threading
import time
import weakref

from ray_tpu._private import events as _events
from ray_tpu._private import profiling as _prof
from ray_tpu._private import telemetry as _tm
from ray_tpu._private.native_build import _REPO_ROOT


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a directory that is
    the same for every process of this checkout, and return it. Call
    before the first compile of any process that compiles.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing
    is set here. Unset: ``<checkout>/.jax_cache``, derived from this
    file's location — the path is part of the cache key, so it must not
    depend on a pid, a session directory or the working directory, or
    no later process would ever hit."""
    import jax

    _listen()
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir

    cache_dir = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


# what JAX reports of a compile (jax.monitoring), and the child span or
# cache outcome each becomes
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
# per thread: `booked`, the CompiledFunction whose call is on the stack;
# `events`, what JAX reported during it, [(kind, start, seconds)];
# `unbooked`, the cache outcome awaiting a compile outside every such call
_calls = threading.local()
_listening = False
# this process's cache outcomes so far, whoever asked: what the two
# counters add up to over `fn`, stamped on every compile span
_cache_totals = {"hit": 0, "miss": 0}


def _booked_events() -> list:
    """What JAX has reported during the wrapped call on this thread."""
    return _calls.__dict__.setdefault("events", [])


def _on_duration(event, duration, **_):
    kind = _DURATIONS.get(event)
    if kind is None:
        return
    start = time.time() - duration
    if getattr(_calls, "booked", None) is not None:
        _booked_events().append((kind, start, duration))
    elif kind == "backend_compile":
        # a compile no wrapper saw (a plain `jax.jit`, an eager
        # operation): one span, under whatever span is live here
        _prof.record_completed_span(
            "compile", kind, start, duration,
            {"persistent_cache": _calls.__dict__.pop("unbooked", "off"),
             "cache_misses_total": _cache_totals["miss"],
             "cache_hits_total": _cache_totals["hit"]})


def _on_event(event, **_):
    outcome = _CACHE_EVENTS.get(event)
    if outcome is None:
        return
    booked = getattr(_calls, "booked", None)
    _cache_totals[outcome] += 1
    _tm.counter_inc(
        "ray_tpu_compile_cache_hits_total" if outcome == "hit"
        else "ray_tpu_compile_cache_misses_total",
        tags={"fn": booked._name if booked is not None else "-"})
    if booked is not None:
        _booked_events().append((outcome, time.time(), 0.0))
    elif outcome == "miss" or "unbooked" not in _calls.__dict__:
        _calls.unbooked = outcome


def _listen():
    """Register the listeners above, once a process; nothing under
    ``RAY_TPU_INTERNAL_TELEMETRY=0``. They run only while JAX traces or
    compiles: a call that hits jit's cache fires none."""
    global _listening
    if _listening or not _tm.ENABLED:
        return
    import jax

    _listening = True
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def _abstract_key(args, kwargs):
    """Hashable per-call signature at jit's abstraction level: pytree
    structure + (shape, dtype) per array leaf, value for hashable
    scalar leaves (static-ish), type name otherwise. The PyTreeDef goes
    into the key AS-IS (it is hashable): rendering it to a string would
    cost a multi-KB format of the whole param tree on the cache-HIT
    path of every training step."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    sig = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            sig.append((tuple(shape), str(dtype)))
        elif isinstance(leaf, (int, float, bool, complex, str,
                               bytes, type(None))):
            sig.append((type(leaf).__name__, leaf))
        else:
            sig.append(type(leaf).__name__)
    return (treedef, tuple(sig))


def _abstract(args, kwargs):
    """The call's arguments as ``jit.lower`` takes them with no array in
    hand: shape, dtype and (where the leaf is committed to one, as jit
    itself asks) sharding of every array leaf, all of them readable on a
    donated array; any other leaf as it is. Lowering them gives the
    program the call ran, under the compile cache's key for it."""
    import jax

    def leaf(x):
        shape, dtype = getattr(x, "shape", None), getattr(x, "dtype", None)
        if shape is None or dtype is None:
            return x
        sharding = x.sharding if getattr(x, "committed", False) else None
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return jax.tree_util.tree_map(leaf, (args, kwargs))


# the scope a train step puts around its optimizer pass
# (`train_step.make_train_step`); a table without it is no table
OPTIMIZER_SCOPE = "optimizer"
# what JAX itself puts on the name stack between the user's scopes
_STRUCTURE = frozenset((
    "checkpoint", "rematted_computation", "while", "body", "body_pred",
    "cond", "closed_call", "shard_map"))
_BRANCH = re.compile(r"branch_\d+_fun\Z")
_SCOPE = re.compile(r"[A-Za-z_]\w*\Z")
_WRAPPED = re.compile(r"(\w+)\((.*)\)\Z", re.S)
_INSTRUCTION = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = ")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(")
_FUSED = re.compile(r" fusion\(.*\), kind=\w+, calls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')


def parse_op_name(op_name: str):
    """An HLO instruction's ``op_name`` -> ``(scopes, phase)``.

    ``op_name`` is JAX's name stack when the operation was traced,
    ``/``-joined, a transform written around the element that follows it.
    From this JAX (0.9.0), the benchmark's Nemotron-H step (remat, a Python
    loop over the layers) and GPT-2 step (a scan) compiled for a v5e:

    ``jit(step)/jvp(blocks)/mamba/conv/jit(silu)/add``
        forward: ``("blocks", "mamba", "conv")``
    ``jit(step)/transpose(jvp(loss_tail))/add_any``
        backward: ``("loss_tail",)``
    ``jit(step)/transpose(jvp(blocks))/jvp(blocks)/checkpoint/rematted_computation/moe/router/bsd,de->bse/dot_general``
        recompute: ``("blocks", "blocks", "moe", "router")`` (a checkpoint
        called inside a scope names it twice; a scan's body does not)
    ``jit(step)/transpose(jvp(blocks))/jvp(blocks)/checkpoint/attn/flash_dq/pallas_call``
        backward: ``("blocks", "blocks", "attn", "flash_dq")``: a
        ``custom_vjp``'s backward rule inherits its caller's scopes, and a
        ``pallas_call``'s ``name`` is one more
    ``jit(step)/transpose(jvp(blocks))/jvp(blocks)/checkpoint/moe/cond/branch_0_fun/transpose(jvp(jit(_through_experts)))/combine/combine/add``
        backward: ``("blocks", "blocks", "moe", "combine", "combine")``
    ``jit(step)/jvp(blocks)/while/body/closed_call/attention/bsd,dhk->bshk/dot_general``
        forward: ``("blocks", "attention")``
    ``jit(step)/optimizer/convert_element_type``
        optimizer: ``("optimizer",)``

    ``scopes``: the ``jax.named_scope`` names, outermost first. Dropped on
    the way: the trailing primitive; ``jit(…)`` (a function's name, not a
    scope) whole; the transforms ``jvp(…)`` / ``transpose(…)`` / ``vmap(…)``
    around a name, the name kept; what JAX's own control flow and
    checkpointing push (``checkpoint``, ``rematted_computation``,
    ``while``, ``body``, ``body_pred``, ``cond``, ``branch_N_fun``,
    ``closed_call``, ``shard_map``); and what is no identifier (the
    scope ``jnp.einsum`` opens under its own specification,
    ``bsd,dhk->bshk``).

    ``phase``: ``optimizer`` under the step's ``optimizer`` scope; else
    ``recompute`` under ``rematted_computation`` (the checkpointed forward
    run again inside the backward pass); else ``backward`` under a
    ``transpose(`` wrapper anywhere in the name (so a ``custom_vjp``'s
    backward rule, which JAX calls while it transposes, and the forward
    that rule differentiates once more, as `layers.moe._one_of`'s does); else
    ``forward``.

    A fusion carries ONE ``op_name``, its root's: the table gives a fusion
    whole to the scope and phase of its root and does not split it
    (`scope_table_of` says what it does where the root has none)."""
    parts, depth, start = [], 0, 0
    for i, c in enumerate(op_name):
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        elif c == "/" and depth == 0:
            parts.append(op_name[start:i])
            start = i + 1
    scopes, transposed = [], False
    for part in parts:              # the trailing primitive stays out
        function = False
        while (m := _WRAPPED.match(part)):
            function = m.group(1) == "jit"
            transposed = transposed or m.group(1) == "transpose"
            part = m.group(2)
        if not function:
            scopes.extend(
                name for name in part.split("/")
                if _SCOPE.match(name) and name not in _STRUCTURE
                and not _BRANCH.match(name))
    if OPTIMIZER_SCOPE in scopes:
        phase = "optimizer"
    elif "/rematted_computation/" in op_name:
        phase = "recompute"
    else:
        phase = "backward" if transposed else "forward"
    return tuple(scopes), phase


def scope_table_of(hlo_text: str):
    """``{instruction name: (scopes, phase)}`` of a compiled program's text
    (``compiled.as_text()``): every instruction that carries an
    ``op_name``, and every fusion that carries none under the last
    ``op_name`` inside its fused computation (the compiler wraps a fused
    root in a ``bitcast`` of its own making, which has no metadata, and
    then the fusion has none either: the instruction the root views is the
    nearest that has). None where no instruction lies under the
    ``optimizer`` scope."""
    table, last_named, unnamed_fusions, computation = {}, {}, {}, None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            computation = head.group(1)
            continue
        name = _INSTRUCTION.match(line)
        if not name:
            continue
        op_name = _OP_NAME.search(line)
        if op_name:
            table[name.group(1)] = last_named[computation] = \
                parse_op_name(op_name.group(1))
        else:
            fused = _FUSED.search(line)
            if fused:
                unnamed_fusions[name.group(1)] = fused.group(1)
    for name, fused in unnamed_fusions.items():
        if fused in last_named:
            table[name] = last_named[fused]
    if not any(phase == "optimizer" for _, phase in table.values()):
        return None
    return table


# every live wrapper by the order it was made in, for `compiled`
_LIVE: "weakref.WeakValueDictionary[int, CompiledFunction]" = \
    weakref.WeakValueDictionary()
_SERIAL = itertools.count()


def compiled(name: str):
    """The newest live ``CompiledFunction`` of this process that was made
    under ``name`` (``"train_step"``), or None."""
    named = [item for item in list(_LIVE.items()) if item[1]._name == name]
    return max(named, key=lambda item: item[0])[1] if named else None


class CompiledFunction:
    """Wraps a jitted callable with compile-cache observability.
    Transparent otherwise: unknown attributes (``lower``,
    ``clear_cache``, ...) delegate to the wrapped function."""

    def __init__(self, fn, name: str):
        self._fn = fn
        self._name = name
        self._seen: set = set()
        self._seen_lock = threading.Lock()
        # the newest compile miss's abstract arguments and, once asked
        # for, the table built from them (False: not built yet)
        self._abstract = None
        self._table = False
        functools.update_wrapper(self, fn, updated=())
        _LIVE[next(_SERIAL)] = self
        if "jax" in sys.modules:    # a plain callable's wrapper never
            _listen()               # makes its process import it

    def __getattr__(self, item):
        if item == "_fn":
            # only reachable mid-unpickle (before __setstate__ ran);
            # without this guard delegation recurses to a stack overflow
            raise AttributeError(item)
        return getattr(self._fn, item)

    # The bare jax.jit return value cloudpickles across task boundaries;
    # the wrapper must too (the lock is unpicklable, and the _seen cache
    # is deliberately dropped: the receiving process's jit cache is
    # empty, so its first call IS a compile — a fresh cache keeps the
    # hit/miss classification truthful there).
    def __getstate__(self):
        return {"fn": self._fn, "name": self._name}

    def __setstate__(self, state):
        self.__init__(state["fn"], state["name"])

    def __call__(self, *args, **kwargs):
        if not _tm.ENABLED:
            return self._fn(*args, **kwargs)
        cache_size = getattr(self._fn, "_cache_size", None)
        if cache_size is None:
            return self._call_classified_by_signature(args, kwargs)
        # O(1) hot path: jit's own cache is the source of truth (it
        # also respects static args / weak types the signature key
        # can't see). A failed compile never grows the cache, so the
        # retry naturally counts as a miss again.
        before = cache_size()
        start = time.time()
        t0 = time.perf_counter()
        tags = {"fn": self._name}
        # what JAX reports while this call is on the stack is this
        # call's (`_on_duration`, `_on_event`)
        outer = getattr(_calls, "booked", None)
        _calls.booked = self
        try:
            out = self._fn(*args, **kwargs)
        except BaseException:
            # NOT gated on the cache delta: jax grows the pjit cache
            # even when tracing raises, so the delta can't distinguish
            # failure modes — the _seen set can (below)
            _calls.__dict__.pop("events", None)
            self._record_failed_call(args, kwargs, start,
                                     time.perf_counter() - t0, tags)
            raise
        finally:
            _calls.booked = outer
        if cache_size() == before:
            if "events" in _calls.__dict__:
                # traced or compiled without growing jit's cache (an
                # eager operation inside a wrapped plain function): no
                # miss of this wrapper, nothing to split
                del _calls.events
            _tm.counter_inc("ray_tpu_pjit_cache_total",
                            tags={**tags, "result": "hit"})
            return out
        # a compile happened: remember the signature (cheap relative to
        # the compile it just paid for) so a LATER failing call of the
        # same signature classifies as a runtime error, not a compile
        # failure
        with self._seen_lock:
            self._seen.add(_abstract_key(args, kwargs))
            self._abstract, self._table = _abstract(args, kwargs), False
        self._record_miss(start, time.perf_counter() - t0, tags)
        return out

    def scope_table(self):
        """``{HLO instruction name: (scopes, phase)}`` of the program the
        newest compile miss built (`parse_op_name` has the rules), the
        names being the device trace's. Built on the first request, from
        the compiled text (the program is in the compile cache; the text
        is dropped once parsed), and kept.

        None where there is nothing to read or nothing to trust: no call
        has compiled yet, telemetry is off, the callable is not jitted, or
        no instruction lies under the step's ``optimizer`` scope. The last
        is a program that has no such scope, or a STALE one: the
        persistent compile cache's key leaves metadata out
        (``jax_compilation_cache_include_metadata_in_key`` is False), so
        an executable loaded from it carries the ``op_name``s of whoever
        compiled it first, which may predate every scope."""
        if self._table is False:
            args = self._abstract
            if args is None or not hasattr(self._fn, "lower"):
                return None
            self._table = scope_table_of(
                self._fn.lower(*args[0], **args[1]).compile().as_text())
        return self._table

    def _record_failed_call(self, args, kwargs, start, dur, tags):
        """Error-path classification (cost is irrelevant here): the
        cache did not grow, so either the compile itself failed (XLA
        error, OOM during lowering — signature never seen to succeed)
        or an already-compiled program failed at runtime (signature in
        _seen; not a compile event at all). Without this, a
        crash-looping worker shows ZERO compile activity on the common
        _cache_size path while the fallback path reports COMPILE_END
        ok=False."""
        try:
            key = _abstract_key(args, kwargs)
        except Exception:
            return
        with self._seen_lock:
            if key in self._seen:
                return   # runtime failure of a compiled program
        _tm.counter_inc("ray_tpu_pjit_cache_total",
                        tags={**tags, "result": "miss"})
        _events.record("COMPILE_BEGIN", fn=self._name, started_at=start)
        _events.record("COMPILE_END", fn=self._name, ok=False,
                       duration_s=dur)

    def _record_miss(self, start: float, dur: float, tags: dict):
        """Metrics + BEGIN/END events + both span planes for one
        compile, materialized after the fact (the cache-size delta is
        only knowable once the call returned). A compile inside an
        active train step additionally lands in the step-anatomy ring
        (and stamps the events) — a recompiling step must show up as a
        compile-bounded step, not unexplained "compute"."""
        from ray_tpu._private import step_anatomy as _sa
        from ray_tpu.util import tracing

        step_id = _sa.current_step_id()
        _tm.counter_inc("ray_tpu_pjit_cache_total",
                        tags={**tags, "result": "miss"})
        _tm.observe("ray_tpu_pjit_compile_seconds", dur, tags=tags)
        _events.record("COMPILE_BEGIN", fn=self._name, started_at=start,
                       step=step_id)
        _events.record("COMPILE_END", fn=self._name, ok=True,
                       duration_s=dur, step=step_id)
        if step_id is not None:
            m1 = time.monotonic()
            _sa.record_activity("compile", m1 - dur, m1, blocking=True,
                                fn=self._name)
        start_ns = int(start * 1e9)
        end_ns = start_ns + int(dur * 1e9)
        self._record_split(start, dur, step_id,
                           _calls.__dict__.pop("events", ()))
        tracing.record_completed_span(f"compile {self._name}", "INTERNAL",
                                      start_ns, end_ns,
                                      attributes={"fn": self._name,
                                                  "step": step_id})

    def _record_split(self, start: float, dur: float, step_id, events):
        """The timeline's `compile::<fn>` and, under it, what JAX
        reported during the call: `trace` (the longest trace: a nested
        jit's is inside its caller's), and every `lower`,
        `backend_compile` (the compile on a persistent-cache miss, the
        load on a hit) and `cache_load` (the retrieval inside that
        load), each with its own start and duration."""
        from ray_tpu._private import step_anatomy as _sa

        traces = [e for e in events if e[0] == "trace"]
        children = ([max(traces, key=lambda e: e[2])] if traces else []) \
            + [e for e in events
               if e[0] in ("lower", "backend_compile", "cache_load")]
        outcomes = {e[0] for e in events}
        cache = ("miss" if "miss" in outcomes
                 else "hit" if "hit" in outcomes else "off")
        span_id = _prof.record_completed_span(
            "compile", f"compile::{self._name}", start, dur,
            {"fn": self._name, "step": step_id, "persistent_cache": cache,
             "first_execute_s": max(0.0, dur - _sa._total(_sa._merge(
                 [(s, s + d) for _, s, d in children]))),
             "cache_misses_total": _cache_totals["miss"],
             "cache_hits_total": _cache_totals["hit"]})
        for kind, child_start, seconds in children:
            _prof.record_completed_span("compile", kind, child_start,
                                        seconds, {"fn": self._name},
                                        parent=span_id)

    def _call_classified_by_signature(self, args, kwargs):
        """Plain (non-jit) callables have no ``_cache_size``: classify
        by a per-signature key. The signature is taken BEFORE the call —
        donated buffers are unreadable after."""
        key = _abstract_key(args, kwargs)
        with self._seen_lock:
            hit = key in self._seen
            if not hit:
                self._seen.add(key)
        tags = {"fn": self._name}
        if hit:
            _tm.counter_inc("ray_tpu_pjit_cache_total",
                            tags={**tags, "result": "hit"})
            return self._fn(*args, **kwargs)
        from ray_tpu._private import step_anatomy as _sa
        from ray_tpu.util import tracing

        _tm.counter_inc("ray_tpu_pjit_cache_total",
                        tags={**tags, "result": "miss"})
        _events.record("COMPILE_BEGIN", fn=self._name)
        t0 = time.perf_counter()
        m0 = time.monotonic()
        try:
            with _prof.record_span("compile", f"compile::{self._name}"):
                with tracing.span(f"compile {self._name}", "INTERNAL",
                                  attributes={"fn": self._name}):
                    out = self._fn(*args, **kwargs)
        except BaseException:
            # a failed compile must not be remembered as compiled —
            # the retry should count (and be timed) as a miss again
            with self._seen_lock:
                self._seen.discard(key)
            _events.record("COMPILE_END", fn=self._name, ok=False,
                           duration_s=time.perf_counter() - t0)
            raise
        dur = time.perf_counter() - t0
        _sa.record_activity("compile", m0, time.monotonic(),
                            blocking=True, fn=self._name)
        _tm.observe("ray_tpu_pjit_compile_seconds", dur, tags=tags)
        _events.record("COMPILE_END", fn=self._name, ok=True,
                       duration_s=dur)
        return out


@contextlib.contextmanager
def mesh_build_timer(kind: str):
    """Time one device-mesh construction into
    ``ray_tpu_mesh_build_seconds{kind}`` + both span planes."""
    if not _tm.ENABLED:
        yield
        return
    from ray_tpu.util import tracing

    t0 = time.perf_counter()
    with _prof.record_span("mesh", f"mesh_build::{kind}"):
        with tracing.span(f"mesh_build {kind}", "INTERNAL",
                          attributes={"kind": kind}):
            yield
    _tm.observe("ray_tpu_mesh_build_seconds",
                time.perf_counter() - t0, tags={"kind": kind})


def timed_mesh_build(kind: str):
    """Decorator form of ``mesh_build_timer`` for the mesh factories."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with mesh_build_timer(kind):
                return fn(*args, **kwargs)
        return wrapper
    return deco
