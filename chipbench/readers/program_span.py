"""Seconds of the run's start-up by the program's own spans: what the
program recorded, on the benchmark's clock (``time.time()``), between
``process_start`` and ``window_start``.

The program keeps a ring of spans a process and merges them on request
(``ray_tpu.timeline()``: the driver's, each raylet's and every worker's);
a span carries ``cat``, ``name``, ``ts`` / ``dur`` in µs and, in ``args``,
its ``id``, its ``parent`` and the training ``run`` it belongs to. This
reader asks for the merged timeline once a run, from inside the train
worker (where the readers run), and keeps it in ``ctx``. It picks the spans
of THIS run (``args.run`` is the run of the worker's live span; a span
without a run, as ``init`` and its children are, counts where it started
after ``process_start``) that started before ``window_start``, cut at
``window_start``:

* ``spans``: the seconds those names cover, counted once (a name's spans
  are summed; names that overlap are not counted twice);
* ``under``: only spans whose parent is a span of that name
  (``trace`` + ``lower`` under ``compile::train_step``);
* ``unspanned``: instead, ``window_start − process_start`` less what
  ``spans`` cover: the start-up the program's tracing does not see;
* ``stamp``: instead, the number the newest of the ``compile`` spans before
  the window carries under that key in ``args`` (the program stamps every
  compile span with its process's cache counters as they stood then:
  ``cache_misses_total`` is ``ray_tpu_compile_cache_misses_total`` summed
  over ``fn``).

A number, or ``LookupError`` naming the span that is missing: a start-up
span the program lost must not read as a fast start-up. None only where the
program has no such tracing at all (spans carry no ``id``: a program from
before these metrics).
"""
import sys

from chipbench import trace_reduce

CATEGORIES = ("startup", "compile")


def _run_of_this_thread():
    from ray_tpu._private import profiling

    live = profiling.current()
    return live[1] if live else None


def _timeline(ctx):
    """``(spans, by id)`` of this run before the window, or None where
    the program's spans carry no ids."""
    if "program_spans" not in ctx:
        profiling = sys.modules.get("ray_tpu._private.profiling")
        if not hasattr(profiling, "cause"):
            ctx["program_spans"] = None
        else:
            import ray_tpu

            clock, run = ctx["clock"], _run_of_this_thread()
            lo = int(clock["process_start"] * 1e6)
            hi = int(clock["window_start"] * 1e6)
            spans = [
                ev for ev in ray_tpu.timeline()
                if ev.get("ph") == "X" and ev.get("cat") in CATEGORIES
                and lo <= ev["ts"] < hi
                and ev["args"].get("run", run) == run]
            ctx["program_spans"] = (
                spans, {ev["args"]["id"]: ev for ev in spans}, hi)
    return ctx["program_spans"]


def read(ctx, spans=(), under=None, unspanned=False, stamp=None):
    found = _timeline(ctx)
    if found is None:
        return None
    events, by_id, hi = found
    if stamp is not None:
        stamped = [ev for ev in events if stamp in ev["args"]]
        if not stamped:
            raise LookupError(
                f"no compile span before the window carries {stamp!r}")
        newest = max(stamped, key=lambda ev: ev["ts"] + ev["dur"])
        return newest["args"][stamp]
    intervals = []
    for name in spans:
        named = [ev for ev in events if ev["name"] == name and (
            under is None or by_id.get(
                ev["args"].get("parent"), {}).get("name") == under)]
        if not named:
            where = f" under {under!r}" if under else ""
            raise LookupError(
                f"the program's timeline holds no span {name!r}{where} "
                f"of this run before the window")
        intervals += [(ev["ts"], min(ev["ts"] + ev["dur"], hi))
                      for ev in named]
    covered = trace_reduce.total(intervals) / 1e6
    if unspanned:
        clock = ctx["clock"]
        return clock["window_start"] - clock["process_start"] - covered
    return covered
