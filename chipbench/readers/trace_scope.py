"""Device time by the program's own names: the share of the device's busy
time spent in the instructions that lie under given ``jax.named_scope``s, or
in one phase of the step, in percent.

The profiler's event text is the HLO instruction and carries no scope; the
program's compiled text does, and its instruction names are the trace's.
The program keeps that as a table, ``{instruction name: (scopes, phase)}``
(``ray_tpu.parallel.compile_watch``: ``compiled("train_step").scope_table()``,
asked in the worker process, after the window), and this reader folds the
trace's per-operation seconds over it (the name is what precedes `` = `` in
the event's text):

* ``scopes``: an instruction counts when ANY of the names is in its tuple
  (``scopes`` is outermost first: ``("blocks", "moe", "router")``), once
  however many of them are;
* ``phase``: and, if given, when its phase is this one of ``forward``,
  ``recompute`` (the checkpointed forward run again inside the backward
  pass), ``backward`` and ``optimizer``;
* ``unscoped``: instead, the instructions the table lacks or whose tuple is
  empty, what no scope of the program reaches (0.0 where there is none).

The four phases and ``unscoped`` (given ``phase``, an instruction outside
every scope is the unscoped share's, not its phase's) add to 100. A fusion
carries one name, its root's, and is counted whole.

None without a trace, without a table (a program that has none, or whose
table was refused: no instruction under the step's ``optimizer`` scope,
which is also what an executable from a compile cache filled before the
scopes existed looks like), and where a scope or phase matched nothing.
"""


def _table():
    from ray_tpu.parallel import compile_watch

    find = getattr(compile_watch, "compiled", None)   # the program has none
    step = find("train_step") if find else None
    return step.scope_table() if step is not None else None


def read(ctx, scopes=None, phase=None, unscoped=False):
    trace = ctx["trace"]
    if not trace:
        return None
    table = _table()
    if table is None:
        return None
    wanted = set(scopes or ())
    seconds, matched = 0.0, False
    for text, spent in trace["per_op_s"].items():
        name = text.partition(" = ")[0].strip().lstrip("%")
        under, at = table.get(name, ((), None))
        if unscoped:
            counts = not under
        else:
            counts = (bool(under)
                      and (not wanted or not wanted.isdisjoint(under))
                      and phase in (None, at))
        if counts:
            seconds, matched = seconds + spent, True
    if not matched and not unscoped:
        return None
    return 100.0 * seconds / trace["busy_s"]
