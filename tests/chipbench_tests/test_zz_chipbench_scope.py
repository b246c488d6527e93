"""`readers/trace_scope.py` on a hand-made trace and scope table whose
answers are known, and each of its first nine metrics resolved in exactly
the cells BENCHMARK.json lists it for: in the manifest as it is, and with
a later cell appended (``later_cell.py``)."""
import pytest

from chipbench import catalog
from chipbench.readers import trace_scope
from tests.chipbench_tests import later_cell

MANIFEST = catalog.load_manifest()

# instruction -> (scopes, phase), as `CompiledFunction.scope_table` gives it
TABLE = {
    "fusion.1": (("embed",), "forward"),
    "fusion.2": (("blocks", "attention"), "forward"),
    "flash_fwd.3": (("blocks", "attention"), "recompute"),
    "fusion.4": (("blocks", "blocks", "moe", "router"), "recompute"),
    "gmm.5": (("blocks", "moe", "experts"), "backward"),
    "fusion.6": (("loss_tail",), "backward"),
    "fusion.7": (("optimizer",), "optimizer"),
    "add_any.8": ((), "backward"),          # transposed outside every scope
    "fusion.9": (("blocks",), "forward"),    # the loop's own slices
}
# event text -> seconds: 100 in all; copy.10 is in no table
PER_OP_S = {
    "%fusion.1 = f32[8]{0} fusion(%p), kind=kLoop": 2.0,
    "%fusion.2 = f32[8]{0} fusion(%p), kind=kOutput": 10.0,
    "%flash_fwd.3 = bf16[8]{0} custom-call(%q, %k, %v)": 8.0,
    "%fusion.4 = f32[8]{0} fusion(%p), kind=kLoop": 5.0,
    "%gmm.5 = bf16[8]{0} custom-call(%a, %b)": 25.0,
    "%fusion.6 = f32[8]{0} fusion(%p), kind=kOutput": 20.0,
    "%fusion.7 = f32[8]{0} fusion(%p), kind=kLoop": 15.0,
    "%add_any.8 = f32[8]{0} add(%a, %b)": 4.0,
    "fusion.9 = f32[8]{0} fusion(%p), kind=kLoop": 6.0,
    "%copy.10 = f32[8]{0} copy(%p)": 5.0,
}
CTX = {"trace": {"per_op_s": PER_OP_S, "busy_s": 100.0}}


@pytest.fixture
def table(monkeypatch):
    monkeypatch.setattr(trace_scope, "_table", lambda: TABLE)


def test_phases_and_unscoped_add_to_100(table):
    shares = {phase: trace_scope.read(CTX, phase=phase) for phase in
              ("forward", "recompute", "backward", "optimizer")}
    assert shares == {"forward": 18.0, "recompute": 13.0, "backward": 45.0,
                      "optimizer": 15.0}
    # an instruction outside every scope is the unscoped share's, whatever
    # its phase; so is one the table does not know
    assert trace_scope.read(CTX, unscoped=True) == 9.0
    assert sum(shares.values()) + 9.0 == pytest.approx(100.0)


def test_scopes_match_anywhere_in_the_tuple_and_count_once(table):
    assert trace_scope.read(CTX, scopes=["loss_tail"]) == 20.0
    assert trace_scope.read(CTX, scopes=["attention", "attn"]) == 18.0
    # under both `moe` and `router`: once
    assert trace_scope.read(CTX, scopes=["moe", "router", "experts"]) == 30.0
    assert trace_scope.read(CTX, scopes=["blocks"]) == 54.0
    assert trace_scope.read(CTX, scopes=["moe"], phase="backward") == 25.0


def test_no_unscoped_instruction_reads_zero(table):
    scoped = {k: v for k, v in PER_OP_S.items()
              if "add_any" not in k and "copy" not in k}
    ctx = {"trace": {"per_op_s": scoped, "busy_s": 91.0}}
    assert trace_scope.read(ctx, unscoped=True) == 0.0


@pytest.mark.parametrize("case", ["no trace", "no table", "nothing matched",
                                  "no program to ask"])
def test_none_where_there_is_nothing_to_read(monkeypatch, case):
    args = {"scopes": ["mamba"]} if case == "nothing matched" else \
        {"phase": "backward"}
    ctx = dict(CTX, trace=None) if case == "no trace" else CTX
    if case == "no program to ask":
        # a program without `compile_watch.compiled` (the parent's), or
        # one in which no train step was made: nothing is raised
        from ray_tpu.parallel import compile_watch

        monkeypatch.delattr(compile_watch, "compiled")
    else:
        monkeypatch.setattr(trace_scope, "_table", lambda: (
            None if case == "no table" else TABLE))
    assert trace_scope.read(ctx, **args) is None
    if case in ("no table", "no program to ask"):
        assert trace_scope.read(ctx, unscoped=True) is None


def test_asks_the_newest_train_step_of_the_process(monkeypatch):
    from ray_tpu.parallel import compile_watch

    class Step:
        def scope_table(self):
            return TABLE

    monkeypatch.setattr(compile_watch, "compiled",
                        lambda name: Step() if name == "train_step" else None)
    assert trace_scope.read(CTX, phase="optimizer") == 15.0


ALL = {w["name"] for w in MANIFEST["workloads"]}
# the reader's first nine metrics; which cells report each is the
# manifest's to say (`workloads`, or every cell), not this file's
SCOPE_METRICS = (
    "train_step.backward_share", "train_step.recompute_share",
    "train_step.optimizer_share", "train_step.loss_tail_share",
    "train_step.mlp_share", "train_step.unscoped_share",
    "attn.scoped_share", "moe.scoped_share", "ssm.scoped_share")
# remats, and no metric of its own or of the first nine reads its
# recomputation (PERF.md section 7)
NO_RECOMPUTE_METRIC = {"lfm2moe5l-b2s8k"}


def check_metric_in_cell(manifest, metric, cell):
    entry = next(m for m in manifest["per_layer"] if m["name"] == metric)
    cells = entry.get("workloads",
                      [w["name"] for w in manifest["workloads"]])
    per_layer = catalog.resolve_cell(manifest, cell, "per_layer")
    resolved = {m["name"]: m for m in per_layer["metrics"]}
    assert (metric in resolved) == (cell in cells)
    if metric in resolved:
        spec = resolved[metric]
        assert spec["reader"] == "chipbench.readers.trace_scope"
        assert spec["unit"] == "%"
        # the reader takes the file's arguments as they are
        assert set(spec["args"]) <= {"scopes", "phase", "unscoped"}
    # the cells that remat are the cells that report a recomputation, each
    # by `train_step.recompute_share` or by a metric of its own
    if metric == "train_step.recompute_share":
        recomputed = [m["name"] for m in resolved.values()
                      if m["reader"] == "chipbench.readers.trace_scope"
                      and m["args"] == {"phase": "recompute"}]
        if per_layer["traffic"]["remat"]:
            assert bool(recomputed) == (cell not in NO_RECOMPUTE_METRIC)
        else:
            assert not recomputed, recomputed


@pytest.mark.parametrize("cell", sorted(ALL))
@pytest.mark.parametrize("metric", sorted(SCOPE_METRICS))
def test_each_metric_resolves_in_exactly_its_cells(metric, cell):
    check_metric_in_cell(MANIFEST, metric, cell)


def test_a_later_remat_cell_breaks_none_of_them():
    grown = later_cell.with_a_later_cell(MANIFEST)
    for metric in SCOPE_METRICS:
        for cell in sorted(ALL | {later_cell.CELL}):
            check_metric_in_cell(grown, metric, cell)
    # and the check does fail where a remat cell brings no such metric
    grown["per_layer"].pop()
    with pytest.raises(AssertionError):
        check_metric_in_cell(grown, "train_step.recompute_share",
                             later_cell.CELL)
