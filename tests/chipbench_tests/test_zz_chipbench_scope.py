"""`readers/trace_scope.py` on a hand-made trace and scope table whose
answers are known, and each of its metrics resolved in exactly the cells
BENCHMARK.json lists it for."""
import pytest

from chipbench import catalog
from chipbench.readers import trace_scope

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


GPT2 = {"gpt2s-b16", "gpt2m-b16-remat", "gpt2l-dp2tp2"}
ALL = {w["name"] for w in MANIFEST["workloads"]}
CELLS_OF = {
    "train_step.backward_share": ALL,
    "train_step.recompute_share": {"gpt2m-b16-remat", "gpt2l-dp2tp2",
                                   "nemotronh9l-b1s8k",
                                   "smallthinker4l-b1s16k"},
    "train_step.optimizer_share": ALL,
    "train_step.loss_tail_share": ALL,
    "train_step.mlp_share": GPT2,
    "train_step.unscoped_share": ALL,
    "attn.scoped_share": ALL,
    "moe.scoped_share": {"olmoe1l-b2s4k", "nemotronh9l-b1s8k",
                         "smallthinker4l-b1s16k"},
    "ssm.scoped_share": {"nemotronh9l-b1s8k"},
}


@pytest.mark.parametrize("cell", sorted(ALL))
@pytest.mark.parametrize("metric", sorted(CELLS_OF))
def test_each_metric_resolves_in_exactly_its_cells(metric, cell):
    resolved = {m["name"]: m for m in catalog.resolve_cell(
        MANIFEST, cell, "per_layer")["metrics"]}
    assert (metric in resolved) == (cell in CELLS_OF[metric])
    if metric in resolved:
        spec = resolved[metric]
        assert spec["reader"] == "chipbench.readers.trace_scope"
        assert spec["unit"] == "%"
        # the reader takes the file's arguments as they are
        assert set(spec["args"]) <= {"scopes", "phase", "unscoped"}
    # the cells that remat are the cells that report a recomputation
    if metric == "train_step.recompute_share":
        traffic = catalog.resolve_cell(MANIFEST, cell, "per_layer")["traffic"]
        assert bool(traffic["remat"]) == (cell in CELLS_OF[metric])
