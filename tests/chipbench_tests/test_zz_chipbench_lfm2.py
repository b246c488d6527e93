"""The ``lfm2_moe`` architecture's benchmark files, without the chip: the
fixture ``lfm2-tiny`` (a configuration and a traffic mix in THIS directory;
model, reference and accounting are the program's and the benchmark's own)
through the ``train_fit`` job on the CPU, the accounting's arithmetic at the
published sizes against numbers worked out by hand, the configuration file
against the catalog row, the manifest's entries, the new reader on a
hand-made trace and scope table, and the precision controls of
``benchmarks/precision_control.py`` through the same job."""
import json
import time

import numpy as np
import pytest

from chipbench import catalog, flops
from chipbench.accounting import lfm2_moe as accounting
from chipbench.jobs import train_fit
from chipbench.readers import mfu, trace_scope, trace_short_conv
from tests.chipbench_tests import later_cell, tiny_fit

MANIFEST = {
    "paths": ["chipbench", "tests/chipbench_tests"],
    "workloads": [{"name": "lfm2-tiny", "config": "lfm2-tiny",
                   "traffic": "fit-lfm2-tiny", "chips": 1,
                   "why": "conv and attention layers, dense and routed "
                          "feed-forwards, at test sizes"}],
    "end_to_end": [
        {"name": "tokens_per_s_per_chip", "unit": "tokens/s/chip"},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [
        {"name": "train_step.compiles_in_window", "unit": "count"},
        {"name": "conv.scoped_share", "unit": "%"},
        {"name": "kernels.short_conv_roofline", "unit": "%"},
        {"name": "mlp.gated_scoped_share", "unit": "%"},
        {"name": "moe.sigmoid_held_share", "unit": "%"}],
}
REAL = catalog.load_manifest()
PUBLISHED = catalog.load_json(REAL, "configs", "lfm2-24b-a2b-5l")
CELL = "lfm2moe5l-b2s8k"
NEW_METRICS = ("conv.scoped_share", "kernels.short_conv_roofline",
               "mlp.gated_scoped_share", "moe.sigmoid_held_share")
LEAVES = ("wte", "w_in", "conv_w", "q_norm", "wv", "wg", "w_gate", "w_down")


@pytest.fixture(scope="module")
def fit(once_a_run):
    """ONE traced fit with the metrics of both groups, once a test run: the
    two cases below read a group each of it (`tiny_fit.py`)."""
    return once_a_run("lfm2_tiny_fit", lambda: tiny_fit.traced(
        MANIFEST, "lfm2-tiny", seed=40))


@pytest.mark.parametrize("trace", [False, True])
def test_lfm2_tiny_through_the_trainer(fit, trace):
    cell = catalog.resolve_cell(MANIFEST, "lfm2-tiny",
                                "per_layer" if trace else "end_to_end")
    assert cell["accounting"] == "chipbench.accounting.lfm2_moe"
    assert cell["reference"] == "chipbench.references.lfm2_moe"
    record = fit
    json.dumps(record)
    assert record["correct"], (record["verdicts"], record["check"])
    assert set(record["check"]["errors"]) == {"loss"} | {
        "grad_" + k for k in LEAVES}
    assert record["failed"] == 0 and record["attempted"] >= 4
    values = tiny_fit.values_of(record, cell)
    if trace:
        # no TPU plane in a CPU trace: the cell's own metrics are left
        # out, not invented
        assert set(values) == {"train_step.compiles_in_window"}
        assert values["train_step.compiles_in_window"] == 0
        return
    assert values["tokens_per_s_per_chip"] == pytest.approx(
        record["attempted"] * 2 * 40 / record["clock"]["window_s"])
    # the mfu reader, given a peak. A token uses: the tied head; four conv
    # operators (in 64·192, out 64·64) and two attentions (q, o 64·64; k, v
    # 64·32); two dense feed-forwards of 3·64·96; in each of the four routed
    # layers the router over 16 and 3 · 4/16 of a gated expert of 3·64·32;
    # the scores over 40 · 41 / 2 pairs in the two attention layers
    used = (256 * 64 + 4 * (64 * 192 + 64 * 64)
            + 2 * (2 * 64 * 64 + 2 * 64 * 32) + 2 * 3 * 64 * 96
            + 4 * (64 * 16 + 0.75 * 3 * 64 * 32))
    per_token = 6 * used + 12 * 2 * 820 * 64 / 40
    assert per_token == 1_026_816.0
    assert accounting.train_flops_per_token(cell["model"], 40) == 1_026_816
    ctx = {"accounting": cell["accounting"], "model": cell["model"],
           "traffic": cell["traffic"], "chips": 1, "clock": record["clock"],
           "counters": {"steps": record["attempted"]},
           "peaks": {"bf16_flops_per_s": 1e12}}
    assert mfu.read(ctx) == pytest.approx(
        100 * record["attempted"] * 2 * 40 / record["clock"]["window_s"]
        * 1_026_816 / 1e12, rel=1e-12)


def test_the_published_configuration_is_the_catalog_rows():
    """Every key of the public ``config.json`` as the model-configs catalog
    holds it, unchanged but for the two counts the cut reduces; the depth
    the cell runs, the published counts, the deployment and what was
    assumed are filed beside them."""
    period = ["full_attention", "conv", "conv", "conv"]
    for key, value in {
            "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
            "intermediate_size": 11776,
            "layer_types": ["conv", "conv"] + period * 9
            + ["full_attention", "conv"],
            "max_position_embeddings": 128000, "model_type": "lfm2_moe",
            "moe_intermediate_size": 1536, "norm_eps": 1e-05,
            "norm_topk_prob": True, "num_attention_heads": 32,
            "num_dense_layers": 2, "num_experts_per_tok": 4,
            "num_hidden_layers": 40, "num_key_value_heads": 8,
            "rope_parameters": {"rope_theta": 1000000,
                                "rope_type": "default"},
            "routed_scaling_factor": 1, "use_expert_bias": True}.items():
        assert PUBLISHED[key] == value, key
    assert len(PUBLISHED["layer_types"]) == 40
    assert PUBLISHED["layer_types"].count("conv") == 30
    assert PUBLISHED["reduced"] == ["layers", "num_experts", "vocab_size"]
    assert (PUBLISHED["layers"], PUBLISHED["num_experts"],
            PUBLISHED["vocab_size"]) == (5, 8, 8192)
    assert PUBLISHED["published"] == {
        "layers": 40, "num_experts": 64, "vocab_size": 65536}
    deployment = PUBLISHED["deployment"]
    assert deployment["chips_sharing_a_layer"] == 8
    assert deployment["first_expert"] == 0
    assert "8 chips share each layer" in deployment["what"]
    assert deployment["chips_sharing_a_layer"] * PUBLISHED["num_experts"] \
        == 64
    assert deployment["chips_sharing_a_layer"] * PUBLISHED["vocab_size"] \
        == 65536
    for key in ("layers", "head_dim", "tie_embedding", "operator",
                "feed_forward", "router", "sequence", "weights",
                "param_dtype", "compute_dtype"):
        assert key in PUBLISHED["assumed"], key
    assert "1e-6" in PUBLISHED["assumed"]["router"]
    assert "no auxiliary loss" in PUBLISHED["assumed"]["router"]
    assert "30 : 10" in PUBLISHED["assumed"]["layers"]
    assert "table normal at 128" in PUBLISHED["assumed"]["weights"]
    assert "ONE pass" in PUBLISHED["assumed"]["compute_dtype"]
    assert PUBLISHED["entry"] == "ray_tpu.models.lfm2:lfm2_24b_a2b_5l"
    assert PUBLISHED["reference"] == "lfm2_moe"
    # the layers the cut runs: the published 1-5, one of them dense
    assert accounting.layout(PUBLISHED) == (
        ["conv", "full_attention", "conv", "conv", "conv"], 1)
    # the cell is the manifest's, with the traffic ISSUE 40 gives it
    cell = catalog.resolve_cell(REAL, CELL, "per_layer")
    assert cell["workload"]["chips"] == 1
    assert cell["workload"]["traffic"] == "fit-b2-s8192-remat"
    assert cell["traffic"] == dict(
        catalog.load_json(REAL, "traffic", "fit-b1-s8192-remat"), batch=2)
    assert {k: cell["traffic"][k] for k in (
        "job", "batch", "seq", "remat", "attention", "check_sequences",
        "vocab_divisor", "batches", "trace_from_step", "trace_steps",
        "mesh")} == {
        "job": "train_fit", "batch": 2, "seq": 8192, "remat": True,
        "attention": "auto", "check_sequences": 1, "vocab_divisor": 16,
        "batches": 64, "trace_from_step": 10, "trace_steps": 3,
        "mesh": {"dp": 1}}
    assert cell["traffic"]["optimizer"] == {
        "learning_rate": 0.0003, "warmup_steps": 10, "total_steps": 10000}
    reported = {m["name"] for m in cell["metrics"]}
    assert set(NEW_METRICS) | {
        "kernels.flash_share", "kernels.flash_roofline", "attn.scoped_share",
        "train_step.loss_tail_share", "train_step.optimizer_share",
        "train_step.unscoped_share", "device.idle_share"} <= reported
    assert not reported & {"moe.routed_share", "moe.held_routed_share",
                           "ssm.mixer_share", "attn.window_flash_share",
                           "train_step.mlp_share", "moe.scoped_share"}


def check_the_manifests_entries(manifest):
    """Appended behind what was there, found by name: a later PR's entries
    behind them break nothing here."""
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index("lfm2-24b-a2b-5l") == \
        configs.index("smallthinker-21b-a3b-4l") + 1
    config = manifest["configs"][configs.index("lfm2-24b-a2b-5l")]
    assert config["source"] == PUBLISHED["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    assert config["file"] == "chipbench/configs/lfm2-24b-a2b-5l.json"
    assert config["reduced"] == PUBLISHED["reduced"]
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) == cells.index("smallthinker4l-b1s16k") + 1
    assert manifest["workloads"][cells.index(CELL)]["chips"] == 1
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index("ssm.scoped_share") + 1
    assert tuple(names[at:at + 4]) == NEW_METRICS
    for m, (layer, better) in zip(manifest["per_layer"][at:at + 4], (
            ("conv", "lower"), ("kernels", "higher"), ("mlp", "lower"),
            ("moe", "lower"))):
        assert (m["layer"], m["better"], m["unit"], m["moves"], m["source"],
                m["workloads"]) == (layer, better, "%", "mfu", "device_trace",
                                    [CELL])
    # beside its own four, the cell is named by the two accepted metrics of
    # the plain flash calls (a `benchmark` PR's edit) and by no other's
    naming = {m["name"] for m in manifest["per_layer"] + manifest["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert naming == set(NEW_METRICS) | {"kernels.flash_share",
                                         "kernels.flash_roofline"}
    for entry in manifest["configs"] + manifest["workloads"]:
        assert len(entry["why"]) <= 200, entry["name"]
    # three of the four are metric files over the scope reader
    for name, scopes in (("conv.scoped_share", ["short_conv"]),
                         ("mlp.gated_scoped_share", ["mlp"]),
                         ("moe.sigmoid_held_share", ["moe"])):
        assert catalog.load_json(manifest, "metrics", name) == {
            "reader": "trace_scope", "args": {"scopes": scopes}}
    assert catalog.load_json(manifest, "metrics",
                             "kernels.short_conv_roofline") \
        == {"reader": "trace_short_conv"}


def test_the_manifests_new_entries():
    check_the_manifests_entries(REAL)


def test_a_later_cell_breaks_nothing_here():
    check_the_manifests_entries(later_cell.with_a_later_cell(REAL))


def test_params_and_flops_a_token_by_hand():
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    router, expert = 2048 * 64 + 64, 3 * 2048 * 1536
    dense = 3 * 2048 * 11776
    assert (conv, attention, router, expert, dense) == (
        16_783_360, 10_485_888, 131_136, 9_437_184, 72_351_744)
    norms = 2 * 2048
    first = conv + norms + dense
    attn_layer = attention + norms + router + 8 * expert
    conv_layer = conv + norms + router + 8 * expert
    assert (first, attn_layer, conv_layer) == (
        89_139_200, 86_118_592, 92_416_064)
    assert accounting.params(PUBLISHED) == \
        first + attn_layer + 3 * conv_layer + 8192 * 2048 + 2048 == \
        469_285_248
    # 16 bytes a parameter: 7.51 GB of state; all 64 experts 9.7 GB a layer;
    # the next larger cuts: 16 held and a quarter of the vocabulary 12.6 GB,
    # two periods at 8 experts 13.3 GB
    assert 16 * 469_285_248 == pytest.approx(7.51e9, rel=1e-3)
    assert 16 * 64 * expert == pytest.approx(9.66e9, rel=1e-3)
    wider = accounting.params(dict(PUBLISHED, num_experts=16,
                                   vocab_size=16384))
    assert wider == 788_052_352 and 16 * wider == pytest.approx(12.6e9,
                                                                rel=3e-3)
    deeper = accounting.params(dict(PUBLISHED, layers=9))
    assert deeper == 469_285_248 + attn_layer + 3 * conv_layer == 832_652_032
    assert 16 * deeper == pytest.approx(13.3e9, rel=3e-3)
    # uncut: the row's "24B"; both leading dense layers, 38 routed
    whole = dict(PUBLISHED, layers=40, num_experts=64, vocab_size=65536)
    assert accounting.layout(whole)[1] == 2
    assert accounting.params(whole) == 23_843_661_440
    # FLOPs a token at S 8,192: the matmul parameters a token uses, the held
    # experts at their expectation of 4 · 8/64 of one; the scores at the
    # causal area of the one attention layer
    used = (4 * 2048 * 8192 + 2 * 2048 * 2048 + 2 * 2048 * 512 + dense
            + 4 * (2048 * 64 + 0.5 * expert) + 2048 * 8192)
    assert used == 186_122_240
    pairs = 8192 * 8193 // 2
    assert accounting.train_flops_per_token(PUBLISHED, 8192) == \
        round(6 * used + 12 * pairs * 2048 / 8192) == 1_217_409_024
    # 406 M forward a token; 16,384 tokens a step: 19.9 TFLOP, 101 ms at the
    # chip's peak
    forward = 1_217_409_024 / 3
    assert forward == pytest.approx(405.8e6, rel=1e-3)
    assert 16384 * 1_217_409_024 / 197e12 == pytest.approx(101.2e-3, rel=1e-3)
    # by part, of the forward's 406 M: the four conv operators 33 %, the
    # dense feed-forward 36, the held experts 9, head 8, scores 8, the
    # attention projections 5
    for share, part in ((0.331, 2 * 4 * 2048 * 8192),
                        (0.357, 2 * dense),
                        (0.093, 2 * 4 * 0.5 * expert),
                        (0.083, 2 * 2048 * 8192),
                        (0.083, 4 * pairs * 2048 / 8192),
                        (0.052, 2 * (2 * 2048 * 2048 + 2 * 2048 * 512))):
        assert part / forward == pytest.approx(share, abs=2e-3)
    # the program's presets run the filed sizes
    import dataclasses

    from ray_tpu.models import lfm2
    assert accounting.ran_sizes(lfm2.lfm2_24b_a2b_5l()) == \
        accounting.filed_sizes(PUBLISHED)
    assert accounting.ran_sizes(lfm2.lfm2_24b_a2b()) == \
        accounting.filed_sizes(whole)
    # and a preset that bent the pattern or the dense count would be refused
    bent = dataclasses.replace(lfm2.lfm2_24b_a2b_5l(),
                               layer_types=("conv",) * 5)
    assert accounting.ran_sizes(bent) != accounting.filed_sizes(PUBLISHED)
    bent = dataclasses.replace(lfm2.lfm2_24b_a2b_5l(), n_dense=2)
    assert accounting.ran_sizes(bent) != accounting.filed_sizes(PUBLISHED)


def test_short_conv_cost_by_hand():
    """One conv layer's gates and taps on the cell's 16,384 tokens: float32
    [16384, 6144] in and [16384, 2048] out forward, the same in with the
    [16384, 2048] cotangent and [16384, 6144] out backward; memory-bound by
    three orders; forward, recomputed and backward 2.46 ms a layer."""
    cost = accounting.short_conv_cost(PUBLISHED, 16384)
    assert cost["forward"] == (7 * 16384 * 2048, 4 * 16384 * 2048 * 4)
    assert cost["backward"] == (21 * 16384 * 2048, 7 * 16384 * 2048 * 4)
    assert cost["forward"][1] == 16384 * 6144 * 4 + 16384 * 2048 * 4
    peaks = flops.peaks_for("TPU v5 lite")
    forward, bound = flops.least_seconds(*cost["forward"], peaks)
    backward, _ = flops.least_seconds(*cost["backward"], peaks)
    assert bound == "memory"
    assert cost["forward"][0] / peaks["bf16_flops_per_s"] < forward / 500
    assert 2 * forward + backward == pytest.approx(2.458e-3, rel=1e-3)


@pytest.fixture(scope="module")
def tiny_params():
    """The tiny preset's parameters for the two cases that start from them;
    one program: leaf by leaf the CPU takes seconds more."""
    import jax

    from ray_tpu.models import lfm2
    return jax.jit(lambda key: lfm2.init(key, lfm2.lfm2_tiny()))(
        jax.random.PRNGKey(0))


def test_pick_and_put_name_the_first_layer_of_each_kind(tiny_params):
    import jax

    from ray_tpu.models import lfm2
    cfg = lfm2.lfm2_tiny()
    params = tiny_params
    leaves = accounting.pick(params)
    assert {k: v.shape for k, v in leaves.items()} == {
        "wte": (256, 64), "w_in": (64, 192), "conv_w": (3, 64),
        "q_norm": (16,), "wv": (64, 2, 16), "wg": (64, 16),
        "w_gate": (2, 64, 32), "w_down": (2, 32, 64)}
    assert tuple(leaves) == LEAVES
    layers = params["layers"]
    np.testing.assert_array_equal(leaves["w_in"], layers[0]["op"]["w_in"])
    np.testing.assert_array_equal(leaves["q_norm"], layers[1]["op"]["q_norm"])
    # the first ROUTED layer is the third: two dense ones lead
    np.testing.assert_array_equal(leaves["wg"], layers[2]["ff"]["wg"])
    np.testing.assert_array_equal(leaves["w_down"],
                                  layers[2]["ff"]["w_down"][:2])
    zeroed = accounting.put(params, jax.tree_util.tree_map(
        lambda a: a * 0, leaves))
    assert not zeroed["wte"].any()
    got = zeroed["layers"]
    assert not got[0]["op"]["w_in"].any() and got[0]["op"]["w_out"].any()
    assert not got[0]["op"]["conv_w"].any() and got[2]["op"]["conv_w"].any()
    assert not got[1]["op"]["q_norm"].any() and got[1]["op"]["k_norm"].any()
    assert not got[1]["op"]["wv"].any() and got[4]["op"]["wv"].any()
    assert not got[2]["ff"]["wg"].any() and got[3]["ff"]["wg"].any()
    assert not got[2]["ff"]["w_gate"][:2].any()
    assert got[2]["ff"]["w_gate"][2:].any() and got[2]["ff"]["w_up"].any()
    assert jax.tree_util.tree_structure(zeroed) == \
        jax.tree_util.tree_structure(params)
    # the tree that went in is as it was
    assert params["layers"][0]["op"]["w_in"].any()
    # in the cell's own preset: layer 0 conv, layer 1 attention AND routed
    cut = jax.eval_shape(lambda: lfm2.init(jax.random.PRNGKey(0),
                                           lfm2.lfm2_24b_a2b_5l()))
    assert accounting._places(cut) == (0, 1, 1)


# ------------------------------------------------------------- the reader

_IN = "f32[2,8192,6144]{2,1,0:T(8,128)}"
_OUT = "f32[2,8192,2048]{2,1,0:T(8,128)}"
OPS = {
    "gates": f"%fusion.11 = {_OUT} fusion({_IN} %bcu, f32[3,2048] %w), "
             f"kind=kLoop",
    "gates_again": f"%fusion.12.remat = {_OUT} fusion({_IN} %bcu, "
                   f"f32[3,2048] %w), kind=kLoop",
    "gates_back": f"%fusion.13 = {_IN} fusion({_IN} %bcu, {_OUT} %dy, "
                  f"f32[3,2048] %w), kind=kLoop",
    "in_proj": f"%fusion.20 = {_IN} fusion(bf16[2,8192,2048] %a, "
               f"bf16[2048,6144] %w_in), kind=kOutput",
    "mlp": "%fusion.30 = bf16[2,8192,11776]{2,1,0} fusion(bf16[2,8192,2048] "
           "%m, bf16[2048,11776] %w), kind=kOutput",
}
TABLE = {
    "fusion.11": (("blocks", "short_conv", "gate_conv"), "forward"),
    "fusion.12.remat": (("blocks", "short_conv", "gate_conv"), "recompute"),
    "fusion.13": (("blocks", "short_conv", "gate_conv"), "backward"),
    "fusion.20": (("blocks", "short_conv"), "forward"),
    "fusion.30": (("blocks", "mlp"), "forward"),
    "fusion.99": (("optimizer",), "optimizer"),
}


def _ctx(per_op_s, busy_s=1.0, model=PUBLISHED, remat=True,
         accounting_module="chipbench.accounting.lfm2_moe"):
    return {"trace": {"per_op_s": per_op_s, "busy_s": busy_s, "steps": 3,
                      "per_op_calls": {k: 3 for k in per_op_s}},
            "model": model, "chips": 1,
            "traffic": {"batch": 2, "seq": 8192, "remat": remat},
            "accounting": accounting_module,
            "peaks": flops.peaks_for("TPU v5 lite")}


@pytest.fixture
def table(monkeypatch):
    monkeypatch.setattr(trace_scope, "_table", lambda: dict(TABLE))


def test_the_short_conv_reader_gives_a_share_of_the_hbm_peak(table):
    """Four conv layers, three traced steps: the least time is 12 × (two
    forwards and a backward) = 29.5 ms; the gates took 48 ms under
    `gate_conv`, so 61 %. Only what lies under that scope is the
    denominator: the in-projection beside it and the dense layer are not."""
    spent = {OPS["gates"]: 0.012, OPS["gates_again"]: 0.012,
             OPS["gates_back"]: 0.024, OPS["in_proj"]: 0.2, OPS["mlp"]: 0.3}
    least = 4 * 3 * (2 * 536_870_912 + 939_524_096) / 819e9
    assert least == pytest.approx(29.5e-3, rel=2e-3)
    got = trace_short_conv.read(_ctx(spent))
    assert got == pytest.approx(100 * least / 0.048, rel=1e-9)
    assert got == pytest.approx(61.45, abs=0.05)
    # without remat a layer's forward runs once a step
    assert trace_short_conv.read(_ctx(spent, remat=False)) == pytest.approx(
        100 * 4 * 3 * (536_870_912 + 939_524_096) / 819e9 / 0.048, rel=1e-9)
    # and the scope reader reads the same table: the operator's share
    assert trace_scope.read(_ctx(spent), scopes=["short_conv"]) == \
        pytest.approx(100 * 0.248)
    assert trace_scope.read(_ctx(spent), scopes=["mlp"]) == \
        pytest.approx(100 * 0.3)
    assert trace_scope.read(_ctx(spent), scopes=["moe"]) is None


@pytest.mark.parametrize("case", ["no_trace", "no_table", "no_operator",
                                  "no_cost", "not_in_trace"])
def test_nothing_to_read_is_nothing_reported(case, monkeypatch):
    """No trace (a CPU run), no table (the parent's program, a refused
    table), a table without the scope (another architecture's program), an
    accounting module without ``short_conv_cost``, a trace without the
    instructions: None, never a raise and never a zero."""
    spent = {OPS["gates"]: 0.012, OPS["in_proj"]: 0.2}
    ctx = _ctx(spent)
    tables = {"no_table": None,
              "no_operator": {k: v for k, v in TABLE.items()
                              if "gate_conv" not in v[0]}}
    monkeypatch.setattr(trace_scope, "_table",
                        lambda: tables.get(case, dict(TABLE)))
    if case == "no_trace":
        ctx = {"trace": None}
    elif case == "no_cost":
        ctx = _ctx(spent, accounting_module="chipbench.accounting.olmoe")
    elif case == "not_in_trace":
        ctx = _ctx({OPS["in_proj"]: 0.2, OPS["mlp"]: 0.3})
    assert trace_short_conv.read(ctx) is None


def test_a_share_over_105_is_refused(table):
    """Faster than the HBM peak allows: the count or the scope is wrong,
    and the reader says so instead of printing it."""
    spent = {OPS["gates"]: 0.006, OPS["gates_again"]: 0.006,
             OPS["gates_back"]: 0.012, OPS["in_proj"]: 0.2}
    with pytest.raises(ValueError, match="122.9 % of their roofline"):
        trace_short_conv.read(_ctx(spent))
    # up to 105 it is reported
    least = 4 * 3 * (2 * 536_870_912 + 939_524_096) / 819e9
    assert trace_short_conv.read(_ctx({OPS["gates"]: least / 1.04})) == \
        pytest.approx(104.0)


# ----------------------------------------------------------- the controls

@pytest.mark.parametrize("control, caught", [("stated", False),
                                             ("e4m3", True)])
def test_a_precision_control_through_the_job(control, caught):
    """The cell's `entry` pointed at the control module: the wrapper alone
    reads as the cell does; with every matmul weight (the tied table too)
    rounded to an 8-bit float, the precision below the stated bf16 products,
    the harness's own comparison says not correct, by several leaves and not
    by the loss's fall."""
    from benchmarks import precision_control

    cell = catalog.resolve_cell(MANIFEST, "lfm2-tiny", "end_to_end")
    cell["model"] = precision_control.controlled_entry(cell["model"], control)
    assert cell["model"]["entry"] == \
        f"benchmarks.precision_control:{control}__lfm2_tiny"
    record = train_fit.run(cell, seed=40, seconds=0.5, trace=False,
                           t_start=time.time(), require_tpu=False)
    verdicts = record["verdicts"]
    assert verdicts["every_loss_finite"] and verdicts["loss_fell"]
    assert verdicts["agrees_with_reference"] is not caught
    assert record["correct"] is not caught
    over = [k for k, v in record["check"]["errors"].items()
            if k != "loss" and v > 8e-2]
    assert (len(over) >= 3) is caught, record["check"]["errors"]


def test_the_controls_know_their_programs(tiny_params):
    from benchmarks import precision_control
    from ray_tpu.models import lfm2, smallthinker

    one = precision_control.one_pass__lfm2_tiny()
    assert isinstance(one, lfm2.Lfm2Config)
    assert (one.control, one.three_pass, one.program) == (
        "one_pass", False, "ray_tpu.models.lfm2")
    assert precision_control.stated__lfm2_24b_a2b_5l().three_pass == \
        lfm2.lfm2_24b_a2b_5l().three_pass
    other = precision_control.e4m3__smallthinker_tiny()
    assert isinstance(other, smallthinker.SmallThinkerConfig)
    assert other.program == "ray_tpu.models.smallthinker"
    with pytest.raises(AttributeError):
        precision_control.e4m3__olmoe_tiny
    # the 8-bit rounding reaches every matmul leaf of the tree, the tied
    # table among them, and no norm, tap, router or bias
    import jax
    params = tiny_params
    rounded = jax.jit(precision_control._eight_bit)(params)
    changed = {jax.tree_util.keystr(path)
               for (path, a), b in zip(
                   jax.tree_util.tree_leaves_with_path(params),
                   jax.tree_util.tree_leaves(rounded))
               if not np.array_equal(a, b)}
    names = {name.rpartition("['")[2].rstrip("']") for name in changed}
    assert names == {"wte", "w_in", "w_out", "wq", "wk", "wv", "wo",
                     "w_gate", "w_up", "w_down"}
