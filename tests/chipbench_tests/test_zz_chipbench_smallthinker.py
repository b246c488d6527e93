"""The ``smallthinker`` architecture's benchmark files, without the chip:
the fixture ``smallthinker-tiny`` (a configuration and a traffic mix in THIS
directory; model, reference and accounting are the program's and the
benchmark's own) through the ``train_fit`` job on the CPU, the accounting's
arithmetic at the published sizes against numbers worked out by hand, the
manifest's entries, the trace readers of the cell's own four metrics on
hand-made operations, and the precision controls of
``benchmarks/precision_control.py`` through the same job: what the
comparison catches of a program below its stated precision, and what not."""
import json
import time

import numpy as np
import pytest

from chipbench import catalog, flops
from chipbench.accounting import smallthinker as accounting
from chipbench.jobs import train_fit
from chipbench.readers import mfu, trace_flash, trace_pre_routed, trace_window
from tests.chipbench_tests import later_cell, tiny_fit

MANIFEST = {
    "paths": ["chipbench", "tests/chipbench_tests"],
    "workloads": [{"name": "smallthinker-tiny", "config": "smallthinker-tiny",
                   "traffic": "fit-smallthinker-tiny", "chips": 1,
                   "why": "window and global layers at test sizes"}],
    "end_to_end": [
        {"name": "tokens_per_s_per_chip", "unit": "tokens/s/chip"},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [
        {"name": "train_step.compiles_in_window", "unit": "count"},
        {"name": "attn.window_flash_share", "unit": "%"},
        {"name": "kernels.window_flash_roofline", "unit": "%"},
        {"name": "moe.pre_routed_share", "unit": "%"},
        {"name": "kernels.pre_gmm_share", "unit": "%"}],
}
REAL = catalog.load_manifest()
PUBLISHED = catalog.load_json(REAL, "configs", "smallthinker-21b-a3b-4l")
CELL = "smallthinker4l-b1s16k"
NEW_METRICS = ("attn.window_flash_share", "kernels.window_flash_roofline",
               "moe.pre_routed_share", "kernels.pre_gmm_share")


@pytest.fixture(scope="module")
def fit(once_a_run):
    """ONE traced fit with the metrics of both groups, once a test run: the
    two cases below read a group each of it (`tiny_fit.py`)."""
    return once_a_run("smallthinker_tiny_fit", lambda: tiny_fit.traced(
        MANIFEST, "smallthinker-tiny", seed=36))


@pytest.mark.parametrize("trace", [False, True])
def test_smallthinker_tiny_through_the_trainer(fit, trace):
    cell = catalog.resolve_cell(MANIFEST, "smallthinker-tiny",
                                "per_layer" if trace else "end_to_end")
    assert cell["accounting"] == "chipbench.accounting.smallthinker"
    assert cell["reference"] == "chipbench.references.smallthinker"
    record = fit
    json.dumps(record)
    assert record["correct"], (record["verdicts"], record["check"])
    assert set(record["check"]["errors"]) == {"loss"} | {
        "grad_" + k for k in ("head", "wq_global", "wv_global", "wq_window",
                              "wv_window", "wg", "w_gate", "w_down")}
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
    # the mfu reader, given a peak. A token uses: the head; in each of the
    # eight layers four GQA projections, the router over 16 and 3 · 4/16 of
    # a gated expert; the scores over the pairs the masks keep: 40 · 41 / 2
    # in the two global layers, 24 · 40 − 24 · 23 / 2 in the six window ones
    layer = 2 * 64 * 64 + 2 * 64 * 32 + 64 * 16 + 0.75 * 3 * 64 * 32
    pairs = 2 * 820 + 6 * 684
    per_token = 6 * (256 * 64 + 8 * layer) + 12 * pairs * 64 / 40
    assert per_token == 1_068_748.8
    assert accounting.train_flops_per_token(cell["model"], 40) == 1_068_749
    ctx = {"accounting": cell["accounting"], "model": cell["model"],
           "traffic": cell["traffic"], "chips": 1, "clock": record["clock"],
           "counters": {"steps": record["attempted"]},
           "peaks": {"bf16_flops_per_s": 1e12}}
    assert mfu.read(ctx) == pytest.approx(
        100 * record["attempted"] * 2 * 40 / record["clock"]["window_s"]
        * 1_068_749 / 1e12, rel=1e-12)


def test_the_published_configuration_is_the_catalog_rows():
    """Every key of the public ``config.json`` as the model-configs catalog
    holds it, unchanged but for the two counts the cut reduces; the depth
    the cell runs, the published counts, the deployment and what was
    assumed are filed beside them."""
    period = [0, 1, 1, 1]
    for key, value in {
            "head_dim": 128, "hidden_size": 2560,
            "max_position_embeddings": 16384,
            "model_name": "smallthinker_21b_instruct",
            "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
            "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
            "num_attention_heads": 28, "num_hidden_layers": 52,
            "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
            "rope_layout": period * 13, "rope_scaling": None,
            "rope_theta": 1500000, "sliding_window_layout": period * 13,
            "sliding_window_size": 4096,
            "tie_word_embeddings": False}.items():
        assert PUBLISHED[key] == value, key
    assert PUBLISHED["reduced"] == ["layers", "moe_num_primary_experts",
                                    "vocab_size"]
    assert (PUBLISHED["layers"], PUBLISHED["moe_num_primary_experts"],
            PUBLISHED["vocab_size"]) == (4, 16, 37984)
    assert PUBLISHED["published"] == {
        "layers": 52, "moe_num_primary_experts": 64, "vocab_size": 151936}
    deployment = PUBLISHED["deployment"]
    assert deployment["chips_sharing_a_layer"] == 4
    assert deployment["first_expert"] == 0
    assert "4 chips share each layer" in deployment["what"]
    assert deployment["chips_sharing_a_layer"] * \
        PUBLISHED["moe_num_primary_experts"] == 64
    assert deployment["chips_sharing_a_layer"] * PUBLISHED["vocab_size"] \
        == 151936
    assert "15.7" in deployment["decided_by"]      # the plan that decided it
    for key in ("layers", "router_input", "router", "secondary_experts",
                "experts", "attention", "sequence", "param_dtype",
                "compute_dtype", "weights"):
        assert key in PUBLISHED["assumed"], key
    assert "un-normalised" in PUBLISHED["assumed"]["router_input"]
    assert "no auxiliary loss" in PUBLISHED["assumed"]["router"]
    # the cell is the manifest's, with the traffic ISSUE 36 gives it
    cell = catalog.resolve_cell(REAL, CELL, "per_layer")
    assert cell["workload"]["chips"] == 1
    assert cell["workload"]["traffic"] == "fit-b1-s16384-remat"
    assert {k: cell["traffic"][k] for k in (
        "job", "batch", "seq", "remat", "attention", "check_sequences",
        "vocab_divisor", "batches", "trace_from_step", "trace_steps",
        "mesh")} == {
        "job": "train_fit", "batch": 1, "seq": 16384, "remat": True,
        "attention": "auto", "check_sequences": 1, "vocab_divisor": 16,
        "batches": 64, "trace_from_step": 10, "trace_steps": 3,
        "mesh": {"dp": 1}}
    assert cell["traffic"]["optimizer"] == {
        "learning_rate": 0.0003, "warmup_steps": 10, "total_steps": 10000}
    reported = {m["name"] for m in cell["metrics"]}
    assert set(NEW_METRICS) | {"kernels.flash_share",
                               "kernels.flash_roofline"} <= reported
    assert not reported & {"moe.routed_share", "moe.held_routed_share",
                           "ssm.mixer_share"}


@pytest.mark.parametrize("control, caught", [("stated", False),
                                             ("e4m3", True)])
def test_a_precision_control_through_the_job(control, caught):
    """The cell's `entry` pointed at the control module: the wrapper alone
    reads as the cell does; with every matmul weight rounded to an 8-bit
    float, the precision below the stated bf16 products, the harness's own
    comparison says not correct, by several leaves and not by the loss's
    fall."""
    from benchmarks import precision_control

    cell = catalog.resolve_cell(MANIFEST, "smallthinker-tiny", "end_to_end")
    cell["model"] = precision_control.controlled_entry(cell["model"], control)
    assert cell["model"]["entry"] == \
        f"benchmarks.precision_control:{control}__smallthinker_tiny"
    record = train_fit.run(cell, seed=36, seconds=0.5, trace=False,
                           t_start=time.time(), require_tpu=False)
    verdicts = record["verdicts"]
    assert verdicts["every_loss_finite"] and verdicts["loss_fell"]
    assert verdicts["agrees_with_reference"] is not caught
    assert record["correct"] is not caught
    over = [k for k, v in record["check"]["errors"].items()
            if k != "loss" and v > 8e-2]
    assert (len(over) >= 3) is caught, record["check"]["errors"]


def test_the_controls_eight_bit_rounding_is_the_casts():
    """Arithmetic and not a cast (the TPU compiler drops a cast pair), yet
    the same value: normal, subnormal, tie, zero, the largest."""
    import jax
    import jax.numpy as jnp

    from benchmarks import precision_control

    x = jnp.concatenate(
        [jax.random.normal(jax.random.PRNGKey(s), (50000,)) * scale
         for s, scale in enumerate((0.02, 1.0, 100.0, 1e-3))]
        + [jnp.array([0.0, 2.0 ** -6, 2.0 ** -9, 2.0 ** -10, 3 * 2.0 ** -10,
                      448.0, -0.0176, 0.017578125, 0.0185546875])])
    want = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    np.testing.assert_array_equal(precision_control._e4m3(x), want)
    assert float(jnp.linalg.norm(want[:50000] - x[:50000])
                 / jnp.linalg.norm(x[:50000])) > 0.02


def test_a_control_of_another_architecture_is_refused():
    from benchmarks import precision_control

    with pytest.raises(SystemExit, match="no control for"):
        precision_control.controlled_entry(
            {"entry": "ray_tpu.models.olmoe:olmoe_1b_7b_1l"}, "e4m3")
    with pytest.raises(AttributeError):
        precision_control.fp4__smallthinker_tiny


# the accepted metrics that a `benchmark` PR pointed at this cell too
SHARED_METRICS = {"kernels.flash_share", "kernels.flash_roofline",
                  "train_step.recompute_share", "moe.scoped_share"}


def check_the_manifests_entries(manifest):
    """Found BY NAME, wherever a later PR's entries put them in their
    lists: the configuration, the cell and its four metrics."""
    config = next(c for c in manifest["configs"]
                  if c["name"] == "smallthinker-21b-a3b-4l")
    assert config["source"] == PUBLISHED["source"] == (
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
        "blob/main/config.json")
    assert config["file"] == "chipbench/configs/smallthinker-21b-a3b-4l.json"
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) == 5       # the sixth cell, and it stays so
    cell = manifest["workloads"][5]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "smallthinker-21b-a3b-4l", "fit-b1-s16384-remat", 1)
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(NEW_METRICS[0])
    new = manifest["per_layer"][at:at + 4]
    assert tuple(m["name"] for m in new) == NEW_METRICS
    for m, (layer, better) in zip(new, (("attn", "lower"),
                                        ("kernels", "higher"),
                                        ("moe", "lower"),
                                        ("kernels", "lower"))):
        assert (m["layer"], m["better"], m["unit"], m["moves"], m["source"],
                m["workloads"]) == (layer, better, "%", "mfu", "device_trace",
                                    [CELL])
    # beside its own four, the cell is named by these accepted metrics'
    # lists and by no other's
    naming = {m["name"] for m in manifest["per_layer"] + manifest["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert naming == set(NEW_METRICS) | SHARED_METRICS


def test_the_manifests_new_entries():
    check_the_manifests_entries(REAL)


def test_a_later_cell_breaks_nothing_here():
    check_the_manifests_entries(later_cell.with_a_later_cell(REAL))


def test_params_and_flops_a_token_by_hand():
    attention = 2 * 2560 * 3584 + 2 * 2560 * 512
    expert = 3 * 2560 * 768
    outside = attention + 2 * 2560 + 2560 * 64
    ends = 2 * 38016 * 2560 + 2560
    assert (attention, expert, outside, ends) == (
        20_971_520, 5_898_240, 21_140_480, 194_644_480)
    layer = outside + 16 * expert
    assert layer == 115_512_320
    assert accounting.params(PUBLISHED) == 4 * layer + ends == 656_693_760
    # 16 bytes a parameter: 10.51 GB of state; the fallback's 8 experts and
    # an eighth of the vocabulary 5.94 GB; all 64 experts 6.4 GB a layer
    assert 16 * 656_693_760 == pytest.approx(10.51e9, rel=1e-3)
    assert accounting.params(dict(PUBLISHED, moe_num_primary_experts=8,
                                  vocab_size=18992)) == 370_956_800
    assert 16 * 370_956_800 == pytest.approx(5.94e9, rel=1e-3)
    assert 16 * (outside + 64 * expert) == pytest.approx(6.38e9, rel=1e-3)
    # uncut: the row's "21B"
    whole = dict(PUBLISHED, layers=52, moe_num_primary_experts=64,
                 vocab_size=151936)
    assert accounting.params(whole) == 21_506_562_560
    # FLOPs a token at S 16,384: the matmul parameters a token uses, the
    # held experts at their expectation of 6 · 16/64 of one; the scores at
    # the pairs the masks keep
    used = 4 * (attention + 2560 * 64 + 1.5 * expert) + 2560 * 38016
    assert used == 217_251_840
    assert accounting.kept_pairs(16384) == 16384 * 16385 // 2 == 134_225_920
    assert accounting.kept_pairs(16384, 4096) == \
        4096 * 16384 - 4096 * 4095 // 2 == 58_722_304
    assert accounting.kept_pairs(1024, 4096) == 1024 * 1025 // 2
    # a window layer keeps 43.7 % of the causal area (75 % at S 8,192)
    assert 58_722_304 / 134_225_920 == pytest.approx(0.4375, abs=1e-3)
    assert accounting.kept_pairs(8192, 4096) / accounting.kept_pairs(8192) \
        == pytest.approx(0.75, abs=1e-3)
    pairs = 134_225_920 + 3 * 58_722_304
    assert accounting.train_flops_per_token(PUBLISHED, 16384) == \
        round(6 * used + 12 * pairs * 3584 / 16384) == 2_118_292_224
    # 706 M forward a token; 16,384 tokens a step: 34.7 TFLOP, 176 ms at
    # the chip's peak
    assert 2_118_292_224 / 3 == pytest.approx(706.1e6, rel=1e-3)
    assert 16384 * 2_118_292_224 / 197e12 == pytest.approx(176.2e-3, rel=1e-3)
    # by part, of the forward's 706 M: scores 38 % (global 17, three window
    # layers 22), projections 24, head 28, held experts 10
    forward = 2_118_292_224 / 3
    assert 4 * 134_225_920 * 3584 / 16384 / forward == pytest.approx(
        0.166, abs=2e-3)
    assert 3 * 4 * 58_722_304 * 3584 / 16384 / forward == pytest.approx(
        0.218, abs=2e-3)
    assert 2 * 4 * attention / forward == pytest.approx(0.238, abs=2e-3)
    assert 2 * 2560 * 38016 / forward == pytest.approx(0.276, abs=2e-3)
    assert 2 * 4 * 1.5 * expert / forward == pytest.approx(0.100, abs=2e-3)
    # the program's presets run the filed sizes
    from ray_tpu.models import smallthinker
    assert accounting.ran_sizes(smallthinker.smallthinker_21b_a3b_4l()) == \
        accounting.filed_sizes(PUBLISHED)
    assert accounting.ran_sizes(smallthinker.smallthinker_21b_a3b()) == \
        accounting.filed_sizes(dict(whole, deployment={"first_expert": 0}))
    # and a preset that bent a layout would be refused
    bent = smallthinker.smallthinker_21b_a3b_4l()
    import dataclasses
    bent = dataclasses.replace(bent, rope_layout=(1, 1, 1, 1))
    assert accounting.ran_sizes(bent) != accounting.filed_sizes(PUBLISHED)


def test_window_flash_cost_by_hand_and_as_the_program_plans_it():
    fwd, fwd_bytes = accounting.window_flash_cost(PUBLISHED, "fwd", 1, 16384)
    assert fwd == 2 * 2 * 28 * 58_722_304 * 128 == 841_842_950_144
    q, kv, rows = 28 * 16384 * 128 * 2, 4 * 16384 * 128 * 2, 28 * 16384 * 4
    assert fwd_bytes == 2 * q + 2 * kv + rows
    dq, dq_bytes = accounting.window_flash_cost(PUBLISHED, "dq", 1, 16384)
    dkv, dkv_bytes = accounting.window_flash_cost(PUBLISHED, "dkv", 1, 16384)
    assert (dq, dkv) == (fwd * 3 // 2, fwd * 2)
    assert dq_bytes == 3 * q + 2 * kv + 2 * rows
    assert dkv_bytes == 2 * q + 4 * kv + 2 * rows
    # the kept area is the program's own count of its mask
    import jax.numpy as jnp

    from ray_tpu.ops import flash_attention as fa
    plan = fa.window_plan(16384, 4096, fa.tile_plan(16384, 128,
                                                    jnp.bfloat16).fwd)
    assert plan["kept_area"] == accounting.kept_pairs(16384, 4096)
    # compute-bound: 4.27 ms the forward, against 0.33 ms for its bytes;
    # what `flops.flash_call_cost` counts for the same call (half of S²) is
    # 1 / 0.4375 of it
    least, bound = flops.least_seconds(fwd, fwd_bytes,
                                       flops.peaks_for("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(4.273e-3, rel=1e-3)
    assert 2 * 2 * 28 * 16384 * 16384 * 128 // 2 / fwd == pytest.approx(
        1 / 0.4375, rel=1e-3)


def test_pick_and_put_name_a_global_and_a_window_layer():
    """``wq`` / ``wv`` of layer 0 (global, not rotated) and of layer 1
    (window, rotated); the FIRST layer's router and two held experts."""
    import jax

    from ray_tpu.models import smallthinker
    cfg = smallthinker.smallthinker_tiny()
    assert cfg.kinds[0] == (0, 0) and cfg.kinds[1] == (1, 1)
    # one program: leaf by leaf the CPU takes seconds more
    params = jax.jit(lambda key: smallthinker.init(key, cfg))(
        jax.random.PRNGKey(0))
    leaves = accounting.pick(params)
    assert {k: v.shape for k, v in leaves.items()} == {
        "head": (256, 64), "wq_global": (64, 4, 16), "wv_global": (64, 2, 16),
        "wq_window": (64, 4, 16), "wv_window": (64, 2, 16), "wg": (64, 16),
        "w_gate": (2, 64, 32), "w_down": (2, 32, 64)}
    blocks = params["blocks"]
    np.testing.assert_array_equal(leaves["wq_global"], blocks["attn"]["wq"][0])
    np.testing.assert_array_equal(leaves["wv_window"], blocks["attn"]["wv"][1])
    np.testing.assert_array_equal(leaves["wg"], blocks["moe"]["wg"][0])
    np.testing.assert_array_equal(leaves["w_down"],
                                  blocks["moe"]["w_down"][0, :2])
    zeroed = accounting.put(params, jax.tree_util.tree_map(
        lambda a: a * 0, leaves))["blocks"]
    attn, moe = zeroed["attn"], zeroed["moe"]
    assert not attn["wq"][:2].any() and attn["wq"][2:].all(axis=0).any()
    assert not attn["wv"][:2].any() and attn["wv"][2].any()
    assert attn["wk"].any() and attn["wo"][0].any()
    assert not moe["wg"][0].any() and moe["wg"][1].any()
    assert not moe["w_gate"][0, :2].any() and moe["w_gate"][0, 2:].any()
    assert moe["w_gate"][1].any() and moe["w_up"][0].any()
    assert jax.tree_util.tree_structure(zeroed) == \
        jax.tree_util.tree_structure(blocks)


# ----------------------------------------------------------- the readers

def _ctx(per_op_s, calls=None, busy_s=2.4, model=PUBLISHED):
    return {"trace": {"per_op_s": per_op_s, "busy_s": busy_s, "steps": 3,
                      "per_op_calls": calls or {k: 3 for k in per_op_s}},
            "model": model, "chips": 1,
            "traffic": {"batch": 1, "seq": 16384, "remat": True},
            "accounting": "chipbench.accounting.smallthinker",
            "peaks": flops.peaks_for("TPU v5 lite")}


_Q = "bf16[28,16384,128]{2,1,0:T(8,128)(2,1)}"
_KV = "bf16[4,16384,128]{2,1,0:T(8,128)(2,1)}"
_ROW = "f32[28,128,8,128]{3,2,1,0:T(8,128)}"
_TAIL = 'custom_call_target="tpu_custom_call", operand_layout_constraints={}'
WINDOWED = {
    "fwd": f"%flash_window_fwd.7 = ({_Q}, {_ROW}) custom-call({_Q} %q, "
           f"{_KV} %k, {_KV} %v), {_TAIL}",
    "dq": f"%flash_window_dq.6 = {_Q} custom-call({_Q} %q, {_KV} %k, {_KV} "
          f"%v, {_Q} %do, {_ROW} %lse, {_ROW} %delta), {_TAIL}",
    "dkv": f"%flash_window_dkv.8 = ({_Q}, {_Q}) custom-call({_Q} %q, {_KV} "
           f"%k, {_KV} %v, {_Q} %do, {_ROW} %lse, {_ROW} %delta), {_TAIL}",
}
# the global layer's calls: the same operand shapes, no name of their own
CAUSAL = {
    "fwd": WINDOWED["fwd"].replace("%flash_window_fwd.7", "%attention.6"),
    "dq": WINDOWED["dq"].replace("%flash_window_dq.6", "%attention.8"),
    "dkv": WINDOWED["dkv"].replace("%flash_window_dkv.8", "%attention.7"),
}
NOT_FLASH = [
    "%fusion.1744 = (f32[16384]{0}, f32[16384,38016]{1,0}) fusion("
    "f32[38016,2560] %state_params__head__.1, bf16[1,16384,2560] %x)",
    "%gmm.35 = bf16[98304,2560]{1,0} custom-call(s32[] %n, s32[65]{0} %g, "
    f"bf16[98304,768] %rows, bf16[16,768,2560] %w), {_TAIL}",
    "%flash_window_fwd_fusion.3 = f32[16384]{0} fusion(f32[16384] %a)",
]


def test_the_window_reader_finds_the_named_calls_and_no_others():
    for text in WINDOWED.values():
        assert trace_window._CALL.match(text), text
        # `flash_call_cost` goes by signature and would count them too, at
        # half of S²: `trace_flash` leaves them out by this name
        assert flops.flash_call_cost(text)
    for text in list(CAUSAL.values()) + NOT_FLASH:
        assert not trace_window._CALL.match(text), text
    assert [trace_window._CALL.match(t).group(1) for t in WINDOWED.values()] \
        == ["fwd", "dq", "dkv"]
    # three traced steps of one window layer's calls beside the global one's
    per_op = {WINDOWED["fwd"]: 0.069, WINDOWED["dq"]: 0.044,
              WINDOWED["dkv"]: 0.101, CAUSAL["fwd"]: 0.148,
              CAUSAL["dq"]: 0.099, CAUSAL["dkv"]: 0.225, NOT_FLASH[0]: 0.5}
    ctx = _ctx(per_op)
    assert trace_window.read(ctx, "share") == pytest.approx(
        100 * 0.214 / 2.4)
    fwd = 841_842_950_144 / 197e12
    assert trace_window.read(ctx, "roofline") == pytest.approx(
        100 * 3 * (fwd + 1.5 * fwd + 2 * fwd) / 0.214, rel=1e-9)
    # 26.9 % here, where the causal counter reads 1 / 0.4375 of it
    assert trace_window.read(ctx, "roofline") == pytest.approx(26.96,
                                                               abs=0.01)


def test_the_plain_flash_reader_leaves_the_windowed_calls_alone():
    """A windowed name is not read, a plain one is: `flash_call_cost` goes
    by signature and would count a window layer's calls at half of S², on
    top of `trace_window`'s reading of them at the area the window keeps
    (`kernels.flash_roofline` 138.81 % in this cell: ledger, PR 61). The
    global layer's calls, named `flash_fwd` / `_dq` / `_dkv` or not at all,
    are read as in every other cell: 2 / 3 / 4 products of 28 · 16,384² / 2
    · 128 pairs."""
    windowed = {WINDOWED["fwd"]: 0.069, WINDOWED["dq"]: 0.044,
                WINDOWED["dkv"]: 0.101}
    plain = {CAUSAL["fwd"]: 0.148,
             CAUSAL["dq"].replace("%attention.8", "%flash_dq.2"): 0.099,
             CAUSAL["dkv"].replace("%attention.7", "flash_dkv.2"): 0.225}
    for text in plain:
        assert flops.flash_call_cost(text) \
            and not trace_window._CALL.match(text), text
    least = 3 * (2 + 3 + 4) * 2 * (28 * 16384 * 16384 // 2) * 128 / 197e12
    for per_op in (plain, dict(plain, **windowed, **{NOT_FLASH[0]: 0.5})):
        assert trace_flash.read(_ctx(per_op), "share") == pytest.approx(
            100 * 0.472 / 2.4)
        assert trace_flash.read(_ctx(per_op), "roofline") == pytest.approx(
            100 * least / 0.472, rel=1e-9)
    for what in ("share", "roofline"):
        assert trace_flash.read(_ctx(windowed), what) is None
    # the two readers' seconds add up to every flash call's, counted once
    ctx = _ctx(dict(plain, **windowed))
    assert trace_flash.read(ctx, "share") + trace_window.read(ctx, "share") \
        == pytest.approx(100 * (0.472 + 0.214) / 2.4)


@pytest.mark.parametrize("what", ["share", "roofline"])
def test_no_windowed_call_is_nothing_reported(what):
    """No trace (a CPU run), a trace of a program without the window, or a
    configuration without one: None, never a raise and never a zero."""
    assert trace_window.read({"trace": None}, what) is None
    assert trace_window.read(_ctx({t: 0.1 for t in CAUSAL.values()}),
                             what) is None
    other = {k: v for k, v in PUBLISHED.items() if k != "sliding_window_size"}
    assert trace_window.read(_ctx({t: 0.1 for t in WINDOWED.values()},
                                  model=other), what) is None


ROUTED = [
    NOT_FLASH[1],
    "%tgmm.3 = bf16[16,2560,768]{2,1,0} custom-call(s32[] %n, s32[65]{0} %g,"
    f" bf16[98304,2560] %rows, bf16[98304,768] %d), {_TAIL}",
    "%fusion.119 = bf16[98304,2560]{1,0} fusion(bf16[98304,2560]{1,0} %y, "
    "s32[98304]{0} %order), kind=kCustom, calls=%gather",
    "%fusion.117 = bf16[98304,2560]{1,0} fusion(bf16[16384,2560]{1,0} %m, "
    "s32[98304]{0} %order), kind=kCustom, calls=%gather",
    "%reshape.1899 = f32[98304,2560]{1,0} reshape(f32[16384,6,2560] %b)",
    "%broadcast.970 = f32[16384,6,2560]{2,1,0} broadcast(f32[16384,2560] "
    "%d), dimensions={0,2}",
    "%fusion.77 = (f32[1,16384,64]{2,1,0}, s32[1,16384,6]{2,1,0}) fusion("
    "f32[16384,2560] %x, f32[2560,64] %wg), kind=kOutput",
    "%sort.3 = (s32[98304]{0}, s32[98304]{0}) sort(s32[98304] %key, "
    "s32[98304] %iota), dimensions={0}",
    "%fusion.31 = bf16[98304,768]{1,0} fusion(bf16[98304,768] %gate, "
    "bf16[98304,768] %up), kind=kLoop",
    "%slice_bitcast_fusion.2 = bf16[16,2560,768]{2,1,0} fusion("
    "f32[4,16,2560,768] %state_params__blocks____moe____w_gate__.1)",
]
NOT_ROUTED = [
    # the optimizer's pass over the held experts, and the stacked gradients
    "%fusion.625 = (f32[4,16,2560,768]{3,2,1,0}, f32[4,16,2560,768]{3,2,1,0})"
    " fusion(f32[4,16,2560,768] %state_params__blocks____moe____w_up__.1, "
    "f32[4,16,2560,768] %state_opt_state_1__0__nu__blocks____moe____w_up__.1"
    ", bf16[16,2560,768]{2,1,0} %tgmm.3), kind=kLoop",
    "%concatenate.4 = f32[4,16,768,2560]{3,2,1,0} concatenate(f32[1,16,768,"
    "2560] %a, f32[1,16,768,2560] %b), dimensions={0}",
    # attention, its projections, the head
    WINDOWED["fwd"], CAUSAL["dkv"],
    "%fusion.1031 = f32[16384,28,128]{2,0,1} fusion(bf16[16384,2560,1] %a, "
    "f32[4,2560,28,128] %state_params__blocks____attn____wq__.1)",
    "%fusion.699 = bf16[16384,2560]{0,1} fusion(f32[1,16384,28,64] %a, "
    "f32[1,16384,28,64] %b, f32[2560,28,128] %wo), kind=kOutput",
    NOT_FLASH[0],
]


def test_the_routed_reader_tells_the_routed_layers_from_the_rest():
    per_op = {text: 0.01 for text in ROUTED + NOT_ROUTED}
    assert trace_pre_routed.read(_ctx(per_op)) == pytest.approx(
        100 * 0.01 * len(ROUTED) / 2.4)
    for text in ROUTED:
        assert trace_pre_routed.read(_ctx({text: 0.24})) == \
            pytest.approx(10.0), text
    for text in NOT_ROUTED:
        assert trace_pre_routed.read(_ctx({text: 0.24})) is None, text
    assert trace_pre_routed.read({"trace": None}) is None
    assert trace_pre_routed.read({"trace": None}, "gmm_share") is None
    # the grouped products alone: by name, whatever else the layer runs
    products = [t for t in ROUTED if t.startswith(("%gmm", "%tgmm"))]
    assert len(products) == 2
    assert trace_pre_routed.read(_ctx(per_op), "gmm_share") == \
        pytest.approx(100 * 0.01 * len(products) / 2.4)
    rest = {t: 0.24 for t in ROUTED + NOT_ROUTED if t not in products}
    assert trace_pre_routed.read(_ctx(rest), "gmm_share") is None
    # a configuration of another architecture: nothing to read, no raise
    nemotron = catalog.load_json(REAL, "configs",
                                 "nemotron-twotower-30b-a3b-9l")
    assert trace_pre_routed.read(_ctx(per_op, model=nemotron)) is None
