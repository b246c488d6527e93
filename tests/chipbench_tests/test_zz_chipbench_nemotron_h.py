"""The ``nemotron_h`` architecture's benchmark files, without the chip: the
fixture ``nemotronh-tiny`` (a configuration and a traffic mix in THIS
directory; model, reference and accounting are the program's and the
benchmark's own) through the ``train_fit`` job on the CPU, the accounting's
arithmetic at the published sizes against numbers worked out by hand, and
the trace readers of the state-space and the routed layers' metrics on
hand-made operations."""
import json

import numpy as np
import pytest

from chipbench import catalog, flops
from chipbench.accounting import nemotron_h as accounting
from chipbench.readers import mfu, trace_held, trace_ssm
from tests.chipbench_tests import tiny_fit

MANIFEST = {
    "paths": ["chipbench", "tests/chipbench_tests"],
    "workloads": [{"name": "nemotronh-tiny", "config": "nemotronh-tiny",
                   "traffic": "fit-nemotronh-tiny", "chips": 1,
                   "why": "the hybrid tower at test sizes"}],
    "end_to_end": [
        {"name": "tokens_per_s_per_chip", "unit": "tokens/s/chip"},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [
        {"name": "train_step.compiles_in_window", "unit": "count"},
        {"name": "ssm.mixer_share", "unit": "%"},
        {"name": "kernels.ssd_roofline", "unit": "%"},
        {"name": "moe.held_routed_share", "unit": "%"},
        {"name": "kernels.held_gmm_share", "unit": "%"}],
}
REAL = catalog.load_manifest()
PUBLISHED = catalog.load_json(REAL, "configs", "nemotron-twotower-30b-a3b-9l")
CELL = "nemotronh9l-b1s8k"


@pytest.fixture(scope="module")
def fit(once_a_run):
    """ONE traced fit with the metrics of both groups, once a test run: the
    two cases below read a group each of it (`tiny_fit.py`)."""
    return once_a_run("nemotronh_tiny_fit", lambda: tiny_fit.traced(
        MANIFEST, "nemotronh-tiny", seed=34))


@pytest.mark.parametrize("trace", [False, True])
def test_nemotronh_tiny_through_the_trainer(fit, trace):
    cell = catalog.resolve_cell(MANIFEST, "nemotronh-tiny",
                                "per_layer" if trace else "end_to_end")
    assert cell["accounting"] == "chipbench.accounting.nemotron_h"
    assert cell["reference"] == "chipbench.references.nemotron_h"
    record = fit
    json.dumps(record)
    assert record["correct"], (record["verdicts"], record["check"])
    assert set(record["check"]["errors"]) == {"loss"} | {
        "grad_" + k for k in ("head", "wq", "wv", "w_in", "A_log", "dt_bias",
                              "w_out", "wg", "w1", "w2", "shared_w1")}
    assert record["failed"] == 0 and record["attempted"] >= 4
    values = tiny_fit.values_of(record, cell)
    if trace:
        # no TPU plane in a CPU trace: the mixers' metrics are left out,
        # not invented
        assert set(values) == {"train_step.compiles_in_window"}
        assert values["train_step.compiles_in_window"] == 0
        return
    assert values["tokens_per_s_per_chip"] == pytest.approx(
        record["attempted"] * 2 * 40 / record["clock"]["window_s"])
    # the mfu reader, given a peak. A token uses: the head; in each of the
    # two M layers both projections and the recurrence; in the one
    # attention layer four GQA projections and the scores over 40 tokens;
    # in each of the two E layers the router, the shared expert and 3 · 4/16
    # of a held expert
    mamba = 64 * (64 + 128 + 8) + 64 * 64
    attention = 2 * 64 * 64 + 2 * 64 * 32
    routed = 64 * 16 + 0.75 * 2 * 64 * 32 + 2 * 64 * 48
    per_token = (6 * (256 * 64 + 2 * mamba + attention + 2 * routed)
                 + 6 * 40 * 64 + 3 * 2 * 4 * 8 * 8 * 16)
    assert per_token == 537_600
    ctx = {"accounting": cell["accounting"], "model": cell["model"],
           "traffic": cell["traffic"], "chips": 1, "clock": record["clock"],
           "counters": {"steps": record["attempted"]},
           "peaks": {"bf16_flops_per_s": 1e12}}
    assert mfu.read(ctx) == pytest.approx(
        100 * record["attempted"] * 2 * 40 / record["clock"]["window_s"]
        * per_token / 1e12, rel=1e-12)


def test_the_published_configuration_is_the_catalog_rows():
    """Every key of the public ``config.json`` as the model-configs catalog
    holds it, unchanged but for the two counts the cut reduces; the depth,
    the pattern the cell runs, the published counts and the deployment are
    filed beside them."""
    for key, value in {
            "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
            "expand": 2, "head_dim": 128, "hidden_size": 2688,
            "hybrid_override_pattern":
                "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
            "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
            "mamba_head_dim": 64, "mamba_hidden_act": "silu",
            "mamba_num_heads": 64, "mamba_proj_bias": False,
            "max_position_embeddings": 262144, "mlp_bias": False,
            "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
            "moe_intermediate_size": 1856,
            "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
            "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
            "norm_topk_prob": True, "num_attention_heads": 32,
            "num_experts_per_tok": 6, "num_hidden_layers": 52,
            "num_key_value_heads": 2, "num_logits_to_keep": 1,
            "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
            "residual_in_fp32": False, "rope_theta": 10000,
            "routed_scaling_factor": 2.5, "sliding_window": None,
            "ssm_state_size": 128, "tie_word_embeddings": False,
            "time_step_floor": 0.0001, "time_step_limit": [0, None],
            "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
            "use_bias": False, "use_conv_bias": True,
            "use_mamba_kernels": True}.items():
        assert PUBLISHED[key] == value, key
    assert PUBLISHED["reduced"] == ["layers", "n_routed_experts",
                                    "vocab_size"]
    assert (PUBLISHED["layers"], PUBLISHED["n_routed_experts"],
            PUBLISHED["vocab_size"]) == (9, 8, 16384)
    assert PUBLISHED["published"] == {
        "layers": 52, "n_routed_experts": 128, "vocab_size": 131072}
    assert PUBLISHED["pattern"] == "MEMEM*EME" == \
        PUBLISHED["hybrid_override_pattern"][:PUBLISHED["layers"]]
    deployment = PUBLISHED["deployment"]
    assert deployment["chips_sharing_a_layer"] == 16
    assert "16 chips share each layer" in deployment["what"]
    assert deployment["chips_sharing_a_layer"] * \
        PUBLISHED["n_routed_experts"] == 128
    for key in ("towers", "rotary", "router", "sequence", "param_dtype",
                "weights"):
        assert key in PUBLISHED["assumed"], key
    # the cell is the manifest's, with the traffic ISSUE 34 gives it
    cell = catalog.resolve_cell(REAL, CELL, "per_layer")
    assert cell["workload"]["chips"] == 1
    assert {k: cell["traffic"][k] for k in (
        "batch", "seq", "remat", "check_sequences", "vocab_divisor",
        "batches", "trace_from_step", "trace_steps", "mesh")} == {
        "batch": 1, "seq": 8192, "remat": True, "check_sequences": 1,
        "vocab_divisor": 16, "batches": 64, "trace_from_step": 10,
        "trace_steps": 3, "mesh": {"dp": 1}}
    reported = {m["name"] for m in cell["metrics"]}
    assert {"ssm.mixer_share", "kernels.ssd_roofline", "kernels.flash_share",
            "kernels.flash_roofline"} <= reported
    assert not reported & {"moe.routed_share",
                           "kernels.grouped_matmul_roofline"}


def test_params_and_flops_a_token_by_hand():
    mamba = 2688 * 10304 + 5 * 6144 + 3 * 64 + 4096 + 4096 * 2688 + 2688
    attention = 2 * 2688 * 4096 + 2 * 2688 * 256 + 2688
    expert, shared = 2 * 2688 * 1856, 2 * 2688 * 3712
    routed = 2688 * 128 + 128 + 8 * expert + shared + 2688
    ends = 2 * 16384 * 2688 + 2688
    assert (mamba, attention, expert, routed, ends) == (
        38_744_896, 23_399_040, 9_977_856, 100_125_440, 88_083_072)
    assert accounting.params(PUBLISHED) == \
        4 * mamba + attention + 4 * routed + ends == 666_963_456
    # 16 bytes a parameter: 10.67 GB of state; with 16 experts held, 15.8
    assert 16 * 666_963_456 == pytest.approx(10.67e9, rel=1e-3)
    assert 16 * accounting.params(dict(PUBLISHED, n_routed_experts=16)) == \
        pytest.approx(15.78e9, rel=1e-3)
    # uncut: the row's "30B"
    whole = dict(PUBLISHED, layers=52, n_routed_experts=128,
                 vocab_size=131072,
                 pattern=PUBLISHED["hybrid_override_pattern"])
    assert accounting.params(whole) == 31_577_940_288
    # FLOPs a token at S 8,192: the matmul parameters a token uses, the
    # held experts at their expectation of 6 · 8/128 of one
    used = (4 * (2688 * 10304 + 4096 * 2688)
            + 2 * 2688 * 4096 + 2 * 2688 * 256
            + 4 * (2688 * 128 + 0.375 * expert + shared)
            + 2688 * 16384)
    assert used == 318_431_232
    assert accounting.recurrence_flops_per_token(PUBLISHED) == 2_097_152
    assert accounting.train_flops_per_token(PUBLISHED, 8192) == \
        6 * used + 6 * 8192 * 4096 + 4 * 3 * 2_097_152 == 2_137_079_808
    # 8,192 tokens a step: 17.5 TFLOP, 88.9 ms at the chip's peak
    assert 8192 * 2_137_079_808 / 197e12 == pytest.approx(88.87e-3, rel=1e-3)
    # the program's presets run the filed sizes
    from ray_tpu.models import nemotron_h
    assert accounting.ran_sizes(
        nemotron_h.nemotron_twotower_30b_a3b_9l()) == \
        accounting.filed_sizes(PUBLISHED)
    assert accounting.ran_sizes(nemotron_h.nemotron_twotower_30b_a3b()) == \
        accounting.filed_sizes(dict(whole, deployment={"first_expert": 0}))


def test_ssd_cost_by_hand_and_as_the_program_plans_it():
    needed, moved = accounting.ssd_cost(PUBLISHED, 8192)
    assert needed == (2 * 8192 * 128 * 8 * 128 + 2 * 8192 * 128 * 64 * 64
                      + 2 * 2 * 8192 * 64 * 64 * 128) == 27_917_287_424
    assert moved == (2 * 8192 * 4096 + 2 * 8192 * 1024) * 2 + 8192 * 64 * 4
    from ray_tpu.ops.ssd import ssd_plan
    plan = ssd_plan(8192, 64, 64, 128, 8, 128)
    assert (plan["flops"], plan["bytes"]) == (needed, moved)
    least, bound = flops.least_seconds(needed, moved,
                                       flops.peaks_for("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(0.2074e-3, rel=1e-3)


def test_pick_and_put_name_a_layer_of_each_kind():
    """The middle mixer and attention layer; the FIRST routed layer, ahead
    of which no token's choice of experts differs from the reference's."""
    import jax

    from ray_tpu.models import nemotron_h
    cfg = nemotron_h.nemotron_h_tiny()
    # one program: leaf by leaf the CPU takes seconds more
    params = jax.jit(lambda key: nemotron_h.init(key, cfg))(
        jax.random.PRNGKey(0))
    leaves = accounting.pick(params)
    assert {k: v.shape for k, v in leaves.items()} == {
        "head": (256, 64), "wq": (64, 4, 16), "wv": (64, 2, 16),
        "w_in": (64, 200), "A_log": (8,), "dt_bias": (8,),
        "w_out": (64, 64), "wg": (64, 16), "w1": (2, 64, 32),
        "w2": (2, 32, 64), "shared_w1": (64, 48)}
    np.testing.assert_array_equal(leaves["w1"], params["moe"]["w1"][0, :2])
    np.testing.assert_array_equal(leaves["wg"], params["moe"]["wg"][0])
    np.testing.assert_array_equal(leaves["w_in"], params["mamba"]["w_in"][1])
    zeroed = accounting.put(params, jax.tree_util.tree_map(
        lambda a: a * 0, leaves))
    moe, mamba = zeroed["moe"], zeroed["mamba"]
    assert not moe["w1"][0, :2].any() and moe["w1"][0, 2:].any()
    assert moe["w1"][1].any() and not moe["wg"][0].any()
    assert moe["wg"][1].any() and not moe["shared_w1"][0].any()
    assert not mamba["w_in"][1].any() and mamba["w_in"][0].any()
    assert not zeroed["head"].any() and zeroed["wte"].any()
    assert jax.tree_util.tree_structure(zeroed) == \
        jax.tree_util.tree_structure(params)


# ------------------------------------------------------------ the reader

def _ctx(per_op_s, steps=3, busy_s=1.2, remat=True):
    return {"trace": {"per_op_s": per_op_s, "busy_s": busy_s, "steps": steps},
            "model": PUBLISHED, "chips": 1,
            "traffic": {"batch": 1, "seq": 8192, "remat": remat},
            "accounting": "chipbench.accounting.nemotron_h",
            "peaks": flops.peaks_for("TPU v5 lite")}


SCAN = [
    "%fusion.439 = f32[64,8,8,64,128]{4,3,2,1,0} fusion(bf16[64,8,8,128,128]"
    "{4,3,2,1,0} %m, bf16[1,64,128,8,8,64]{5,4,3,2,1,0} %x), kind=kOutput",
    "%fusion.1694 = f32[64,8,128,128]{3,2,1,0} fusion(bf16[64,128,8,128] %c,"
    " bf16[64,128,8,128] %b), kind=kOutput",
    "%fusion.1389 = f32[1,64,128,8,8]{4,3,2,1,0} fusion(f32[1,64,128,8,8] "
    "%a), kind=kLoop",
    "%copy.2421 = f32[64,8,8,128]{3,2,1,0} copy(f32[64,8,8,128]{1,3,2,0} %r)",
    "%fusion.7 = f32[1,8,8,64,128]{4,3,2,1,0} fusion(f32[1,8,8,64,128] %h, "
    "f32[1,8,8] %keep), kind=kLoop",
    "%slice_bitcast_fusion.11 = f32[512,8,64,128]{3,2,1,0} fusion(f32[64,1,"
    "8,8,64,128] %s), kind=kLoop",
]
MIXER_ONLY = [
    "%fusion.1100 = f32[8192,10304]{0,1} fusion(bf16[8192,2688]{1,0} %h, "
    "bf16[2688,10304]{1,0} %w_in), kind=kOutput",
    "%fusion.60 = f32[1,8192,6144]{2,1,0} fusion(f32[1,8192,6144] %xbc, "
    "f32[4,6144] %conv_w), kind=kLoop",
    "%fusion.997 = f32[8192,2688]{0,1} fusion(bf16[1,8192,4096]{2,1,0} %y, "
    "bf16[4096,2688]{1,0} %w_out), kind=kOutput",
    "%fusion.31 = f32[8192,8,512]{2,1,0} fusion(f32[8192,8]{1,0} %rms, "
    "f32[8192,8,512] %y), kind=kLoop",
]
NOT_MIXER = [
    # the optimizer's pass: the stacked leaves have one axis more, but a
    # layer's gradient comes in the projection's own shape
    "%fusion.540 = (f32[4,2688,10304]{2,1,0}, f32[4,2688,10304]{2,1,0}) "
    "fusion(f32[4,2688,10304] %state_params__mamba____w_in__.1, "
    "f32[4,2688,10304] %state_opt_state_1__0__nu__mamba____w_in__.1, "
    "bf16[1,2688,10304]{2,1,0} %fusion.1540), kind=kLoop",
    "%fusion.52 = (f32[4,2688,10304]{2,1,0}, f32[4,2688,10304]{2,1,0}) "
    "fusion(f32[4,2688,10304] %p, f32[4,2688,10304] %g), kind=kLoop",
    "%fusion.53 = f32[4,4096,2688]{2,1,0} fusion(f32[4,4096,2688] %p)",
    # attention: three axes, and the flash kernels' row statistics
    "%attention.7 = (bf16[32,8192,128]{2,1,0}, f32[32,64,8,128]{3,2,1,0}) "
    "custom-call(bf16[32,8192,128] %q, bf16[2,8192,128] %k, "
    'bf16[2,8192,128] %v), custom_call_target="tpu_custom_call"',
    "%fusion.88 = bf16[8192,32,128]{2,0,1} fusion(bf16[8192,2688] %h, "
    "bf16[2688,32,128] %wq), kind=kOutput",
    "%broadcast.5 = f32[32,64,8,128]{3,2,1,0} broadcast(f32[32,64,128] %l)",
    # the routed layer and the head
    "%fusion.9 = bf16[49152,2688]{1,0} fusion(bf16[8192,2688]{1,0} %h, "
    "s32[49152]{0} %order), kind=kCustom, calls=%gather",
    "%fusion.12 = f32[4,8,2688,1856]{3,2,1,0} fusion(f32[4,8,2688,1856] %p)",
    "%fusion.307 = (f32[1,8192]{1,0}, f32[1,8192,16384]{2,1,0}) fusion()",
]


def test_the_reader_tells_the_scan_the_mixer_and_the_rest():
    sizes = trace_ssm._sizes(PUBLISHED, 8192)
    for text in SCAN:
        assert trace_ssm._is_scan(text, sizes), text
        assert trace_ssm._is_mixer(text, sizes), text
    for text in MIXER_ONLY:
        assert not trace_ssm._is_scan(text, sizes), text
        assert trace_ssm._is_mixer(text, sizes), text
    for text in NOT_MIXER:
        assert not trace_ssm._is_mixer(text, sizes), text
    per_op = {text: 0.01 for text in SCAN + MIXER_ONLY + NOT_MIXER}
    assert trace_ssm.read(_ctx(per_op), "mixer_share") == pytest.approx(
        100 * 0.01 * (len(SCAN) + len(MIXER_ONLY)) / 1.2)


@pytest.mark.parametrize("remat, executions", [(True, 3), (False, 2)])
def test_ssd_roofline_is_least_time_over_time_taken(remat, executions):
    """Three traced steps whose scan operations took 180 ms in all: one
    execution of one layer's scan needs 0.2074 ms (memory-bound), four
    layers run it forward, again under remat, and backward."""
    per_op = {SCAN[0]: 0.1, SCAN[1]: 0.05, SCAN[4]: 0.03, MIXER_ONLY[0]: 0.5,
              NOT_MIXER[2]: 0.5}
    assert trace_ssm.read(_ctx(per_op, remat=remat), "ssd_roofline") == \
        pytest.approx(100 * 0.2074e-3 * 4 * executions * 3 / 0.18, rel=1e-3)


@pytest.mark.parametrize("what", ["mixer_share", "ssd_roofline"])
def test_nothing_to_read_is_nothing_reported(what):
    """No trace (a CPU run), or a trace of a program without the mixer:
    None, never a raise and never a zero."""
    assert trace_ssm.read({"trace": None}, what) is None
    assert trace_ssm.read(_ctx({text: 0.1 for text in NOT_MIXER}),
                          what) is None


# ------------------------------------- the reader of the held experts' layer

ROUTED = [
    "%gmm.20 = bf16[49152,1920]{1,0} custom-call(s32[] %n, s32[129]{0} %g, "
    'bf16[49152,2688] %rows, bf16[8,2688,1920] %w), custom_call_target='
    '"tpu_custom_call"',
    "%tgmm.3 = bf16[8,2688,1920]{2,1,0} custom-call(s32[] %n, s32[129]{0} "
    '%g, bf16[49152,2688] %rows), custom_call_target="tpu_custom_call"',
    "%fusion.118 = bf16[49152,2688]{1,0} fusion(bf16[8192,2688]{1,0} %h, "
    "s32[49152]{0} %order), kind=kCustom, calls=%gather",
    "%reshape.4182 = f32[8192,6,2688]{2,1,0} reshape(f32[49152,2688] %y)",
    "%fusion.77 = (f32[1,8192,128]{2,1,0}, s32[1,8192,6]{2,1,0}) fusion("
    "f32[8192,2688] %h, f32[2688,128] %wg), kind=kOutput",
    "%sort.3 = (s32[49152]{0}, s32[49152]{0}) sort(s32[49152] %key, "
    "s32[49152] %iota), dimensions={0}",
    # the held experts' compute-dtype copies, as published and padded
    "%slice_bitcast_fusion.2 = bf16[8,2688,1856]{2,1,0} fusion("
    "f32[4,8,2688,1856] %state_params__moe____w1__.1), kind=kLoop",
    "%pad.4 = bf16[1,8,1920,2688]{3,2,1,0} pad(bf16[8,1856,2688] %w2, "
    "bf16[] %zero), padding=0_0x0_0x0_64x0_0",
    # the shared expert
    "%fusion.2659 = f32[8192,3712]{1,0} fusion(bf16[8192,2688] %h, "
    "bf16[2688,3712] %shared_w1), kind=kOutput",
    "%convolution_convert_fusion.14 = bf16[8192,2688]{0,1} fusion("
    "bf16[8192,3712]{1,0} %hidden, f32[2688,3712]{1,0} %w), kind=kOutput",
]
NOT_ROUTED = [
    # the optimizer's pass over the shared expert and the held ones
    "%fusion.718 = (f32[4,3712,2688]{2,1,0}, f32[4,3712,2688]{2,1,0}) "
    "fusion(f32[4,3712,2688] %state_params__moe____shared_w2__.1, "
    "f32[4,3712,2688] %state_opt_state_1__0__nu__moe____shared_w2__.1, "
    "bf16[1,3712,2688]{2,1,0} %fusion.1963), kind=kLoop",
    "%fusion.526 = f32[4,8,2688,1856]{3,2,1,0} fusion(f32[4,8,2688,1856] %p,"
    " f32[4,8,2688,1856] %g), kind=kLoop",
    # the mixers: B and C end in the state's 128, eight groups lead some
    "%fusion.1694 = f32[64,8,128,128]{3,2,1,0} fusion(bf16[64,128,8,128] %c,"
    " bf16[64,128,8,128] %b), kind=kOutput",
    "%fusion.61 = bf16[8,8192,128]{2,1,0} fusion(f32[1,8192,8,128] %b)",
    "%fusion.1100 = f32[8192,10304]{0,1} fusion(bf16[8192,2688]{1,0} %h, "
    "bf16[2688,10304]{1,0} %w_in), kind=kOutput",
    # attention and the head
    "%attention.7 = (bf16[32,8192,128]{2,1,0}, f32[32,64,8,128]{3,2,1,0}) "
    "custom-call(bf16[32,8192,128] %q, bf16[2,8192,128] %k, "
    'bf16[2,8192,128] %v), custom_call_target="tpu_custom_call"',
    "%fusion.307 = (f32[1,8192]{1,0}, f32[1,8192,16384]{2,1,0}) fusion()",
]


def test_the_reader_tells_the_routed_layers_from_the_rest():
    sizes = trace_held._sizes(PUBLISHED, 8192)
    for text in ROUTED:
        assert trace_held._is_routed(text, sizes), text
    for text in NOT_ROUTED:
        assert not trace_held._is_routed(text, sizes), text
    # the two readers share no operation
    mixer = trace_ssm._sizes(PUBLISHED, 8192)
    assert not any(trace_ssm._is_mixer(text, mixer) for text in ROUTED)
    assert not any(trace_held._is_routed(text, sizes)
                   for text in SCAN + MIXER_ONLY)
    per_op = {text: 0.01 for text in ROUTED + NOT_ROUTED}
    assert trace_held.read(_ctx(per_op), "routed_share") == pytest.approx(
        100 * 0.01 * len(ROUTED) / 1.2)
    # the products alone: `gmm.N` and `tgmm.N`, by name
    assert trace_held.read(_ctx(per_op), "gmm_share") == pytest.approx(
        100 * 0.01 * 2 / 1.2)


@pytest.mark.parametrize("what", ["routed_share", "gmm_share"])
def test_no_routed_operation_is_nothing_reported(what):
    """No trace, a trace without the layer, or a configuration that holds
    every expert it scores (`trace_moe`'s to read): None, never a raise."""
    assert trace_held.read({"trace": None}, what) is None
    assert trace_held.read(_ctx({text: 0.1 for text in NOT_ROUTED}),
                           what) is None
    whole = _ctx({text: 0.1 for text in ROUTED})
    whole["model"] = {k: v for k, v in PUBLISHED.items() if k != "published"}
    assert trace_held.read(whole, what) is None
