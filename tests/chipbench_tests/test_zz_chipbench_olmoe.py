"""The ``olmoe`` architecture's benchmark files, without the chip: the
fixture ``olmoe-tiny`` (a configuration and a traffic mix in THIS directory;
model, reference and accounting are the program's and the benchmark's own)
through the ``train_fit`` job on the CPU, the accounting's arithmetic at the
published sizes against numbers worked out by hand, and the trace reader of
the two routed-layer metrics on hand-made operations."""
import json

import numpy as np
import pytest

from chipbench import catalog, flops
from chipbench.accounting import olmoe as accounting
from chipbench.readers import mfu, trace_moe
from tests.chipbench_tests import tiny_fit

MANIFEST = {
    "paths": ["chipbench", "tests/chipbench_tests"],
    "workloads": [{"name": "olmoe-tiny", "config": "olmoe-tiny",
                   "traffic": "fit-olmoe-tiny", "chips": 1,
                   "why": "the routed architecture at test sizes"}],
    "end_to_end": [
        {"name": "tokens_per_s_per_chip", "unit": "tokens/s/chip"},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [
        {"name": "train_step.compiles_in_window", "unit": "count"},
        {"name": "moe.routed_share", "unit": "%"},
        {"name": "kernels.grouped_matmul_roofline", "unit": "%"}],
}
PUBLISHED = catalog.load_json(catalog.load_manifest(), "configs",
                              "olmoe-1b-7b-1l")


@pytest.fixture(scope="module")
def fit(once_a_run):
    """ONE traced fit with the metrics of both groups, once a test run: the
    two cases below read a group each of it (`tiny_fit.py`)."""
    return once_a_run("olmoe_tiny_fit", lambda: tiny_fit.traced(
        MANIFEST, "olmoe-tiny", seed=29))


@pytest.mark.parametrize("trace", [False, True])
def test_olmoe_tiny_through_the_trainer(fit, trace):
    cell = catalog.resolve_cell(MANIFEST, "olmoe-tiny",
                                "per_layer" if trace else "end_to_end")
    assert cell["accounting"] == "chipbench.accounting.olmoe"
    assert cell["reference"] == "chipbench.references.olmoe"
    record = fit
    json.dumps(record)
    assert record["correct"], (record["verdicts"], record["check"])
    assert set(record["check"]["errors"]) == {
        "loss", "grad_head", "grad_wq", "grad_wv", "grad_wg", "grad_w_gate",
        "grad_w_down"}
    assert record["failed"] == 0 and record["attempted"] >= 4
    values = tiny_fit.values_of(record, cell)
    if trace:
        # no TPU plane in a CPU trace: the routed layer's metrics are left
        # out, not invented
        assert set(values) == {"train_step.compiles_in_window"}
        assert values["train_step.compiles_in_window"] == 0
        return
    assert values["tokens_per_s_per_chip"] == pytest.approx(
        record["attempted"] * 4 * 48 / record["clock"]["window_s"])
    # the mfu reader, given a peak: a token uses the head, and a layer the
    # four attention projections, the router and 2 of the 8 experts
    per_token = (6 * (64 * 256 + 2 * (4 * 64 * 64 + 64 * 8
                                      + 2 * 3 * 64 * 32))
                 + 6 * 2 * 48 * 64)
    assert per_token == 485_376
    ctx = {"accounting": cell["accounting"], "model": cell["model"],
           "traffic": cell["traffic"], "chips": 1, "clock": record["clock"],
           "counters": {"steps": record["attempted"]},
           "peaks": {"bf16_flops_per_s": 1e12}}
    assert mfu.read(ctx) == pytest.approx(
        100 * record["attempted"] * 4 * 48 / record["clock"]["window_s"]
        * per_token / 1e12, rel=1e-12)


def test_the_published_configuration_is_the_catalog_rows():
    """Every key of the public ``config.json`` as the model-configs catalog
    holds it, unchanged; the depth the cell runs is filed beside them."""
    for key, value in {
            "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
            "hidden_size": 2048, "intermediate_size": 1024,
            "max_position_embeddings": 4096, "model_type": "olmoe",
            "norm_topk_prob": False, "num_attention_heads": 16,
            "num_experts": 64, "num_experts_per_tok": 8,
            "num_hidden_layers": 16, "num_key_value_heads": 16,
            "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
            "tie_word_embeddings": False, "vocab_size": 50304}.items():
        assert PUBLISHED[key] == value, key
    assert PUBLISHED["layers"] == 1 and PUBLISHED["reduced"] == ["layers"]
    assert PUBLISHED["router_aux_loss_coef"] == 0.01
    assert PUBLISHED["router_z_loss_coef"] == 0.001


def test_params_and_flops_a_token_by_hand():
    assert accounting.params(PUBLISHED) == 625_616_896
    assert accounting.params(dict(PUBLISHED, layers=16)) == 6_919_161_856
    attention, router, expert = 4 * 2048 * 2048, 2048 * 64, 3 * 2048 * 1024
    head = 2048 * 50304
    assert (attention, router, expert, head) == (
        16_777_216, 131_072, 6_291_456, 103_022_592)
    assert accounting.train_flops_per_token(PUBLISHED, 4096) == \
        6 * (attention + router + 8 * expert) + 6 * head \
        + 6 * 1 * 4096 * 2048 == 1_071_906_816
    # 8,192 tokens a step: 8.78 TFLOP
    assert 8192 * 1_071_906_816 == pytest.approx(8.78e12, rel=1e-3)
    # the program's preset runs the filed sizes
    from ray_tpu.models import olmoe
    assert accounting.ran_sizes(olmoe.olmoe_1b_7b_1l()) == \
        accounting.filed_sizes(PUBLISHED)
    assert accounting.ran_sizes(olmoe.olmoe_1b_7b()) == \
        accounting.filed_sizes(dict(PUBLISHED, layers=16))


def test_grouped_matmul_cost_by_hand():
    needed, moved = accounting.grouped_matmul_cost(PUBLISHED, 8192)
    product = 2 * 65_536 * 2048 * 1024
    assert needed == 9 * product == 2_473_901_162_496
    assert moved == 9 * 2 * (65_536 * 2048 + 65_536 * 1024
                             + 64 * 2048 * 1024)
    least, bound = flops.least_seconds(needed, moved,
                                       flops.peaks_for("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(12.558e-3, rel=1e-3)


def test_pick_and_put_name_four_experts_of_the_middle_layer():
    import jax

    from ray_tpu.models import olmoe
    cfg = olmoe.olmoe_tiny()
    # one program: leaf by leaf the CPU takes seconds more
    params = jax.jit(lambda key: olmoe.init(key, cfg))(
        jax.random.PRNGKey(0))
    leaves = accounting.pick(params)
    assert {k: v.shape for k, v in leaves.items()} == {
        "head": (256, 64), "wq": (64, 4, 16), "wv": (64, 4, 16),
        "wg": (64, 8), "w_gate": (4, 64, 32), "w_down": (4, 32, 64)}
    np.testing.assert_array_equal(
        leaves["w_gate"], params["blocks"]["moe"]["w_gate"][1, :4])
    zeroed = accounting.put(params, jax.tree_util.tree_map(
        lambda a: a * 0, leaves))
    moe = zeroed["blocks"]["moe"]
    assert not moe["w_gate"][1, :4].any() and moe["w_gate"][1, 4:].any()
    assert moe["w_gate"][0].any() and not moe["wg"][1].any()
    assert not zeroed["head"].any() and zeroed["wte"].any()
    assert jax.tree_util.tree_structure(zeroed) == \
        jax.tree_util.tree_structure(params)


# ------------------------------------------------------------ the reader

def _ctx(per_op_s, steps=3, busy_s=0.3):
    return {"trace": {"per_op_s": per_op_s, "busy_s": busy_s, "steps": steps},
            "model": PUBLISHED, "chips": 1,
            "traffic": {"batch": 2, "seq": 4096},
            "accounting": "chipbench.accounting.olmoe",
            "peaks": flops.peaks_for("TPU v5 lite")}


ROUTED = [
    "%ragged-dot-none.3 = bf16[65536,1024]{1,0} custom-call(s32[1]{0} %a, "
    "bf16[65536,2048]{1,0} %x, bf16[64,2048,1024]{2,1,0} %w), "
    'custom_call_target="tpu_custom_call"',
    "%ragged-dot-metadata.1 = (s32[65]{0}, s32[191]{0}) custom-call("
    's32[64]{0} %gs), custom_call_target="tpu_custom_call"',
    "%fusion.9 = bf16[65536,2048]{1,0} fusion(bf16[8192,2048]{1,0} %h, "
    "s32[65536]{0} %order), kind=kCustom, calls=%gather",
    "%sort.2 = (s32[65536]{0}, s32[65536]{0}) sort(s32[65536]{0} %key)",
    "%fusion.4 = f32[2,4096,64]{2,1,0} fusion(f32[2,4096,2048] %h), "
    "kind=kOutput",
    "%fusion.5 = (f32[2,4096,8]{2,1,0}, s32[2,4096,8]{2,1,0}) fusion()",
    "%convert.7 = bf16[64,2048,1024]{2,1,0} fusion(f32[64,2048,1024] %w)",
    "%copy.178 = bf16[1,64,2048,1024]{2,3,1,0} copy(bf16[1,64,2048,1024] %c)",
]
NOT_ROUTED = [
    "%fusion.52 = (f32[1,64,2048,1024]{3,2,1,0}, f32[1,64,2048,1024]{3,2,1,0})"
    " fusion(f32[1,64,2048,1024] %p, bf16[64,2048,1024]{2,1,0} %grad), "
    "kind=kLoop",
    "%attention.7 = (bf16[32,4096,128]{2,1,0}, bf16[32,4096,128]{2,1,0}) "
    'custom-call(bf16[32,4096,128] %q), custom_call_target="tpu_custom_call"',
    "%fusion.307 = (f32[2,4096]{1,0}, f32[2,4096,50304]{2,1,0}) fusion()",
    "%fusion.60 = bf16[50304,2048]{1,0} fusion(f32[50304,2048] %head)",
    "%fusion.1 = f32[32,8,4096]{2,1,0} fusion(f32[32,8,4096] %lse)",
]


def test_routed_share_counts_the_routed_layers_operations_only():
    for text in ROUTED:
        assert trace_moe._is_routed(text, 8192, 64, 8), text
    for text in NOT_ROUTED:
        assert not trace_moe._is_routed(text, 8192, 64, 8), text
    per_op = {text: 0.01 for text in ROUTED + NOT_ROUTED}
    assert trace_moe.read(_ctx(per_op)) == pytest.approx(
        100 * 0.01 * len(ROUTED) / 0.3)


def test_nothing_to_read_is_nothing_reported():
    """No trace (a CPU run), or a trace of a program without the routed
    layer (the parent's): None, never a raise and never a zero."""
    assert trace_moe.read({"trace": None}) is None
    assert trace_moe.read(_ctx({text: 0.1 for text in NOT_ROUTED})) is None
