"""The trace reader of ``kernels.grouped_matmul_roofline`` on hand-made
operation texts: the grouped products under all three names a program may
give them (the compiler's ``ragged-dot-*``, JAX's Pallas ``gmm.N`` and
``tgmm.N``), what is not a product, and the quotient worked out by hand at
the cell's shapes."""
import pytest

from chipbench import catalog, flops
from chipbench.readers import trace_grouped

PUBLISHED = catalog.load_json(catalog.load_manifest(), "configs",
                              "olmoe-1b-7b-1l")
# the nine products of one step at 8,192 tokens, at the chip's bf16 peak
LEAST_S = 9 * 2 * 65_536 * 2048 * 1024 / 197e12


def _ctx(per_op_s, steps=3):
    return {"trace": {"per_op_s": per_op_s, "busy_s": 0.3, "steps": steps},
            "model": PUBLISHED, "chips": 1,
            "traffic": {"batch": 2, "seq": 4096},
            "accounting": "chipbench.accounting.olmoe",
            "peaks": flops.peaks_for("TPU v5 lite")}


RAGGED = ("%ragged-dot-none.3 = bf16[65536,1024]{1,0} custom-call(s32[1]{0} "
          "%a, bf16[65536,2048]{1,0} %x, bf16[64,2048,1024]{2,1,0} %w), "
          'custom_call_target="tpu_custom_call"')
GMM = ("%gmm.11 = bf16[65536,1024]{1,0:T(8,128)(2,1)} custom-call(s32[65]{0} "
       "%offsets, s32[191]{0} %ids, s32[191]{0} %tiles, s32[1]{0} %first, "
       "bf16[65536,2048]{1,0} %x, bf16[64,2048,1024]{2,1,0} "
       '%convert_bitcast_fusion.4), custom_call_target="tpu_custom_call"')
TGMM = ("%tgmm.2 = bf16[64,1024,2048]{2,1,0:T(8,128)(2,1)} custom-call("
        "s32[65]{0} %offsets, s32[191]{0} %ids, s32[191]{0} %tiles, s32[1]{0} "
        "%first, bf16[65536,1024]{1,0} %hidden, bf16[65536,2048]{1,0} %dy), "
        'custom_call_target="tpu_custom_call"')
PRODUCTS = [RAGGED, RAGGED.replace("none.3", "none"), GMM,
            GMM.replace("gmm.11", "gmm"), TGMM, TGMM.replace("tgmm.2", "tgmm")]
NOT_PRODUCTS = [
    "%ragged-dot-metadata.1 = (s32[65]{0}, s32[191]{0}) custom-call("
    's32[64]{0} %gs), custom_call_target="tpu_custom_call"',
    # the weight's cast, which `moe.routed_share` counts, is no product
    "%convert_bitcast_fusion.4 = bf16[64,2048,1024]{2,1,0} fusion("
    "f32[1,64,2048,1024]{3,2,1,0} %w), kind=kLoop",
    "%copy.269 = bf16[1,64,2048,1024]{2,3,1,0} copy(f32[1,64,2048,1024] %w)",
    # a fusion that reads a product's result, or is named after something
    # that merely starts like one
    "%fusion.45 = bf16[65536,1024]{1,0} fusion(bf16[65536,1024]{1,0} "
    "%gmm.11, bf16[65536,1024]{1,0} %gmm.12), kind=kLoop",
    "%gmm_epilogue.1 = bf16[65536,1024]{1,0} fusion(bf16[65536,1024] %gmm.3)",
    "%attention.7 = (bf16[32,4096,128]{2,1,0}, bf16[32,4096,128]{2,1,0}) "
    'custom-call(bf16[32,4096,128] %q), custom_call_target="tpu_custom_call"',
]


@pytest.mark.parametrize("text", PRODUCTS)
def test_a_grouped_product_is_told_by_any_of_its_three_names(text):
    assert trace_grouped._GROUPED.match(text), text
    assert trace_grouped.read(_ctx({text: 0.09})) == pytest.approx(
        100 * 3 * LEAST_S / 0.09, rel=1e-3)


@pytest.mark.parametrize("text", NOT_PRODUCTS)
def test_what_is_not_a_product_is_not_counted(text):
    assert not trace_grouped._GROUPED.match(text), text
    assert trace_grouped.read(_ctx({text: 0.5})) is None
    assert trace_grouped.read(_ctx({text: 0.5, GMM: 0.09})) == \
        trace_grouped.read(_ctx({GMM: 0.09}))


def test_the_quotient_by_hand_at_the_cells_shapes():
    """Three traced steps; a step's six `gmm` calls took 15 ms and its
    three `tgmm` calls 9: the nine need 12.558 ms at the peak, 52.3 %.
    The parent's program on the same yardstick: 27.2 ms a step, 46.2 %."""
    assert LEAST_S == pytest.approx(12.558e-3, rel=1e-3)
    change = {GMM.replace("gmm.11", f"gmm.{10 + i}"): 3 * 0.0025
              for i in range(6)}
    change.update({TGMM.replace("tgmm.2", f"tgmm.{2 + i}"): 3 * 0.003
                   for i in range(3)})
    change[NOT_PRODUCTS[0]], change[NOT_PRODUCTS[1]] = 0.5, 0.5
    assert trace_grouped.read(_ctx(change)) == pytest.approx(
        100 * 12.558 / 24.0, rel=1e-3)
    parent = {RAGGED.replace("none.3", f"none.{i}"): 3 * 0.0272 / 9
              for i in range(9)}
    parent[NOT_PRODUCTS[0]] = 0.5
    assert trace_grouped.read(_ctx(parent)) == pytest.approx(
        100 * 12.558 / 27.2, rel=1e-3)


def test_nothing_to_read_is_nothing_reported():
    """No trace (a CPU run), or a trace of a program without a grouped
    product (a GPT-2 cell's): None, never a raise and never a zero."""
    assert trace_grouped.read({"trace": None}) is None
    assert trace_grouped.read(_ctx({})) is None
    assert trace_grouped.read(
        _ctx({text: 0.1 for text in NOT_PRODUCTS})) is None


def test_the_manifest_reports_it_in_the_routed_cell_only():
    manifest = catalog.load_manifest()
    for cell in (w["name"] for w in manifest["workloads"]):
        names = [m["name"] for m in
                 catalog.resolve_cell(manifest, cell, "per_layer")["metrics"]]
        assert ("kernels.grouped_matmul_roofline" in names) == \
            (cell == "olmoe1l-b2s4k")
