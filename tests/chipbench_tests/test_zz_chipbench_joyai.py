"""The ``joyai_flash`` architecture's benchmark files, without the chip: the
fixture ``joyai-tiny`` (a configuration and a traffic mix in THIS directory;
model, reference and accounting are the program's and the benchmark's own)
through the ``train_fit`` job on the CPU, the accounting's arithmetic at the
published sizes against numbers worked out by hand, the configuration file
against the catalog row, the manifest's entries, the new reader on a
hand-made trace, and the precision controls of
``benchmarks/precision_control.py`` through the same job."""
import json
import os
import time

import numpy as np
import pytest

from chipbench import catalog, flops
from chipbench.accounting import joyai_flash as accounting
from chipbench.jobs import train_fit
from chipbench.readers import mfu, trace_flash, trace_latent, trace_scope
from tests.chipbench_tests import tiny_fit

MANIFEST = {
    "paths": ["chipbench", "tests/chipbench_tests"],
    "workloads": [{"name": "joyai-tiny", "config": "joyai-tiny",
                   "traffic": "fit-joyai-tiny", "chips": 1,
                   "why": "latent attention, a dense and two routed layers "
                          "with a shared expert, the prediction module, at "
                          "test sizes"}],
    "end_to_end": [
        {"name": "tokens_per_s_per_chip", "unit": "tokens/s/chip"},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [
        {"name": "train_step.compiles_in_window", "unit": "count"},
        {"name": "attn.latent_flash_share", "unit": "%"},
        {"name": "kernels.latent_flash_roofline", "unit": "%"},
        {"name": "attn.latent_proj_share", "unit": "%"},
        {"name": "mtp.scoped_share", "unit": "%"},
        {"name": "moe.gated_shared_share", "unit": "%"},
        {"name": "train_step.latent_recompute_share", "unit": "%"}],
}
REAL = catalog.load_manifest()
PUBLISHED = catalog.load_json(REAL, "configs", "joyai-llm-flash-5l")
CELL = "joyaiflash5l-b2s8k"
NEW_METRICS = ("attn.latent_flash_share", "kernels.latent_flash_roofline",
               "attn.latent_proj_share", "mtp.scoped_share",
               "moe.gated_shared_share", "train_step.latent_recompute_share")
LEAVES = ("head", "wq_b", "wkv_b", "wkv_a", "wo", "wg", "w_gate", "w_down",
          "shared_w_gate", "eh_proj")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def fit(once_a_run):
    """ONE traced fit with the metrics of both groups, once a test run: the
    two cases below read a group each of it (`tiny_fit.py`)."""
    return once_a_run("joyai_tiny_fit", lambda: tiny_fit.traced(
        MANIFEST, "joyai-tiny", seed=43))


@pytest.mark.parametrize("trace", [False, True])
def test_joyai_tiny_through_the_trainer(fit, trace):
    cell = catalog.resolve_cell(MANIFEST, "joyai-tiny",
                                "per_layer" if trace else "end_to_end")
    assert cell["accounting"] == "chipbench.accounting.joyai_flash"
    assert cell["reference"] == "chipbench.references.joyai_flash"
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
    # the mfu reader, given a peak. A token uses: the head TWICE (the
    # module's too); four latent attentions (wq_a 64·48, wq_b 48·4·32, wkv_a
    # 64·40, wkv_b 32·4·40, wo 4·16·64); one dense feed-forward of 3·64·96;
    # in each of the three routed layers (two of the trunk, the module's) the
    # router over 16, the shared expert of 3·64·32 and 3 · 4/16 of another;
    # eh_proj 128·64; the scores over 40 · 41 / 2 pairs in four layers at
    # (32 + 16) · 4 heads
    attention = 64 * 48 + 48 * 128 + 64 * 40 + 32 * 160 + 64 * 64
    used = (2 * 256 * 64 + 4 * attention + 3 * 64 * 96
            + 3 * (64 * 16 + 1.75 * 3 * 64 * 32) + 128 * 64)
    per_token = 6 * used + 6 * 4 * 820 * 192 / 40
    assert per_token == 1_166_592.0
    assert accounting.train_flops_per_token(cell["model"], 40) == 1_166_592
    ctx = {"accounting": cell["accounting"], "model": cell["model"],
           "traffic": cell["traffic"], "chips": 1, "clock": record["clock"],
           "counters": {"steps": record["attempted"]},
           "peaks": {"bf16_flops_per_s": 1e12}}
    assert mfu.read(ctx) == pytest.approx(
        100 * record["attempted"] * 2 * 40 / record["clock"]["window_s"]
        * 1_166_592 / 1e12, rel=1e-12)


def test_the_published_configuration_is_the_catalog_rows():
    """Every key of the public ``config.json`` as the model-configs catalog
    holds it, unchanged but for the two counts the cut reduces; the depth
    the cell runs, the published counts, the deployment and what was
    assumed are filed beside them."""
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_hidden_layers": 40, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
        "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128}
    for key, value in published.items():
        assert PUBLISHED[key] == value, key
    if os.path.exists(CATALOG):     # the row itself, where the guide is
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "JoyAI-LLM-Flash")
        assert PUBLISHED["source"] == row["source_url"]
        changed = {k for k, v in row["config"].items() if PUBLISHED[k] != v}
        assert changed == {"n_routed_experts", "vocab_size"}
        assert set(published) == set(row["config"]) - changed
    assert PUBLISHED["reduced"] == ["layers", "n_routed_experts",
                                    "vocab_size"]
    assert (PUBLISHED["layers"], PUBLISHED["n_routed_experts"],
            PUBLISHED["vocab_size"]) == (5, 16, 16160)
    assert PUBLISHED["published"] == {
        "layers": 40, "n_routed_experts": 256, "vocab_size": 129280}
    assert PUBLISHED["mtp_loss_weight"] == 0.3
    deployment = PUBLISHED["deployment"]
    assert deployment["chips_sharing_a_layer"] == 16
    assert deployment["first_expert"] == 0
    assert "16 chips share each layer" in deployment["what"]
    assert "LAST pipeline stage" in deployment["what"]
    assert deployment["chips_sharing_a_layer"] * PUBLISHED[
        "n_routed_experts"] == 256
    assert deployment["vocabulary_slices"] * PUBLISHED["vocab_size"] == 129280
    for key in ("layers", "mtp", "head_dim", "attention", "feed_forward",
                "router", "vocabulary", "sequence", "weights", "param_dtype",
                "compute_dtype"):
        assert key in PUBLISHED["assumed"], key
    assert "0.3" in PUBLISHED["assumed"]["mtp"]
    assert "FIRST" in PUBLISHED["assumed"]["mtp"]
    assert "16,256" in PUBLISHED["assumed"]["vocabulary"]
    assert "update rule" in PUBLISHED["assumed"]["router"]
    assert "table normal at 2,048" in PUBLISHED["assumed"]["weights"]
    assert "normal at 0.15" in PUBLISHED["assumed"]["weights"]
    assert "eh_proj normal at 4" in PUBLISHED["assumed"]["weights"]
    assert "ONE pass" in PUBLISHED["assumed"]["compute_dtype"]
    assert PUBLISHED["entry"] == "ray_tpu.models.joyai:joyai_llm_flash_5l"
    assert PUBLISHED["reference"] == "joyai_flash"
    assert accounting.layout(PUBLISHED) == (1, 4)
    # the cell is the manifest's, with the traffic ISSUE 43 gives it: the
    # file `lfm2moe5l-b2s8k` uses
    cell = catalog.resolve_cell(REAL, CELL, "per_layer")
    assert cell["workload"]["chips"] == 1
    assert cell["workload"]["traffic"] == "fit-b2-s8192-remat"
    assert {k: cell["traffic"][k] for k in (
        "job", "batch", "seq", "remat", "attention", "check_sequences",
        "vocab_divisor", "batches", "trace_from_step", "trace_steps",
        "mesh")} == {
        "job": "train_fit", "batch": 2, "seq": 8192, "remat": True,
        "attention": "auto", "check_sequences": 1, "vocab_divisor": 16,
        "batches": 64, "trace_from_step": 10, "trace_steps": 3,
        "mesh": {"dp": 1}}
    reported = {m["name"] for m in cell["metrics"]}
    assert set(NEW_METRICS) | {
        "attn.scoped_share", "train_step.loss_tail_share",
        "train_step.optimizer_share", "train_step.backward_share",
        "train_step.unscoped_share", "train_step.hbm_plan_gb",
        "device.idle_share"} <= reported
    # the generic flash reader knows three and six operands: it finds
    # nothing here, and its two metrics list the cells where it does
    assert not reported & {
        "kernels.flash_share", "kernels.flash_roofline", "moe.routed_share",
        "moe.held_routed_share", "ssm.mixer_share", "moe.scoped_share",
        "attn.window_flash_share", "train_step.recompute_share",
        "moe.sigmoid_held_share", "conv.scoped_share"}


def test_the_manifests_new_entries():
    """Appended behind what was there, found by name: a later PR's entries
    behind them break nothing here."""
    configs = [c["name"] for c in REAL["configs"]]
    assert configs.index("joyai-llm-flash-5l") == \
        configs.index("lfm2-24b-a2b-5l") + 1
    config = REAL["configs"][configs.index("joyai-llm-flash-5l")]
    assert config["source"] == PUBLISHED["source"] == (
        "https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/"
        "config.json")
    assert config["file"] == "chipbench/configs/joyai-llm-flash-5l.json"
    assert config["reduced"] == PUBLISHED["reduced"]
    cells = [w["name"] for w in REAL["workloads"]]
    assert cells.index(CELL) == cells.index("lfm2moe5l-b2s8k") + 1
    assert REAL["workloads"][cells.index(CELL)]["chips"] == 1
    names = [m["name"] for m in REAL["per_layer"]]
    at = names.index("moe.sigmoid_held_share") + 1
    assert tuple(names[at:at + 6]) == NEW_METRICS
    for m, (layer, better) in zip(REAL["per_layer"][at:at + 6], (
            ("attn", "lower"), ("kernels", "higher"), ("attn", "lower"),
            ("mtp", "lower"), ("moe", "lower"), ("train_step", "lower"))):
        assert (m["layer"], m["better"], m["unit"], m["moves"], m["source"],
                m["workloads"]) == (layer, better, "%", "mfu", "device_trace",
                                    [CELL])
    # no accepted metric's list of cells names the new one, and the two
    # generic flash metrics list the cells accepted before it
    for m in REAL["per_layer"][:at] + REAL["end_to_end"]:
        assert CELL not in m.get("workloads", [])
    for name in ("kernels.flash_share", "kernels.flash_roofline"):
        assert REAL["per_layer"][names.index(name)]["workloads"] == \
            cells[:cells.index(CELL)]
    for entry in REAL["configs"] + REAL["workloads"]:
        assert len(entry["why"]) <= 200, entry["name"]
    for name, args in (
            ("attn.latent_proj_share", {"scopes": ["latent_proj"]}),
            ("mtp.scoped_share", {"scopes": ["mtp"]}),
            ("moe.gated_shared_share", {"scopes": ["moe"]}),
            ("train_step.latent_recompute_share", {"phase": "recompute"})):
        assert catalog.load_json(REAL, "metrics", name) == {
            "reader": "trace_scope", "args": args}
    for name, what in (("attn.latent_flash_share", "share"),
                       ("kernels.latent_flash_roofline", "roofline")):
        assert catalog.load_json(REAL, "metrics", name) == {
            "reader": "trace_latent", "args": {"what": what}}


def test_params_and_flops_a_token_by_hand():
    attention = (2048 * 1536 + 1536 + 1536 * 6144 + 2048 * 576 + 512
                 + 512 * 8192 + 4096 * 2048)
    expert, router = 3 * 2048 * 768, 2048 * 256 + 256
    dense = 3 * 2048 * 7168
    assert (attention, expert, router, dense) == (
        26_347_520, 4_718_592, 524_544, 44_040_192)
    norms = 2 * 2048
    first = attention + norms + dense
    routed = attention + norms + router + 17 * expert
    module = 2 * 2048 + 4096 * 2048 + routed + 2048
    assert (first, routed, module) == (70_391_808, 107_092_224, 115_486_976)
    # ISSUE 43's count at the slice's 16,160 rows, and the 96 padding rows
    # of both tables beside it
    issue = first + 4 * routed + 2048 + module + 2 * 16160 * 2048
    assert issue == 680_441_088
    assert accounting.params(PUBLISHED) == issue + 2 * 96 * 2048 == \
        680_834_304
    # 16 bytes a parameter: 10.89 GB of state; all 256 experts 19.3 GB a
    # layer; a fifth routed layer beside the module 12.6 GB
    assert 16 * 680_834_304 == pytest.approx(10.89e9, rel=1e-3)
    assert 16 * 256 * expert == pytest.approx(19.3e9, rel=2e-3)
    deeper = accounting.params(dict(PUBLISHED, layers=6))
    assert deeper == 680_834_304 + routed
    assert 16 * deeper == pytest.approx(12.6e9, rel=2e-3)
    # uncut: the row's 48B-A2.7B with its module: one dense layer, 39 routed
    whole = dict(PUBLISHED, layers=40, n_routed_experts=256,
                 vocab_size=129280)
    assert accounting.layout(whole) == (1, 39)
    assert accounting.params(whole) == 50_190_491_648
    # FLOPs a token at S 8,192: six latent attentions, the dense
    # feed-forward, five routed layers (router, the shared expert whole and
    # 8 · 16/256 = half a routed expert), eh_proj, the head twice; the scores
    # at the causal area, QKᵀ at 192 and P·V at 128 on 32 heads
    matmuls = attention - 1536 - 512
    used = (6 * matmuls + dense + 5 * (2048 * 256 + 1.5 * expert)
            + 4096 * 2048 + 2 * 2048 * 16256)
    assert used == 315_097_088
    pairs = 6 * 8192 * 8193 // 2
    assert accounting.train_flops_per_token(PUBLISHED, 8192) == \
        round(6 * used + 6 * pairs * 320 * 32 / 8192) == 3_400_716_288
    forward = 3_400_716_288 / 3
    assert forward == pytest.approx(1.134e9, rel=1e-3)
    assert 16384 * 3_400_716_288 / 197e12 == pytest.approx(282.8e-3, rel=1e-3)
    # by part, of the forward's 1.13 G: scores 44 %, the latent projections
    # 28, the two heads 12, the dense feed-forward 8, five shared experts 4,
    # the held experts 2
    for share, part in ((0.444, 2 * pairs * 320 * 32 / 8192),
                        (0.279, 2 * 6 * matmuls),
                        (0.117, 2 * 2 * 2048 * 16256),
                        (0.078, 2 * dense),
                        (0.042, 2 * 5 * expert),
                        (0.021, 2 * 5 * 0.5 * expert)):
        assert part / forward == pytest.approx(share, abs=2e-3)
    # the program's presets run the filed sizes
    import dataclasses

    from ray_tpu.models import joyai
    assert accounting.ran_sizes(joyai.joyai_llm_flash_5l()) == \
        accounting.filed_sizes(PUBLISHED)
    assert accounting.ran_sizes(joyai.joyai_llm_flash()) == \
        accounting.filed_sizes(whole)
    # and a preset that left the module out or bent a width would be refused
    for bent in ({"n_mtp": 0}, {"rope_dim": 128}, {"mtp_weight": 0.1},
                 {"n_dense": 2}):
        assert accounting.ran_sizes(dataclasses.replace(
            joyai.joyai_llm_flash_5l(), **bent)) != \
            accounting.filed_sizes(PUBLISHED), bent


def test_latent_flash_cost_by_hand():
    """One call on the cell's 2 × 8,192 tokens, 32 heads: half of S² pairs,
    QKᵀ / dS·K / dSᵀ·Q at 192 and P·V / dO·Vᵀ / Pᵀ·dO at 128; compute-bound
    by nearly an order. `flops.flash_call_cost` would count every product at
    q's 192: 1.2 / 1.125 / 1.2 × these."""
    pairs = 64 * 8192 * 8192 // 2
    rows = 64 * 8192
    stat = rows * 8 * 4
    shared = 2 * 8192 * 64 * 2
    want = {
        "fwd": (2 * pairs * (192 + 128),
                rows * 2 * (192 + 128 + 128 + 128) + shared + stat),
        "dq": (2 * pairs * (192 + 128 + 192),
               rows * 2 * (192 + 128 + 128 + 128 + 192) + shared + 2 * stat),
        "dkv": (2 * pairs * (192 + 128 + 128 + 192),
                rows * 2 * (192 + 128 + 128 + 128 + 128 + 64 + 128) + shared
                + 2 * stat)}
    peaks = flops.peaks_for("TPU v5 lite")
    for kernel, (needed, moved) in want.items():
        assert accounting.latent_flash_cost(PUBLISHED, kernel, 2, 8192) == \
            (needed, moved), kernel
        least, bound = flops.least_seconds(needed, moved, peaks)
        assert bound == "compute" and least > 5 * moved / 819e9
    generic = {"fwd": 2 * 192, "dq": 3 * 192, "dkv": 4 * 192}
    for kernel, over in (("fwd", 1.2), ("dq", 1.125), ("dkv", 1.2)):
        assert 2 * pairs * generic[kernel] / want[kernel][0] == \
            pytest.approx(over)
    # a call each 6.98 + 11.16 + 13.95 = 32.1 ms at the peak; a step holds
    # six of each (recomputation keeps the forward's results): 192.5 ms
    assert sum(6 * v[0] for v in want.values()) / 197e12 == \
        pytest.approx(192.5e-3, rel=1e-3)


@pytest.fixture(scope="module")
def tiny_params():
    """The tiny preset's parameters for the two cases that start from them;
    one program: leaf by leaf the CPU takes seconds more."""
    import jax

    from ray_tpu.models import joyai
    return jax.jit(lambda key: joyai.init(key, joyai.joyai_tiny()))(
        jax.random.PRNGKey(0))


def test_pick_and_put_name_their_layers(tiny_params):
    import jax

    from ray_tpu.models import joyai
    cfg = joyai.joyai_tiny()
    params = tiny_params
    leaves = accounting.pick(params)
    assert {k: v.shape for k, v in leaves.items()} == {
        "head": (256, 64), "wq_b": (48, 4, 32), "wkv_b": (32, 4, 40),
        "wkv_a": (64, 40), "wo": (4, 16, 64), "wg": (64, 16),
        "w_gate": (2, 64, 32), "w_down": (2, 32, 64),
        "shared_w_gate": (64, 32), "eh_proj": (128, 64)}
    assert tuple(leaves) == LEAVES
    layers = params["layers"]
    np.testing.assert_array_equal(leaves["wq_b"], layers[0]["attn"]["wq_b"])
    # two routed layers: the first is layer 1, the middle one layer 2
    np.testing.assert_array_equal(leaves["wg"], layers[1]["ff"]["wg"])
    np.testing.assert_array_equal(leaves["wkv_a"], layers[2]["attn"]["wkv_a"])
    zeroed = accounting.put(params, jax.tree_util.tree_map(
        lambda a: a * 0, leaves))
    assert not zeroed["head"].any() and zeroed["wte"].any()
    got = zeroed["layers"]
    assert not got[0]["attn"]["wq_b"].any() and got[1]["attn"]["wq_b"].any()
    assert not got[0]["attn"]["wkv_b"].any() and got[0]["attn"]["wq_a"].any()
    assert not got[2]["attn"]["wkv_a"].any() and got[1]["attn"]["wkv_a"].any()
    assert not got[2]["attn"]["wo"].any() and got[0]["attn"]["wo"].any()
    assert not got[1]["ff"]["wg"].any() and got[2]["ff"]["wg"].any()
    assert not got[1]["ff"]["w_gate"][:2].any()
    assert got[1]["ff"]["w_gate"][2:].any() and got[1]["ff"]["w_up"].any()
    assert not got[1]["ff"]["shared"]["w_gate"].any()
    assert got[1]["ff"]["shared"]["w_up"].any()
    assert got[2]["ff"]["shared"]["w_gate"].any()
    assert not zeroed["mtp"]["eh_proj"].any()
    assert zeroed["mtp"]["layer"]["ff"]["wg"].any()
    assert jax.tree_util.tree_structure(zeroed) == \
        jax.tree_util.tree_structure(params)
    assert params["layers"][0]["attn"]["wq_b"].any()    # as it was
    # in the cell's own preset: layer 0 dense, layer 1 the first routed,
    # layer 3 the middle of the four
    cut = jax.eval_shape(lambda: joyai.init(jax.random.PRNGKey(0),
                                            joyai.joyai_llm_flash_5l()))
    assert accounting._places(cut) == (0, 1, 3)


# ------------------------------------------------------------- the reader

_Q = "bf16[64,8192,192]{2,1,0:T(8,128)(2,1)}"
_K = "bf16[64,8192,128]{2,1,0:T(8,128)(2,1)}"
_PE = "bf16[2,8192,64]{2,1,0:T(8,128)(2,1)}"
_ROW = "f32[64,64,8,128]{3,2,1,0:T(8,128)}"
_TARGET = 'custom_call_target="tpu_custom_call"'
OPS = {
    "fwd": f"%flash_latent_fwd.6 = ({_K}, {_ROW}) custom-call({_Q} %q, {_K} "
           f"%k, {_PE} %pe, {_K} %v), {_TARGET}",
    "dq": f"%flash_latent_dq.6 = {_Q} custom-call({_Q} %q, {_K} %k, {_PE} "
          f"%pe, {_K} %v, {_K} %do, {_ROW} %lse, {_ROW} %delta), {_TARGET}",
    "dkv": f"%flash_latent_dkv.6 = ({_K}, bf16[64,8192,64]{{2,1,0}}, {_K}) "
           f"custom-call({_Q} %q, {_K} %k, {_PE} %pe, {_K} %v, {_K} %do, "
           f"{_ROW} %lse, {_ROW} %delta), {_TARGET}",
    "plain": f"%flash_fwd.3 = ({_K}, {_ROW}) custom-call({_K} %q, {_K} %k, "
             f"{_K} %v), {_TARGET}",
    "proj": "%fusion.20 = bf16[2,8192,32,192]{3,2,1,0} fusion(bf16[2,8192,"
            "1536] %c_q, bf16[1536,32,192] %wq_b), kind=kOutput",
}


def _ctx(per_op_s, busy_s=1.0, model=PUBLISHED,
         accounting_module="chipbench.accounting.joyai_flash"):
    return {"trace": {"per_op_s": per_op_s, "busy_s": busy_s, "steps": 3,
                      "per_op_calls": {k: 3 for k in per_op_s}},
            "model": model, "chips": 1,
            "traffic": {"batch": 2, "seq": 8192, "remat": True},
            "accounting": accounting_module,
            "peaks": flops.peaks_for("TPU v5 lite")}


def test_the_latent_reader_counts_the_calls_at_their_own_widths():
    """Three traced steps of one layer's three calls: the least time is 3 ×
    (6.98 + 11.16 + 13.95 ms) = 96.3 ms; the calls took 150 ms, so 64.2 %.
    The equal-width call beside them and the projection are not theirs, and
    the generic reader, which goes by three and six operands, takes none of
    the latent calls for its own."""
    spent = {OPS["fwd"]: 0.03, OPS["dq"]: 0.05, OPS["dkv"]: 0.07,
             OPS["plain"]: 0.004, OPS["proj"]: 0.2}
    least = 3 * sum(accounting.latent_flash_cost(PUBLISHED, k, 2, 8192)[0]
                    for k in ("fwd", "dq", "dkv")) / 197e12
    assert least == pytest.approx(96.27e-3, rel=1e-3)
    assert trace_latent.read(_ctx(spent), "roofline") == pytest.approx(
        100 * least / 0.150, rel=1e-9)
    assert trace_latent.read(_ctx(spent), "roofline") == pytest.approx(
        64.2, abs=0.05)
    assert trace_latent.read(_ctx(spent), "share") == pytest.approx(15.0)
    for name in ("fwd", "dq", "dkv", "proj"):
        assert flops.flash_call_cost(OPS[name]) is None, name
    assert flops.flash_call_cost(OPS["plain"])[0] == "fwd"
    only_latent = {k: v for k, v in spent.items() if k != OPS["plain"]}
    assert trace_flash.read(_ctx(only_latent), "share") is None
    assert trace_flash.read(_ctx(only_latent), "roofline") is None
    # names as a bare `jax.grad` gives them are the same calls
    renamed = {OPS["fwd"].replace("%flash_latent_fwd.6",
                                  "%jvp_flash_latent_fwd_.1"): 0.03}
    assert trace_latent.read(_ctx(renamed), "share") == pytest.approx(3.0)


@pytest.mark.parametrize("case", ["no_trace", "no_cost", "not_in_trace"])
def test_nothing_to_read_is_nothing_reported(case):
    """No trace (a CPU run), an accounting module without
    ``latent_flash_cost`` (another architecture), a trace without the calls
    (the parent's program): None, never a raise and never a zero."""
    spent = {OPS["fwd"]: 0.006, OPS["proj"]: 0.2}
    ctx = _ctx(spent)
    if case == "no_trace":
        ctx = {"trace": None}
    elif case == "no_cost":
        ctx = _ctx(spent, accounting_module="chipbench.accounting.olmoe")
    else:
        ctx = _ctx({OPS["plain"]: 0.004, OPS["proj"]: 0.2})
    for what in ("share", "roofline"):
        assert trace_latent.read(ctx, what) is None


def test_the_scope_metrics_read_the_programs_table(monkeypatch):
    """`latent_proj` inside `attention`, the module's layer under the same
    names inside `mtp`, its head under `mtp` and `loss_tail`: each metric's
    scopes count an instruction once."""
    table = {
        "fusion.1": (("blocks", "attention", "latent_proj"), "forward"),
        "fusion.2": (("blocks", "attention", "latent_proj"), "recompute"),
        "fusion.3": (("mtp", "attention", "latent_proj"), "backward"),
        "fusion.4": (("mtp", "loss_tail"), "forward"),
        "fusion.5": (("blocks", "moe", "shared_expert"), "recompute"),
        "fusion.6": (("mtp", "moe", "router"), "forward"),
        "fusion.9": (("optimizer",), "optimizer"),
    }
    monkeypatch.setattr(trace_scope, "_table", lambda: dict(table))
    spent = {f"%{name} = f32[8]{{0}} fusion()": 0.1 for name in table}
    ctx = _ctx(spent)

    def read(name):
        return trace_scope.read(ctx, **catalog.load_json(
            REAL, "metrics", name)["args"])
    assert read("attn.latent_proj_share") == pytest.approx(30.0)
    assert read("mtp.scoped_share") == pytest.approx(30.0)
    assert read("moe.gated_shared_share") == pytest.approx(20.0)
    assert read("train_step.latent_recompute_share") == pytest.approx(20.0)


# ----------------------------------------------------------- the controls

@pytest.mark.parametrize("control, caught", [("stated", False),
                                             ("e4m3", True)])
def test_a_precision_control_through_the_job(control, caught):
    """The cell's `entry` pointed at the control module: the wrapper alone
    reads as the cell does; with every matmul weight rounded to an 8-bit
    float, the precision below the stated bf16 products, the harness's own
    comparison says not correct, by several leaves and not by the loss's
    fall."""
    from benchmarks import precision_control

    cell = catalog.resolve_cell(MANIFEST, "joyai-tiny", "end_to_end")
    cell["model"] = precision_control.controlled_entry(cell["model"], control)
    assert cell["model"]["entry"] == \
        f"benchmarks.precision_control:{control}__joyai_tiny"
    record = train_fit.run(cell, seed=43, seconds=0.5, trace=False,
                           t_start=time.time(), require_tpu=False)
    verdicts = record["verdicts"]
    assert verdicts["every_loss_finite"] and verdicts["loss_fell"]
    assert verdicts["agrees_with_reference"] is not caught
    assert record["correct"] is not caught
    over = [k for k, v in record["check"]["errors"].items()
            if k != "loss" and v > 8e-2]
    assert (len(over) >= 3) is caught, record["check"]["errors"]


def test_the_control_reaches_every_matmul_leaf(tiny_params):
    import jax

    from benchmarks import precision_control
    from ray_tpu.models import joyai

    control = precision_control.e4m3__joyai_llm_flash_5l()
    assert isinstance(control, joyai.JoyaiConfig)
    assert control.program == "ray_tpu.models.joyai"
    params = tiny_params
    rounded = jax.jit(precision_control._eight_bit)(params)
    changed = {jax.tree_util.keystr(path)
               for (path, a), b in zip(
                   jax.tree_util.tree_leaves_with_path(params),
                   jax.tree_util.tree_leaves(rounded))
               if not np.array_equal(a, b)}
    names = {name.rpartition("['")[2].rstrip("']") for name in changed}
    # no norm, router, bias or embedding table (the head is untied)
    assert names == {"head", "wq_a", "wq_b", "wkv_a", "wkv_b", "wo",
                     "w_gate", "w_up", "w_down", "eh_proj"}
    assert any("['shared']" in name for name in changed)
    assert any("['mtp']['layer']" in name for name in changed)
