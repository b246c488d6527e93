"""The ``qwen3_next`` architecture's benchmark files, without the chip: the
fixture ``qwen3-next-tiny`` (a configuration and a traffic mix in THIS
directory; model, reference and accounting are the program's and the
benchmark's own) through the ``train_fit`` job on the CPU, the accounting's
arithmetic at the published sizes against numbers worked out by hand, the
configuration file against the catalog row, the manifest's entries FOUND BY
NAME (a later cell's entries behind them break nothing here), the new reader
on a hand-made trace, and the precision controls of
``benchmarks/precision_control.py`` through the same job."""
import json
import os
import time

import numpy as np
import pytest

from chipbench import catalog, flops
from chipbench.accounting import qwen3_next as accounting
from chipbench.jobs import train_fit
from chipbench.readers import mfu, trace_delta, trace_flash, trace_scope
from tests.chipbench_tests import tiny_fit

MANIFEST = {
    "paths": ["chipbench", "tests/chipbench_tests"],
    "workloads": [{"name": "qwen3-next-tiny", "config": "qwen3-next-tiny",
                   "traffic": "fit-qwen3-next-tiny", "chips": 1,
                   "why": "three delta layers to one gated attention layer "
                          "and a delta layer, a gated shared expert beside a "
                          "softmax router, at test sizes"}],
    "end_to_end": [
        {"name": "tokens_per_s_per_chip", "unit": "tokens/s/chip"},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [
        {"name": "train_step.compiles_in_window", "unit": "count"},
        {"name": "linattn.scoped_share", "unit": "%"},
        {"name": "linattn.delta_rule_share", "unit": "%"},
        {"name": "kernels.delta_rule_roofline", "unit": "%"},
        {"name": "attn.wide_flash_share", "unit": "%"},
        {"name": "kernels.wide_flash_roofline", "unit": "%"},
        {"name": "moe.softmax_held_share", "unit": "%"},
        {"name": "train_step.linattn_recompute_share", "unit": "%"}],
}
REAL = catalog.load_manifest()
PUBLISHED = catalog.load_json(REAL, "configs", "qwen3-next-80b-a3b-4l")
CELL = "qwen3next4l-b2s8k"
NEW_METRICS = {
    "linattn.scoped_share": ("linattn", "lower"),
    "linattn.delta_rule_share": ("linattn", "lower"),
    "kernels.delta_rule_roofline": ("kernels", "higher"),
    "attn.wide_flash_share": ("attn", "lower"),
    "kernels.wide_flash_roofline": ("kernels", "higher"),
    "moe.softmax_held_share": ("moe", "lower"),
    "train_step.linattn_recompute_share": ("train_step", "lower")}
LEAVES = ("head", "w_in", "w_out", "A_log", "dt_bias", "conv_w", "wq",
          "k_norm", "wg", "w_gate", "w_down", "shared_w_gate", "w_sg")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def fit(once_a_run):
    """ONE traced fit with the metrics of both groups, once a test run: the
    two cases below read a group each of it (`tiny_fit.py`)."""
    return once_a_run("qwen3_next_tiny_fit", lambda: tiny_fit.traced(
        MANIFEST, "qwen3-next-tiny", seed=43))


@pytest.mark.parametrize("trace", [False, True])
def test_qwen3_next_tiny_through_the_trainer(fit, trace):
    cell = catalog.resolve_cell(MANIFEST, "qwen3-next-tiny",
                                "per_layer" if trace else "end_to_end")
    assert cell["accounting"] == "chipbench.accounting.qwen3_next"
    assert cell["reference"] == "chipbench.references.qwen3_next"
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
    # the mfu reader, given a peak. A token uses: the head; four delta
    # layers (w_in 64·192, w_ba 64·8, w_out 64·64) and the rule at 18·16·64
    # FLOPs a token and layer; one attention layer (wq 64·4·64, wk and wv
    # 64·2·32, wo 128·64); in each of the five layers the router over 16,
    # the shared expert of 3·64·32 with its gate's 64 and 3 · 4/16 of
    # another; the scores over 40 · 41 / 2 pairs in one layer at 4 heads of
    # 32
    delta = 64 * 192 + 64 * 8 + 64 * 64
    attention = 64 * 256 + 2 * 64 * 64 + 128 * 64
    used = (256 * 64 + 4 * delta + attention
            + 5 * (64 * 16 + 64 + 1.75 * 3 * 64 * 32))
    per_token = 6 * used + 4 * 18 * 16 * 64 + 12 * 820 * 128 / 40
    assert per_token == 1_160_832.0
    assert accounting.train_flops_per_token(cell["model"], 40) == 1_160_832
    ctx = {"accounting": cell["accounting"], "model": cell["model"],
           "traffic": cell["traffic"], "chips": 1, "clock": record["clock"],
           "counters": {"steps": record["attempted"]},
           "peaks": {"bf16_flops_per_s": 1e12}}
    assert mfu.read(ctx) == pytest.approx(
        100 * record["attempted"] * 2 * 40 / record["clock"]["window_s"]
        * 1_160_832 / 1e12, rel=1e-12)


def test_the_published_configuration_is_the_catalog_rows():
    """Every key of the public ``config.json`` as the model-configs catalog
    holds it, unchanged but for the two counts the cut reduces; the depth
    the cell runs, the published counts, the deployment and what was
    assumed are filed beside them."""
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts_per_tok": 10, "num_hidden_layers": 48,
        "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False}
    for key, value in published.items():
        assert PUBLISHED[key] == value, key
    if os.path.exists(CATALOG):     # the row itself, where the guide is
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert PUBLISHED["source"] == row["source_url"]
        changed = {k for k, v in row["config"].items() if PUBLISHED[k] != v}
        assert changed == {"num_experts", "vocab_size"}
        assert set(published) == set(row["config"]) - changed
    assert PUBLISHED["reduced"] == ["layers", "num_experts", "vocab_size"]
    assert (PUBLISHED["layers"], PUBLISHED["num_experts"],
            PUBLISHED["vocab_size"]) == (4, 32, 18992)
    assert PUBLISHED["published"] == {
        "layers": 48, "num_experts": 512, "vocab_size": 151936}
    deployment = PUBLISHED["deployment"]
    assert deployment["chips_sharing_a_layer"] == 16
    assert deployment["first_expert"] == 0
    assert "16 chips share each layer" in deployment["what"]
    assert "experts 0-31" in deployment["what"]
    assert "stages of a pipeline" in deployment["what"]
    assert deployment["chips_sharing_a_layer"] * PUBLISHED[
        "num_experts"] == 512
    assert deployment["vocabulary_slices"] * PUBLISHED["vocab_size"] == 151936
    for key in ("layers", "w_in_layout", "delta_layer", "attention", "norms",
                "feed_forward", "router", "mtp", "vocabulary", "sequence",
                "weights", "param_dtype", "compute_dtype"):
        assert key in PUBLISHED["assumed"], key
    assert "[q | k | v | z]" in PUBLISHED["assumed"]["w_in_layout"]
    assert "not built" in PUBLISHED["assumed"]["mtp"]
    assert "no auxiliary loss" in PUBLISHED["assumed"]["router"]
    assert "19,072" in PUBLISHED["assumed"]["vocabulary"]
    assert "table normal at 8,192" in PUBLISHED["assumed"]["weights"]
    assert "W_g normal at 0.15" in PUBLISHED["assumed"]["weights"]
    assert "written before the runs" in PUBLISHED["assumed"]["weights"]
    assert "ONE pass" in PUBLISHED["assumed"]["compute_dtype"]
    assert "float32" in PUBLISHED["assumed"]["compute_dtype"]
    assert PUBLISHED["entry"] == \
        "ray_tpu.models.qwen3_next:qwen3_next_80b_a3b_4l"
    assert PUBLISHED["reference"] == "qwen3_next"
    assert accounting.layout(PUBLISHED) == [
        "linear_attention"] * 3 + ["full_attention"]
    assert accounting.rotary_dim(PUBLISHED) == 64
    # the cell is the manifest's, with the traffic ISSUE 48 gives it: the
    # file `lfm2moe5l-b2s8k` and `joyaiflash5l-b2s8k` use
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
    # the accepted metrics that list their cells list none of this one
    assert not reported & {
        "kernels.flash_share", "kernels.flash_roofline", "moe.routed_share",
        "moe.held_routed_share", "ssm.mixer_share", "moe.scoped_share",
        "attn.window_flash_share", "train_step.recompute_share",
        "moe.sigmoid_held_share", "conv.scoped_share",
        "attn.latent_flash_share", "moe.gated_shared_share"}


def test_the_manifests_new_entries():
    """Found BY NAME, wherever a later PR's entries put them in their lists:
    the configuration, the cell and the seven metrics with their files."""
    config = next(c for c in REAL["configs"]
                  if c["name"] == "qwen3-next-80b-a3b-4l")
    assert config["source"] == PUBLISHED["source"] == (
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/"
        "config.json")
    assert config["file"] == "chipbench/configs/qwen3-next-80b-a3b-4l.json"
    assert config["reduced"] == PUBLISHED["reduced"]
    cell = next(w for w in REAL["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3-next-80b-a3b-4l", "fit-b2-s8192-remat", 1)
    for said in ("320 rows", "1/16", "over their share", "45%"):
        assert said in cell["why"], said
    for entry in (config, cell):
        assert len(entry["why"]) <= 200, entry["name"]
    by_name = {m["name"]: m for m in REAL["per_layer"]}
    for name, (layer, better) in NEW_METRICS.items():
        m = by_name[name]
        assert (m["layer"], m["better"], m["unit"], m["moves"], m["source"],
                m["workloads"]) == (layer, better, "%", "mfu", "device_trace",
                                    [CELL]), name
    # behind the accepted cells' entries, which none of them edits: no
    # accepted metric's list of cells names the new one
    accepted = [m for m in REAL["per_layer"] + REAL["end_to_end"]
                if m["name"] not in NEW_METRICS]
    for m in accepted:
        assert CELL not in m.get("workloads", []), m["name"]
    names = [m["name"] for m in REAL["per_layer"]]
    assert min(names.index(n) for n in NEW_METRICS) > names.index(
        "train_step.latent_recompute_share")
    cells = [w["name"] for w in REAL["workloads"]]
    assert cells.index(CELL) > cells.index("joyaiflash5l-b2s8k")
    for name, args in (
            ("linattn.scoped_share", {"scopes": ["gdn"]}),
            ("linattn.delta_rule_share", {"scopes": ["delta_rule"]}),
            ("moe.softmax_held_share", {"scopes": ["moe"]}),
            ("train_step.linattn_recompute_share", {"phase": "recompute"})):
        assert catalog.load_json(REAL, "metrics", name) == {
            "reader": "trace_scope", "args": args}
    for name, what in (("attn.wide_flash_share", "share"),
                       ("kernels.wide_flash_roofline", "roofline")):
        assert catalog.load_json(REAL, "metrics", name) == {
            "reader": "trace_flash", "args": {"what": what}}
    assert catalog.load_json(REAL, "metrics",
                             "kernels.delta_rule_roofline") == {
        "reader": "trace_delta"}


def test_params_and_flops_a_token_by_hand():
    delta = (2048 * 12288 + 2048 * 64 + 4 * 8192 + 64 + 128 + 4096 * 2048)
    attention = 2048 * 16 * 512 + 2 * 2048 * 512 + 4096 * 2048 + 512
    assert (delta, attention) == (33_718_464, 27_263_488)
    expert = 3 * 2048 * 512
    ff = 2048 * 512 + expert + 2048 + 32 * expert
    layer = lambda mixer: mixer + 2 * 2048 + ff      # noqa: E731
    assert (layer(delta), layer(attention)) == (138_582_208, 132_127_232)
    total = 3 * layer(delta) + layer(attention) + 2 * 19072 * 2048 + 2048
    assert total == 625_994_816
    assert accounting.params(PUBLISHED) == total
    assert 16 * total / 1e9 == pytest.approx(10.02, abs=0.005)
    # forward, a token, at S 8,192: the issue's own table
    proj = 2048 * 12288 + 2048 * 64 + 4096 * 2048
    parts = {
        "delta_proj": 3 * 2 * proj,
        "rule": 3 * 6 * 128 * 128 * 32,
        "attn_proj": 2 * (2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048),
        "scores": 4 * 8193 / 2 * 4096,
        "ff": 4 * 2 * (2048 * 512 + expert + 2048 + 10 * 32 / 512 * expert),
        "head": 2 * 19072 * 2048}
    forward = sum(parts.values())
    assert forward == pytest.approx(0.46e9, rel=0.01)
    assert {k: round(100 * v / forward) for k, v in parts.items()} == {
        "delta_proj": 44, "rule": 2, "attn_proj": 12, "scores": 15,
        "ff": 11, "head": 17}
    per_token = accounting.train_flops_per_token(PUBLISHED, 8192)
    assert per_token == round(3 * forward)
    assert per_token == pytest.approx(1.38e9, rel=0.005)
    assert per_token * 16384 / 197e12 == pytest.approx(0.115, rel=0.005)
    # a size the file does not state would be refused by the job
    for key in ("linear_num_value_heads", "head_dim", "num_experts_per_tok",
                "partial_rotary_factor", "full_attention_interval"):
        bent = dict(PUBLISHED, **{key: PUBLISHED[key] * 2})
        assert accounting.filed_sizes(bent) != \
            accounting.filed_sizes(PUBLISHED), key


def test_delta_rule_cost_by_hand():
    """One delta layer's rule on the cell's 2 × 8,192 tokens: the
    recurrence's 6 · 128 · 128 FLOPs a head and token; q and k [T, 2,048], v
    [T, 4,096], g and β [T, 32] read and o [T, 4,096] written in float32.
    Memory-bound by four: 0.99 ms forward against 0.26 ms of products."""
    tokens = 16384
    ops = 6 * tokens * 32 * 128 * 128
    read = tokens * (2048 + 2048 + 4096 + 64) * 4
    wrote = tokens * 4096 * 4
    cost = accounting.delta_rule_cost(PUBLISHED, tokens)
    assert cost == {"forward": (ops, read + wrote),
                    "backward": (2 * ops, 2 * read + wrote)}
    peaks = flops.peaks_for("TPU v5 lite")
    least, bound = flops.least_seconds(*cost["forward"], peaks)
    assert bound == "memory"
    assert least == pytest.approx(0.988e-3, rel=2e-3)
    assert ops / 197e12 == pytest.approx(0.262e-3, rel=2e-3)
    back, bound = flops.least_seconds(*cost["backward"], peaks)
    assert bound == "memory" and back == pytest.approx(1.649e-3, rel=2e-3)


@pytest.fixture(scope="module")
def tiny_params():
    """The tiny preset's parameters for the two cases that start from them;
    one program: leaf by leaf the CPU takes seconds more."""
    import jax

    from ray_tpu.models import qwen3_next
    cfg = qwen3_next.qwen3_next_tiny()
    return jax.jit(lambda key: qwen3_next.init(key, cfg))(
        jax.random.PRNGKey(0))


def test_pick_and_put_name_their_layers(tiny_params):
    import jax

    from ray_tpu.models import qwen3_next
    cfg = qwen3_next.qwen3_next_tiny()
    params = tiny_params
    leaves = accounting.pick(params)
    assert {k: v.shape for k, v in leaves.items()} == {
        "head": (256, 64), "w_in": (64, 192), "w_out": (64, 64),
        "A_log": (4,), "dt_bias": (4,), "conv_w": (4, 128),
        "wq": (64, 4, 64), "k_norm": (32,), "wg": (64, 16),
        "w_gate": (2, 64, 32), "w_down": (2, 32, 64),
        "shared_w_gate": (64, 32), "w_sg": (64, 1)}
    assert tuple(leaves) == LEAVES
    layers = params["layers"]
    np.testing.assert_array_equal(leaves["w_in"], layers[0]["mixer"]["w_in"])
    np.testing.assert_array_equal(leaves["wq"], layers[3]["mixer"]["wq"])
    np.testing.assert_array_equal(leaves["wg"], layers[0]["ff"]["wg"])
    # zeros for the matrices; the norm's weight is drawn at 0, so ones there
    moved = accounting.put(params, jax.tree_util.tree_map(
        lambda a: a * 0 + 7, leaves))
    got = moved["layers"]
    assert (moved["head"] == 7).all() and not (moved["wte"] == 7).any()
    for name in ("w_in", "w_out", "A_log", "dt_bias", "conv_w"):
        assert (got[0]["mixer"][name] == 7).all(), name
        assert not (got[1]["mixer"][name] == 7).any(), name
    assert not (got[0]["mixer"]["w_ba"] == 7).any()
    assert (got[3]["mixer"]["wq"] == 7).all()
    assert (got[3]["mixer"]["k_norm"] == 7).all()
    assert not (got[3]["mixer"]["q_norm"] == 7).any()
    assert not (got[3]["mixer"]["wk"] == 7).any()
    assert (got[0]["ff"]["wg"] == 7).all() and (got[0]["ff"]["w_sg"] == 7).all()
    assert not (got[1]["ff"]["wg"] == 7).any()
    assert (got[0]["ff"]["w_gate"][:2] == 7).all()
    assert not (got[0]["ff"]["w_gate"][2:] == 7).any()
    assert not (got[0]["ff"]["w_up"] == 7).any()
    assert (got[0]["ff"]["shared"]["w_gate"] == 7).all()
    assert not (got[0]["ff"]["shared"]["w_up"] == 7).any()
    assert jax.tree_util.tree_structure(moved) == \
        jax.tree_util.tree_structure(params)
    assert not (params["layers"][0]["mixer"]["w_in"] == 7).any()  # as it was
    # in the cell's own preset: layer 0 the first delta layer, layer 3 the
    # attention layer
    cut = jax.eval_shape(lambda: qwen3_next.init(
        jax.random.PRNGKey(0), qwen3_next.qwen3_next_80b_a3b_4l()))
    assert accounting._places(cut) == (0, 3)


# ------------------------------------------------------------- the readers

_Q = "bf16[32,8192,256]{2,1,0:T(8,128)(2,1)}"
_KV = "bf16[4,8192,256]{2,1,0:T(8,128)(2,1)}"
_ROW = "f32[32,64,8,128]{3,2,1,0:T(8,128)}"
_TARGET = 'custom_call_target="tpu_custom_call"'
OPS = {
    "fwd": f"%flash_fwd.6 = ({_Q}, {_ROW}) custom-call({_Q} %q, {_KV} %k, "
           f"{_KV} %v), {_TARGET}",
    "dq": f"%flash_dq.6 = {_Q} custom-call({_Q} %q, {_KV} %k, {_KV} %v, "
          f"{_Q} %do, {_ROW} %lse, {_ROW} %delta), {_TARGET}",
    "dkv": f"%flash_dkv.6 = ({_KV}, {_KV}) custom-call({_Q} %q, {_KV} %k, "
           f"{_KV} %v, {_Q} %do, {_ROW} %lse, {_ROW} %delta), {_TARGET}",
    "conv": "%mamba_conv_fwd.9 = f32[2,8192,8192]{2,1,0} custom-call("
            f"f32[2,8192,12288] %src, f32[4,8192] %w, f32[1,8192] %b, "
            f"f32[2,8192,12288] %halo), {_TARGET}",
}
TABLE = {
    "fusion.1": (("blocks", "gdn", "delta_proj"), "forward"),
    "fusion.2": (("blocks", "gdn", "delta_rule"), "forward"),
    "while.3": (("blocks", "gdn", "delta_rule"), "recompute"),
    "fusion.4": (("blocks", "gdn", "delta_rule"), "backward"),
    "fusion.5": (("blocks", "gdn", "delta_gate_norm"), "backward"),
    "mamba_conv_fwd.9": (("blocks", "gdn", "delta_conv"), "recompute"),
    "fusion.6": (("blocks", "attn", "attn_gate"), "forward"),
    "fusion.7": (("blocks", "moe", "router"), "recompute"),
    "fusion.8": (("blocks", "moe", "shared_expert"), "backward"),
    "fusion.9": (("optimizer",), "optimizer"),
}


def _ctx(per_op_s, busy_s=1.0, model=PUBLISHED,
         accounting_module="chipbench.accounting.qwen3_next"):
    return {"trace": {"per_op_s": per_op_s, "busy_s": busy_s, "steps": 3,
                      "per_op_calls": {k: 3 for k in per_op_s}},
            "model": model, "chips": 1,
            "traffic": {"batch": 2, "seq": 8192, "remat": True},
            "accounting": accounting_module,
            "peaks": flops.peaks_for("TPU v5 lite")}


def _spent(table, seconds=0.1):
    return {f"%{name} = f32[8]{{0}} fusion()": seconds for name in table}


def test_the_delta_reader_counts_the_rule_under_its_scope(monkeypatch):
    """Three traced steps of three delta layers, each a forward, a
    recomputed forward and a backward: the least time is 3 · 3 · (2 · 0.988 +
    1.649) ms = 32.6 ms; the three instructions under `delta_rule` took 300
    ms, so 10.9 %. The rule's neighbours under `gdn` are not counted."""
    monkeypatch.setattr(trace_scope, "_table", lambda: dict(TABLE))
    ctx = _ctx(_spent(TABLE))
    cost = accounting.delta_rule_cost(PUBLISHED, 16384)
    least = 9 * (2 * cost["forward"][1] + cost["backward"][1]) / 819e9
    assert least == pytest.approx(32.63e-3, rel=2e-3)
    assert trace_delta.read(ctx) == pytest.approx(100 * least / 0.3,
                                                  rel=1e-9)
    # a faster implementation reads higher; past 105 % the work is miscounted
    fast = _ctx(_spent(TABLE, 0.0105))
    assert trace_delta.read(fast) == pytest.approx(103.6, abs=0.1)
    with pytest.raises(ValueError, match="of its roofline"):
        trace_delta.read(_ctx(_spent(TABLE, 0.009)))


@pytest.mark.parametrize("case", ["no_trace", "no_cost", "no_table",
                                  "not_in_table", "not_in_trace"])
def test_nothing_to_read_is_nothing_reported(case, monkeypatch):
    """No trace (a CPU run), an accounting module without
    ``delta_rule_cost`` (another architecture), a program without a scope
    table or without the scope (the parent's program), a trace without its
    instructions: None, never a raise and never a zero."""
    table = dict(TABLE)
    if case == "no_table":
        table = None
    elif case == "not_in_table":
        table = {k: v for k, v in TABLE.items() if "delta_rule" not in v[0]}
    monkeypatch.setattr(trace_scope, "_table", lambda: table)
    ctx = _ctx(_spent(TABLE))
    if case == "no_trace":
        ctx = {"trace": None}
    elif case == "no_cost":
        ctx = _ctx(_spent(TABLE),
                   accounting_module="chipbench.accounting.olmoe")
    elif case == "not_in_trace":
        ctx = _ctx(_spent({"fusion.1": 0, "fusion.9": 0}))
    assert trace_delta.read(ctx) is None


def test_the_scope_metrics_read_the_programs_table(monkeypatch):
    """`delta_rule` inside `gdn`, the gate under `attn`, router and shared
    expert inside `moe`: each metric's scopes count an instruction once."""
    monkeypatch.setattr(trace_scope, "_table", lambda: dict(TABLE))
    ctx = _ctx(_spent(TABLE))

    def read(name):
        return trace_scope.read(ctx, **catalog.load_json(
            REAL, "metrics", name)["args"])
    assert read("linattn.scoped_share") == pytest.approx(60.0)
    assert read("linattn.delta_rule_share") == pytest.approx(30.0)
    assert read("moe.softmax_held_share") == pytest.approx(20.0)
    assert read("train_step.linattn_recompute_share") == pytest.approx(30.0)
    assert read("attn.scoped_share") == pytest.approx(10.0)


def test_the_flash_reader_counts_the_wide_calls_exactly():
    """Head size 256, 16 query heads on 2 KV heads, 2 × 8,192 tokens: three
    and six operands of equal WIDTH, which `flops.flash_call_cost` counts at
    q's rows: 2 / 3 / 4 products of 32 · 8,192² / 2 · 256 pairs. The conv
    stage's call has four operands and is not taken for one."""
    pairs = 32 * 8192 * 8192 // 2
    kinds = {"fwd": ("fwd", 2), "dq": ("bwd_dq", 3), "dkv": ("bwd_dkv", 4)}
    for name, (kind, products) in kinds.items():
        got = flops.flash_call_cost(OPS[name])
        assert got[:2] == (kind, products * 2 * pairs * 256), name
    q, kv, row = 32 * 8192 * 256 * 2, 4 * 8192 * 256 * 2, 32 * 64 * 8 * 128 * 4
    assert flops.flash_call_cost(OPS["fwd"])[2] == 2 * q + 2 * kv + row
    assert flops.flash_call_cost(OPS["dq"])[2] == 3 * q + 2 * kv + 2 * row
    assert flops.flash_call_cost(OPS["dkv"])[2] == 2 * q + 4 * kv + 2 * row
    assert flops.flash_call_cost(OPS["conv"]) is None
    spent = {OPS["fwd"]: 0.03, OPS["dq"]: 0.03, OPS["dkv"]: 0.04,
             OPS["conv"]: 0.2}
    least = 3 * (2 + 3 + 4) * 2 * pairs * 256 / 197e12
    assert trace_flash.read(_ctx(spent), "share") == pytest.approx(10.0)
    assert trace_flash.read(_ctx(spent), "roofline") == pytest.approx(
        100 * least / 0.1, rel=1e-9)


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

    cell = catalog.resolve_cell(MANIFEST, "qwen3-next-tiny", "end_to_end")
    cell["model"] = precision_control.controlled_entry(cell["model"], control)
    assert cell["model"]["entry"] == \
        f"benchmarks.precision_control:{control}__qwen3_next_tiny"
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
    from ray_tpu.models import qwen3_next

    control = precision_control.e4m3__qwen3_next_80b_a3b_4l()
    assert isinstance(control, qwen3_next.Qwen3NextConfig)
    assert control.program == "ray_tpu.models.qwen3_next"
    params = tiny_params
    rounded = jax.jit(precision_control._eight_bit)(params)
    changed = {jax.tree_util.keystr(path)
               for (path, a), b in zip(
                   jax.tree_util.tree_leaves_with_path(params),
                   jax.tree_util.tree_leaves(rounded))
               if not np.array_equal(a, b)}
    names = {name.rpartition("['")[2].rstrip("']") for name in changed}
    # no norm, router, scalar gate, taps, decay leaf or embedding table
    assert names == {"head", "w_in", "w_ba", "w_out", "wq", "wk", "wv", "wo",
                     "w_gate", "w_up", "w_down"}
    assert any("['shared']" in name for name in changed)
    # and the bf16-stream control wraps the layer's first half
    assert precision_control._PROGRAMS["ray_tpu.models.qwen3_next"][1] == \
        "_mixer_apply"
    assert callable(getattr(qwen3_next, "_mixer_apply"))
