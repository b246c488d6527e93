"""The ``phi4_flash`` architecture's benchmark files, without the chip: the
fixture ``phi4flash-tiny`` (a configuration and a traffic mix in THIS
directory; model, reference and accounting are the program's and the
benchmark's own) through the ``train_fit`` job on the CPU, the accounting's
arithmetic at the published sizes against numbers worked out by hand, the
configuration file against the catalog row, the manifest's entries FOUND BY
NAME (a later cell's entries behind them break nothing here), and the two
new readers on a hand-made trace and scope table — the scoped operator's
roofline on a second operator too, which its metric file alone names."""
import json
import os
import time

import pytest

from chipbench import catalog, flops
from chipbench.accounting import phi4_flash as accounting
from chipbench.jobs import train_fit
from chipbench.readers import (
    mfu,
    trace_delta,
    trace_diff_flash,
    trace_scope,
    trace_scope_roofline,
)

MANIFEST = {
    "paths": ["chipbench", "tests/chipbench_tests"],
    "workloads": [{"name": "phi4flash-tiny", "config": "phi4flash-tiny",
                   "traffic": "fit-phi4flash-tiny", "chips": 1,
                   "why": "every kind of SambaY layer, two readers of each "
                          "shared result, at test sizes"}],
    "end_to_end": [
        {"name": "tokens_per_s_per_chip", "unit": "tokens/s/chip"},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [{"name": "train_step.compiles_in_window", "unit": "count"}],
}
REAL = catalog.load_manifest()
PUBLISHED = catalog.load_json(REAL, "configs", "phi-4-mini-flash-6l")
CELL = "phi4flash6l-b1s8k"
SCAN = {"scope": "selective_scan", "cost": "selective_scan_cost",
        "kind": "mamba"}
# name -> (layer, better, the metric file)
SCOPED = {
    "ssm.s6_mixer_share": ("ssm", {"scopes": ["mamba1"]}),
    "ssm.selective_scan_share": ("ssm", {"scopes": ["selective_scan"]}),
    "attn.cross_decoder_share": ("attn", {"scopes": ["cross_attn", "gmu"]}),
    "mlp.dense_gated_share": ("mlp", {"scopes": ["mlp"]}),
    "train_step.hybrid_recompute_share": ("train_step",
                                          {"phase": "recompute"})}
NEW_METRICS = {
    **{name: (layer, "lower", {"reader": "trace_scope", "args": args})
       for name, (layer, args) in SCOPED.items()},
    "kernels.selective_scan_roofline": (
        "kernels", "higher", {"reader": "trace_scope_roofline",
                              "args": SCAN}),
    "attn.diff_flash_share": (
        "attn", "lower", {"reader": "trace_diff_flash",
                          "args": {"what": "share"}}),
    "kernels.diff_flash_roofline": (
        "kernels", "higher", {"reader": "trace_diff_flash",
                              "args": {"what": "roofline"}})}
LEAVES = ("mamba_w_in", "mamba_A_log", "mamba_dt_bias", "mamba_w_dt",
          "mamba_conv_w", "memory_w_x", "memory_D", "window_w_qkv",
          "window_subln", "full_w_qkv", "full_w_o", "gmu_w_in", "cross_w_q",
          "ff_w_gate", "wte")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_phi4flash_tiny_through_the_trainer():
    cell = catalog.resolve_cell(MANIFEST, "phi4flash-tiny", "end_to_end")
    assert cell["accounting"] == "chipbench.accounting.phi4_flash"
    assert cell["reference"] == "chipbench.references.phi4_flash"
    record = train_fit.run(cell, seed=3_000_000_043, seconds=1.0,
                           trace=False, t_start=time.time(),
                           require_tpu=False)
    json.dumps(record)
    assert record["correct"], (record["verdicts"], record["check"])
    assert set(record["check"]["errors"]) == {"loss"} | {
        "grad_" + k for k in LEAVES}
    assert record["failed"] == 0 and record["attempted"] >= 4
    values = {k: v["value"] for k, v in record["metrics"].items()}
    assert values["tokens_per_s_per_chip"] == pytest.approx(
        record["attempted"] * 2 * 40 / record["clock"]["window_s"])
    # the mfu reader, given a peak. A token uses: the tied table as the
    # head; eight feed-forwards of 3·64·128; two Mamba mixers (w_in 64·256,
    # w_x 128·12, w_dt 4·128, w_out 128·64) and the recurrence at 18·128·4
    # a layer; two attention layers (w_qkv 64·128, w_o 64·64), two cross
    # layers (w_q and w_o 64·64), two gated memory units (2·64·128); the
    # scores of three full-causal layers over 40·41/2 pairs and of the
    # window layer over 8·9/2 + 32·8, at 8 query heads of 8 + 16 columns
    used = (256 * 64 + 8 * 3 * 64 * 128
            + 2 * (64 * 256 + 128 * 12 + 4 * 128 + 128 * 64)
            + 2 * (64 * 128 + 64 * 64) + 2 * 2 * 64 * 64 + 2 * 2 * 64 * 128)
    pairs = 3 * 820 + (36 + 32 * 8)
    per_token = 6 * used + 2 * 18 * 128 * 4 + 6 * 24 * 8 * pairs / 40
    assert accounting.train_flops_per_token(cell["model"], 40) == \
        round(per_token)
    ctx = {"accounting": cell["accounting"], "model": cell["model"],
           "traffic": cell["traffic"], "chips": 1, "clock": record["clock"],
           "counters": {"steps": record["attempted"]},
           "peaks": {"bf16_flops_per_s": 1e12}}
    assert mfu.read(ctx) == pytest.approx(
        100 * record["attempted"] * 2 * 40 / record["clock"]["window_s"]
        * round(per_token) / 1e12, rel=1e-12)


def test_the_published_configuration_is_the_catalog_rows():
    """Every key of the public ``config.json`` as the model-configs catalog
    holds it, unchanged but for the vocabulary the cut slices; the depth
    the cell runs, the published counts, the deployment and what was
    assumed are filed beside them."""
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20,
        "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False}
    for key, value in published.items():
        assert PUBLISHED[key] == value, key
    if os.path.exists(CATALOG):     # the row itself, where the guide is
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Phi-4-mini-flash-reasoning")
        assert PUBLISHED["source"] == row["source_url"]
        changed = {k for k, v in row["config"].items() if PUBLISHED[k] != v}
        assert changed == {"vocab_size"}
        assert set(published) == set(row["config"]) - changed
    assert PUBLISHED["reduced"] == ["layers", "vocab_size"]
    assert (PUBLISHED["layers"], PUBLISHED["first_layer"],
            PUBLISHED["vocab_size"]) == (6, 14, 25008)
    assert PUBLISHED["published"] == {"layers": 32, "vocab_size": 200064}
    deployment = PUBLISHED["deployment"]
    assert deployment["pipeline_stage_layers"] == [14, 19]
    assert deployment["vocabulary_slices"] * PUBLISHED["vocab_size"] == 200064
    for said in ("published 14-19", "9 : 8 : 1 : 7 : 7",
                 "neither 4 nor 8", "further stages"):
        assert said in deployment["what"], said
    for said in ("697,299,072", "3,852,562,944", "11.16 GB"):
        assert said in deployment["decided_by"], said
    assumed = PUBLISHED["assumed"]
    assert {k: assumed[k] for k in ("mamba_d_state", "mamba_d_conv",
                                    "mamba_expand", "mamba_dt_rank")} == {
        "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
        "mamba_dt_rank": 160}
    for key in ("state_space", "layers", "norms", "biases", "positions",
                "pairing", "feed_forward", "vocabulary", "sequence",
                "weights", "param_dtype", "compute_dtype"):
        assert key in assumed, key
    assert "PUBLISHED index" in assumed["pairing"]
    assert "25,088" in assumed["vocabulary"]
    assert "ONE pass" in assumed["compute_dtype"]
    assert PUBLISHED["entry"] == \
        "ray_tpu.models.phi4_flash:phi_4_mini_flash_6l"
    assert PUBLISHED["reference"] == "phi4_flash"
    assert accounting.layout(PUBLISHED) == [
        "mamba", "window_attention", "mamba", "full_attention", "gmu",
        "cross_attention"]
    # what the file states is what the preset runs
    from ray_tpu.models import phi4_flash
    assert accounting.filed_sizes(PUBLISHED) == accounting.ran_sizes(
        phi4_flash.phi_4_mini_flash_6l())
    # a size the file does not state would be refused by the job
    for key in ("sliding_window", "num_key_value_heads",
                "intermediate_size", "first_layer"):
        bent = dict(PUBLISHED, **{key: PUBLISHED[key] // 2})
        assert accounting.filed_sizes(bent) != \
            accounting.filed_sizes(PUBLISHED), key
    bent = dict(PUBLISHED, assumed=dict(assumed, mamba_d_state=8))
    assert accounting.filed_sizes(bent) != accounting.filed_sizes(PUBLISHED)


def test_the_manifests_new_entries():
    """Found BY NAME, wherever a later PR's entries put them in their lists:
    the configuration, the cell and the eight metrics with their files."""
    config = next(c for c in REAL["configs"]
                  if c["name"] == "phi-4-mini-flash-6l")
    assert config["source"] == PUBLISHED["source"] == (
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/"
        "main/config.json")
    assert config["file"] == "chipbench/configs/phi-4-mini-flash-6l.json"
    assert config["reduced"] == PUBLISHED["reduced"]
    cells = {w["name"]: w for w in REAL["workloads"]}
    assert (cells[CELL]["config"], cells[CELL]["traffic"],
            cells[CELL]["chips"]) == ("phi-4-mini-flash-6l",
                                      "fit-b1-s8192-remat", 1)
    for entry in (config, cells[CELL]):
        assert len(entry["why"]) <= 200, entry["name"]
    # a quarter of the cells, rounded down, may take four chips
    assert sum(w["chips"] == 4 for w in REAL["workloads"]) <= max(
        1, len(REAL["workloads"]) // 4)
    # no accepted metric's list of cells names the new cell
    for m in REAL["per_layer"] + REAL["end_to_end"]:
        if m["name"] not in NEW_METRICS:
            assert CELL not in m.get("workloads", []), m["name"]
    reported = {m["name"] for m in catalog.resolve_cell(
        REAL, CELL, "per_layer")["metrics"]}
    assert set(NEW_METRICS) | {
        "attn.scoped_share", "train_step.loss_tail_share",
        "train_step.optimizer_share", "train_step.backward_share",
        "train_step.unscoped_share", "train_step.hbm_plan_gb",
        "device.idle_share"} <= reported
    assert not reported & {
        "kernels.flash_share", "kernels.flash_roofline", "ssm.mixer_share",
        "ssm.scoped_share", "attn.window_flash_share",
        "train_step.recompute_share", "mlp.gated_scoped_share"}


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_each_new_metric_is_filed_as_named(name):
    layer, better, spec = NEW_METRICS[name]
    m = next(m for m in REAL["per_layer"] if m["name"] == name)
    assert (m["layer"], m["better"], m["unit"], m["moves"], m["source"],
            m["workloads"]) == (layer, better, "%", "mfu", "device_trace",
                                [CELL])
    assert catalog.load_json(REAL, "metrics", name) == spec


def test_params_and_flops_a_token_by_hand():
    """ISSUE 51's table, recounted."""
    ff = 2560 * 20480 + 10240 * 2560
    mamba = (2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 160 * 5120 + 5120
             + 5120 * 16 + 5120 + 5120 * 2560)
    attention = 2560 * 5120 + 5120 + 2560 * 2560 + 2560 + 4 * 64 + 128
    gmu = 2560 * 5120 + 5120 * 2560
    cross = 2 * (2560 * 2560 + 2560) + 4 * 64 + 128
    assert (ff, mamba, attention, gmu, cross) == (
        78_643_200, 41_241_600, 19_668_864, 26_214_400, 13_112_704)
    six = 2 * mamba + 2 * attention + gmu + cross + 6 * (ff + 4 * 2560)
    assert six == 633_068_672
    total = six + 25088 * 2560 + 2 * 2560
    assert total == 697_299_072 == accounting.params(PUBLISHED)
    assert 16 * total / 1e9 == pytest.approx(11.16, abs=0.005)
    whole = dict(PUBLISHED, layers=32, first_layer=0, vocab_size=200064)
    kinds = accounting.layout(whole)
    assert [kinds.count(k) for k in ("mamba", "window_attention",
                                     "full_attention", "gmu",
                                     "cross_attention")] == [9, 8, 1, 7, 7]
    assert accounting.params(whole) == 3_852_562_944 == (
        9 * mamba + 9 * attention + 7 * gmu + 7 * cross
        + 32 * (ff + 4 * 2560) + 200064 * 2560 + 2 * 2560)
    # forward, a token, at S 8,192: the issue's own shares
    window = (512 * 513 // 2 + (8192 - 512) * 512)
    assert accounting.kept_pairs(8192, 512) == window == 4_063_488
    assert window / accounting.kept_pairs(8192) == pytest.approx(0.121,
                                                                 abs=5e-4)
    pair = 2 * (64 + 128) * 40      # QKᵀ at 64 and P·V at 128, 40 heads
    parts = {
        "ff": 6 * 2 * ff,
        "mamba_proj": 2 * 2 * (2560 * 10240 + 5120 * 192 + 160 * 5120
                               + 5120 * 2560),
        "scores": pair * (2 * accounting.kept_pairs(8192) + window) / 8192,
        "head": 2 * 25088 * 2560,
        "attn_proj": 2 * (2 * (2560 * 5120 + 2560 * 2560)
                          + 2 * 2560 * 2560),
        "gmu": 2 * gmu, "scan": 2 * 6 * 5120 * 16}
    forward = sum(parts.values())
    assert forward == pytest.approx(1.528e9, rel=5e-4)
    assert {k: round(100 * v / forward, 1) for k, v in parts.items()} == {
        "ff": 61.7, "mamba_proj": 10.8, "scores": 8.7, "head": 8.4,
        "attn_proj": 6.9, "gmu": 3.4, "scan": 0.1}
    assert accounting.train_flops_per_token(PUBLISHED, 8192) == \
        round(3 * forward)


def test_the_scans_cost_by_hand():
    """One Mamba layer's scan on 8,192 tokens: 6·5,120·16 FLOPs a token; s
    and Δ [T, 5,120], B and C [T, 16] read, y written, float32: memory-bound
    by three hundred."""
    cost = accounting.selective_scan_cost(PUBLISHED, 8192)
    ops = 6 * 8192 * 5120 * 16
    read, wrote = 8192 * (2 * 5120 + 32) * 4, 8192 * 5120 * 4
    assert cost == {"forward": (ops, read + wrote),
                    "backward": (2 * ops, 2 * read + wrote)}
    peaks = flops.peaks_for("TPU v5 lite")
    least, bound = flops.least_seconds(*cost["forward"], peaks)
    assert bound == "memory" and least == pytest.approx(0.616e-3, rel=2e-3)
    back, _ = flops.least_seconds(*cost["backward"], peaks)
    assert back == pytest.approx(1.027e-3, rel=2e-3)


_Q, _O = 20 * 8192 * 64 * 2, 20 * 8192 * 128 * 2
_K, _V, _ROWS = 10 * 8192 * 64 * 2, 10 * 8192 * 128 * 2, 20 * 8192 * 4


@pytest.mark.parametrize("windowed, kept", [(True, 4_063_488),
                                            (False, 8192 * 8193 // 2)])
@pytest.mark.parametrize("kernel, products, moved", [
    ("fwd", 64 + 128, _Q + _K + _V + _O + _ROWS),
    ("dq", 2 * 64 + 128, 2 * _Q + _K + _V + _O + 2 * _ROWS),
    ("dkv", 2 * 64 + 2 * 128, _Q + 2 * _K + 2 * _V + _O + 2 * _ROWS)])
def test_a_differential_calls_cost_by_hand(kernel, products, moved, windowed,
                                           kept):
    """One differential call of a layer: 20 query heads on 10 KV pairs,
    `QKᵀ` at 64 and `P·V` at 128, over the call's kept pairs; the full
    forward is compute-bound."""
    assert accounting.diff_flash_cost(
        PUBLISHED, kernel, windowed, 1, 8192) == (
            2 * 20 * kept * products, moved)
    if (kernel, windowed) == ("fwd", False):
        assert flops.least_seconds(*accounting.diff_flash_cost(
            PUBLISHED, kernel, windowed, 1, 8192),
            flops.peaks_for("TPU v5 lite"))[1] == "compute"


def test_pick_and_put_name_their_layers():
    import jax

    from ray_tpu.models import phi4_flash
    import jax.numpy as jnp
    cfg = phi4_flash.phi4_flash_tiny()
    # places and names come from the tree: zeros in init's shapes will do
    params = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, a.dtype), jax.eval_shape(
            lambda: phi4_flash.init(jax.random.PRNGKey(0), cfg)))
    assert accounting._places(params) == {
        "mamba": 0, "memory": 2, "window": 1, "full": 3, "cross": 5,
        "gmu": 4}
    leaves = accounting.pick(params)
    assert tuple(leaves) == LEAVES
    moved = accounting.put(params, jax.tree_util.tree_map(
        lambda a: a * 0 + 7, leaves))
    got = moved["layers"]
    assert (moved["wte"] == 7).all()
    for place, part, leaf in accounting._PICKED.values():
        at = accounting._places(params)[place]
        assert (got[at][part][leaf] == 7).all(), (place, leaf)
    assert not (got[0]["mixer"]["w_x"] == 7).any()
    assert not (got[2]["mixer"]["w_in"] == 7).any()
    assert not (got[3]["mixer"]["subln"] == 7).any()
    assert not (got[7]["mixer"]["w_q"] == 7).any()
    assert not (got[0]["ff"]["w_gate"] == 7).any()
    assert jax.tree_util.tree_structure(moved) == \
        jax.tree_util.tree_structure(params)
    assert not (params["layers"][0]["mixer"]["w_in"] == 7).any()
    cut = jax.eval_shape(lambda: phi4_flash.init(
        jax.random.PRNGKey(0), phi4_flash.phi_4_mini_flash_6l()))
    assert accounting._places(cut) == {
        "mamba": 0, "memory": 2, "window": 1, "full": 3, "cross": 5,
        "gmu": 4}


# ------------------------------------------------------------- the readers

_TARGET = 'custom_call_target="tpu_custom_call"'
TABLE = {
    "fusion.1": (("blocks", "mamba1"), "forward"),
    "while.2": (("blocks", "mamba1", "selective_scan"), "forward"),
    "while.3": (("blocks", "mamba1", "selective_scan"), "recompute"),
    "while.4": (("blocks", "mamba1", "selective_scan"), "backward"),
    "mamba_conv_fwd.5": (("blocks", "mamba1", "conv"), "recompute"),
    "flash_window_fwd.6": (("blocks", "attn", "diff_flash"), "forward"),
    "flash_dq.7": (("blocks", "attn", "cross_attn", "diff_flash"),
                   "backward"),
    "flash_dkv.8": (("blocks", "attn", "diff_flash"), "backward"),
    # a flash call of another layer kind, outside the scope: not counted
    "flash_fwd.9": (("blocks", "attn"), "forward"),
    "fusion.10": (("blocks", "attn", "diff_combine"), "backward"),
    "fusion.11": (("blocks", "gmu"), "forward"),
    "fusion.12": (("blocks", "mlp"), "recompute"),
    "fusion.13": (("optimizer",), "optimizer"),
}


def _spent(table, seconds=0.05):
    return {f"%{name} = f32[8]{{0}} custom-call(), {_TARGET}": seconds
            for name in table}


def _ctx(per_op_s, busy_s=1.0, model=PUBLISHED,
         accounting_module="chipbench.accounting.phi4_flash"):
    return {"trace": {"per_op_s": per_op_s, "busy_s": busy_s, "steps": 3,
                      "per_op_calls": {k: 3 for k in per_op_s}},
            "model": model, "chips": 1,
            "traffic": {"batch": 1, "seq": 8192, "remat": True},
            "accounting": accounting_module,
            "peaks": flops.peaks_for("TPU v5 lite")}


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 5])
def test_the_conditioned_draw_holds_every_lambda_away_from_one(seed):
    """`accounting.conditioned`, which the comparison's freshly made
    parameters go through: a lambda pair whose product is over
    ``LAMBDA_DOT_MOST`` comes down to it, both vectors by one factor and in
    their directions; a pair within it, and every other leaf, is the draw's
    own array. So lambda stays under ``lambda_init + 0.1`` on every seed,
    where the draw alone puts it within 0.02 of 1 on some."""
    import jax
    import numpy as np

    from ray_tpu.models import phi4_flash
    cfg = phi4_flash.phi4_flash_tiny()
    drawn = phi4_flash.init(jax.random.PRNGKey(seed), cfg)
    # the full-size draw's products are normal at 0.08 (64 columns at 0.1);
    # the tiny model's eight columns give 0.03: one layer's first pair made
    # as large as the draw that failed the driver's seed, its second left
    layers = list(drawn["layers"])
    at = accounting._places(drawn)["window"]
    mixer = dict(layers[at]["mixer"])
    mixer["lambda_q1"] = mixer["lambda_q1"] * 0 + 0.3
    mixer["lambda_k1"] = mixer["lambda_k1"] * 0 - 0.2
    layers[at] = dict(layers[at], mixer=mixer)
    drawn = dict(drawn, layers=layers)
    most = accounting.LAMBDA_DOT_MOST

    def products(m):
        return [float((m[q] * m[k]).sum()) for q, k in (
            ("lambda_q1", "lambda_k1"), ("lambda_q2", "lambda_k2"))]

    assert products(mixer)[0] == pytest.approx(-0.06 * cfg.head_dim)
    got = accounting.conditioned(drawn)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(drawn)
    seen = 0
    for before, after in zip(drawn["layers"], got["layers"]):
        assert after["ff"] is before["ff"]
        b, a = before["mixer"], after["mixer"]
        if "lambda_q1" not in b:
            assert a is b
            continue
        seen += 1
        for (q, k), was, now in zip(
                (("lambda_q1", "lambda_k1"), ("lambda_q2", "lambda_k2")),
                products(b), products(a)):
            if abs(was) <= most:
                assert a[q] is b[q] and a[k] is b[k]
                continue
            assert now == pytest.approx(most if was > 0 else -most, rel=1e-5)
            scale = (most / abs(was)) ** 0.5
            np.testing.assert_allclose(a[q], b[q] * scale, rtol=1e-6)
            np.testing.assert_allclose(a[k], b[k] * scale, rtol=1e-6)
        assert all(a[name] is b[name] for name in b
                   if not name.startswith("lambda"))
        assert max(abs(x) for x in products(a)) <= most * (1 + 1e-5)
    assert seen == 4 and got["wte"] is drawn["wte"]
    assert abs(products(got["layers"][at]["mixer"])[0]) == \
        pytest.approx(most, rel=1e-5)
    # lambda at the published depths: at most lambda_init + e^m - e^-m
    import math
    assert 0.8 + math.exp(most) - math.exp(-most) < 0.91


def test_the_scoped_roofline_counts_the_scan_under_its_scope(monkeypatch):
    """Three traced steps of two Mamba layers, each a forward, a recomputed
    forward and a backward: the least time is 3 · 2 · (2 · 0.616 + 1.027) ms
    = 13.55 ms; the three instructions under `selective_scan` took 150 ms,
    so 9.0 %. The mixer's other instructions are not counted; past 105 the
    work is miscounted."""
    monkeypatch.setattr(trace_scope, "_table", lambda: dict(TABLE))
    cost = accounting.selective_scan_cost(PUBLISHED, 8192)
    least = 6 * (2 * cost["forward"][1] + cost["backward"][1]) / 819e9
    assert least == pytest.approx(13.55e-3, rel=2e-3)
    assert trace_scope_roofline.read(
        _ctx(_spent(TABLE)), **SCAN) == pytest.approx(100 * least / 0.15,
                                                      rel=1e-9)
    assert trace_scope_roofline.read(
        _ctx(_spent(TABLE, 0.0044)), **SCAN) == pytest.approx(102.6, abs=0.1)
    with pytest.raises(ValueError, match="of its roofline"):
        trace_scope_roofline.read(_ctx(_spent(TABLE, 0.004)), **SCAN)


def test_the_scoped_roofline_is_one_reader_for_any_such_operator(monkeypatch):
    """Another architecture's operator through the same reader, named by
    arguments alone: the gated delta rule of `qwen3next4l-b2s8k` reads what
    its accepted reader `trace_delta` reads, to the last digit."""
    table = {"while.1": (("blocks", "linear_attn", "delta_rule"), "forward"),
             "while.2": (("blocks", "linear_attn", "delta_rule"), "backward"),
             "fusion.3": (("blocks", "mlp"), "forward")}
    monkeypatch.setattr(trace_scope, "_table", lambda: dict(table))
    ctx = _ctx(_spent(table),
               model=catalog.load_json(REAL, "configs",
                                       "qwen3-next-80b-a3b-4l"),
               accounting_module="chipbench.accounting.qwen3_next")
    want = trace_delta.read(ctx)
    assert want is not None and 0 < want < 105
    assert trace_scope_roofline.read(
        ctx, scope="delta_rule", cost="delta_rule_cost",
        kind="linear_attention") == want


def test_the_flash_reader_counts_each_call_at_its_kept_area(monkeypatch):
    """The windowed forward at the window's pairs and the full-causal dq
    and dk/dv at the causal half, each at 64 / 128: three calls of each in
    0.15 s; the call outside `diff_flash` is another layer's."""
    monkeypatch.setattr(trace_scope, "_table", lambda: dict(TABLE))
    ctx = _ctx(_spent(TABLE))
    peaks = ctx["peaks"]
    least = 3 * sum(
        flops.least_seconds(*accounting.diff_flash_cost(
            PUBLISHED, kernel, windowed, 1, 8192), peaks)[0]
        for kernel, windowed in (("fwd", True), ("dq", False),
                                 ("dkv", False)))
    assert trace_diff_flash.read(ctx, "share") == pytest.approx(15.0)
    assert trace_diff_flash.read(ctx, "roofline") == pytest.approx(
        100 * least / 0.15, rel=1e-9)
    # the generic count would take the windowed call at the causal half
    causal = flops.least_seconds(*accounting.diff_flash_cost(
        PUBLISHED, "fwd", False, 1, 8192), peaks)[0]
    windowed = flops.least_seconds(*accounting.diff_flash_cost(
        PUBLISHED, "fwd", True, 1, 8192), peaks)[0]
    assert causal / windowed > 4
    with pytest.raises(ValueError, match="counted too high"):
        trace_diff_flash.read(_ctx(_spent(TABLE, 0.004)), "roofline")


@pytest.mark.parametrize("reader", ["scan", "flash"])
@pytest.mark.parametrize("case", ["no_trace", "no_cost", "no_table",
                                  "not_in_table", "not_in_trace"])
def test_nothing_to_read_is_nothing_reported(reader, case, monkeypatch):
    """No trace (a CPU run), an accounting module without the cost function
    (another architecture), a program without a scope table or without the
    scope (the parent's program), a trace without the instructions: None,
    never a raise and never a zero."""
    scope = {"scan": "selective_scan", "flash": "diff_flash"}[reader]
    table = dict(TABLE)
    if case == "no_table":
        table = None
    elif case == "not_in_table":
        table = {k: v for k, v in TABLE.items() if scope not in v[0]}
    monkeypatch.setattr(trace_scope, "_table", lambda: table)
    ctx = _ctx(_spent(TABLE))
    if case == "no_trace":
        ctx = {"trace": None}
    elif case == "no_cost":
        ctx = _ctx(_spent(TABLE),
                   accounting_module="chipbench.accounting.olmoe")
    elif case == "not_in_trace":
        ctx = _ctx(_spent({"fusion.1": 0, "fusion.13": 0}))
    if reader == "scan":
        assert trace_scope_roofline.read(ctx, **SCAN) is None
    else:
        assert trace_diff_flash.read(ctx, "share") is None
        assert trace_diff_flash.read(ctx, "roofline") is None


@pytest.mark.parametrize("name, share", [
    ("ssm.s6_mixer_share", 25.0), ("ssm.selective_scan_share", 15.0),
    ("attn.cross_decoder_share", 10.0), ("mlp.dense_gated_share", 5.0),
    ("train_step.hybrid_recompute_share", 15.0), ("attn.scoped_share", 25.0)])
def test_the_scope_metrics_read_the_programs_table(name, share, monkeypatch):
    """`selective_scan` and `conv` inside `mamba1`, `cross_attn` inside
    `attn`, `gmu` beside it: each metric's scopes count an instruction
    once (13 instructions of 0.05 s in a busy second)."""
    monkeypatch.setattr(trace_scope, "_table", lambda: dict(TABLE))
    assert trace_scope.read(_ctx(_spent(TABLE)), **catalog.load_json(
        REAL, "metrics", name)["args"]) == pytest.approx(share)
