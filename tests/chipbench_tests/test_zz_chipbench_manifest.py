"""BENCHMARK.json against every rule the driver is known to apply, so that a
manifest it would refuse is refused here first (PR 22 was refused over a
``layer`` with a space in it), and the yardstick's arithmetic against
numbers worked out by hand."""
import json
import os
import re

import pytest

from chipbench import catalog, flops

MANIFEST = catalog.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "head",
               "expan", "n_embd", "n_inner", "d_model", "d_ff", "per_tok")
EXCLUDED_FAMILIES = ("gpt-oss", "gpt_oss", "gemma", "llama", "qwen3.5",
                     "qwen3_5")
ALL_METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def _line(text, limit=200):
    return (isinstance(text, str) and 1 <= len(text) <= limit
            and "\n" not in text and "\t" not in text)


def test_top_level_keys_and_sizes():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    path = os.path.join(catalog.ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    assert 1 <= len(MANIFEST["paths"]) <= 16
    assert 1 <= len(MANIFEST["configs"]) <= 24
    assert 2 <= len(MANIFEST["workloads"]) <= 24
    assert 1 <= len(MANIFEST["end_to_end"]) <= 16
    assert 1 <= len(MANIFEST["per_layer"]) <= 128


def test_run_seconds_fits_a_full_check_of_24_cells():
    seconds = MANIFEST["run_seconds"]
    assert isinstance(seconds, int) and not isinstance(seconds, bool)
    assert 10 <= seconds <= 51
    runs = 2 + 14 * 24
    assert runs * (seconds + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_paths_and_command():
    for path in MANIFEST["paths"]:
        assert PATH.match(path) and not path.startswith("/")
        assert ".." not in path.split("/")
        assert os.path.isdir(os.path.join(catalog.ROOT, path))
    command = MANIFEST["command"]
    assert 1 <= len(command) <= 32
    for word in command:
        assert _line(word)
        assert not word.startswith("/") and ".." not in word.split("/")
    # the module the command names lies under `paths`
    module = command[command.index("-m") + 1]
    assert module.split(".")[0] in MANIFEST["paths"]
    assert os.path.isfile(os.path.join(
        catalog.ROOT, module.replace(".", "/") + ".py"))


def test_files_under_paths_are_named_from_name_characters():
    for base in MANIFEST["paths"]:
        for folder, dirs, files in os.walk(os.path.join(catalog.ROOT, base)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name),
                                      catalog.ROOT)
                assert PATH.match(rel), rel


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end",
                                   "per_layer"])
def test_names_are_identifiers_and_unique(group):
    names = [entry["name"] for entry in MANIFEST[group]]
    for name in names:
        assert NAME.match(name), name
    assert len(set(names)) == len(names)
    if group in ("end_to_end", "per_layer"):
        every = [m["name"] for m in ALL_METRICS]
        assert len(set(every)) == len(every)


def test_the_name_rule_is_the_drivers():
    """At most 64 of letters, digits, ``_``, ``.`` and ``-``, the first
    none of ``.`` and ``-``."""
    for name in ("a" * 64, "_x", "9.cell-b_2"):
        assert NAME.match(name), name
    for name in ("a" * 65, ".hidden", "-flag", "two words", "a/b", "a,b", ""):
        assert not NAME.match(name), name


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_every_configuration_names_an_architecture_that_is_there(config):
    """Its ``reference`` resolves to the plain model and to the accounting
    module, each with what the harness calls."""
    filed = catalog.load_json(MANIFEST, "configs", config)
    assert NAME.match(filed["reference"])
    reference = catalog.load_module(MANIFEST, "references",
                                    filed["reference"])
    assert callable(reference.loss)
    accounting = catalog.load_module(MANIFEST, "accounting",
                                     filed["reference"])
    for function in ("filed_sizes", "ran_sizes", "train_flops_per_token",
                     "pick", "put"):
        assert callable(getattr(accounting, function)), function
    sizes = accounting.filed_sizes(filed)
    assert sizes["n_params"] > 0
    assert sizes["padded_vocab"] == flops.padded_vocab(filed["vocab_size"])


def test_configs():
    used = {w["config"] for w in MANIFEST["workloads"]}
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(set(files)) == len(files)
    for config in MANIFEST["configs"]:
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert config["name"] in used, "a configuration no cell uses"
        assert _line(config["source"]) and _line(config["why"])
        assert any(config["file"].startswith(p + "/")
                   for p in MANIFEST["paths"])
        assert len(config["reduced"]) <= 16
        for key in config["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank"))
            assert not any(word in key for word in WIDTH_WORDS), key
        with open(os.path.join(catalog.ROOT, config["file"])) as f:
            filed = json.load(f)
        assert filed["source"] == config["source"]
        assert filed["reduced"] == config["reduced"]
        text = json.dumps([config, filed]).lower()
        for family in EXCLUDED_FAMILIES:
            assert family not in text, family


def test_workloads():
    configs = {c["name"] for c in MANIFEST["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert _line(w["why"])
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(MANIFEST["workloads"]) // 4)


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_every_file_a_cell_names_exists(cell):
    for group in ("end_to_end", "per_layer"):
        resolved = catalog.resolve_cell(MANIFEST, cell, group)
        assert resolved["metrics"], f"{cell} reports no {group} metric"
        for spec in resolved["metrics"]:
            assert os.path.isfile(os.path.join(
                catalog.ROOT, spec["reader"].replace(".", "/") + ".py"))
    catalog.find(MANIFEST, "jobs", resolved["traffic"]["job"], ".py")
    architecture = resolved["model"]["reference"]
    assert resolved["reference"] == catalog.module_name(
        MANIFEST, "references", architecture)
    assert resolved["accounting"] == catalog.module_name(
        MANIFEST, "accounting", architecture)


def test_metrics():
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        # PR 22's refusal: an identifier, not prose
        assert NAME.match(m["layer"]), m["layer"]
    cells = {w["name"] for w in MANIFEST["workloads"]}
    for m in ALL_METRICS:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    setup = [m for m in MANIFEST["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and setup[0]["bound"] <= 0.1
    assert "workloads" not in setup[0]


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_each_cell_reports_what_the_contract_asks(cell):
    end_to_end = {m["name"] for m in
                  catalog.metrics_of(MANIFEST, cell, "end_to_end")}
    assert "setup_s" in end_to_end and len(end_to_end) >= 2
    per_layer = catalog.metrics_of(MANIFEST, cell, "per_layer")
    assert per_layer
    for m in per_layer:
        # a per-layer metric is reported only where the metric it moves is
        assert m["moves"] in end_to_end, (cell, m["name"], m["moves"])


# ------------------------------------------------------------ arithmetic

@pytest.mark.parametrize("config, params, per_token", [
    ("gpt2-small", 124_439_040, 803.3e6),
    ("gpt2-medium", 354_772_992, 2.280e9),
    ("gpt2-large", 773_905_920, 4.927e9),
])
def test_params_and_flops_a_token(config, params, per_token):
    filed = catalog.load_json(MANIFEST, "configs", config)
    accounting = catalog.load_module(MANIFEST, "accounting",
                                     filed["reference"])
    assert accounting.params(filed) == params
    got = accounting.train_flops_per_token(filed, 1024)
    assert got == 6 * params + 6 * filed["n_layer"] * 1024 * filed["n_embd"]
    assert abs(got - per_token) / per_token < 5e-4
    # the program counts the same parameters
    from ray_tpu.models import gpt2
    preset = filed["entry"].split(":")[1]
    assert getattr(gpt2, preset)().n_params == params


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_raise():
    v5e = flops.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["source"]
    for kind in ("cpu", "TPU v9", ""):
        with pytest.raises(KeyError, match="peaks.json"):
            flops.peaks_for(kind)


def test_flash_call_cost_from_the_profilers_text():
    shard = "bf16[192,1024,64]{2,1,0:T(8,128)(2,1)}"
    row = "f32[192,8,1024]{2,1,0:T(8,128)S(1)}"
    tail = ('custom_call_target="tpu_custom_call", operand_layout_constraints'
            '={bf16[192,1024,64]{2,1,0}}')
    fwd = (f"%closed_call.55 = ({shard}, {row}) custom-call({shard} %a, "
           f"{shard} %b, {shard} %c), {tail}")
    dq = (f"%closed_call.56 = {shard} custom-call({shard} %a, {shard} %b, "
          f"{shard} %c, {shard} %d, {row} %e, {row} %f), {tail}")
    dkv = dq.replace(f"= {shard} custom-call", f"= ({shard}, {shard}) "
                                               f"custom-call")
    qkv = 192 * 1024 * 64 * 2
    lse = 192 * 8 * 1024 * 4
    matmul = 2 * 192 * 1024 * 1024 * 64 // 2       # one causal S x S x D
    assert flops.flash_call_cost(fwd) == ("fwd", 2 * matmul, 4 * qkv + lse)
    assert flops.flash_call_cost(dq) == ("bwd_dq", 3 * matmul,
                                         5 * qkv + 2 * lse)
    assert flops.flash_call_cost(dkv) == ("bwd_dkv", 4 * matmul,
                                          6 * qkv + 2 * lse)
    assert flops.flash_call_cost(
        '%custom-call.24 = bf16[12,16]{1,0} custom-call(), '
        'custom_call_target="AllocateBuffer"') is None
    assert flops.flash_call_cost("%fusion.1 = f32[8]{0} fusion()") is None
    peaks = flops.peaks_for("TPU v5 lite")
    least, bound = flops.least_seconds(2 * matmul, 4 * qkv + lse, peaks)
    assert bound == "compute" and least == 2 * matmul / 197e12
    assert flops.least_seconds(1e6, 819e9, peaks) == (1.0, "memory")
