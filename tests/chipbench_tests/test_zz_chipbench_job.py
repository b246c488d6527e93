"""Rehearsals of a benchmark run, without the chip: the ``train_fit`` job's
loop through ``JaxTrainer.fit()`` at a tiny configuration that lives in THIS
directory (configuration, traffic mix, one metric and its reader: the
harness finds them by name and no file under ``chipbench/`` knows them),
the command's refusal to run without a TPU, and the plain reference against
the system's ``loss_fn``. Beside it a second architecture that is files
only: ``gated-tiny`` (model, preset, configuration, traffic mix, accounting
and reference, all in this directory) goes through the same job."""
import dataclasses
import importlib
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from chipbench import catalog, compare, generate
from chipbench.jobs import train_fit
from chipbench.readers import mfu
from tests.chipbench_tests import tiny_fit

# "No TPU required" is an argument this test passes, not an option of the
# command. The tiny cell reports what needs no chip and no peak.
TINY_MANIFEST = {
    "paths": ["chipbench", "tests/chipbench_tests"],
    "workloads": [{"name": "tiny", "config": "gpt2-tiny",
                   "traffic": "fit-tiny", "chips": 1, "why": "rehearsal"},
                  {"name": "tiny-dp2tp2", "config": "gpt2-tiny",
                   "traffic": "fit-tiny-dp2tp2", "chips": 4,
                   "why": "the sharded path on four virtual devices"},
                  {"name": "gated-tiny", "config": "gated-tiny",
                   "traffic": "fit-gated-tiny", "chips": 1,
                   "why": "an architecture that is not GPT-2's"}],
    "end_to_end": [
        {"name": "tokens_per_s_per_chip", "unit": "tokens/s/chip"},
        {"name": "step_ms_p90", "unit": "ms"},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [
        {"name": "tiny.epochs", "unit": "count"},
        {"name": "data.wait_ms", "unit": "ms"},
        {"name": "train.report_ms", "unit": "ms"},
        {"name": "train.fit_startup_s", "unit": "s"},
        {"name": "train_step.compiles_in_window", "unit": "count"},
        {"name": "train_step.hbm_plan_gb", "unit": "GB"},
        {"name": "device.idle_share", "unit": "%"},
        {"name": "collectives.exposed_share", "unit": "%",
         "workloads": ["another-cell"]}],
}


def test_a_new_cell_is_files_and_entries_only():
    cell = catalog.resolve_cell(TINY_MANIFEST, "tiny", "per_layer")
    assert cell["model"]["entry"] == "ray_tpu.models.gpt2:gpt2_tiny"
    assert cell["traffic"]["batch"] == 8
    readers = {m["name"]: m["reader"] for m in cell["metrics"]}
    # found in the tests' own directory / in the benchmark's
    assert readers["tiny.epochs"] == \
        "tests.chipbench_tests.readers.steps_per_epoch"
    assert readers["data.wait_ms"] == "chipbench.readers.span_percentile"
    # a metric whose `workloads` leaves the cell out is not computed there
    assert "collectives.exposed_share" not in readers
    with pytest.raises(KeyError, match="no workload 'nope'"):
        catalog.resolve_cell(TINY_MANIFEST, "nope", "end_to_end")
    with pytest.raises(FileNotFoundError, match="chipbench/metrics/ghost"):
        catalog.resolve_cell(dict(TINY_MANIFEST, end_to_end=[
            {"name": "ghost", "unit": "s"}]), "tiny", "end_to_end")


@pytest.mark.parametrize("trace", [False, True])
def test_train_fit_job_through_the_trainer(trace):
    cell = catalog.resolve_cell(TINY_MANIFEST, "tiny",
                                "per_layer" if trace else "end_to_end")
    t_start = time.time()
    # a window long enough for the 32 steps asserted below on a host that
    # five other test processes share (1.5 s gave 31 once)
    record = train_fit.run(cell, seed=3, seconds=2.5, trace=trace,
                           t_start=t_start, require_tpu=False)
    json.dumps(record)                       # plain data all the way down
    assert {"correct", "attempted", "failed", "metrics", "device"} <= \
        set(record)
    assert record["device"]["platform"] == "cpu"
    assert record["device"]["count"] >= 1
    assert record["device"]["memory_peak_bytes"] > 0
    assert record["attempted"] >= 32 and record["failed"] == 0
    assert record["verdicts"]["every_loss_finite"]
    assert record["verdicts"]["loss_fell"]
    assert set(record["losses"]) == {"1", "8", "32"}
    assert record["clock"]["window_s"] >= 2.5
    values = {k: v["value"] for k, v in record["metrics"].items()}
    if not trace:
        # a 90th percentile only where ten samples lie beyond it
        tail = {"step_ms_p90"} if record["attempted"] >= 100 else set()
        assert set(values) == {"tokens_per_s_per_chip", "setup_s"} | tail
        assert values["tokens_per_s_per_chip"] == pytest.approx(
            record["attempted"] * 8 * 32 / record["clock"]["window_s"])
        assert 0 < values["setup_s"] < time.time() - t_start
    else:
        # no TPU plane in a CPU trace: trace metrics are left out, not
        # invented, and the line carries no breakdown
        assert set(values) == {
            "tiny.epochs", "data.wait_ms", "train.report_ms",
            "train.fit_startup_s", "train_step.compiles_in_window",
            "train_step.hbm_plan_gb"}
        assert "breakdown" not in record
        assert values["train_step.compiles_in_window"] == 0
        assert values["tiny.epochs"] == record["attempted"] / 8
        assert 0 < values["train.fit_startup_s"]
        assert not os.path.exists(train_fit.TRACE_DIR)


@pytest.fixture(scope="module")
def gated_fit(once_a_run):
    """ONE traced fit of `gated-tiny` with the metrics of both groups, once
    a test run: the two cases below read a group each of it (`tiny_fit.py`;
    the job's untraced path is the `tiny` cell's pair above)."""
    return once_a_run("gated_tiny_fit", lambda: tiny_fit.traced(
        TINY_MANIFEST, "gated-tiny", seed=11))


@pytest.mark.parametrize("trace", [False, True])
def test_another_architecture_is_files_only(gated_fit, trace):
    """RMSNorm, rotary positions, a gated MLP, an untied head, public key
    names: through the same job, correct against its own float32
    reference on its own leaves, its MFU from its own accounting."""
    cell = catalog.resolve_cell(TINY_MANIFEST, "gated-tiny",
                                "per_layer" if trace else "end_to_end")
    assert cell["accounting"] == "tests.chipbench_tests.accounting.gated_lm"
    assert cell["reference"] == "tests.chipbench_tests.references.gated_lm"
    record = gated_fit
    json.dumps(record)
    assert record["correct"], (record["verdicts"], record["check"])
    assert set(record["check"]["errors"]) == {
        "loss", "grad_head", "grad_embed", "grad_gate", "grad_wq", "grad_wv"}
    assert record["failed"] == 0 and record["attempted"] >= 8
    values = tiny_fit.values_of(record, cell)
    if trace:
        assert values["train_step.compiles_in_window"] == 0
        assert values["tiny.epochs"] == record["attempted"] / 16
    else:
        assert values["tokens_per_s_per_chip"] == pytest.approx(
            record["attempted"] * 4 * 48 / record["clock"]["window_s"])
    # the mfu reader, given a peak (the CPU has none on file): 6 FLOPs for
    # each parameter of the head and the two layers' seven matrices, none
    # for the embedding's lookup, plus causal attention; by hand
    per_token = (6 * (64 * 256 + 2 * (4 * 64 * 64 + 3 * 64 * 160))
                 + 6 * 2 * 48 * 64)
    assert per_token == 700_416
    ctx = {"accounting": cell["accounting"], "model": cell["model"],
           "traffic": cell["traffic"], "chips": 1, "clock": record["clock"],
           "counters": {"steps": record["attempted"]},
           "peaks": {"bf16_flops_per_s": 1e12}}
    assert mfu.read(ctx) == pytest.approx(
        100 * record["attempted"] * 4 * 48 / record["clock"]["window_s"]
        * per_token / 1e12, rel=1e-12)


def test_nothing_under_chipbench_knows_the_fixture():
    """The second architecture added files here and not a line there. Only
    the fixture's own names are looked for: a published configuration
    filed under ``chipbench/configs/`` has the public keys the fixture
    borrows, and may well be gated."""
    for folder, _, files in os.walk(os.path.join(catalog.ROOT,
                                                 "chipbench")):
        for name in files:
            if name.endswith((".py", ".json")):
                with open(os.path.join(folder, name)) as f:
                    text = f.read()
                for word in ("gated_lm", "gated-tiny", "GatedConfig"):
                    assert word not in text, (name, word)


def test_sharded_cell_on_virtual_devices():
    """dp=2 x tp=2 on four of the CPU's virtual devices: the mesh, the
    sharded state, the gather of the parameters onto one device for the
    reference, and agreement with it."""
    cell = catalog.resolve_cell(TINY_MANIFEST, "tiny-dp2tp2", "end_to_end")
    record = train_fit.run(cell, seed=4, seconds=1.0, trace=False,
                           t_start=time.time(), require_tpu=False)
    assert record["failed"] == 0 and record["verdicts"]["loss_fell"]
    assert record["check"]["errors"]["loss"] < 1e-3
    assert record["metrics"]["tokens_per_s_per_chip"]["value"] == \
        pytest.approx(record["attempted"] * 8 * 32
                      / record["clock"]["window_s"] / 4)


def test_a_failed_loop_is_a_failed_job():
    cell = catalog.resolve_cell(TINY_MANIFEST, "tiny", "end_to_end")
    cell["model"] = dict(cell["model"], n_layer=3)   # not what the preset is
    with pytest.raises(train_fit.JobFailed, match="configuration file says"):
        train_fit.run(cell, seed=0, seconds=1.0, trace=False,
                      t_start=time.time(), require_tpu=False)


@pytest.mark.parametrize("name, key", [("tiny", "n_layer"),
                                       ("gated-tiny", "num_hidden_layers")])
def test_filed_sizes_that_are_not_the_presets_raise(name, key):
    cell = catalog.resolve_cell(TINY_MANIFEST, name, "end_to_end")
    train_fit._model(cell)                   # as filed: accepted
    cell["model"] = dict(cell["model"], **{key: 3})
    with pytest.raises(ValueError, match=f"'{key}': 3"):
        train_fit._model(cell)


def test_a_configuration_without_accounting_names_the_file(tmp_path):
    """No default stands in for a missing module: the error is
    ``catalog.find``'s, with every path it looked for."""
    for kind, name, text in (
            ("configs", "orphan.json", '{"reference": "orphan"}'),
            ("traffic", "none.json", "{}"),
            ("references", "orphan.py", "")):
        (tmp_path / "bench" / kind).mkdir(parents=True)
        (tmp_path / "bench" / kind / name).write_text(text)
    manifest = {"paths": ["bench", "more"], "end_to_end": [], "workloads": [
        {"name": "cell", "config": "orphan", "traffic": "none"}]}
    with pytest.raises(FileNotFoundError) as e:
        catalog.resolve_cell(manifest, "cell", "end_to_end", str(tmp_path))
    assert ("no accounting file for 'orphan': looked for "
            "bench/accounting/orphan.py, more/accounting/orphan.py"
            ) in str(e.value)


def test_command_exits_nonzero_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RAY_TPU_TESTING", None)         # the real chip probe must run
    manifest = catalog.load_manifest()
    proc = subprocess.run(
        [sys.executable if w == "python3" else w
         for w in manifest["command"]]
        + ["--workload", manifest["workloads"][0]["name"], "--seed", "0",
           "--seconds", "1", "--trace", "0"],
        cwd=catalog.ROOT, env=env, capture_output=True, text=True,
        timeout=180)
    assert proc.returncode != 0
    assert "found 0" in proc.stderr
    assert '"correct"' not in proc.stdout and '"metrics"' not in proc.stdout


def test_parent_side_never_imports_jax():
    code = ("import sys; import chipbench.run, chipbench.jobs.train_fit, "
            "chipbench.generate, chipbench.trace_reduce, chipbench.flops; "
            "import ray_tpu, ray_tpu.data; "
            "from ray_tpu.train.trainer import JaxTrainer; "
            "sys.exit('jax' in sys.modules)")
    proc = subprocess.run([sys.executable, "-c", code], cwd=catalog.ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_command_refuses_a_directory_without_the_program(tmp_path):
    """BENCHMARK.json and the files under `paths`, nothing else."""
    import shutil

    manifest = catalog.load_manifest()
    shutil.copy(os.path.join(catalog.ROOT, "BENCHMARK.json"), tmp_path)
    for base in manifest["paths"]:
        shutil.copytree(os.path.join(catalog.ROOT, base), tmp_path / base,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "gpt2s-b16",
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    assert "not the program" in proc.stderr and not proc.stdout.strip()


# ------------------------------------------------- reference vs system

def _tiny(dtype):
    import jax
    import jax.numpy as jnp

    from chipbench.references import gpt2 as reference
    from ray_tpu.models import gpt2

    cfg = dataclasses.replace(gpt2.gpt2_tiny(), attention="reference",
                              dtype=getattr(jnp, dtype))
    params = gpt2.init(jax.random.PRNGKey(0), cfg)
    # biases and scales off their initial 0 and 1, so that a reference
    # that dropped one would be caught
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        leaf + 0.02 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])
    tokens = generate.token_rows(
        {"batches": 1, "batch": 2, "seq": 64, "vocab_divisor": 1}, 256, 5)
    filed = catalog.load_json(TINY_MANIFEST, "configs", "gpt2-tiny")
    return gpt2, cfg, params, tokens, lambda p, t: reference.loss(p, t, filed)


def test_reference_is_the_systems_function_in_float32():
    """Same parameters, same tokens, float32 on both sides: the loss and
    the four gradients agree to float32 rounding, so the two compute the
    same function (tied head, no attention bias, tanh GELU, eps 1e-5)."""
    import jax

    from chipbench.accounting import gpt2 as accounting

    gpt2, cfg, params, tokens, reference = _tiny("float32")
    got = compare.compare(
        lambda p, t: gpt2.loss_fn(p, {"tokens": t}, cfg)[0],
        reference, params, tokens, jax.devices()[0],
        pick=accounting.pick, put=accounting.put)
    assert got["errors"]["loss"] < 1e-6
    assert max(got["errors"].values()) < 1e-4, got["errors"]
    assert got["within"]


def test_bf16_passes_and_eight_bit_matmuls_would_fail():
    """At the cell's dtype the system is inside the tolerances; with its
    matmul inputs rounded to an 8-bit float (what computing in fp8 does to
    them) it is outside: the comparison would catch a lower precision than
    the configuration states."""
    import jax
    import jax.numpy as jnp

    from chipbench.accounting import gpt2 as accounting

    gpt2, cfg, params, tokens, reference = _tiny("bfloat16")
    leaves = {"pick": accounting.pick, "put": accounting.put}

    def system(p, t):
        return gpt2.loss_fn(p, {"tokens": t}, cfg)[0]

    got = compare.compare(system, reference, params, tokens,
                          jax.devices()[0], **leaves)
    assert got["within"], got["errors"]

    def eight_bit(p, t):
        def cast(a):      # straight-through: the gradient passes unrounded
            rounded = a.astype(jnp.float8_e4m3fn).astype(a.dtype)
            return a + jax.lax.stop_gradient(rounded - a)
        blocks = dict(p["blocks"], mlp=jax.tree_util.tree_map(
            cast, p["blocks"]["mlp"]), attn=jax.tree_util.tree_map(
            cast, p["blocks"]["attn"]))
        return system(dict(p, blocks=blocks), t)

    low = compare.compare(eight_bit, reference, params, tokens,
                          jax.devices()[0], **leaves)
    assert not low["within"], low["errors"]
    # each number beside its limit, the nearest to its limit first: the
    # one that failed the control leads, and the verdict is theirs alone
    for check in (got, low):
        beside = compare.beside_limits(check["errors"])
        assert {k: v[0] for k, v in beside.items()} == check["errors"]
        assert all(limit == (compare.LOSS_RTOL if k == "loss"
                             else compare.GRAD_RTOL)
                   for k, (_, limit) in beside.items())
        ratios = [v / limit for v, limit in beside.values()]
        assert ratios == sorted(ratios, reverse=True)
        assert check["within"] == (ratios[0] <= 1)


def test_the_result_line_ends_with_what_was_compared():
    """`compared` is the line's LAST key: the verdicts, then each number
    beside its limit in the comparison's own order; nothing of the record
    that the driver reads is moved by it."""
    from chipbench import run

    record = {"correct": False, "attempted": 7, "failed": 0,
              "metrics": {"setup_s": {"value": 1.5, "unit": "s"}},
              "device": {"platform": "tpu", "count": 1},
              "verdicts": {"agrees_with_reference": False,
                           "every_loss_finite": True, "loss_fell": True},
              "compared": {"grad_w": [0.09, 0.08], "loss": [1e-5, 3e-4]},
              "plan": {"temp": 1}}
    line = run.result_line(record)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert list(line["compared"].items()) == [
        ("agrees_with_reference", False), ("every_loss_finite", True),
        ("loss_fell", True), ("grad_w", [0.09, 0.08]),
        ("loss", [1e-5, 3e-4])]
    traced = run.result_line(dict(record, breakdown={"device_ops": []}))
    assert list(traced)[-2:] == ["breakdown", "compared"]
    json.dumps(line)


@pytest.mark.parametrize("name, key, other", [
    ("tiny", "layer_norm_epsilon", 1e-2),
    ("gated-tiny", "num_attention_heads", 2),
    ("gated-tiny", "rope_theta", 100.0),
    ("gated-tiny", "rms_norm_eps", 1e-2)])
def test_a_reference_reads_the_configuration_it_is_handed(name, key, other):
    """What no leaf's shape gives reaches the reference through its third
    argument, the configuration file as the cell runs it, and through
    nothing else: another value there is another function."""
    import jax

    cell = catalog.resolve_cell(TINY_MANIFEST, name, "end_to_end")
    _, cfg = train_fit._model(cell)
    module = importlib.import_module(cell["model"]["entry"].split(":")[0])
    reference = importlib.import_module(cell["reference"])
    # weights eight times their initial size, so that attention is far
    # from uniform and the rotation's base shows
    params = jax.tree_util.tree_map(
        lambda a: 8 * a if a.ndim > 1 else a,
        module.init(jax.random.PRNGKey(2), cfg))
    tokens = generate.token_rows(
        {"batches": 1, "batch": 2, "seq": 32, "vocab_divisor": 1}, 256, 9)
    filed = float(reference.loss(params, tokens, cell["model"]))
    assert filed == float(reference.loss(params, tokens, dict(cell["model"])))
    changed = float(reference.loss(params, tokens,
                                   dict(cell["model"], **{key: other})))
    assert abs(changed - filed) > 1e-4 * filed


def test_token_rows_come_from_the_seed():
    traffic = {"batches": 3, "batch": 4, "seq": 16, "vocab_divisor": 16}
    a = generate.token_rows(traffic, 50304, 7)
    assert a.shape == (12, 17) and a.dtype == np.int32
    assert a.min() >= 0 and a.max() < 50304 // 16
    assert np.array_equal(a, generate.token_rows(traffic, 50304, 7))
    assert not np.array_equal(a, generate.token_rows(traffic, 50304, 8))
