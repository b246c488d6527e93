"""chip_smoke.py without a chip: its train loop and checker through
JaxTrainer.fit() at gpt2_tiny on CPU, its refusal to pass without a TPU,
and the two rules it leans on — where the compile cache lives and when a
native library is rebuilt."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = {"model": "gpt2_tiny", "attention": "reference", "remat": False,
        "mesh": {"dp": 1}, "batch": 8, "seq": 32, "warmup": 2, "steps": 5,
        "require_tpu": False, "out_dir": None}


def _fit(loop, config):
    import ray_tpu.data
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train.trainer import JaxTrainer

    n_rows = config["batch"] * (config["warmup"] + config["steps"])
    tokens = chip_smoke.make_tokens(n_rows, config["seq"], 256)
    return JaxTrainer(
        loop, train_loop_config=config,
        scaling_config=ScalingConfig(num_workers=1),
        datasets={"train": ray_tpu.data.from_numpy(tokens)}).fit()


def _raising_loop(config):
    raise RuntimeError("boom in the train loop")


def test_smoke_loop_and_checker_through_trainer(ray_start_regular):
    result = _fit(chip_smoke.train_loop, TINY)
    assert result.error is None
    report = result.metrics["smoke_report"]
    assert report["device"]["platform"] == "cpu"
    assert report["attention"] == "reference"
    assert len(report["losses"]) == 7
    assert report["losses"][-1] < report["losses"][0]
    # one report per step, then the summary
    assert [m.get("step") for m in result.metrics_history[:-1]] == \
        list(range(7))
    assert chip_smoke.check_result(result, TINY) == []
    # the same run judged as a chip run fails on every TPU-only check
    as_chip = chip_smoke.check_result(result, {**TINY, "require_tpu": True})
    assert any("backend 'cpu'" in line for line in as_chip)
    assert any("compiled Pallas calls" in line for line in as_chip)

    # fit() RETURNS a failed loop (default FailureConfig): an unchecked
    # smoke would exit 0 on a crash, so the checker must name it
    crashed = _fit(_raising_loop, TINY)
    assert crashed.error is not None
    failures = chip_smoke.check_result(crashed, TINY)
    assert failures and "boom in the train loop" in failures[0]


def test_chip_smoke_exits_nonzero_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RAY_TPU_TESTING", None)     # the real chip probe must run
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_worker_granted_tpu_must_find_the_tpu(monkeypatch):
    """JAX falls back to the CPU with a warning when libtpu cannot take
    the chips and JAX_PLATFORMS is unset; a worker whose lease holds TPU
    fails the gang instead of training there."""
    import pytest

    from ray_tpu import exceptions as exc
    from ray_tpu.train.worker_group import TrainWorker

    worker = TrainWorker(0, 1, num_tpus=4)
    worker._require_tpu_backend()            # JAX_PLATFORMS=cpu: pinned
    monkeypatch.setenv("JAX_PLATFORMS", "")
    with pytest.raises(exc.TpuBackendError, match="holds 4 TPU"):
        worker._require_tpu_backend()
    TrainWorker(0, 1)._require_tpu_backend()  # no TPU in the lease


def test_flash_operand_report_reads_partitioned_hlo():
    hlo = '''
ENTRY %main (p: bf16[64,1024,12,64]) -> bf16[192,1024,64] {
  %p = bf16[16,1024,12,64]{3,2,1,0:T(8,128)(2,1)} parameter(0), sharding={devices=[4,1,1,1]<=[4]}
  %fusion.1 = bf16[192,1024,64]{2,1,0:T(8,128)(2,1)} fusion(%p), kind=kLoop, calls=%fused_computation
  %ag = bf16[768,1024,64]{2,1,0} all-gather(%fusion.1), channel_id=1, dimensions={0}
  %custom-call.1 = (bf16[192,1024,64]{2,1,0:T(8,128)(2,1)}, f32[192,8,1024]{2,1,0:T(8,128)}) custom-call(%fusion.1, %fusion.1, %fusion.1), custom_call_target="tpu_custom_call"
  %custom-call.2 = bf16[768,1024,64]{2,1,0} custom-call(%ag, %ag, %ag), custom_call_target="tpu_custom_call"
}
'''
    rep = chip_smoke.flash_operand_report(hlo, (192, 1024, 64))
    assert rep["operand_shapes"] == [(192, 1024, 64), (768, 1024, 64)]
    assert not rep["all_local"]
    assert rep["all_gather_feeds"] == ["custom-call.2 <- ag"]
    sharded_only = hlo.replace("%custom-call.2", "%other").replace(
        'custom-call(%ag, %ag, %ag), custom_call_target="tpu_custom_call"',
        "copy(%ag)")
    rep = chip_smoke.flash_operand_report(sharded_only, (192, 1024, 64))
    assert rep["all_local"] and not rep["all_gather_feeds"]


def test_compile_cache_rule(monkeypatch, tmp_path):
    import jax

    from ray_tpu.parallel import compile_watch

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: updates.append(a))
    # set: JAX reads the variable itself, code sets nothing
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_watch.configure_compile_cache() == str(tmp_path)
    assert updates == []
    # unset: <checkout>/.jax_cache, whatever the cwd
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.chdir(tmp_path)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_watch.configure_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]


def test_native_build_is_keyed_on_content(monkeypatch, tmp_path):
    from ray_tpu._private import native_build as nb

    src = tmp_path / "src" / "t.cc"
    src.parent.mkdir()
    src.write_text('extern "C" int answer() { return 41; }\n')
    monkeypatch.setattr(nb, "_REPO_ROOT", str(tmp_path))
    monkeypatch.setattr(nb, "_BUILD_DIR", str(tmp_path / "build"))
    monkeypatch.setattr(nb, "_LIBS", {"t": ["src/t.cc"]})
    compiles = []
    real_run = subprocess.run
    monkeypatch.setattr(
        nb.subprocess, "run",
        lambda cmd, **kw: compiles.append(cmd) or real_run(cmd, **kw))

    first = nb.ensure_lib("t")
    assert os.path.exists(first) and len(compiles) == 1
    # a copy resets mtimes: source newer than the artefact, and the
    # reverse, both leave the artefact valid
    os.utime(src, (2_000_000_000, 2_000_000_000))
    assert nb.ensure_lib("t") == first
    os.utime(src, (1_000_000_000, 1_000_000_000))
    assert nb.ensure_lib("t") == first
    assert len(compiles) == 1
    # an edit is a different artefact
    src.write_text('extern "C" int answer() { return 42; }\n')
    second = nb.ensure_lib("t")
    assert second != first and os.path.exists(second)
    assert len(compiles) == 2
