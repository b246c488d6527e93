"""Multi-slice MPMD pipeline parallelism (late-alphabet; sequenced
after the tier-1 timeout horizon by design).

Covers the tentpole end to end on a simulated >=2-slice cluster:

- SPREAD_ACROSS_SLICES places each pipeline stage's sub-gang contiguous
  on a DISTINCT slice (asserted through ``summarize_topology``);
- a 2-stage ``PipelineTrainer`` run matches the single-gang
  ``reference_run`` loss oracle BIT FOR BIT per seed (GPipe, 1F1B, and
  the GPipe ack-window variant — same float op order by construction),
  final params included (via the full-pipeline checkpoint);
- step_anatomy's measured per-stage bubble fraction lands within
  tolerance of the (P-1)/(M+P-1) schedule theory (SleepStage pipeline:
  sleeps don't contend for CPU, so the number reproduces under load);
- inter-stage hops show bf16 ``ray_tpu_collective_wire_bytes_total``
  when ``PipelineConfig.wire_dtype="bf16"`` (polled live, mid-run);
- a seeded ``kill_actor:stage1-rank0...`` chaos schedule drives the
  PR 5 teardown -> checkpoint -> resume path without hanging the other
  stages' send/recv windows;
- the streaming data plane feeds stage 0 from a ``ray_tpu.data``
  Dataset shard.
"""
import json
import os
import threading
import time

import numpy as np
import pytest

GROUP_SEED = 11


def _two_slice(cluster, hosts_per_slice=1, cpus=4):
    cluster.remove_node(cluster.head_node)
    cluster.head_node = cluster.add_node(num_cpus=4)   # driver-only
    nodes = {}
    for sid in ("s0", "s1"):
        for wid in range(hosts_per_slice):
            nodes[(sid, wid)] = cluster.add_node(
                num_cpus=cpus, num_tpus=4,
                tpu_topology={"slice_id": sid, "worker_id": wid,
                              "chips": 4})
    cluster.connect()
    import ray_tpu

    return ray_tpu, nodes


def _stages():
    from ray_tpu.train.pipeline import DenseStage

    return [DenseStage(6, 5, "tanh"), DenseStage(5, 3, "none")]


_KW = dict(num_steps=3, microbatch_size=4, learning_rate=0.05,
           seed=GROUP_SEED)


# ------------------------------------------------------------- pure units

def test_schedule_orders():
    from ray_tpu.train.pipeline import (build_schedule, gpipe_schedule,
                                        max_inflight,
                                        one_f_one_b_schedule)

    g = gpipe_schedule(0, 2, 4)
    assert g == [("fwd", i) for i in range(4)] + \
        [("bwd", i) for i in range(4)]
    assert max_inflight(g) == 4
    # 1F1B: stage 0 of 2 warms up 1 forward, then alternates
    f = one_f_one_b_schedule(0, 2, 4)
    assert f == [("fwd", 0), ("fwd", 1), ("bwd", 0), ("fwd", 2),
                 ("bwd", 1), ("fwd", 3), ("bwd", 2), ("bwd", 3)]
    assert max_inflight(f) == 2
    # last stage: strict alternation, in-flight 1
    last = one_f_one_b_schedule(1, 2, 4)
    assert max_inflight(last) == 1
    # every schedule issues each microbatch exactly once per phase and
    # backwards in 0..M-1 order (the oracle's accumulation order)
    for p in (2, 3, 4):
        for s in range(p):
            for m in (1, 2, 5, 8):
                for name in ("gpipe", "1f1b"):
                    acts = build_schedule(name, s, p, m)
                    fwds = [i for k, i in acts if k == "fwd"]
                    bwds = [i for k, i in acts if k == "bwd"]
                    assert fwds == list(range(m))
                    assert bwds == list(range(m))
                    # no bwd before its fwd
                    seen = set()
                    for k, i in acts:
                        if k == "fwd":
                            seen.add(i)
                        else:
                            assert i in seen
                    if name == "1f1b":
                        assert max_inflight(acts) <= min(m, p - s)
    with pytest.raises(ValueError):
        build_schedule("interleaved", 0, 2, 4)


def test_theoretical_bubble_fraction():
    from ray_tpu.train.pipeline import theoretical_bubble_fraction

    assert theoretical_bubble_fraction(1, 8) == 0.0
    assert theoretical_bubble_fraction(2, 4) == pytest.approx(1 / 5)
    assert theoretical_bubble_fraction(4, 12) == pytest.approx(3 / 15)
    # more microbatches -> smaller bubble, monotonically
    fr = [theoretical_bubble_fraction(4, m) for m in (1, 2, 4, 8, 16)]
    assert fr == sorted(fr, reverse=True)


def test_pipeline_config_validation():
    from ray_tpu.train.pipeline import PipelineConfig, PipelineTrainer

    with pytest.raises(ValueError, match="schedule"):
        PipelineConfig(schedule="zigzag")
    with pytest.raises(ValueError, match="num_microbatches"):
        PipelineConfig(num_microbatches=0)
    # a typo'd wire format fails at construction on the driver, not in
    # a remote worker's first send
    with pytest.raises(ValueError, match="wire"):
        PipelineConfig(wire_dtype="fp16")
    PipelineConfig(wire_dtype="off")     # off-aliases stay valid
    with pytest.raises(ValueError, match="stage"):
        PipelineTrainer([])


def test_reference_run_learns():
    """The oracle itself behaves like training: loss decreases over
    steps on its deterministic synthetic task."""
    from ray_tpu.train.pipeline import reference_run

    ref = reference_run(_stages(), num_microbatches=4, num_steps=6,
                        microbatch_size=8, learning_rate=0.1,
                        seed=GROUP_SEED)
    assert len(ref["losses"]) == 6
    assert ref["losses"][-1] < ref["losses"][0]


# --------------------------------------------------- placement + topology

def test_stage_subgangs_on_distinct_slices(ray_start_cluster):
    """ACCEPTANCE: with 2 slices x 2 hosts and ranks_per_stage=2, each
    stage's sub-gang lands contiguous on its own slice — asserted
    through the state API's topology rollup."""
    ray_tpu, nodes = _two_slice(ray_start_cluster, hosts_per_slice=2,
                                cpus=2)
    from ray_tpu.experimental.state.api import summarize_topology
    from ray_tpu.util.placement_group import placement_group

    pg = placement_group([{"TPU": 4}] * 4,
                         strategy="SPREAD_ACROSS_SLICES",
                         bundle_stages=[0, 0, 1, 1], name="mpmd-gang")
    assert pg.wait(10)
    worker = ray_tpu._private.api._require_worker()
    snap = worker.gcs.call("get_placement_group", pg_id=pg.id)
    by_node = {nodes[k].node_id: k for k in nodes}
    placed = [by_node[n] for n in snap["BundleNodes"]]
    slice_of_stage = {0: {s for s, _ in placed[:2]},
                      1: {s for s, _ in placed[2:]}}
    assert len(slice_of_stage[0]) == 1 and len(slice_of_stage[1]) == 1
    assert slice_of_stage[0] != slice_of_stage[1], placed
    for pair in (placed[:2], placed[2:]):
        wids = sorted(w for _, w in pair)
        assert wids[1] - wids[0] == 1, f"stage not contiguous: {pair}"
    topo = summarize_topology()
    assert topo["num_slices"] == 2
    row = next(r for r in topo["placement_groups"]
               if r["name"] == "mpmd-gang")
    assert set(row["stages"]) == {"0", "1"}
    assert row["stages"]["0"] != row["stages"]["1"]
    occupied = {sid for sids in row["stages"].values() for sid in sids}
    for sid in occupied:
        assert row["placement_group_id"] in topo["slices"][sid]["occupants"]


# ------------------------------------------------------ loss oracle E2Es

def test_gpipe_matches_reference_bit_for_bit(ray_start_cluster):
    """ACCEPTANCE: the 2-stage distributed pipeline reproduces the
    single-gang oracle's per-step losses AND final params bit for bit
    (exact wire, same float op order) — per seed."""
    _two_slice(ray_start_cluster)
    from ray_tpu.train.pipeline import (PipelineConfig, PipelineTrainer,
                                        reference_run)

    stages = _stages()
    ref = reference_run(stages, num_microbatches=4, **_KW)
    result = PipelineTrainer(
        stages, pipeline_config=PipelineConfig(num_microbatches=4,
                                               group_name="zzp_gpipe"),
        **_KW).fit()
    assert result.error is None, result.error
    assert [r["loss"] for r in result.metrics_history] == ref["losses"]
    # final checkpoint carries every stage's params — compare exactly
    state = result.checkpoint.to_dict()
    assert state["step"] == _KW["num_steps"] - 1
    for si, ps in enumerate(ref["params"]):
        got = state["stage_params"][si]
        assert len(got) == len(ps)
        for a, b in zip(got, ps):
            assert np.array_equal(np.asarray(a), b), f"stage {si} params"


def test_1f1b_and_ack_window_match_reference(ray_start_cluster):
    """1F1B and the GPipe in-flight ack window change the SCHEDULE, not
    the math: both stay bit-identical to the oracle."""
    _two_slice(ray_start_cluster)
    from ray_tpu.train.pipeline import (PipelineConfig, PipelineTrainer,
                                        reference_run)

    stages = _stages()
    ref = reference_run(stages, num_microbatches=4, **_KW)
    for pc in (PipelineConfig(num_microbatches=4, schedule="1f1b",
                              group_name="zzp_1f1b"),
               PipelineConfig(num_microbatches=4, inflight_window=1,
                              group_name="zzp_win")):
        result = PipelineTrainer(stages, pipeline_config=pc, **_KW).fit()
        assert result.error is None, result.error
        got = [r["loss"] for r in result.metrics_history]
        assert got == ref["losses"], (pc.schedule, pc.inflight_window)


def test_bf16_wire_on_interstage_hops(ray_start_cluster):
    """ACCEPTANCE: with wire_dtype="bf16" the inter-stage hops emit
    ray_tpu_collective_wire_bytes_total{op="send",format="bf16"}
    (observed LIVE, while the gang runs — worker registries die with
    the gang), and the loss trajectory is close to, but not bitwise
    equal to, the exact-wire oracle."""
    _two_slice(ray_start_cluster)
    from ray_tpu.train.pipeline import (PipelineConfig, PipelineTrainer,
                                        reference_run)

    stages = _stages()
    ref = reference_run(stages, num_microbatches=4, **_KW)
    seen: list = []
    stop = threading.Event()

    def _poll():
        from ray_tpu.experimental.state.api import metrics_summary

        while not stop.is_set():
            try:
                snaps = {m["name"]: m for m in metrics_summary()}
                wb = snaps.get("ray_tpu_collective_wire_bytes_total")
                rows = [v for v in (wb or {}).get("values", ())
                        if v["tags"].get("format") == "bf16"
                        and v["tags"].get("op") == "send"
                        and v["tags"].get("group") == "zzp_bf16"]
                if rows:
                    seen.append(rows)
                    return
            except Exception:
                pass
            time.sleep(0.2)

    t = threading.Thread(target=_poll, daemon=True)
    t.start()
    result = PipelineTrainer(
        stages, pipeline_config=PipelineConfig(num_microbatches=4,
                                               wire_dtype="bf16",
                                               group_name="zzp_bf16"),
        **_KW).fit()
    stop.set()
    t.join(timeout=5)
    assert result.error is None, result.error
    got = [r["loss"] for r in result.metrics_history]
    assert got != ref["losses"], "bf16 wire should not be bit-exact"
    for a, b in zip(got, ref["losses"]):
        assert abs(a - b) / abs(b) < 0.05, (a, b)
    assert seen, "no bf16 send wire bytes observed during the run"
    assert sum(v["value"] for v in seen[0]) > 0


# ------------------------------------------------------- bubble fraction

def test_bubble_fraction_matches_schedule_theory(ray_start_cluster):
    """ACCEPTANCE: measured per-stage bubble fraction ~ (P-1)/(M+P-1).
    SleepStage compute is contention-immune, so the measurement is
    stable under a loaded suite; tolerance is max(50% relative, 0.1
    absolute). The per-rank attribution is also visible through
    summarize_steps (step_anatomy `pipeline_bubble` activities)."""
    _two_slice(ray_start_cluster)
    from ray_tpu.train.pipeline import (PipelineConfig, PipelineTrainer,
                                        SleepStage,
                                        theoretical_bubble_fraction)

    P, M = 2, 4
    stages = [SleepStage(4, fwd_s=0.03) for _ in range(P)]
    fused: list = []
    stop = threading.Event()

    def _poll():
        from ray_tpu.experimental.state.api import summarize_steps

        while not stop.is_set():
            try:
                s = summarize_steps()
                good = [st for st in s.get("steps", [])
                        if st.get("complete") and len(st["ranks"]) == P
                        and all(r.get("bubble_s", 0) > 0
                                for r in st["ranks"].values())]
                if len(good) >= 2:
                    fused.append(good)
                    return
            except Exception:
                pass
            time.sleep(0.2)

    t = threading.Thread(target=_poll, daemon=True)
    t.start()
    result = PipelineTrainer(
        stages,
        pipeline_config=PipelineConfig(num_microbatches=M,
                                       group_name="zzp_bubble"),
        num_steps=6, microbatch_size=2, learning_rate=0.0, seed=1).fit()
    stop.set()
    t.join(timeout=5)
    assert result.error is None, result.error
    theory = theoretical_bubble_fraction(P, M)
    fracs = [r["bubble_fraction"] for r in result.metrics_history][1:]
    measured = sum(fracs) / len(fracs)
    assert abs(measured - theory) < max(0.5 * theory, 0.1), \
        (measured, theory)
    assert fused, "summarize_steps never showed per-rank bubble_s"
    step = fused[0][-1]
    for rank, br in step["ranks"].items():
        assert 0 < br["bubble_s"] < br["wall_s"], (rank, br)


# ------------------------------------------------------------- chaos E2E

@pytest.fixture
def chaos_cluster_env(ray_start_cluster):
    """2-slice cluster whose every process inherits a seeded fault
    schedule (env exported BEFORE any node starts)."""
    def _start(seed, schedule):
        os.environ["RAY_TPU_FAULT_SEED"] = str(seed)
        os.environ["RAY_TPU_FAULT_SCHEDULE"] = schedule
        return _two_slice(ray_start_cluster)

    yield _start
    os.environ.pop("RAY_TPU_FAULT_SEED", None)
    os.environ.pop("RAY_TPU_FAULT_SCHEDULE", None)


@pytest.mark.chaos
@pytest.mark.fault_injection
def test_stage_rank_death_checkpoint_resume(chaos_cluster_env, tmp_path,
                                            monkeypatch):
    """ACCEPTANCE (CI/chaos satellite): a seeded kill_actor schedule
    shoots stage 1's rank while it serves its 4th next_result —
    mid-training, after checkpointed steps. Held to the ORDER of what
    follows, not to how long it took: the death poisons the gang (stage
    0's pending send/recv windows raise instead of wedging until the
    300s op timeout), fit() tears down and rebuilds once, the rebuilt
    gang restores the newest persisted checkpoint, and every step it
    then runs is the oracle's.

    5 steps and the 4th call: the kill races rank 0's 4th result to the
    driver, so 3 or 4 iterations are persisted and the rebuilt stage 1
    serves 3 or 2 calls (steps, then the end) — under the 4 that would
    shoot it again, since `#N` counts per process."""
    from ray_tpu._private import events
    from ray_tpu._private import flight_recorder as fr
    from ray_tpu.air.config import FailureConfig, RunConfig
    from ray_tpu.train.pipeline import (PipelineConfig, PipelineTrainer,
                                        reference_run)

    # before any node starts: every process writes its dumps here
    monkeypatch.setenv("RAY_TPU_FLIGHT_RECORDER_DIR", str(tmp_path))
    monkeypatch.setattr(fr, "_last_auto_dump_ts", 0.0)
    chaos_cluster_env(7, "kill_actor:stage1-rank0.next_result:#4")
    stages = _stages()
    kw = dict(_KW, num_steps=5)
    ref = reference_run(stages, num_microbatches=4, **kw)

    seq0 = max((e["seq"] for e in events.snapshot()), default=0)
    result = PipelineTrainer(
        stages,
        pipeline_config=PipelineConfig(num_microbatches=4,
                                       checkpoint_every=1,
                                       group_name="zzp_chaos"),
        run_config=RunConfig(failure_config=FailureConfig(max_failures=2)),
        **kw).fit()
    assert result.error is None, result.error

    # the driver's own ring, in the order it recorded: one gang, its
    # death, one rebuild — each incarnation announcing its slice layout
    kinds = ("PIPELINE_GANG_STARTED", "GANG_FAILED", "GANG_RESTARTED")
    mine = sorted((e for e in events.snapshot()
                   if e["seq"] > seq0 and e["kind"] in kinds
                   and e.get("group") == "zzp_chaos"),
                  key=lambda e: e["seq"])
    assert [e["kind"] for e in mine] == [
        "PIPELINE_GANG_STARTED", "GANG_FAILED", "GANG_RESTARTED",
        "PIPELINE_GANG_STARTED"], [e["kind"] for e in mine]
    started, failed, restarted, rebuilt = mine
    assert failed["dead_ranks"] == [1], failed
    assert all(len(e["stage_slices"]) == 2 for e in (started, rebuilt))

    # death -> poison: the rendezvous actor's record of it, out of the
    # dumps the failure wrote while that process still lived; it names
    # the rank that died, and the rebuild came after it
    poisons = []
    for path in tmp_path.glob("blackbox_*/*.jsonl"):
        for line in path.read_text().splitlines():
            if '"COLLECTIVE_GROUP_POISONED"' in line:
                poisons.append(json.loads(line))
    poisons = [e for e in poisons if e.get("group") == "zzp_chaos"]
    assert poisons, sorted(p.name for p in tmp_path.iterdir())
    assert all(e["dead_ranks"] == [1] for e in poisons), poisons
    assert min(e["ts"] for e in poisons) <= restarted["ts"]

    # restore -> resumed steps: the rebuilt gang took up after the last
    # persisted iteration and ran the rest of the oracle's trajectory
    resumed_at = restarted["resume_iteration"]
    assert resumed_at in (3, 4), restarted
    hist = result.metrics_history
    assert [r["step"] for r in hist] == \
        list(range(resumed_at, kw["num_steps"]))
    assert [r["loss"] for r in hist] == ref["losses"][resumed_at:], \
        "resume diverged from oracle"


# ------------------------------------------------------- data-plane feed

def test_streaming_dataset_feeds_stage_zero(ray_start_cluster):
    """Stage 0 pulls microbatches from a ray_tpu.data shard (the
    streaming executor path); later stages receive activations only.
    Loss must be finite and the run completes."""
    _two_slice(ray_start_cluster)
    import ray_tpu.data as rdata
    from ray_tpu.train.pipeline import (PipelineConfig, PipelineTrainer)

    rng = np.random.default_rng(5)
    items = [{"x": rng.standard_normal(6).astype(np.float32),
              "y": rng.standard_normal(3).astype(np.float32)}
             for _ in range(64)]
    ds = rdata.from_items(items, parallelism=4)
    result = PipelineTrainer(
        _stages(),
        pipeline_config=PipelineConfig(num_microbatches=2,
                                       group_name="zzp_data"),
        datasets={"train": ds}, num_steps=2, microbatch_size=4,
        learning_rate=0.05, seed=3).fit()
    assert result.error is None, result.error
    for r in result.metrics_history:
        assert np.isfinite(r["loss"])
