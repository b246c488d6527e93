"""The reduction from a trace to numbers: on a hand-made event list whose
answers are known, and on a trace recorded on the v5e (five steps of the
``gpt2s-b16`` cell, trimmed to the lines the reduction reads)."""
import os

import pytest

from chipbench import flops
from chipbench import trace_reduce as tr
from chipbench.readers import (
    trace_collective_exposed,
    trace_flash,
    trace_idle_share,
    trace_step_device_ms,
)

E = tr.Event
SPANS = ("data_next", "step_dispatch", "loss_fetch", "report")
RECORDED = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "chipbench", "testdata",
    "gpt2s-b16.trimmed.xplane.pb")


def test_interval_arithmetic():
    assert tr.union([(5, 9), (0, 3), (2, 4), (9, 9), (8, 12)]) == \
        [(0, 4), (5, 12)]
    assert tr.total([(0, 10), (5, 15), (20, 21)]) == 16
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    assert tr.subtract([(0, 100)], [(10, 20), (15, 30), (90, 120)]) == \
        [(0, 10), (30, 90)]
    assert tr.subtract([(0, 10)], []) == [(0, 10)]


def test_nested_events_count_each_instant_once():
    ops = [E("while", 0, 100), E("a", 10, 30), E("inner", 30, 50),
           E("b", 35, 45), E("c", 60, 90), E("d", 120, 130)]
    assert tr.self_segments(ops) == [
        ("while", 0, 10), ("a", 10, 30), ("inner", 30, 35), ("b", 35, 45),
        ("inner", 45, 50), ("while", 50, 60), ("c", 60, 90),
        ("while", 90, 100), ("d", 120, 130)]


def _synthetic():
    """Two periods of 1,000 ns: a step program of 800 ns holding a loop
    with compute 0-300, a synchronous all-reduce 300-400 (all exposed),
    compute 400-700, and an async all-reduce 600-800 of which compute
    hides 600-700: half hidden. Then 200 ns idle, in which the host
    fetches the loss (100), reports (60) and fetches data (40)."""
    ops, async_ops, modules, host = [], [], [], []
    for k in (0, 1, 2):
        t = 1000 * k
        modules.append(E("jit_step(1)", t, t + 800))
        modules.append(E("jit_other(2)", t + 900, t + 910))
        ops += [E("%while.1 = () while()", t, t + 700),
                E("%fusion.1 = f32[8]{0} fusion(), kind=kLoop", t, t + 300),
                E("%all-reduce.1 = f32[8]{0} all-reduce()", t + 300, t + 400),
                E("%fusion.2 = f32[8]{0} fusion(), kind=kLoop",
                  t + 400, t + 700),
                E("%all-reduce-done.2 = f32[8]{0} all-reduce-done()",
                  t + 700, t + 800)]
        async_ops.append(
            E("%all-reduce-start.2 = f32[8]{0} all-reduce-start()",
              t + 600, t + 800))
        host += [E("loss_fetch", t + 10, t + 900),
                 E("report", t + 900, t + 960),
                 E("data_next", t + 960, t + 1000),
                 E("step_dispatch", t + 1000, t + 1010)]
    return {"devices": {"/device:TPU:0": {
        "ops": ops, "async": async_ops, "modules": modules}}, "host": host}


def test_synthetic_trace_has_the_known_answers():
    summary = tr.reduce_trace(_synthetic(), SPANS)
    dev = summary["devices"]["/device:TPU:0"]
    assert dev["steps"] == 2 and dev["window_ns"] == 2000
    assert dev["busy_ns"] == 1600 and dev["step_busy_ns"] == [800, 800]
    assert dev["gaps"] == [(800, 1000), (1800, 2000)]
    # a collective half hidden: 300 ns a step, 200 with no compute beside
    assert dev["collective_ns"] == 600
    assert dev["collective_exposed_ns"] == 400
    # the loop's own time is nothing: its body covers it
    assert "%while.1 = () while()" not in {
        k for k, v in dev["per_op_ns"].items() if v}
    assert summary["idle_by_span_s"] == pytest.approx(
        {"loss_fetch": 200e-9, "report": 120e-9, "data_next": 80e-9})
    ctx = {"trace": summary}
    assert trace_idle_share.read(ctx) == pytest.approx(20.0)
    assert trace_step_device_ms.read(ctx) == pytest.approx(800e-6)
    assert trace_collective_exposed.read(ctx) == pytest.approx(20.0)
    down = tr.breakdown(summary)
    assert down["device_ops"][0] == ["fusion.1 fusion:kLoop f32[8]",
                                     pytest.approx(600e-9)]
    assert [g[0] for g in down["idle_gaps"]] == ["loss_fetch", "report",
                                                 "data_next"]
    # no trace, no number; one execution is no period
    assert trace_idle_share.read({"trace": None}) is None
    one = _synthetic()
    one["devices"]["/device:TPU:0"]["modules"] = [E("jit_step(1)", 0, 800)]
    assert tr.reduce_trace(one, SPANS) is None


def test_gap_no_span_covers_is_other():
    gaps = tr.attribute_gaps([(0, 100)], [E("report", 20, 50),
                                          E("unrelated", 0, 100)], SPANS)
    assert gaps == {"report": 30, "other": 70}


def test_short_op_names():
    assert tr.short_op_name(
        "%fusion.217 = f32[50304,768]{1,0:T(8,128)} fusion(f32[16]{0} %x), "
        "kind=kOutput, calls=%fused") == \
        "fusion.217 fusion:kOutput f32[50304,768]"
    assert tr.short_op_name(
        '%closed_call.55 = (bf16[192,1024,64]{2,1,0:T(8,128)(2,1)S(1)}, '
        'f32[192,8,1024]{2,1,0}) custom-call(bf16[192,1024,64]{2,1,0} %q), '
        'custom_call_target="tpu_custom_call"') == \
        ("closed_call.55 custom-call:tpu_custom_call "
         "(bf16[192,1024,64], f32[192,8,1024])")
    assert tr.short_op_name("ThreadpoolListener::Record") == \
        "ThreadpoolListener::Record"
    assert tr.is_collective("%all-reduce-start.3 = f32[8] all-reduce-start()")
    assert tr.is_collective("%reduce-scatter.1 = f32[8] reduce-scatter()")
    assert not tr.is_collective("%fusion.1 = f32[8] fusion()")


def test_a_collective_is_told_by_its_opcode_not_its_name():
    """As a sharded step has them (PR 27's trace): an all-reduce that
    ``jax.lax.psum`` named, an asynchronous neighbour exchange whose
    ``-start`` is on the async line and whose ``-done`` waits on the
    compute line, and a fusion that is only NAMED after a collective."""
    psum = "%psum.101 = f32[36,1280,2560]{2,1,0} all-reduce(f32[8]{0} %x)"
    named = ("%all-reduce-scatter.3 = f32[8]{0} fusion(f32[8]{0} %x), "
             "kind=kLoop")
    start = ("%collective-permute-start.6 = (bf16[8]{0}, bf16[8]{0}) "
             "collective-permute-start(bf16[8]{0} %y)")
    done = ("%collective-permute-done.6 = bf16[8]{0} "
            "collective-permute-done((bf16[8]{0}, bf16[8]{0}) %z)")
    assert tr.is_collective(psum)
    assert tr.is_collective(start) and tr.is_collective(done)
    assert not tr.is_collective(named)
    assert not tr.is_collective("all-reduce.1")       # no instruction text
    assert tr.is_collective(
        "%a2a.1 = (f32[8]{0}, f32[8]{0}) all-to-all(f32[8]{0} %x)")
    # periods of 1,000 ns: the fusion 0-400, the exchange in flight
    # 300-600 and waited for 400-600 (200 exposed, 100 hidden), the psum
    # 600-700 (exposed), idle after
    ops, async_ops, modules = [], [], []
    for t in (0, 1000, 2000):
        modules.append(E("jit_step(1)", t, t + 700))
        ops += [E(named, t, t + 400), E(done, t + 400, t + 600),
                E(psum, t + 600, t + 700)]
        async_ops.append(E(start, t + 300, t + 600))
    summary = tr.reduce_trace({"devices": {"/device:TPU:0": {
        "ops": ops, "async": async_ops, "modules": modules}}, "host": []},
        SPANS)
    dev = summary["devices"]["/device:TPU:0"]
    assert dev["collective_ns"] == 2 * 400
    assert dev["collective_exposed_ns"] == 2 * 300
    assert trace_collective_exposed.read({"trace": summary}) == \
        pytest.approx(30.0)


@pytest.mark.parametrize("text, collective", [
    # a fused reduce-scatter as the TPU compiler is remembered to emit it
    ("%all-reduce-scatter.3 = f32[8]{0} fusion(f32[32]{0} %x), kind=kCustom, "
     "calls=%all-reduce-scatter.3.computation", True),
    ("%fusion.9 = f32[8]{0} fusion(f32[32]{0} %x), kind=kCustom, "
     "calls=%reduce-scatter.clone", True),
    # the gather and the scatter of the recorded v5e trace: kCustom, compute
    ("%fusion.2 = f32[50304,768]{1,0:T(8,128)} fusion(f32[16384]{0} %x), "
     "kind=kCustom, calls=%fused_computation.299", False),
    # named after a collective, an ordinary fusion
    ("%all-reduce-scatter.3 = f32[8]{0} fusion(f32[8]{0} %x), kind=kOutput, "
     "calls=%fused_computation.4", False),
    # an asynchronous wrapper that is not printed as <opcode>-start
    ("%all-to-all-start.1 = ((f32[8]{0}), f32[8]{0}, u32[]) "
     "async-start(f32[8]{0} %x), calls=%async_computation.1", True),
    ("%async-done.2 = f32[8]{0} async-done(((f32[8]{0}), f32[8]{0}, u32[]) "
     "%s), calls=%all-gather.7.wrapped", True),
    ("%async-start.5 = ((f32[8]{0}), f32[8]{0}, u32[]) "
     "async-start(f32[8]{0} %x), calls=%wrapped_sort", False),
    ("%ag.1 = f32[8]{0} all-gather-update((f32[8]{0}, f32[8]{0}) %s)", True),
])
def test_a_wrapped_collective_is_told_by_what_it_wraps(text, collective):
    assert tr.is_collective(text) == collective


def test_recorded_v5e_trace():
    """Numbers of the recorded trace, as read by hand from the same file:
    five periods of about 158 ms, the device busy for all but a gap of
    2-3 ms a step that the host spends in the loss fetch's wake-up and
    in report; 12 layers x 5 steps = 60 calls of each flash kernel."""
    trace = tr.load_xplane(RECORDED, SPANS)
    assert list(trace["devices"]) == ["/device:TPU:0"]
    summary = tr.reduce_trace(trace, SPANS)
    assert summary["steps"] == 5 and summary["chips"] == 1
    assert 0.75 < summary["window_s"] < 0.85
    ctx = {"trace": summary, "peaks": flops.peaks_for("TPU v5 lite")}
    assert 150.0 < trace_step_device_ms.read(ctx) < 160.0
    assert 0.5 < trace_idle_share.read(ctx) < 5.0
    assert trace_collective_exposed.read(ctx) is None      # one chip
    assert 20.0 < trace_flash.read(ctx, what="share") < 35.0
    assert 10.0 < trace_flash.read(ctx, what="roofline") < 25.0
    kinds = {}
    for name, calls in summary["per_op_calls"].items():
        cost = flops.flash_call_cost(name)
        if cost:
            kinds[cost[0]] = calls
            assert cost[1:] in ((25769803776, 106954752),
                                (38654705664, 138412032),
                                (51539607552, 163577856))
    assert kinds == {"fwd": 60, "bwd_dq": 60, "bwd_dkv": 60}
    idle = summary["idle_by_span_s"]
    assert sum(idle.values()) == pytest.approx(
        summary["window_s"] - summary["busy_s"])
    assert max(idle, key=idle.get) in ("loss_fetch", "report")
    down = tr.breakdown(summary)
    assert len(down["device_ops"]) == 10
    assert "tpu_custom_call" in down["device_ops"][0][0]
