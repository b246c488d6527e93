"""Global test configuration.

All tests run on a virtual 8-device CPU mesh (the TPU analog of the
reference's single-node gloo collective tests — see
/root/reference/python/ray/util/collective/tests/single_node_cpu_tests/):
sharding/collective code paths compile and execute exactly as they would on
an 8-chip slice, but on host CPU devices.
"""
import os

# Must be set before any jax backend initializes; worker processes the
# tests spawn inherit both variables.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("RAY_TPU_TESTING", "1")

import signal  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import pytest  # noqa: E402


def _fault_banner() -> str | None:
    """The active fault-injection plane as one reproducible line (an
    in-process install() wins over the env pair it was derived from)."""
    from ray_tpu._private import fault_injection

    if fault_injection.ACTIVE is not None:
        return fault_injection.ACTIVE.banner()
    schedule = os.environ.get("RAY_TPU_FAULT_SCHEDULE")
    if schedule:
        seed = os.environ.get("RAY_TPU_FAULT_SEED", "0")
        return f"RAY_TPU_FAULT_SEED={seed} " \
               f"RAY_TPU_FAULT_SCHEDULE='{schedule}'"
    return None


def _raylint_banner() -> str:
    """The lint baseline size, printed in every run's header so drift
    is visible tier-1-wide: the number should only ever SHRINK (fixed
    findings get their baseline lines deleted) — a session that grew it
    added a documented-by-design exception and must justify it."""
    try:
        from ray_tpu._private.analysis import load_baseline

        entries = load_baseline()
        return (f"raylint: {len(entries)} baselined finding(s) "
                f"(ray_tpu/_private/analysis/baseline.txt; gate: "
                f"tests/test_zz_lint.py, `ray-tpu lint`)")
    except Exception as e:   # never block the suite on the lint plane
        return f"raylint: baseline unreadable ({e!r})"


def pytest_report_header(config):
    lines = [_raylint_banner()]
    banner = _fault_banner()
    if banner:
        lines.append(f"fault injection: ACTIVE — {banner}")
    else:
        lines.append("fault injection: disabled "
                     "(RAY_TPU_FAULT_SCHEDULE activates it; see "
                     "ray_tpu/_private/fault_injection.py)")
    return lines


def _memory_orphan_digest() -> str:
    """One-line leak digest for failed chaos tests: the local memory
    ledger's sweep verdict (orphan count/bytes, worst offender's
    category+group+reason, dropped-free stages) — points a post-mortem
    at `ray-tpu memory` / summarize_memory() without the full fan-out
    cost on every failure."""
    try:
        from ray_tpu._private import memory_anatomy as _ma

        snap = _ma.local_snapshot(top_k=1)
        if not snap.get("enabled", True):
            return "memory anatomy disabled (RAY_TPU_INTERNAL_TELEMETRY=0)"
        orphans = snap.get("orphans") or []
        dropped = snap.get("dropped_frees") or {}
        if not orphans and not dropped:
            return ("no orphans, no dropped frees "
                    "(state.api.summarize_memory() for the cluster view)")
        parts = []
        if orphans:
            worst = max(orphans, key=lambda r: r.get("nbytes") or 0)
            parts.append(
                f"{len(orphans)} orphan(s), "
                f"{sum(int(r.get('nbytes') or 0) for r in orphans)} bytes "
                f"(worst: {worst.get('category')} "
                f"group={worst.get('group')} reason={worst.get('reason')})")
        if dropped:
            parts.append("dropped frees: " + ", ".join(
                f"{k}={v}" for k, v in sorted(dropped.items())))
        return "; ".join(parts) + \
            " — summarize_memory() / `ray-tpu memory` for provenance"
    except Exception as e:
        return f"memory anatomy unavailable ({e!r})"


def _flight_recorder_hint() -> str:
    """Where this failure's black box is (or would be): the last dump
    this process wrote, else the base dir cluster processes dump into —
    post-mortems of seeded-kill tests start from the black box, not
    from scrollback."""
    try:
        from ray_tpu._private import flight_recorder as fr

        path = fr.last_dump_path() or fr.find_latest_dump()
        if path:
            return f"dump: {path}"
        return (f"no dump written yet; auto-dumps land under "
                f"{fr.base_dir()} (ray-tpu blackbox dump for a "
                f"manual one)")
    except Exception as e:
        return f"flight recorder unavailable ({e!r})"


def _checkpoint_hint() -> str:
    """Newest sharded-checkpoint generation + its manifest status for
    failed chaos tests: a restore that 'lost' progress usually means the
    newest generation is torn/quarantined — say so next to the black
    box instead of making the post-mortem rediscover it with the CLI."""
    try:
        import os as _os

        root = _os.environ.get("RAY_TPU_CHECKPOINT_DIR")
        if not root:
            return ("no checkpoint root in this process "
                    "(RAY_TPU_CHECKPOINT_DIR unset; `ray-tpu "
                    "checkpoints <root>` to inspect one)")
        from ray_tpu.train.sharded_checkpoint import summarize_checkpoints

        entries = summarize_checkpoints(root, digests=False)
        if not entries:
            return f"no generations under {root}"
        newest = entries[0]
        return (f"newest generation: {newest['path']} "
                f"status={newest['status']}"
                + (f" reason={newest['reason']}" if newest["reason"]
                   else "")
                + f" ({len(entries)} on disk; `ray-tpu checkpoints "
                  f"{root}` for digests)")
    except Exception as e:
        return f"checkpoint summary unavailable ({e!r})"


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """Stamp failures with the seed+schedule that reproduces the exact
    injected-fault sequence (the injector is deterministic per call
    index, so this one line replays the failure), and — for chaos /
    fault_injection-marked tests — with the flight-recorder dump path,
    so the post-mortem starts from the black box."""
    outcome = yield
    rep = outcome.get_result()
    if rep.when == "call" and rep.failed:
        banner = _fault_banner()
        if banner:
            rep.sections.append(
                ("fault injection", f"reproduce with: {banner}"))
        if item.get_closest_marker("chaos") is not None or \
                item.get_closest_marker("fault_injection") is not None:
            rep.sections.append(
                ("flight recorder", _flight_recorder_hint()))
            rep.sections.append(
                ("memory anatomy", _memory_orphan_digest()))
            rep.sections.append(
                ("checkpoints", _checkpoint_hint()))


# ---------------------------------------------------------------------------
# A limit for every case. Nothing the suite guards is worth ten minutes of
# one worker in a `get()` (ROADMAP D15): a phase of a case (set-up, call,
# tear-down) that runs past its limit FAILS, by name, with where it hung,
# and the worker goes on to its next case. CASE_LIMIT_S by default;
# `@pytest.mark.limit(seconds, reason="...")` where a case needs another.
# The alarm is SIGALRM's: it reaches the main thread when the interpreter
# next runs Python there, so a call that never leaves native code is out of
# its reach.
CASE_LIMIT_S = 300.0


def _other_threads() -> str:
    names = {t.ident: t.name for t in threading.enumerate()}
    return "".join(
        f"--- thread {names.get(ident, ident)}\n"
        + "".join(traceback.format_stack(frame))
        for ident, frame in sys._current_frames().items()
        if ident != threading.get_ident())


def _limited(item, phase):
    marker = item.get_closest_marker("limit")
    seconds, why = (marker.args[0], marker.kwargs["reason"]) if marker \
        else (CASE_LIMIT_S, "the default")
    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def over(signum, frame):
        pytest.fail(f"{item.nodeid}: its {phase} ran past the case's limit "
                    f"of {seconds:g} s ({why}); the other threads:\n"
                    f"{_other_threads()}")

    previous = signal.signal(signal.SIGALRM, over)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    yield from _limited(item, "set-up")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    yield from _limited(item, "call")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item):
    yield from _limited(item, "tear-down")


# ---------------------------------------------------------------------------
# The suite's clock. The driver runs the whole of `tests/` under six
# workers (`-n 6 --dist load`) and cuts the command at CLOCK_S; a cut run
# counts only as far as it got. The session ends with the facts that
# command depends on and fails on the one thing that can cut a run by
# itself: a case that is not `slow` and takes over CASE_MAX_S of a worker.
# The benchmark's own tests (tests/chipbench_tests/, a `benchmark` PR's to
# change) are reported and not judged.
CLOCK_S = 1470.0
CASE_MAX_S = 120.0
_cases: dict = {}       # nodeid -> [seconds over its phases, marked slow]
_started = time.monotonic()


def pytest_runtest_logreport(report):
    case = _cases.setdefault(report.nodeid, [0.0, False])
    case[0] += report.duration
    case[1] = case[1] or "slow" in report.keywords


def clock_report(cases: dict, wall_s: float, workers) -> tuple:
    """`(lines, over)`: the facts, and the cases that fail the session."""
    longest = max(cases, key=lambda c: cases[c][0])
    lines = [f"{sum(s for s, _ in cases.values()):.0f} case-seconds over "
             f"{len(cases)} cases in {wall_s:.0f} s of wall time",
             f"longest case: {cases[longest][0]:.1f} s  {longest}"]
    if workers == 6:
        lines.append(f"{100 * wall_s / CLOCK_S:.0f} % of the driver's "
                     f"{CLOCK_S:.0f} s for this command (-n 6)")
    over = sorted((c for c, (s, slow) in cases.items()
                   if s > CASE_MAX_S and not slow
                   and not c.startswith("tests/chipbench_tests/")),
                  key=lambda c: -cases[c][0])
    lines += [f"OVER {CASE_MAX_S:.0f} s and not marked slow: "
              f"{cases[c][0]:.1f} s  {c}" for c in over]
    return lines, over


def pytest_sessionfinish(session, exitstatus):
    if hasattr(session.config, "workerinput") or not _cases:
        return          # a worker of a parallel run: its controller reports
    lines, over = clock_report(
        _cases, time.monotonic() - _started,
        getattr(session.config.option, "numprocesses", None))
    tr = session.config.pluginmanager.get_plugin("terminalreporter")
    if tr is not None:
        tr.write_sep("=", "the suite's clock", red=bool(over))
        for line in lines:
            tr.write_line(line)
    if over and session.exitstatus == 0:
        session.exitstatus = 1


@pytest.fixture(scope="session")
def once_a_run(tmp_path_factory):
    """``once_a_run(name, make)``: ``make()``'s JSON-able result, made
    once a test run — the workers of a parallel run share the first one's
    through a file beside their temp directories (a heavy fixture's
    cluster, fit or subprocess is then paid once, not once a worker that
    is handed one of its cases)."""
    import json

    def once(name, make):
        if not os.environ.get("PYTEST_XDIST_WORKER"):
            return make()
        from filelock import FileLock

        shared = tmp_path_factory.getbasetemp().parent / f"{name}.json"
        with FileLock(f"{shared}.lock"):
            if not shared.is_file():
                shared.write_text(json.dumps(make()))
            return json.loads(shared.read_text())

    return once


@pytest.fixture
def ray_start_regular():
    """Start a fresh single-node runtime for a test, shut down after.

    Mirrors the reference fixture of the same name
    (python/ray/tests/conftest.py:245-360).
    """
    try:
        import ray_tpu

        ray_tpu.init(num_cpus=4, object_store_memory=64 * 1024 * 1024)
    except (ImportError, ModuleNotFoundError) as e:
        pytest.skip(f"runtime not built yet: {e}")
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """A multi-node in-process cluster, the reference's central test trick
    (python/ray/cluster_utils.py:99)."""
    try:
        from ray_tpu.cluster_utils import Cluster
    except (ImportError, ModuleNotFoundError) as e:
        pytest.skip(f"cluster_utils not built yet: {e}")
    cluster = Cluster()
    yield cluster
    cluster.shutdown()


@pytest.fixture
def runs_on(monkeypatch):
    """``runs_on("tpu")``: until the test ends `ops.target.where` answers
    ``(platform, devices)`` whatever the mesh — what the ops see in a
    compile for a described chip. Every op's kernel-or-plain choice hangs
    on that one function, so this is the tests' one seam for it."""
    def answer(platform: str, devices: int = 1):
        from ray_tpu.ops import target

        monkeypatch.setattr(
            target, "where",
            lambda mesh=None, *, interpret=False: (platform, devices))
    return answer


@pytest.fixture
def interpreted(monkeypatch):
    """`apply_attention(impl="flash")` through the Pallas interpreter: what
    a TPU's flash kernels compute, on this CPU."""
    import functools

    from ray_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, interpret=True))
