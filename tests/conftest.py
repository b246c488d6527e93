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
# Tier-1 duration guard. The tier-1 budget is a hard 870 s wall-clock
# timeout over the alphabetical file order, so one slow EARLY file
# silently starves every file behind it out of the run (DOTS_PASSED is
# wall-clock sensitive). This guard turns that silent starvation into an
# attributable failure: any early-alphabet test file whose summed test
# durations (the same per-phase numbers --durations reports) exceed the
# per-file budget fails the session at the end. Late-alphabet files
# (test_z*) are exempt by design — they are sequenced last precisely so
# they spill past the timeout, not displace others. Override/disable via
# RAY_TPU_TEST_FILE_BUDGET_S (0 disables).

_FILE_BUDGET_DEFAULT_S = 120.0
_file_durations: dict = {}


def _file_budget_s() -> float:
    try:
        return float(os.environ.get("RAY_TPU_TEST_FILE_BUDGET_S",
                                    _FILE_BUDGET_DEFAULT_S))
    except ValueError:
        return _FILE_BUDGET_DEFAULT_S


def pytest_runtest_logreport(report):
    fname = report.nodeid.split("::", 1)[0]
    _file_durations[fname] = \
        _file_durations.get(fname, 0.0) + report.duration


def _early_alphabet(fname: str) -> bool:
    base = os.path.basename(fname)
    return base.startswith("test_") and not base.startswith("test_z")


def pytest_sessionfinish(session, exitstatus):
    budget = _file_budget_s()
    if budget <= 0:
        return
    if len(_file_durations) < 10:
        return   # targeted run (one file / a few tests), not the suite:
                 # a developer iterating on a slow file shouldn't fail
                 # their own focused run
    over = sorted(((f, d) for f, d in _file_durations.items()
                   if _early_alphabet(f) and d > budget),
                  key=lambda p: -p[1])
    if not over:
        return
    tr = session.config.pluginmanager.get_plugin("terminalreporter")
    lines = [f"  {f}: {d:.1f}s > {budget:.0f}s budget" for f, d in over]
    msg = ("tier-1 duration guard: early-alphabet test file(s) over the "
           "per-file wall-clock budget (slow early files starve the "
           "870s tier-1 run; mark tests `slow`, speed them up, or raise "
           "RAY_TPU_TEST_FILE_BUDGET_S):\n" + "\n".join(lines))
    if tr is not None:
        tr.write_sep("=", "tier-1 duration guard", red=True)
        tr.write_line(msg)
    if session.exitstatus in (0, 1):
        # escalate only from ok/tests-failed — an interrupted (2) or
        # internally-errored (3) session keeps its more-severe code
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
