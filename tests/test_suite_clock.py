"""tests/conftest.py's two guards of the suite's clock, each driven through
a pytest session of its own (a subprocess with this conftest loaded as a
plugin, on files written for the case): the limit on every case, and the
facts and the verdict the session ends with."""
import os
import subprocess
import sys
import textwrap

import pytest

from tests import conftest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _session(tmp_path, files, *args):
    """Run pytest on `files` (path -> source) under `tmp_path` as the root,
    with tests/conftest.py as a plugin and the repo's own pytest.ini."""
    for name, source in files.items():
        path = tmp_path / name
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(source))
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "-p", "tests.conftest", "-c",
         os.path.join(ROOT, "pytest.ini"), "--rootdir", str(tmp_path),
         "-p", "no:cacheprovider", "-q", *args, str(tmp_path)],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, PYTHONPATH=ROOT))
    return done.returncode, done.stdout + done.stderr


def test_a_case_past_its_limit_fails_by_name_and_its_worker_goes_on(
        tmp_path):
    """One worker is handed three cases: the first sleeps in the main
    thread past its 1 s, the second waits there for a thread that sleeps,
    the third is fine. Two failures that name their case, its limit and
    the reason; the sleeping thread's stack in the second; the third passes
    in the same worker."""
    code, out = _session(tmp_path, {"test_hangs.py": """
        import threading
        import time

        import pytest


        @pytest.mark.limit(1, reason="this case is the limit's own test")
        def test_sleeps_past_it():
            time.sleep(60)


        def a_get_that_never_returns():
            time.sleep(60)


        @pytest.mark.limit(1, reason="this case is the limit's own test")
        def test_waits_for_a_thread():
            thread = threading.Thread(target=a_get_that_never_returns,
                                      name="the-getter", daemon=True)
            thread.start()
            thread.join()


        def test_the_next_case():
            assert True
        """}, "-p", "xdist", "-n", "1")
    assert code == 1, out
    assert "2 failed, 1 passed" in out, out
    assert "node down" not in out and "crashed" not in out, out
    for case in ("test_sleeps_past_it", "test_waits_for_a_thread"):
        assert f"test_hangs.py::{case}: its call ran past the case's " \
               "limit of 1 s (this case is the limit's own test)" in out, out
    assert "--- thread the-getter" in out
    assert "a_get_that_never_returns" in out


def test_the_limit_is_300_s_and_a_marker_without_a_reason_is_refused(
        tmp_path):
    assert conftest.CASE_LIMIT_S == 300.0
    code, out = _session(tmp_path, {"test_bare.py": """
        import pytest


        @pytest.mark.limit(600)
        def test_wants_more_and_does_not_say_why():
            pass
        """})
    assert code == 1 and "KeyError: 'reason'" in out, out


PRETEND = """
    import pytest

    SECONDS = {"test_compiles_a_cell": 221.0, "test_is_slow_and_says_so": 500.0,
               "test_takes_two_minutes": %r}


    @pytest.hookimpl(tryfirst=True)
    def pytest_runtest_logreport(report):
        name = report.nodeid.rsplit("::", 1)[1]
        if report.when == "call" and name in SECONDS:
            report.duration = SECONDS[name]
    """
RECORDED = {
    "tests/chipbench_tests/test_cells.py": """
        def test_compiles_a_cell():
            pass
        """,
    "tests/test_mine.py": """
        import pytest


        def test_takes_two_minutes():
            pass


        @pytest.mark.slow
        def test_is_slow_and_says_so():
            pass


        def test_is_quick():
            pass
        """}


@pytest.mark.parametrize("seconds, code", [(121.0, 1), (119.0, 0)])
def test_the_session_fails_on_a_case_over_120_s_that_is_not_slow(
        tmp_path, seconds, code):
    """Recorded durations (an inner conftest rewrites three reports): a
    case of the benchmark's own tests at 221 s and a `slow` one at 500 s
    are reported and not judged; one of the suite's own at 121 s fails a
    session whose every case passed, at 119 s it does not."""
    got, out = _session(
        tmp_path, dict(RECORDED, **{"conftest.py": PRETEND % seconds}))
    assert "4 passed" in out, out
    assert got == code, out
    assert "the suite's clock" in out
    assert f"{221 + 500 + seconds:.0f} case-seconds over 4 cases" in out, out
    assert "longest case: 500.0 s  tests/test_mine.py::" \
           "test_is_slow_and_says_so" in out
    assert ("OVER 120 s and not marked slow: 121.0 s  tests/test_mine.py::"
            "test_takes_two_minutes" in out) == bool(code)
    assert out.count("OVER 120 s") == code


def test_the_clocks_share_is_of_the_drivers_command():
    cases = {"tests/test_a.py::test_x": [100.0, False],
             "tests/chipbench_tests/test_b.py::test_y": [221.0, False]}
    lines, over = conftest.clock_report(cases, 1102.5, 6)
    assert over == []
    assert lines == [
        "321 case-seconds over 2 cases in 1102 s of wall time",
        "longest case: 221.0 s  tests/chipbench_tests/test_b.py::test_y",
        "75 % of the driver's 1470 s for this command (-n 6)"]
    # another number of workers is another command: no share
    assert len(conftest.clock_report(cases, 1102.5, None)[0]) == 2
    assert not hasattr(conftest, "_file_budget_s")
    assert "RAY_TPU_TEST_FILE_BUDGET_S" not in open(conftest.__file__).read()
