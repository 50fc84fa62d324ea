"""The suite's own limit (tests/conftest.py `TEST_LIMIT_S`): a test that
runs past it fails with the limit's message, and no wait written into the
subprocess drills, nor XLA's rendezvous limit, is longer than it. A wait
that outlasts the limit holds one xdist worker while the driver's clock
(1,470 s for the whole suite) runs: PR 37's run was cut that way."""

import os
import re
import signal
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture()
def suite(request):
    """tests/conftest.py as pytest loaded it (`import conftest` would name
    whichever conftest.py was imported last)."""
    return request.config.pluginmanager.get_plugin(
        os.path.join(HERE, "conftest.py"))


def test_a_body_that_sleeps_past_the_limit_fails(suite, monkeypatch,
                                                 tmp_path):
    monkeypatch.setattr(suite, "TEST_LIMIT_S", 0.2)
    with open(tmp_path / "stderr", "w+") as stderr:
        monkeypatch.setattr(suite, "_REAL_STDERR", stderr.fileno())
        start = time.monotonic()
        with pytest.raises(pytest.fail.Exception,
                           match="ran past TEST_LIMIT_S = 0.2 s"):
            with suite.limited(suite.TEST_LIMIT_S, "a sleeper"):
                time.sleep(30)
        assert time.monotonic() - start < 5
        stderr.seek(0)
        said = stderr.read()
    # with its stack printed: the sleeping frame, and who it was
    assert "a sleeper ran past its limit" in said
    assert "test_a_body_that_sleeps_past_the_limit_fails" in said


def test_a_body_that_returns_in_time_passes(suite, monkeypatch):
    monkeypatch.setattr(suite, "TEST_LIMIT_S", 0.2)
    with suite.limited(suite.TEST_LIMIT_S, "a sprinter"):
        pass
    time.sleep(0.3)           # the inner timer went with its body


def test_every_test_runs_under_the_constant(suite):
    """The hooks armed this very test, from the constant, and a nested
    use hands the test's own timer back with what it had left."""
    assert suite.TEST_LIMIT_S <= 300
    left = signal.getitimer(signal.ITIMER_REAL)[0]
    assert 0 < left <= suite.TEST_LIMIT_S
    with suite.limited(5, "nested"):
        assert signal.getitimer(signal.ITIMER_REAL)[0] <= 5
    assert 5 < signal.getitimer(signal.ITIMER_REAL)[0] <= left


def test_a_test_that_took_its_worker_down_is_not_run_again(
        suite, request, monkeypatch):
    """xdist's `loadfile` hands a dead worker's file back from the test it
    died in: the word the dead worker left fails that test at once, before
    any fixture of it is built, and the file goes on."""
    monkeypatch.setenv("PYTEST_XDIST_TESTRUNUID", "a-run-of-this-test")
    word = suite._died_here(request.node)
    monkeypatch.setattr(suite.faulthandler, "dump_traceback_later",
                        lambda *a, **kw: None)
    setup = suite.pytest_runtest_setup(request.node)
    next(setup)                       # no word: armed, the set-up may run
    assert open(word).read() == request.node.nodeid
    setup.close()
    with pytest.raises(pytest.fail.Exception, match="not run again"):
        next(suite.pytest_runtest_setup(request.node))
    os.remove(word)
    monkeypatch.delenv("PYTEST_XDIST_TESTRUNUID")
    assert suite._died_here(request.node) == ""   # one process: no word


# every way the drills write a wait: in their own code and in the scripts
# of their children, which are string literals
WAITS = re.compile(
    r"(?:timeout\s*=|sleep\(|join\(|wait\(|(?:time|monotonic)\(\)\s*\+"
    r"|\b[A-Z_]*(?:WAIT|TIMEOUT|DEADLINE)[A-Z_]*\s*=)\s*(\d+(?:\.\d+)?)")


@pytest.mark.parametrize("name", ["test_multiprocess.py",
                                  "test_recompile.py"])
def test_no_literal_wait_outlasts_the_limit(suite, name):
    with open(os.path.join(HERE, name)) as f:
        waits = [float(w) for w in WAITS.findall(f.read())]
    assert waits, "the pattern finds the file's waits"
    assert max(waits) <= suite.TEST_LIMIT_S, sorted(waits)[-3:]


def test_the_pattern_sees_each_kind_of_wait():
    text = ("p.communicate(timeout=600); time.sleep(3600); t.join(500)\n"
            "deadline = time.time() + 700; proc.wait(800)\n"
            "end = time.monotonic() +900\nCHILD_WAIT_S = 1000")
    assert [float(w) for w in WAITS.findall(text)] == [
        600, 3600, 500, 700, 800, 900, 1000]


def test_xla_gives_a_stuck_rendezvous_up_inside_the_limit(suite):
    flags = dict(re.findall(
        r"--xla_cpu_collective_call_(\w+)_timeout_seconds=(\d+)",
        os.environ["XLA_FLAGS"]))
    assert set(flags) == {"warn_stuck", "terminate"}
    assert (0 < int(flags["warn_stuck"]) < int(flags["terminate"])
            < suite.TEST_LIMIT_S)
