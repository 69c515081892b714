"""Test harness configuration.

Multi-chip behavior is tested on a virtual 8-device CPU mesh
(``--xla_force_host_platform_device_count=8``), standing in for a TPU pod
slice — the analog of the reference's Travis single-box "multi-node"
simulation (.travis.yml:10-18, SURVEY.md §4). ``JAX_PLATFORMS=cpu`` is
set here, before JAX is imported, so the suite and every child it
starts stay off any accelerator.
"""

import os

import pytest

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_NUM_CPU_DEVICES", "8")
os.environ["JAX_PLATFORMS"] = "cpu"


def pytest_configure(config):
    """LMR_LOCKCHECK=1: install the runtime lock-order sanitizer before
    test modules import the package, so module-level locks (tracer,
    native-build cache, ...) are created through the recording
    factories.  The session fails in pytest_sessionfinish if any
    observed acquisition order is absent from the static lock model."""
    if os.environ.get("LMR_LOCKCHECK") == "1":
        from lua_mapreduce_tpu.utils import lockcheck
        lockcheck.install()


def pytest_sessionfinish(session, exitstatus):
    if os.environ.get("LMR_LOCKCHECK") != "1":
        return
    from lua_mapreduce_tpu.utils import lockcheck
    from lua_mapreduce_tpu.analysis.lockset import static_lock_model
    lockcheck.uninstall()   # stop recording before the analyzer runs
    rep = lockcheck.report()
    violations = lockcheck.verify(static_lock_model())
    print(f"\n[lockcheck] {rep['acquisitions']} acquisitions across "
          f"{len(rep['sites'])} lock sites, "
          f"{len(rep['edges'])} distinct order edges")
    if violations:
        for v in violations:
            print(f"[lockcheck] VIOLATION: {v}")
        session.exitstatus = 1


@pytest.fixture
def no_thread_leak():
    """Asserts no non-daemon thread outlives the test body — the
    dynamic half of the thread-shutdown audit (the static half is
    analysis.threads.shutdown_report).  A short grace window lets
    executor/pool teardown stragglers finish their last poll."""
    import threading
    import time

    before = set(threading.enumerate())

    def leaked():
        return [t for t in threading.enumerate()
                if t not in before and t.is_alive() and not t.daemon]

    yield
    deadline = time.monotonic() + 5.0
    while leaked() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not leaked(), (
        f"non-daemon threads leaked past teardown: "
        f"{[t.name for t in leaked()]}")


def pytest_addoption(parser):
    parser.addoption(
        "--full", action="store_true", default=False,
        help="also run tests marked 'heavy' (soak, chaos, convergence, "
             "sharded-prefill e2e) — the full-coverage mode test.sh uses")


def pytest_collection_modifyitems(config, items):
    """Default runs skip the heavy tail so the suite stays fast enough
    to be run often (VERDICT r3 weak item 5: 23 min suites get run
    less); ``--full`` / LMR_FULL=1 restores every test."""
    full_env = os.environ.get("LMR_FULL", "")
    if config.getoption("--full") or full_env.lower() not in ("", "0",
                                                              "false"):
        return
    if "heavy" in (config.getoption("-m") or ""):
        return          # explicitly selecting heavy tests runs them
    skip = pytest.mark.skip(
        reason="heavy: run with --full or LMR_FULL=1")
    for item in items:
        if "heavy" in item.keywords:
            item.add_marker(skip)
