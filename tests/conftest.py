"""Global test configuration.

Tests run on CPU with a virtual 8-device mesh so every sharding path
(dp/fsdp/tp/sp) is exercised without TPU hardware, mirroring how the
reference tests multi-node logic in-process (ray: python/ray/tests/conftest.py
fixtures + cluster_utils.Cluster).
"""

import os

# Forced (not setdefault): the outer environment may point JAX at a real
# TPU, but tests need the 8-device virtual CPU mesh.  The env vars cover
# child processes (workers); jax.config covers THIS process in case jax
# was imported before this file ran.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
try:
    jax.config.update("jax_num_cpu_devices", 8)
except RuntimeError:
    # Backends already initialized (something probed jax.devices() before
    # conftest ran).  The XLA_FLAGS env var above can no longer take
    # effect either, so surface a clear failure only if the mesh is
    # actually too small when tests run.
    pass

import contextlib  # noqa: E402
import faulthandler  # noqa: E402
import glob  # noqa: E402
import hashlib  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import pytest  # noqa: E402

#: Seconds every test gets (set-up of its function-scoped fixtures, the
#: call, their tear-down).  About three times the slowest honest test
#: of the tier-1 run on six workers; a test that needs more says so
#: with ``@pytest.mark.limit(seconds)``.
TEST_LIMIT_S = 180

#: Seconds a test gets once a test of its module has run past its
#: limit: the module's shared cluster (a module- or session-scoped
#: fixture) is suspect from then on, and an honest test of a sound one
#: does not need more.  A hung module costs one limit, not one a test.
SUSPECT_MODULE_LIMIT_S = 30

#: The modules of this process in which a test ran past its limit.
_suspect_modules = set()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running scale/stress tests excluded from the "
        "tier-1 `-m 'not slow'` run",
    )
    config.addinivalue_line(
        "markers",
        "limit(seconds): this test's own time limit, in place of the "
        f"{TEST_LIMIT_S} s every test gets (tests/conftest.py)",
    )


@contextlib.contextmanager
def time_limit(seconds: float, name: str):
    """Fail the body, by name, once it has run ``seconds``: a hang
    costs one named failure and not the run, and the rest of its module
    the short limit (``limit_for``).  SIGALRM's handler runs in
    the main thread between two bytecodes (a blocking lock or sleep is
    interrupted for it): it dumps every thread's stack into the failure
    message and raises ``pytest.fail``.  A hang inside a C call never
    gets that far, so ``faulthandler`` ends the process 30 s later (an
    xdist worker, which xdist replaces).  The handler and timer found on
    entry are put back on exit, whatever the outcome."""

    def on_alarm(signum, frame):
        _suspect_modules.add(name.partition("::")[0])
        with tempfile.TemporaryFile() as f:
            faulthandler.dump_traceback(file=f, all_threads=True)
            f.seek(0)
            stacks = f.read().decode(errors="replace")
        pytest.fail(
            f"{name} ran past its limit of {seconds:g} s; the threads "
            f"were at:\n{stacks}",
            pytrace=False,
        )

    old_handler = signal.signal(signal.SIGALRM, on_alarm)
    old_timer = signal.setitimer(signal.ITIMER_REAL, seconds)
    faulthandler.dump_traceback_later(
        seconds + 30, exit=True, file=sys.__stderr__
    )
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()
        signal.setitimer(signal.ITIMER_REAL, *old_timer)
        signal.signal(signal.SIGALRM, old_handler)


def limit_for(nodeid: str, own=None) -> float:
    """The seconds ``nodeid`` gets: its marker's (``own``) or
    ``TEST_LIMIT_S``, and no more than ``SUSPECT_MODULE_LIMIT_S`` once
    a test of its module has run past its limit."""
    seconds = TEST_LIMIT_S if own is None else own
    if nodeid.partition("::")[0] in _suspect_modules:
        seconds = min(seconds, SUSPECT_MODULE_LIMIT_S)
    return seconds


@pytest.fixture(autouse=True)
def _test_limit(request):
    nodeid = request.node.nodeid
    marker = request.node.get_closest_marker("limit")
    seconds = limit_for(nodeid, marker.args[0] if marker else None)
    # Under ``--dist loadfile`` xdist hands a file whose worker died
    # back to the next worker WITH the test that killed it, up to nine
    # times over.  A file per running test, in the run's own name, lets
    # the second worker fail that test at once and go on with the rest.
    run = os.environ.get("PYTEST_XDIST_TESTRUNUID")
    running = run and os.path.join(
        tempfile.gettempdir(),
        f"rt-test-{run}-{hashlib.sha1(nodeid.encode()).hexdigest()[:16]}",
    )
    if running:
        if os.path.exists(running):
            os.unlink(running)
            pytest.fail(
                f"{nodeid} took its xdist worker down earlier in this "
                "run (a hang no signal reached, or a crash): not run again",
                pytrace=False,
            )
        open(running, "w").close()
    try:
        with time_limit(seconds, nodeid):
            yield
    finally:
        if running:
            os.unlink(running)


def _own_session_dirs() -> set:
    """The session directories of clusters THIS process started:
    ``default_session_dir()`` ends their names in the starter's pid."""
    return set(glob.glob(f"/tmp/ray_tpu/session_*_{os.getpid()}"))


def pytest_sessionstart(session):
    # a directory of an earlier process that had this pid is not ours
    session.config._rt_found = _own_session_dirs()


@pytest.hookimpl(trylast=True)
def pytest_sessionfinish(session):
    """Remove the session directories this process's clusters left (3.5 GB
    a run, over six xdist workers: each removes its own), but none that a
    live GCS or raylet still names on its command line.  Nobody else's are
    touched: another run's carry another pid.  A killed raylet's arena in
    /dev/shm is the program's to unlink (``node.unlink_arena_of``)."""
    cmdlines = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        with contextlib.suppress(OSError):
            with open(f"/proc/{pid}/cmdline", errors="replace") as f:
                cmdlines.append(f.read())
    for path in _own_session_dirs() - session.config._rt_found:
        if not any(path in text for text in cmdlines):
            shutil.rmtree(path, ignore_errors=True)


def pytest_collection_modifyitems(config, items):
    """A ``slow`` test outside a ``test_zz_*`` file is a naming error,
    refused at collection: ``slow`` marks what is sized beyond the
    tier-1 run (``-m 'not slow'``), and the file's name is how a reader
    and a ``tests/test_zz_*`` glob find those suites without opening
    every file."""
    bad = sorted({
        os.path.basename(str(item.fspath))
        for item in items
        if item.get_closest_marker("slow") is not None
        and not os.path.basename(str(item.fspath)).startswith("test_zz_")
    })
    if bad:
        raise pytest.UsageError(
            "slow-marked tests outside test_zz_* files: " + ", ".join(bad)
        )


@pytest.fixture
def rt_start_regular():
    """Fresh single-node cluster for a test (ray: conftest.py ray_start_regular:419)."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def rt_start_shared():
    """Shared single-node cluster for a test module (ray_start_regular_shared)."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield ray_tpu
    ray_tpu.shutdown()
