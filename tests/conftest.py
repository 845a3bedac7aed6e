"""Test harness: host-count-faked JAX CPU mesh (SURVEY.md §4 rebuild implication c).

Configured BEFORE jax initializes a backend: the CPU platform with 8 virtual
devices, so every sharding/collective path is exercised without TPU hardware
— the analog of the reference running NetworkManager on local[*] Spark.
Subprocesses the tests spawn inherit ``JAX_PLATFORMS=cpu`` from this
environment (worker mains have no CPU default of their own).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never touch an accelerator
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    # for the subprocesses tests spawn: they inherit 8 virtual devices too
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np
import pytest


@pytest.fixture(scope="session")
def mesh8():
    from synapseml_tpu.parallel import MeshConfig, create_mesh

    return create_mesh(MeshConfig(data=2, fsdp=2, tensor=2))


@pytest.fixture(scope="session")
def mesh_dp8():
    from synapseml_tpu.parallel import MeshConfig, create_mesh

    return create_mesh(MeshConfig(data=-1))


@pytest.fixture()
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="module", autouse=True)
def _no_specless_modules_left_behind():
    """A test file leaves ``sys.modules`` fit for the next file on its
    worker: a stand-in planted without a ``__spec__`` makes every later
    ``importlib.util.find_spec(name)`` in the process raise (``transformers``
    probes its optional packages so at import), and which file meets it
    hangs on how xdist deals the files out. Only public top-level names are
    held to it, the ones such a probe asks for: extension modules register
    private ABI modules (``_cython_3_2_4``) and dotted submodules
    (``cv2.utils.fs``) without a spec, and nothing looks those up."""
    import sys

    before = set(sys.modules)
    yield
    left = sorted(name for name, mod in list(sys.modules.items())
                  if name not in before and name != "__main__"
                  and "." not in name and not name.startswith("_")
                  and getattr(mod, "__spec__", None) is None)
    if left:
        pytest.fail(f"this file left modules without a __spec__ in "
                    f"sys.modules: {left}; remove them after use or give "
                    f"them an importlib.machinery.ModuleSpec", pytrace=False)


def make_tabular_df(n=200, d=8, classes=2, seed=0, num_partitions=2):
    """Shared synthetic dataset builder (TestBase makeBasicDF analog)."""
    from synapseml_tpu.core import DataFrame

    rs = np.random.default_rng(seed)
    X = rs.normal(size=(n, d)).astype(np.float32)
    w = rs.normal(size=(d,)).astype(np.float32)
    logits = X @ w
    if classes == 0:
        y = (logits + 0.1 * rs.normal(size=n)).astype(np.float32)  # regression
    else:
        y = (np.digitize(logits, np.quantile(logits, np.linspace(0, 1, classes + 1)[1:-1]))
             ).astype(np.int32)
    return DataFrame.from_dict({"features": X, "label": y}, num_partitions=num_partitions)


@pytest.fixture()
def tabular_df():
    return make_tabular_df()


@pytest.fixture()
def regression_df():
    return make_tabular_df(classes=0)


def pytest_addoption(parser):
    parser.addoption("--runslow", action="store_true", default=False,
                     help="also run tests marked slow (the full lane; the "
                          "default lane skips them — reference analog: the "
                          "lightgbm split1-6 CI sharding)")
    parser.addoption("--check-slow-manifest", action="store_true", default=False,
                     help="with --runslow: measure per-test durations, "
                          "regenerate resources/slow_tests.txt, and FAIL the "
                          "session on drift (a newly-slow test missing from "
                          "the manifest, or a stale nodeid)")
    parser.addoption("--lane-budget", type=float, default=0.0, metavar="SECONDS",
                     help="fail the session if total test wall time exceeds "
                          "this budget (default-lane target: 480)")


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running (full-size model) "
                            "tests, skipped unless --runslow")
    config.addinivalue_line("markers", "chaos: injected-fault / worker-kill "
                            "tests; guarded by the per-test thread watchdog "
                            "(pyproject.toml registers this marker too)")
    config.addinivalue_line("markers", "registry: model registry + "
                            "deployment plane tests (tier-1; pyproject.toml "
                            "registers this marker too)")


# ---- chaos watchdog ------------------------------------------------------
# Injected-fault tests (tests/test_resilience.py) kill workers, blackhole
# connections and drive retry loops — a bug in any of those paths could hang
# the tier-1 lane forever. Every @pytest.mark.chaos test runs under a
# stdlib-only thread-based alarm: if the test body exceeds its limit
# (default CHAOS_TIMEOUT_S; override with @pytest.mark.chaos(timeout_s=N)),
# a timer thread interrupts the main thread and the hookwrapper below
# converts that into a bounded test FAILURE instead of a session abort.

CHAOS_TIMEOUT_S = 120.0


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    marker = item.get_closest_marker("chaos")
    if marker is None:
        yield
        return
    import signal
    import threading
    import time as _time

    limit = float(marker.kwargs.get("timeout_s", CHAOS_TIMEOUT_S))
    fired = threading.Event()
    done = threading.Event()
    main_ident = threading.main_thread().ident

    def alarm():
        if done.is_set():  # test body already finished: don't interrupt
            return
        fired.set()
        try:
            # a real OS signal interrupts blocking syscalls (sleep, recv) —
            # _thread.interrupt_main() would only set a pending flag
            signal.pthread_kill(main_ident, signal.SIGINT)
        except (ValueError, OSError):
            import _thread
            _thread.interrupt_main()

    timer = threading.Timer(limit, alarm)
    timer.daemon = True
    timer.start()
    outcome = yield
    done.set()
    timer.cancel()
    if fired.is_set() and outcome.excinfo is not None:
        # replace the KeyboardInterrupt (it would abort the whole session)
        # with a bounded failure of just this test
        outcome.force_exception(
            pytest.fail.Exception(f"chaos watchdog: test exceeded {limit:.0f}s",
                                  pytrace=False))
    elif fired.is_set():
        # the alarm raced the end of the test body: absorb the SIGINT it
        # delivered so it can't abort the session in teardown / the next test
        try:
            _time.sleep(0.1)
        except KeyboardInterrupt:
            pass


def _slow_manifest() -> set:
    """Central slow-test list (the reference shards its CI into split1-6
    files, ``lightgbm/src/test/.../split*``; here one manifest of measured
    >=8s node ids keeps the default lane fast without touching test files).
    Regenerate from a --runslow run: pytest --durations=60, take >=8s."""
    path = os.path.join(os.path.dirname(__file__), "resources", "slow_tests.txt")
    try:
        with open(path) as f:
            return {line.strip() for line in f if line.strip()}
    except OSError:
        return set()


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    manifest = _slow_manifest()
    skip = pytest.mark.skip(reason="slow: run with --runslow (full lane)")
    for item in items:
        if "slow" in item.keywords or item.nodeid in manifest:
            item.add_marker(skip)


# ---- slow-manifest drift check + lane budget (VERDICT r3 next-#5) --------
# The manifest is regenerated from MEASURED durations, not hand-maintained:
#   pytest tests/ --runslow --check-slow-manifest -q
# fails (exit 1) and rewrites resources/slow_tests.txt whenever a test
# crossed the slow threshold without being listed or a listed nodeid no
# longer exists — so the default lane cannot drift upward silently.

SLOW_THRESHOLD_S = 8.0
_durations: dict = {}
_session_t0: list = []


def pytest_runtest_logreport(report):
    _durations[report.nodeid] = _durations.get(report.nodeid, 0.0) + report.duration


def pytest_sessionstart(session):
    import time

    _session_t0.append(time.monotonic())


def pytest_sessionfinish(session, exitstatus):
    import time

    config = session.config
    notes = []
    full_run = False
    if config.getoption("--check-slow-manifest"):
        # only a FULL unfiltered --runslow run may regenerate the manifest:
        # a partial run (test file args, -k, -m) would see un-run tests as
        # "stale" and gut the manifest
        tests_dir = os.path.dirname(os.path.abspath(__file__))
        full_run = (config.getoption("--runslow")
                    and not config.getoption("keyword")
                    and not config.getoption("markexpr")
                    and all(os.path.isdir(a.split("::")[0])
                            for a in (config.args or [tests_dir])))
        if not full_run:
            notes.append("--check-slow-manifest ignored: not a full "
                         "unfiltered --runslow run over the tests directory")
    if full_run:
        path = os.path.join(os.path.dirname(__file__), "resources",
                            "slow_tests.txt")
        measured_slow = {n for n, d in _durations.items()
                         if d >= SLOW_THRESHOLD_S}
        collected = set(_durations)
        old = _slow_manifest()
        stale = old - collected          # renamed/removed tests
        missing = measured_slow - old    # newly-slow, unlisted
        # hysteresis: keep listed tests that still take >= half the
        # threshold, so borderline tests don't flap in and out
        keep = {n for n in (old & collected)
                if _durations.get(n, 0.0) >= SLOW_THRESHOLD_S / 2}
        new = sorted(measured_slow | keep)
        if missing or stale:
            with open(path, "w") as f:
                f.write("\n".join(new) + "\n")
            notes.append(
                f"slow-manifest DRIFT: {len(missing)} newly-slow unlisted "
                f"{sorted(missing)}, {len(stale)} stale {sorted(stale)}; "
                f"manifest regenerated — commit it")
            session.exitstatus = 1
    budget = config.getoption("--lane-budget")
    if budget and _session_t0:
        elapsed = time.monotonic() - _session_t0[0]
        if elapsed > budget:
            notes.append(f"lane budget EXCEEDED: {elapsed:.0f}s > {budget:.0f}s "
                         "— move the offenders (pytest --durations=20) into "
                         "resources/slow_tests.txt")
            session.exitstatus = 1
    for n in notes:
        print(f"\n[conftest] {n}")
