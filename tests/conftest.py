"""Test configuration.

Tests run on a virtual 8-device CPU mesh (no TPU needed): the env vars below must
be set before jax is first imported. Hardware-requiring tests are marked `tpu`
(mirroring the reference's marker tiers: pre_merge / gpu, pyproject.toml:164-169).
"""

import os

# Force, don't setdefault: the unit suite must run on the virtual CPU mesh
# (fast, 8 devices) whatever JAX_PLATFORMS the session carries — on a machine
# with a chip JAX would otherwise take the TPU. Escape hatch for hardware runs
# (`pytest -m tpu`): DYN_TPU_TESTS_REAL=1 leaves the platform alone.
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("DYN_TPU_TESTS_REAL") != "1":
    # importing __graft_entry__ is pre-jax safe (it only pulls in os/sys)
    from __graft_entry__ import _ensure_devices  # noqa: E402

    _ensure_devices(8)

import asyncio  # noqa: E402
import signal  # noqa: E402

import pytest  # noqa: E402


# One case of ``tests/benchmark/test_benchmark.py`` waits for a ``benchmark`` PR, as
# ``tests/benchmark/conftest.py`` says of Trinity's cell (that file is the benchmark's and a PR that
# adds a cell edits none of those; this one is not): the test holds every file of
# ``benchmark/workloads/`` to ``prompt + output <= 2048`` on its last line, and
# ``code.mellum2-12b-a2.5b-tp4`` has prompts of 1,536-3,072 under ``--max-model-len 4096``. Marked
# STRICTLY: once the bound is read from the cell's configuration (ROADMAP B11 (c)) the case passes, this
# mark turns it red, and it goes. ``tests/benchmark/test_mellum_cell.py`` holds everything the case holds
# before that line, and the bound by the configuration's own flag.
WAITS_FOR_B11 = ("test_schedule_is_a_pure_function_of_the_seed_and_respects_its_clips"
                 "[code.mellum2-12b-a2.5b-tp4]")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.name == WAITS_FOR_B11:
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=AssertionError,
                reason="test_benchmark.py:113 holds every cell to 2,048 positions; this cell's "
                       "configuration serves 4,096 (ROADMAP B11 (c): read --max-model-len)"))


@pytest.fixture(scope="session")
def model_dir(tmp_path_factory):
    """HF-layout tiny model directory (tokenizer + config), built once."""
    from .fixtures import build_model_dir

    path = tmp_path_factory.mktemp("tiny-llama")
    return build_model_dir(str(path))


@pytest.fixture
def run():
    """Run a coroutine to completion on a fresh event loop."""

    def _run(coro):
        return asyncio.run(coro)

    return _run


# Seconds each phase of one test (set-up, call, tear-down) may take: the
# slowest test measured alone is under 30 s. A test that needs more says so
# with @pytest.mark.timeout(N).
DEFAULT_TEST_LIMIT_S = 120
# Once the limit has struck, the alarm strikes again this often until the
# phase has unwound: asyncio.run cancels its tasks on the way out, and one
# that swallows the cancellation (or the TimeoutError) would wait again.
_UNWIND_S = 10


@pytest.hookimpl(wrapper=True)
def _limited(item):
    """Fail a test that outlives its limit. pytest-timeout is not installed
    where these tests run, so SIGALRM does it: tests run in their process's
    main thread (under xdist too), and the signal interrupts a selector, a
    lock or a subprocess wait alike. Armed around each phase and not by a
    fixture, so that no alarm can strike while pytest writes its report."""
    mark = item.get_closest_marker("timeout")
    limit = mark.args[0] if mark else DEFAULT_TEST_LIMIT_S

    def on_alarm(signum, frame):
        signal.setitimer(signal.ITIMER_REAL, _UNWIND_S)
        raise TimeoutError(f"{item.nodeid} exceeded its limit of {limit} s")

    previous = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


pytest_runtest_setup = pytest_runtest_call = pytest_runtest_teardown = _limited


# Memory mappings a worker may hold when it leaves a test file before JAX's caches go
# (``vm.max_map_count`` is 65,530, and the costliest file alone adds some 32,000).
_MAPPINGS_BETWEEN_FILES = 30_000


@pytest.fixture(scope="module", autouse=True)
def room_for_compiled_programs():
    """Every compiled program is a few memory mappings of the process, and a
    process may hold 65,530 (``vm.max_map_count``): past it the CPU's compiler
    dies with the worker. The programs of the model tests are kept for the
    life of a worker (``tests/step_programs.py``) and the plain references
    compile an operation a shape, so a worker's count only grows: alone in a
    process ``tests/test_xing4.py`` ends at 32,199. So BETWEEN FILES (a
    module-scoped fixture: torn down when the worker's next test is another
    file's, after the file's engines have closed), once the process holds
    30,000, JAX's caches go and the next file's programs compile again. Never
    between the tests of a file, which would compile a file's programs a test
    again."""
    yield
    jax = sys.modules.get("jax")
    if jax is None:
        return
    try:
        with open("/proc/self/maps") as f:
            held = sum(1 for _ in f)
    except OSError:
        return
    if held > _MAPPINGS_BETWEEN_FILES:
        jax.clear_caches()


# runtime modules with process-global state and a reset_for_tests(): one
# test's outages, quarantine latch, dispatch records, fail-slow verdict or
# armed chaos observer must not bleed into the next test's assertions
_RESET_AFTER_EACH_TEST = (
    "control_plane", "integrity", "profiling", "straggler", "chaos",
)


@pytest.fixture(autouse=True)
def _clean_process_globals():
    """After each test: reset the process-global state above and fail a test
    that leaked a background task — a drain-migration coordinator or a
    HealthMonitor left running keeps freezing streams / reaping state under
    every later test. Modules are looked up in sys.modules, never imported:
    the guard must not drag runtime modules into tests that never touch
    them."""
    yield
    for name in _RESET_AFTER_EACH_TEST:
        mod = sys.modules.get(f"dynamo_tpu.runtime.{name}")
        if mod is not None:
            mod.reset_for_tests()
    mig = sys.modules.get("dynamo_tpu.disagg.migration")
    leaked_drains = mig.live_coordinators() if mig else []
    if mig:
        mig.reset_migration_counters()
    health = sys.modules.get("dynamo_tpu.runtime.health")
    leaked_monitors = health.live_monitors() if health else []
    assert not leaked_drains, (
        f"{len(leaked_drains)} MigrationCoordinator drain task(s) leaked "
        f"past test teardown — stop() the coordinator (or shutdown() its "
        f"DistributedRuntime)"
    )
    assert not leaked_monitors, (
        f"{len(leaked_monitors)} HealthMonitor task(s) leaked past test "
        f"teardown — stop() the monitor (or shutdown() its "
        f"DistributedRuntime)"
    )
