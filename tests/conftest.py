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

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line("markers", "tpu: requires real TPU hardware")
    config.addinivalue_line("markers", "slow: long-running test")
    config.addinivalue_line(
        "markers",
        "chaos: composition chaos plane (seeded fault-schedule runs)",
    )


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


@pytest.fixture(autouse=True)
def _reset_control_plane_state():
    """Zero the process-global control-plane connectivity tracker after
    each test: statestore/bus clients note outages into it, and a test
    that legitimately bounced a server must not leave a later test's
    /health reading 'degraded' (imported lazily — same contract as the
    health-monitor guard below)."""
    yield
    import sys

    cp = sys.modules.get("dynamo_tpu.runtime.control_plane")
    if cp is not None:
        cp.reset_for_tests()


@pytest.fixture(autouse=True)
def _no_leaked_migrations():
    """Fail any test that leaves a drain-migration coordinator task running
    past teardown: a leaked drain task keeps freezing/shipping streams in
    the background of every later test (imported lazily — the HealthMonitor
    guard pattern). Also zero the process-global migration counters so one
    test's drains can't bleed into another's gauge assertions."""
    yield
    import sys

    mig = sys.modules.get("dynamo_tpu.disagg.migration")
    if mig is None:
        return
    leaked = mig.live_coordinators()
    assert not leaked, (
        f"{len(leaked)} MigrationCoordinator drain task(s) leaked past test "
        f"teardown — stop() the coordinator (or shutdown() its "
        f"DistributedRuntime)"
    )
    mig.reset_migration_counters()


@pytest.fixture(autouse=True)
def _reset_integrity_state():
    """Drop the process-global integrity tracker after each test: one
    test's corruption trips or quarantine latch must not leave a later
    test's health checks reading 'quarantined' (imported lazily — the
    control-plane reset pattern above)."""
    yield
    import sys

    integ = sys.modules.get("dynamo_tpu.runtime.integrity")
    if integ is not None:
        integ.reset_for_tests()


@pytest.fixture(autouse=True)
def _reset_profiling_state():
    """Drop the process-global profiling timeline / frontend CPU
    accumulator / lag sampler after each test: one test's dispatch
    records must not bleed into another's summary or zero-overhead
    assertions (imported lazily — the control-plane reset pattern)."""
    yield
    import sys

    prof = sys.modules.get("dynamo_tpu.runtime.profiling")
    if prof is not None:
        prof.reset_for_tests()


@pytest.fixture(autouse=True)
def _reset_straggler_state():
    """Drop the process-global straggler detector and verdict latch after
    each test: one test's dispatch samples or latched fail-slow verdict
    must not leave a later test's health checks reading 'suspect'
    (imported lazily — the control-plane reset pattern)."""
    yield
    import sys

    strag = sys.modules.get("dynamo_tpu.runtime.straggler")
    if strag is not None:
        strag.reset_for_tests()


@pytest.fixture(autouse=True)
def _reset_chaos_state():
    """Drop the process-global chaos observer and its once-only env probe
    after each test: one test's armed observer (or noted events) must not
    bleed into another's invariant or zero-overhead assertions (imported
    lazily — the control-plane reset pattern)."""
    yield
    import sys

    ch = sys.modules.get("dynamo_tpu.runtime.chaos")
    if ch is not None:
        ch.reset_for_tests()


@pytest.fixture(autouse=True)
def _no_leaked_health_monitors():
    """Fail any test that leaves a HealthMonitor check task running past
    teardown: a leaked monitor keeps reaping/draining state in the
    background of every later test (imported lazily — the guard must not
    drag runtime modules into tests that never touch them)."""
    yield
    import sys

    health = sys.modules.get("dynamo_tpu.runtime.health")
    if health is None:
        return
    leaked = health.live_monitors()
    assert not leaked, (
        f"{len(leaked)} HealthMonitor task(s) leaked past test teardown — "
        f"stop() the monitor (or shutdown() its DistributedRuntime)"
    )
