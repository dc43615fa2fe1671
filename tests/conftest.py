"""Test harness: a virtual 8-device CPU mesh.

The reference tests every collective under real multi-process MPI
(`mpirun -np N pytest`, reference: .buildkite/gen-pipeline.sh:100). The
TPU-native equivalent is SPMD over N devices in one process: we force the CPU
backend to expose 8 virtual devices so every mesh/collective/sharding path
runs exactly as it would on an 8-chip slice, without TPU hardware.
"""

import os

# XLA_FLAGS must be set before the first backend is created; the platform is
# pinned through jax.config so the suite runs on the CPU whatever
# JAX_PLATFORMS the caller has (a machine with a chip sets "tpu,cpu").
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Keep stall checks snappy in tests; individual tests override as needed.
os.environ.setdefault("HOROVOD_STALL_CHECK_TIME_SECONDS", "2")
os.environ.setdefault("HOROVOD_PROFILER_DISABLE", "1")

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _lock_order_witness():
    """HOROVOD_LOCK_WITNESS=1: wrap every lock horovod_tpu creates during
    the run, record the cross-thread acquisition-order graph, and fail
    the session if any potential deadlock cycle was observed
    (docs/static-analysis.md — CI runs tier-1 with this on)."""
    if os.environ.get("HOROVOD_LOCK_WITNESS") != "1":
        yield
        return
    from horovod_tpu.analysis.lockwitness import (LockOrderWitness,
                                                  format_cycles)
    witness = LockOrderWitness()
    witness.install()
    yield
    witness.uninstall()
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "lock-witness-report.json")
    report = witness.write_report(path)
    if report["cycles"]:
        pytest.fail("lock-order witness observed potential deadlocks "
                    "(full stacks in lock-witness-report.json):\n"
                    + format_cycles(report), pytrace=False)
    # clean pass: don't leave the report in the tree (CI's artifact
    # hygiene step fails on any stray diagnostic dump after the run)
    try:
        os.remove(path)
    except OSError:
        pass


@pytest.fixture(autouse=True)
def _dump_artifacts_to_tmp(monkeypatch, tmp_path):
    """Keep per-run dump artifacts (flight-recorder post-mortems, stats
    profiler reports, XLA device traces) out of the repo root: a test
    that init()s without choosing explicit paths writes into its own tmp
    dir instead of the cwd. Tests that care about these paths override
    or delete the variables like any other env var — a test-level
    monkeypatch wins over this fixture."""
    monkeypatch.setenv("HOROVOD_DIAG_DIR", str(tmp_path / "diag"))
    monkeypatch.setenv("HOROVOD_PROFILER_PATH",
                       str(tmp_path / "profiler.txt"))


@pytest.fixture
def hvd_init():
    import horovod_tpu as hvd
    hvd.init()
    yield hvd
    # Engine state (handle table, response cache) is cleaned between tests by
    # re-initializing; shutdown() also exercises the dump path.


@pytest.fixture(scope="session")
def eight_devices():
    import jax
    assert jax.device_count() == 8, (
        "tests require XLA_FLAGS=--xla_force_host_platform_device_count=8")
    return jax.devices()
