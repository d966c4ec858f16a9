"""Test configuration: force an 8-device virtual CPU mesh before jax imports.

Mirrors the reference's serial-fallback testing posture (mpisppy/MPI.py mock):
all logic tests run without TPU hardware; multi-device sharding is exercised on
a virtual CPU mesh (xla_force_host_platform_device_count), per the build brief.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never take the chip
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_ENABLE_X64"] = "1"

# jax may already have been imported by a pytest plugin; set configs directly
# (safe as long as no computation has run yet, which is the case at collection).
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
# Persistent compilation cache, shared by the xdist workers and by every
# child process a test starts (arm_compile_cache exports the directory):
# warm runs compile almost nothing, which is most of the suite's runtime.
from tpusppy.solvers import aot as _aot  # noqa: E402

_aot.arm_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _obs_isolation():
    """Observability state must never bleed between tests: a host-sync
    tracker left open by a failed/interrupted test (thread-local stacks
    survive the test body) would keep counting fetches into a later
    test's ``host_sync_count`` assertion, and trace/metrics are
    process-global by design.  Reset all three around every test."""
    from tpusppy import tune
    from tpusppy.obs import metrics, trace
    from tpusppy.resilience import faults
    from tpusppy.solvers import aot, hostsync

    hostsync.reset()
    trace.disable()
    trace.reset(capacity=trace.DEFAULT_CAPACITY)
    metrics.reset()
    faults.disarm()
    tune.reset_persist()
    aot.reset()
    yield
    hostsync.reset()
    trace.disable()
    trace.reset(capacity=trace.DEFAULT_CAPACITY)
    metrics.reset()
    faults.disarm()
    tune.reset_persist()
    aot.reset()

