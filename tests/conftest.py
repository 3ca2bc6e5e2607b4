"""Test configuration.

Tests run on a virtual 8-device CPU mesh (the multi-chip sharding paths are
validated without TPU hardware, mirroring the reference's mock-transport
testing strategy — SURVEY.md §4.3). Must set XLA flags before jax imports.
"""
import importlib.util
import os
import sys

# Tests run on the virtual CPU mesh wherever they are started — also on a
# machine with a chip — so assign (not setdefault) before jax init.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()


def _bootstrap_lockwatch():
    """Install the lock-order watchdog (RAPIDS_TPU_LOCKWATCH=1) BEFORE
    anything imports jax or spark_rapids_tpu: the package creates its
    module-/class-level singleton locks (exchange._SHARED_LOCK_INIT,
    DeviceMemoryManager._shared_lock, flight-recorder/metrics guards,
    _JIT_LOCK) at import time, and they must be watched too. The module
    is loaded by FILE PATH (stdlib-only imports) and pre-registered
    under its canonical name, so the later package import yields the
    SAME module/state."""
    if os.environ.get("RAPIDS_TPU_LOCKWATCH", "") in ("", "0", "false"):
        return
    name = "spark_rapids_tpu.analysis.lockwatch"
    if name in sys.modules:
        sys.modules[name].install()
        return
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, "spark_rapids_tpu", "analysis",
                        "lockwatch.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    mod.install()


_bootstrap_lockwatch()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from spark_rapids_tpu.analysis import lockwatch  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running acceptance sweeps excluded from the tier-1 "
        "gate (which runs -m 'not slow')")
    # fallback install (the module-level bootstrap above normally ran
    # first, before the package's import-time locks were created);
    # cluster worker processes install their own watchdog via
    # cluster._main (env is inherited)
    if lockwatch.env_enabled() and not lockwatch.installed():
        lockwatch.install()


def pytest_sessionfinish(session, exitstatus):
    if not lockwatch.installed():
        return
    path = lockwatch.write_report()
    rep = lockwatch.report()
    if rep["inversions"]:
        tr = session.config.pluginmanager.get_plugin("terminalreporter")
        if tr is not None:
            tr.write_line(
                f"lock-order watchdog: "
                f"{len(rep['inversions'])} inversion(s)"
                + (f" — report at {path}" if path else ""), red=True)
            for inv in rep["inversions"][:20]:
                tr.write_line(f"  {inv['why']} at "
                              f"{inv['acquiring_site']}", red=True)
        session.exitstatus = 3


@pytest.fixture
def rng():
    return np.random.default_rng(42)
