"""Test configuration.

Multi-device testing strategy (SURVEY.md §4): the reference tests multi-locale
runs via GASNet-smp oversubscription on one box; we use XLA's virtual CPU
device pool instead — 8 virtual CPU devices, as the driver's multichip dry-run
does.  The tests pin the CPU platform whatever the environment says: the
sandbox has no accelerator, and on a machine that has one the chip belongs to
``chip_smoke.py``, not to the suite.
"""

import os

# The rendezvous timeout is this rig's own need, so it is set here and not by
# the package: 8 virtual devices execute serially on few cores, and XLA's CPU
# runtime kills the process when collective participants arrive more than
# 40 s apart.  (The TPU runtime parses the same XLA_FLAGS string and aborts
# on names it does not know, so nothing appends flags at package import.)
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
    + " --xla_cpu_collective_call_terminate_timeout_seconds=1200")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_X64"] = "true"
# Hermetic tests: the default-on artifact cache (utils/artifacts.py) would
# otherwise let engines restore structures written by earlier sessions (or
# earlier tests) from ~/.cache, flipping `structure_restored` expectations.
# Tests that exercise the layer re-enable it against a tmp_path root.
os.environ["DMT_ARTIFACT_CACHE"] = "off"
# Telemetry stays ON (default, in-memory — the instrumented hot paths run
# under test) but never inherits a sink directory from the environment;
# tests that exercise the JSONL sink point it at tmp_path themselves.
os.environ.pop("DMT_OBS_DIR", None)
os.environ.pop("DMT_OBS", None)

import jax  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)
