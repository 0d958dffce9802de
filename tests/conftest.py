"""Test harness: simulate an 8-device TPU pod slice on CPU.

SURVEY.md §4: multi-"node" DP is testable on one host via
``--xla_force_host_platform_device_count=8``.  We both set the env var and
override the config before any backend initialization, so the suite runs on
the CPU platform even where an accelerator is attached.
"""

import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
# Hermetic tests: never attempt the CIFAR-10 network fetch.
os.environ.setdefault("TPUDP_NO_DOWNLOAD", "1")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def audit_capture():
    """ONE trace-audit capture shared by every analysis test module —
    capturing re-traces all registered step programs (~7s), so the
    suite must not pay it per module."""
    from tpudp.analysis import audit

    audit.force_smoke_backend()
    return audit.capture()


@pytest.fixture(scope="session")
def mesh8():
    from tpudp.mesh import make_mesh

    assert jax.device_count() >= 8, "virtual CPU device count not applied"
    return make_mesh(8)


@pytest.fixture(scope="session")
def mesh4():
    from tpudp.mesh import make_mesh

    return make_mesh(4)
