"""Test fixture: 8 virtual CPU devices on the CPU backend.

Mirrors the reference's hardware-free distributed test strategy
(SURVEY.md §4): where Paddle simulates a cluster with localhost
subprocesses + Gloo, we simulate an 8-chip slice with
--xla_force_host_platform_device_count on the CPU PJRT backend.
"""
import os
import sys

# Must happen before any jax backend initialization.
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import _cpu_mesh_flags  # noqa: E402  (jax-free; shared flag defaults)

_cpu_mesh_flags.apply()

import jax  # noqa: E402

try:
    jax.config.update("jax_platforms", "cpu")
except Exception:
    pass

# NO persistent XLA compile cache, deliberately. It was tried (the suite
# is compile-bound here) and is a process-killer on this jaxlib: a
# DESERIALIZED CPU executable for some programs (observed: the ZeRO-stage-3
# resharded train step) runs once and then SIGABRTs the whole pytest
# process on its SECOND execution — a C++ CHECK, uncatchable, and
# undetectable at cache-write time short of executing the deserialized
# executable twice (side effects forbid that). A warm cache thus turns one
# mid-suite test into a run-ending crash nondeterministically; a cold run
# merely recompiles. Separately, jax's LRUCache.put is a bare write_bytes
# with no overwrite-on-exists, so a kill -9 mid-write (CI timeout, chaos
# soak) poisons the entry permanently. Revisit only on a jaxlib whose
# deserialized executables are re-execution-safe.

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="run tests marked slow (full vision-zoo compile sweep)",
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: XLA-compile-heavy tests skipped by default "
        "(run with --runslow)")
    config.addinivalue_line(
        "markers", "fast: quick smoke subset (`pytest -m fast`)")
    config.addinivalue_line(
        "markers", "chaos: fault-injection soak tests (kill -9 /torn-write "
        "runs via paddle_tpu.testing.chaos; slow — excluded from tier-1)")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip = pytest.mark.skip(reason="compile-heavy; use --runslow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


def free_port():
    """An OS-assigned free TCP port (shared by the multi-process
    rendezvous/rpc tests; keep retry/SO_REUSEADDR tweaks in one place)."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port
