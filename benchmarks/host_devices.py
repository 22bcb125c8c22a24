"""Faked host CPU devices for the sharded suite.

Import and call before anything imports JAX: the flag only takes effect
when the CPU backend starts, and it touches the host platform only.
"""
import os
import sys


def fake_host_devices(count: int = 8) -> None:
    """Ask XLA's CPU backend for ``count`` devices, unless JAX has started
    or ``XLA_FLAGS`` already sets a count."""
    if "jax" in sys.modules:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={count}").strip()
