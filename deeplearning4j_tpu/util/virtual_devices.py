"""Virtual-device platform forcing for cluster simulation.

The reference simulates clusters with Spark ``local[*]`` executors inside one
JVM (SURVEY.md §4.5); the JAX analogue is the XLA host platform with N
virtual CPU devices. One shared helper so tests, the driver entry point, and
multi-process launchers all do the same thing: set JAX_PLATFORMS=cpu +
``--xla_force_host_platform_device_count=N`` before the first backend use,
then assert the device count.
"""

from __future__ import annotations

import os
import re

_FLAG = "xla_force_host_platform_device_count"


def cpu_device_flags(n: int, existing: str = "") -> str:
    """An XLA_FLAGS value forcing >= ``n`` virtual host devices — a pure
    string operation (no jax import, no backend touch), so the
    multi-process bootstrap can set it BEFORE jax.distributed.initialize
    without tripping the backends-already-initialized check."""
    flags = existing
    m = re.search(rf"--{_FLAG}=(\d+)", flags)
    if m is None:
        flags = (flags + f" --{_FLAG}={n}").strip()
    elif int(m.group(1)) < n:
        flags = flags.replace(m.group(0), f"--{_FLAG}={n}")
    return flags


def ensure_cpu_devices(n: int) -> None:
    """Force a pure-CPU JAX platform with at least ``n`` virtual devices.

    Must run before the first backend use (``jax.devices()``, any array
    op): the platform and the host device count are read once, when the
    backend initializes. Called later it cannot change them, and the
    assertion below explains the ordering problem unless the backend that
    is already up has >= n devices.

    On a host that really has >= n chips, set ``DL4J_TPU_REAL_DEVICES=1``
    to skip the forcing and run on hardware.
    """
    import jax

    if os.environ.get("DL4J_TPU_REAL_DEVICES") != "1":
        os.environ["XLA_FLAGS"] = cpu_device_flags(
            n, os.environ.get("XLA_FLAGS", ""))
        os.environ["JAX_PLATFORMS"] = "cpu"
        jax.config.update("jax_platforms", "cpu")

    assert len(jax.devices()) >= n, (
        f"need {n} devices, have {len(jax.devices())} "
        f"(jax backends were initialized before ensure_cpu_devices({n}) "
        f"could force the virtual CPU platform — call it earlier)")
