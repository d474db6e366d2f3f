"""Force a multi-device CPU platform before jax initializes.

XLA only honours ``--xla_force_host_platform_device_count`` if it is set
before the backend is created, so callers (tests/conftest.py, bench and
example entrypoints) must import this module and call
:func:`force_host_devices` before their first ``import jax``.  This
module itself must therefore stay jax-free.
"""

from __future__ import annotations

import os
import sys

__all__ = ["force_host_devices", "device_line"]


def force_host_devices(n: int = 8) -> bool:
    """Append ``--xla_force_host_platform_device_count=n`` to XLA_FLAGS.

    No-op (returns False) if jax is already imported — too late to take
    effect — or if the user's XLA_FLAGS already pins an explicit device
    count (respected).  Returns True if this call set the flag.
    """
    if "jax" in sys.modules:
        return False
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return False
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n}"
    ).strip()
    return True


def device_line() -> str:
    """One line naming what JAX runs on: count, platform and device kind
    (imports jax, so call it after :func:`force_host_devices`)."""
    import jax

    devs = jax.devices()
    return f"devices: {len(devs)} x {devs[0].platform} ({devs[0].device_kind})"
