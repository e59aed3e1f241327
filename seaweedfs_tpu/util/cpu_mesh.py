"""Force JAX onto a virtual N-device CPU platform (shared helper).

Used by tests/conftest.py and __graft_entry__.dryrun_multichip: the
sandbox has no accelerator, so sharding programs are validated on
virtual CPU devices via ``--xla_force_host_platform_device_count``.
(On hardware the multi-chip path is ``chip_smoke.py --chips 4``.)

Why one shared owner: the device count is read from ``XLA_FLAGS`` once,
at the first backend initialization (the first ``jax.devices()`` or
dispatch), and a count already in the environment must be REPLACED, not
appended to. The platform is set both ways — ``JAX_PLATFORMS`` for any
child process, ``jax.config.update("jax_platforms", "cpu")`` for this
one, which works whether or not jax was imported before the call.

This module must NOT import jax at top level: callers need to mutate
``XLA_FLAGS`` before jax's backend reads it.
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def force_cpu_platform(n_devices: int = 8) -> None:
    """Point JAX at a virtual ``n_devices``-CPU platform.

    Safe to call multiple times; replaces (not just appends) any existing
    device-count flag so a stale smaller count from the environment cannot
    silently shrink the mesh. Raises if the backend was already
    initialized with a different platform/count (too late to change).
    """
    flags = os.environ.get("XLA_FLAGS", "")
    want = f"{_COUNT_FLAG}={n_devices}"
    if _COUNT_FLAG in flags:
        flags = re.sub(rf"{_COUNT_FLAG}=\d+", want, flags)
    else:
        flags = f"{flags} {want}".strip()
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("JAX_ENABLE_X64", "0")

    import jax

    jax.config.update("jax_platforms", "cpu")
    got = len(jax.devices("cpu"))
    if got < n_devices:
        raise RuntimeError(
            f"virtual CPU platform has {got} devices, wanted {n_devices}: "
            "the XLA backend was already initialized before "
            "force_cpu_platform() ran — call it before any jax.devices()/"
            "dispatch")
