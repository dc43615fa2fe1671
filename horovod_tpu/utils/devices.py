"""Virtual CPU mesh on request, shared by every harness with a
``--cpu-devices`` flag.

XLA parses ``--xla_force_host_platform_device_count`` once, at the first
client creation in the process, so the flag must be raised (never lowered
or duplicated) before anything touches a backend.
"""

import os
import re

_PAT = r"--xla_force_host_platform_device_count=(\d+)"


def force_host_device_count(n):
    """Pin this process to an ``n``-device virtual CPU mesh: raise the
    host-platform device-count flag to at least ``n`` and select the cpu
    platform. Only for callers that were ASKED for the CPU
    (``--cpu-devices``): nothing here looks at which backend would
    otherwise come up, so it can never turn a missing chip into a quiet
    CPU run. Raises when a backend already exists: the flag is frozen
    then."""
    import jax
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            f"cannot switch to {n} virtual CPU devices: a jax backend "
            "already exists in this process (ask for the CPU mesh before "
            "anything touches jax.devices())")
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(_PAT, flags)
    if not (m and int(m.group(1)) >= n):
        new = f"--xla_force_host_platform_device_count={n}"
        flags = re.sub(_PAT, new, flags) if m else (flags + " " + new).strip()
        os.environ["XLA_FLAGS"] = flags
    jax.config.update("jax_platforms", "cpu")
