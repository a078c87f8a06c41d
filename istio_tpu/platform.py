"""Virtual CPU devices for the tests and the multi-chip dry run.

tests/conftest.py and __graft_entry__.dryrun_multichip need N virtual
host devices so Mesh/shard_map paths run without a chip; this module
is the single home for asking XLA for them. Nothing on the serving
path or in chip_smoke.py calls it: the chip is reached by not forcing
a platform at all.
"""
from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def force_cpu_platform(n_devices: int) -> None:
    """Force JAX onto an n_devices virtual CPU platform.

    Must run before any JAX backend initializes. Rewrites any existing
    xla_force_host_platform_device_count flag whose value is smaller
    than n_devices (a stale smaller count would silently win otherwise).
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(_COUNT_FLAG + r"=(\d+)", flags)
    if m is None:
        flags = (flags + f" {_COUNT_FLAG}={n_devices}").strip()
    elif int(m.group(1)) < n_devices:
        flags = flags[:m.start(1)] + str(n_devices) + flags[m.end(1):]
    os.environ["XLA_FLAGS"] = flags

    import jax

    jax.config.update("jax_platforms", "cpu")
