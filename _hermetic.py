"""CPU-only env construction for harness subprocesses.

One place owns the recipe for keeping a child process off the chip:
force JAX_PLATFORMS=cpu and (optionally) set the simulated host-device
count — replacing any existing count flag while preserving unrelated
XLA_FLAGS.

Used by bench*.py, __graft_entry__.py and tests/test_examples.py.
"""

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def with_device_count(flags: str, n_devices: int) -> str:
    """Return XLA_FLAGS with the host-device-count set to n_devices,
    replacing an existing count flag and keeping everything else."""
    flags = re.sub(rf"{_COUNT_FLAG}=\d+", "", flags or "")
    return " ".join(flags.split() + [f"{_COUNT_FLAG}={n_devices}"])


def hermetic_cpu_env(n_devices=None, base=None):
    """A copy of the environment guaranteed to run JAX on the host CPU."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        env["XLA_FLAGS"] = with_device_count(env.get("XLA_FLAGS"), n_devices)
    return env
