"""TPU slice topology discovery.

Replaces the reference's SSH-based NIC/interface probing (ref:
horovod/runner/driver/driver_service.py [V] — SURVEY.md §2.5): on TPU
the launcher doesn't need to elect network interfaces (ICI is the data
plane and fixed); it needs the list of worker hosts in the slice and the
chip count per host. Those come from TPU-VM environment metadata and the
host's device nodes — never from JAX: this code runs in the launcher
parent, and a parent that initialises JAX takes the chips its workers
need.

Recognized sources, in order:
1. ``HOROVOD_TPU_HOSTS`` — explicit override, same syntax as ``-H``.
2. ``TPU_WORKER_HOSTNAMES`` + ``TPU_WORKER_ID`` — set on TPU VMs by the
   infrastructure (comma-separated host list).
3. This host alone, with the chips its device nodes show.
"""

from __future__ import annotations

import glob
import os
import re
from typing import List, Optional

from .hosts import HostInfo, parse_hosts


def local_chip_count() -> int:
    """TPU chips on this host, counted from the device nodes the TPU
    driver creates: ``/dev/accel<N>`` (v2-v4) or ``/dev/vfio/<N>``
    (v5e and later). 0 on a host without chips."""
    accel = glob.glob("/dev/accel[0-9]*")
    if accel:
        return len(accel)
    return len(
        [p for p in glob.glob("/dev/vfio/*") if os.path.basename(p).isdigit()]
    )


def wants_cpu(env: Optional[dict] = None) -> bool:
    """Whether the workers were asked, explicitly, to run on the CPU."""
    env = os.environ if env is None else env
    return env.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def chips_per_host(env: Optional[dict] = None) -> int:
    """Devices each worker process will drive: the chips this host's
    device nodes show; with none visible, TPU_CHIPS_PER_HOST_BOUNDS
    ("x,y,z", product = chip count — the launcher may sit on a head node
    without chips; nodes come first because the variable describes the
    host's slice shape even where fewer chips are passed through, as on
    a one-chip machine carved from a 2x2 host). On a host without
    either, or under an explicit ``JAX_PLATFORMS=cpu``, the forced
    host-device count (1 unless ``XLA_FLAGS`` says more)."""
    env = os.environ if env is None else env
    if not wants_cpu(env):
        chips = local_chip_count()
        if chips:
            return chips
        bounds = env.get("TPU_CHIPS_PER_HOST_BOUNDS")
        if bounds:
            n = 1
            for part in bounds.split(","):
                n *= int(part)
            return n
    match = re.search(
        r"xla_force_host_platform_device_count=(\d+)",
        env.get("XLA_FLAGS", ""),
    )
    return int(match.group(1)) if match else 1


def discover_hosts(env: Optional[dict] = None) -> List[HostInfo]:
    env = os.environ if env is None else env
    override = env.get("HOROVOD_TPU_HOSTS")
    if override:
        return parse_hosts(override)
    per_host = chips_per_host(env=env)
    hostnames = env.get("TPU_WORKER_HOSTNAMES")
    if hostnames:
        return [
            HostInfo(h.strip(), per_host)
            for h in hostnames.split(",")
            if h.strip()
        ]
    return [HostInfo("localhost", per_host)]
