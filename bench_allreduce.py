"""Allreduce microbenchmark — bandwidth across message sizes AND world
sizes, with scaling efficiency vs perfect-linear.

The harness behind the reference's headline claim (scaling efficiency of
allreduce-dominated training, docs/benchmarks.rst + the Horovod paper
fig. 5-6 [V]; BASELINE.md north star: allreduce scaling efficiency on an
8→256-chip sweep). The sweep is world-size-parameterized: on a pod
slice it walks 8→256 unchanged; on the 8-device CPU simulation it walks
1/2/4/8 (validating the sweep logic with real XLA collectives); on the
1-chip dev box it measures single-device round-trip overhead.

Per (world, size) it prints one JSON line:
  {"metric": "allreduce_busbw", "bytes": N, "world": W,
   "value": GB/s, "unit": "GB/s", "lat_us": ...}
and per world a summary with efficiency vs the base world:
  {"metric": "allreduce_scaling", "world": W, "base_world": B,
   "value": eff, "unit": "ratio", "busbw_gbs": ...}

Bus bandwidth uses the standard ring-allreduce convention:
  busbw = bytes * 2*(W-1)/W / time
(equals algobw for W=1). Ring busbw is world-size-invariant under
perfect scaling, so efficiency(W) = busbw(W) / busbw(base).

Env: BENCH_PLATFORM=cpu for the simulated mesh, BENCH_SIZES (bytes,
comma-sep), BENCH_ITERS, BENCH_WORLDS to override the world sweep.
"""

import json
import os
import time

from _benchlib import stamp as _stamp
from functools import partial

# Quarantine (VERDICT r3 weak #8): a host-simulation number measures
# XLA-on-CPU emulation overhead, not ICI bandwidth/scaling — it must
# never be quotable near BASELINE.md's 90% north star. The note rides
# EVERY non-TPU line (busbw and scaling); save such outputs under a
# sim_ filename prefix (bench.py's stale-artifact fallback skips both).
_SIM_NOTE = (
    "logic-validation only (CPU simulation); NOT a TPU "
    "scaling/efficiency number"
)


def sweep_worlds(n_devices: int):
    """World sizes to sweep given the visible device count: powers of
    two up to n (plus n itself when not a power of two). Large slices
    (>=64 devices) start at 8 — the north star's 8→256 window."""
    worlds = []
    w = 1
    while w <= n_devices:
        worlds.append(w)
        w *= 2
    if worlds[-1] != n_devices:
        worlds.append(n_devices)
    if n_devices >= 64:
        worlds = [w for w in worlds if w >= 8]
    return worlds


def ring_factor(world: int) -> float:
    return 2.0 * (world - 1) / world if world > 1 else 1.0


def scaling_efficiency(busbw_by_world):
    """Efficiency vs perfect-linear: ring busbw is flat across worlds,
    so eff(w) = busbw(w)/busbw(base). Returns (base_world, {w: eff})."""
    if not busbw_by_world:
        return None, {}
    base = min(busbw_by_world)
    base_bw = busbw_by_world[base]
    return base, {
        w: (bw / base_bw if base_bw > 0 else 0.0)
        for w, bw in sorted(busbw_by_world.items())
    }


def main():
    import jax

    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])

    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    import numpy as np

    from horovod_tpu.common.topology import WORLD_AXIS
    from horovod_tpu.ops import traced
    from horovod_tpu.ops.reduction_ops import Average

    devices = jax.devices()
    iters = int(os.environ.get("BENCH_ITERS", "30"))
    sizes_env = os.environ.get("BENCH_SIZES")
    if sizes_env:
        sizes = [int(s) for s in sizes_env.split(",")]
    else:
        sizes = [1 << p for p in range(10, 28, 2)]  # 1 KB .. 128 MB
    worlds_env = os.environ.get("BENCH_WORLDS")
    if worlds_env:
        worlds = [int(w) for w in worlds_env.split(",")]
    else:
        worlds = sweep_worlds(len(devices))

    # Representative size for the scaling figure: the largest swept
    # (bandwidth-bound, like gradient buckets after fusion).
    scale_size = max(sizes)
    busbw_at_scale_size = {}

    for world in worlds:
        mesh = Mesh(np.array(devices[:world]), (WORLD_AXIS,))
        for nbytes in sizes:
            n = max(nbytes // 4, 1)  # float32 elements

            # Average (same wire bytes as Sum) keeps the chained values
            # stationary at 1.0: the timed loop feeds each reduce the
            # previous output, so every iteration data-depends on the
            # last — independent same-input calls would let the final
            # sync cover only one of them (and block_until_ready is
            # never needed; see _benchlib.sync).
            @partial(
                jax.shard_map,
                mesh=mesh,
                in_specs=P(WORLD_AXIS),
                out_specs=P(WORLD_AXIS),
                check_vma=False,
            )
            def reduce(x):
                return traced.allreduce(x[0], op=Average)[None]

            from _benchlib import sync as _sync

            step = jax.jit(reduce)
            x = jnp.ones((world, n), jnp.float32)
            out = step(x)  # compile + warm
            # one chained call before timing: step(out) sees a committed
            # sharded input — a different jit cache key than the fresh
            # jnp.ones — and must be compiled OUTSIDE the timed region
            out = step(out)
            _sync(out)  # scalar host transfer = trustworthy sync
            t0 = time.perf_counter()
            for _ in range(iters):
                out = step(out)
            _sync(out)
            dt = (time.perf_counter() - t0) / iters
            busbw = nbytes * ring_factor(world) / dt / 1e9
            if nbytes == scale_size:
                busbw_at_scale_size[world] = busbw
            line = {
                "metric": "allreduce_busbw",
                "bytes": nbytes,
                "world": world,
                "value": round(busbw, 3),
                "unit": "GB/s",
                "lat_us": round(dt * 1e6, 1),
                "platform": devices[0].platform,
            }
            if devices[0].platform != "tpu":
                line["note"] = _SIM_NOTE
            print(json.dumps(_stamp(line)), flush=True)

    base, eff = scaling_efficiency(busbw_at_scale_size)
    for world, e in eff.items():
        line = {
            "metric": "allreduce_scaling",
            "world": world,
            "base_world": base,
            "bytes": scale_size,
            "value": round(e, 4),
            "unit": "ratio",
            "busbw_gbs": round(busbw_at_scale_size[world], 3),
            "platform": devices[0].platform,
        }
        if devices[0].platform != "tpu":
            line["note"] = _SIM_NOTE
        print(json.dumps(_stamp(line)), flush=True)


if __name__ == "__main__":
    main()
