"""Two-level (topology-aware) wire A/B (PR 10, ops/traced.py recipe
family + ops/overlap.py routing).

Measures what the hierarchical decomposition buys on the axis that
matters at multi-slice scale: bytes crossing the DCN hop. Three legs
over the SAME bucketed gradient exchange (a synthetic multi-slice split
of the 8-device mesh, HOROVOD-style intra groups of ``BENCH_INTRA``),
each appending one JSON artifact under BENCH_ARTIFACT_DIR (default
bench_results/hier/):

* ``ab_flat``      — the flat wire: every bucket is one world-axis
  collective; the whole payload crosses the (modeled) DCN boundary.
* ``ab_hier``      — the two-level wire at fp32: intra reduce-scatter
  -> inter collective on the 1/L shard -> intra all-gather; the DCN
  hop carries 1/L of the bytes.
* ``ab_hier_int8`` — the EQuARX placement: same shape, block-scaled
  int8 with stochastic rounding on the inter hop only (~4x less again
  on the scarce hop; ICI hops stay exact).

Each artifact records ms/step, the lowered collective counts (the
compiled-program evidence: per bucket one intra-group reduce-scatter +
one inter-group collective + one intra-group all-gather), and the
PER-HOP byte accounting from the shared payload-width model
(``FusionManager._hop_bytes`` — ring/topology factors cancel in every
ratio): ``inter_bytes`` / ``intra_bytes`` per step and the
``inter_ratio_vs_flat`` each leg achieves. BENCH_DRYRUN=1 is the CI
smoke shape (tiny tree, 2 iters; ``./ci.sh bench-smoke`` gates on the
artifacts AND on the pre-registered prediction that the hier-int8 leg
drops inter-hop bytes >= 3x vs the flat fp32 leg — docs/perf.md).
CPU lines carry the quarantine note: wall-clock claims need the
on-chip capture; the dryrun validates harness + HLO shape + byte
accounting.

Env: BENCH_LAYERS / BENCH_WIDTH / BENCH_BUCKETS / BENCH_INTRA /
BENCH_ITERS / BENCH_DRYRUN / BENCH_ARTIFACT_DIR.
"""

import json
import os
import time

from _benchlib import stamp as _stamp

_SIM_NOTE = (
    "logic-validation only (CPU simulation); step-time is NOT a TPU "
    "wall-clock number — byte accounting and HLO shape are exact"
)


def _collective_counts(lowered) -> dict:
    """Lowered-module collective counts via the shared
    horovod_tpu.analysis parser (same gate as tests/test_hier_wire)."""
    from horovod_tpu import analysis

    return analysis.parse_module(lowered).counts()


def _hop_accounting(bucket_elems, leg, L, H, block):
    """Per-step per-rank wire bytes by hop, payload-width model
    (FusionManager._hop_bytes). The flat leg's whole payload crosses
    the inter (DCN) boundary on a multi-slice world; the hier legs
    cross with the 1/L shard at the inter wire."""
    from horovod_tpu.ops.fusion import FusionManager

    intra = inter = 0
    for elems in bucket_elems:
        if leg == "ab_flat":
            b, _ = FusionManager._hop_bytes(elems, "fp32", 4, L * H, block)
            inter += b
        else:
            ib, _ = FusionManager._hop_bytes(elems, "fp32", 4, L, block)
            intra += ib
            shard = -(-elems // L)
            wire = "int8" if leg == "ab_hier_int8" else "fp32"
            eb, _ = FusionManager._hop_bytes(shard, wire, 4, H, block)
            inter += eb
    return {"intra_bytes": intra, "inter_bytes": inter}


def main():
    import jax

    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])

    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from _benchlib import sync as _sync
    from jax import shard_map
    from horovod_tpu.common.topology import hierarchical_stage_groups
    from horovod_tpu.ops import overlap
    from horovod_tpu.ops.compression import Compression

    dryrun = os.environ.get("BENCH_DRYRUN", "").strip() in ("1", "true")
    iters = int(os.environ.get("BENCH_ITERS", "2" if dryrun else "30"))
    layers = int(os.environ.get("BENCH_LAYERS", "4" if dryrun else "16"))
    width = int(os.environ.get("BENCH_WIDTH", "64" if dryrun else "1024"))
    n_buckets = int(os.environ.get("BENCH_BUCKETS", "4"))
    intra = int(os.environ.get("BENCH_INTRA", "4"))
    block = 512

    artifact_dir = os.environ.get(
        "BENCH_ARTIFACT_DIR", os.path.join("bench_results", "hier")
    )
    os.makedirs(artifact_dir, exist_ok=True)

    hvd.init()
    mesh = hvd.mesh()
    world = hvd.size()
    if world % intra:
        intra = 2 if world % 2 == 0 else 1
    stages = hierarchical_stage_groups(world, intra)
    if stages is None:
        raise SystemExit(
            f"no two-level split for world={world} intra={intra}"
        )
    L, H = intra, world // intra
    platform = jax.devices()[0].platform
    rng = np.random.default_rng(0)
    grads_host = {
        f"g{i:02d}": rng.normal(size=(world, width, width)).astype(
            np.float32
        )
        for i in range(layers)
    }
    grad_bytes = sum(
        int(np.prod(g.shape[1:])) * 4 for g in grads_host.values()
    )

    def make_step(leg):
        hier = None if leg == "ab_flat" else stages
        comp = (
            Compression.int8_block
            if leg == "ab_hier_int8"
            else Compression.none
        )

        def body(t, s):
            local = jax.tree_util.tree_map(lambda x: x[0], t)
            out = overlap.bucketed_allreduce(
                local, op=hvd.Sum, n_buckets=n_buckets,
                min_bucket_bytes=0, compression=comp, seed=s,
                hier_stages=hier,
            )
            return jax.tree_util.tree_map(lambda x: x[None], out)

        return jax.jit(
            shard_map(
                body,
                mesh=mesh,
                in_specs=(P(hvd.WORLD_AXIS), P()),
                out_specs=P(hvd.WORLD_AXIS),
                check_vma=False,
            )
        )

    def emit(leg, ms, counts, hops, extra=None):
        line = {
            "metric": "hier_ab",
            "leg": leg,
            "world": world,
            "intra": L,
            "slices": H,
            "layers": layers,
            "width": width,
            "grad_bytes": grad_bytes,
            "n_buckets": n_buckets,
            "value": round(ms, 3),
            "unit": "ms/step",
            "platform": platform,
            "collectives": counts,
            **hops,
        }
        if extra:
            line.update(extra)
        if platform != "tpu":
            line["note"] = _SIM_NOTE
        print(json.dumps(_stamp(line)), flush=True)
        with open(
            os.path.join(artifact_dir, f"hier_{leg}.json"), "a"
        ) as f:
            f.write(json.dumps(_stamp(line)) + "\n")

    # the schedule's bucket sizes drive the byte model: build it once
    leaves = [
        np.zeros(g.shape[1:], np.float32) for g in grads_host.values()
    ]
    sched = overlap.build_bucket_schedule(leaves, n_buckets, 0)
    bucket_elems = [b // 4 for b in sched.bucket_bytes]

    flat_hops = None
    results = {}
    for leg in ("ab_flat", "ab_hier", "ab_hier_int8"):
        step = make_step(leg)
        t = {k: jnp.asarray(v) for k, v in grads_host.items()}
        counts = _collective_counts(step.lower(t, jnp.int32(0)))
        out = step(t, jnp.int32(0))  # compile + warm
        _sync(out)
        t0 = time.perf_counter()
        for i in range(iters):
            out = step(t, jnp.int32(i + 1))
        _sync(out)
        ms = (time.perf_counter() - t0) * 1e3 / iters
        hops = _hop_accounting(bucket_elems, leg, L, H, block)
        if leg == "ab_flat":
            flat_hops = hops
        ratio = (
            round(flat_hops["inter_bytes"] / hops["inter_bytes"], 2)
            if hops["inter_bytes"]
            else None
        )
        hops["inter_ratio_vs_flat"] = ratio
        emit(leg, ms, counts, hops)
        results[leg] = (counts, hops)

    # structural gates (valid on every backend): per bucket one
    # intra-group RS + one inter collective + one intra-group AG
    nb = sched.n_buckets
    c_flat, c_hier = results["ab_flat"][0], results["ab_hier"][0]
    assert c_flat["all_reduce"] == nb, c_flat
    assert c_hier["reduce_scatter"] == nb, c_hier
    assert c_hier["all_reduce"] == nb, c_hier
    assert c_hier["all_gather"] == nb, c_hier
    c_q = results["ab_hier_int8"][0]
    assert c_q["reduce_scatter"] == nb, c_q
    assert c_q["all_to_all"] == 2 * nb, c_q  # int8 payload + scales
    # the pre-registered DCN-byte prediction (docs/perf.md): >= L x
    # for hier-fp32, >= 3x for hier-int8 (4L x minus scale overhead)
    assert results["ab_hier"][1]["inter_ratio_vs_flat"] >= L, results
    assert results["ab_hier_int8"][1]["inter_ratio_vs_flat"] >= 3.0, (
        results
    )
    print(
        json.dumps(
            {
                "metric": "hier_ab_summary",
                "inter_ratio_hier": results["ab_hier"][1][
                    "inter_ratio_vs_flat"
                ],
                "inter_ratio_hier_int8": results["ab_hier_int8"][1][
                    "inter_ratio_vs_flat"
                ],
                "gate": "inter bytes drop >=L (fp32) / >=3x (int8)",
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
