"""Expert wire A/B (PR 12, parallel/moe.py + ops/traced.py
quantized/hierarchical alltoall + common/autotune.py CapacityTuner).

Measures what the quantized two-level dispatch buys on the axis that
matters for MoE at multi-slice scale: expert-dispatch bytes crossing
the DCN hop, at IDENTICAL routing. Three legs over the SAME tokens,
router and expert bank (a synthetic multi-slice split of the 8-device
mesh, intra groups of ``BENCH_INTRA``), each appending one JSON
artifact under BENCH_ARTIFACT_DIR (default bench_results/moe/):

* ``ab_flat``      — the seed wire: raw fp32 through one monolithic
  ``lax.all_to_all`` each way; every cross-slice token crosses DCN at
  payload width.
* ``ab_hier_int8`` — the EQuARX placement for expert dispatch: the
  inter hop moves block-scaled int8 (+fp32 scales) for CROSS-SLICE
  tokens only (intra-slice tokens ride ICI exact), ~4x fewer scarce-
  hop bytes. Routing decisions are computed on fp32 logits BEFORE the
  wire, so the two legs route identically — asserted bitwise on the
  expert histograms — and outputs agree within the pre-registered
  bound (docs/perf.md).
* ``ab_captuned``  — the capacity-factor autotuner loop: each
  candidate factor is its own compiled step (capacity is a shape);
  the harness times a few honestly-synced steps per candidate, feeds
  kept-token goodput + the overflow/drop counters into the
  CapacityTuner, and reports the factor it converges on plus the
  drop-rate-vs-factor curve (the docs/perf.md prediction table's
  third row).

Each artifact records ms/step, the lowered all_to_all replica-group
structure (the compiled-program evidence: group-limited intra+inter
legs, NO world-spanning alltoall on the hier leg), and per-hop
expert-dispatch byte accounting from the row-crossing model below
(dispatch + return, payload rows only — the int32 expert map is
world-size-invariant noise). BENCH_DRYRUN=1 is the CI smoke shape
(tiny model, 2 iters; ``./ci.sh bench-smoke`` gates on the artifacts
AND on the pre-registered prediction that the hier-int8 leg drops
inter-hop expert-dispatch bytes >= 3x vs flat fp32 with identical
routing). CPU lines carry the quarantine note: wall-clock claims need
the on-chip capture; the dryrun validates harness + HLO shape + byte
accounting.

Env: BENCH_TOKENS / BENCH_DMODEL / BENCH_DFF / BENCH_INTRA /
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


def _a2a_group_sizes(lowered):
    """Replica-group row lengths of every all_to_all in the module —
    via the shared horovod_tpu.analysis parser (same gate as
    tests/test_moe_wire)."""
    from horovod_tpu import analysis

    return analysis.parse_module(lowered).group_sizes("all_to_all")


def _hop_bytes(leg, L, H, capacity, d, block):
    """Per-step per-rank expert-dispatch wire bytes by hop (dispatch +
    return, payload rows only): a row crosses the INTER (DCN) boundary
    iff its destination lives in another slice — (H-1)·L·C rows either
    way — at fp32 on the flat leg, int8 + per-block fp32 scales on the
    hier-int8 leg. The intra (ICI) hop carries (L-1)·C rows flat /
    (L-1)·H·C rows hier, always exact."""
    nb = -(-d // block)
    int8_row = d + nb * 4
    fp32_row = d * 4
    inter_rows = (H - 1) * L * capacity
    if leg == "ab_hier_int8":
        inter = 2 * inter_rows * int8_row
        intra = 2 * (L - 1) * H * capacity * fp32_row
    else:
        inter = 2 * inter_rows * fp32_row
        intra = 2 * (L - 1) * capacity * fp32_row
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
    from horovod_tpu.common.autotune import shared_capacity_tuner
    from jax import shard_map
    from horovod_tpu.common.metrics import publish_moe
    from horovod_tpu.common.topology import hierarchical_stage_groups
    from horovod_tpu.parallel.moe import (
        MoEParams,
        init_moe_params,
        moe_ffn,
    )

    dryrun = os.environ.get("BENCH_DRYRUN", "").strip() in ("1", "true")
    iters = int(os.environ.get("BENCH_ITERS", "2" if dryrun else "30"))
    tokens = int(os.environ.get("BENCH_TOKENS", "32" if dryrun else "512"))
    d_model = int(os.environ.get("BENCH_DMODEL", "64" if dryrun else "512"))
    d_ff = int(os.environ.get("BENCH_DFF", "128" if dryrun else "2048"))
    intra = int(os.environ.get("BENCH_INTRA", "4"))
    block = min(128, d_model)

    artifact_dir = os.environ.get(
        "BENCH_ARTIFACT_DIR", os.path.join("bench_results", "moe")
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
    e_local = 2
    e_total = e_local * world

    rng = np.random.default_rng(0)
    params = init_moe_params(
        jax.random.PRNGKey(0), d_model, d_ff, e_total, e_total
    )
    spec = MoEParams(
        router=P(), w1=P(hvd.WORLD_AXIS), b1=P(hvd.WORLD_AXIS),
        w2=P(hvd.WORLD_AXIS), b2=P(hvd.WORLD_AXIS),
    )
    x = rng.normal(size=(world, tokens, d_model)).astype(np.float32)

    def make_step(leg, capacity_factor=1.25):
        hier = None if leg == "ab_flat" else stages
        wire = "int8" if leg == "ab_hier_int8" else "fp32"

        def body(p, v, s):
            out, st = moe_ffn(
                p, v[0], axis_name=hvd.WORLD_AXIS,
                capacity_factor=capacity_factor, wire=wire, hier=hier,
                seed=s, block_size=block, return_stats=True,
            )
            return out[None], st

        return jax.jit(
            shard_map(
                body,
                mesh=mesh,
                in_specs=(spec, P(hvd.WORLD_AXIS), P()),
                out_specs=(P(hvd.WORLD_AXIS), P()),
                check_vma=False,
            )
        )

    def emit(leg, ms, a2a_sizes, hops, extra=None):
        line = {
            "metric": "moe_ab",
            "leg": leg,
            "world": world,
            "intra": L,
            "slices": H,
            "tokens_per_rank": tokens,
            "d_model": d_model,
            "e_total": e_total,
            "value": round(ms, 3),
            "unit": "ms/step",
            "platform": platform,
            "a2a_group_sizes": sorted(a2a_sizes),
            **hops,
        }
        if extra:
            line.update(extra)
        if platform != "tpu":
            line["note"] = _SIM_NOTE
        print(json.dumps(_stamp(line)), flush=True)
        with open(
            os.path.join(artifact_dir, f"moe_{leg}.json"), "a"
        ) as f:
            f.write(json.dumps(_stamp(line)) + "\n")

    capacity = int(max(1, round(1.25 * tokens / world)))
    xd = jnp.asarray(x)
    results = {}
    flat_hops = None
    for leg in ("ab_flat", "ab_hier_int8"):
        step = make_step(leg)
        sizes = _a2a_group_sizes(step.lower(params, xd, jnp.int32(0)))
        out, st = step(params, xd, jnp.int32(0))  # compile + warm
        _sync(out)
        t0 = time.perf_counter()
        for i in range(iters):
            out, st = step(params, xd, jnp.int32(i + 1))
        _sync(out)
        ms = (time.perf_counter() - t0) * 1e3 / iters
        hops = _hop_bytes(leg, L, H, capacity, d_model, block)
        if leg == "ab_flat":
            flat_hops = hops
        hops["inter_ratio_vs_flat"] = (
            round(flat_hops["inter_bytes"] / hops["inter_bytes"], 2)
            if hops["inter_bytes"]
            else None
        )
        emit(leg, ms, sizes, hops)
        results[leg] = {
            "sizes": sizes,
            "hops": hops,
            "hist": np.asarray(st.expert_tokens),
            "dropped": float(st.dropped),
            "out": np.asarray(out),
        }

    # ------------------------------------------- capacity autotune leg
    # durable instance (HOROVOD_TUNER_CACHE): warm-started from prior
    # runs, persisted at exit — capacity exploration is paid once per
    # topology fingerprint, not once per process per run
    tuner = shared_capacity_tuner(
        trials=1 if dryrun else 2,
        candidates=(1.0, 2.0) if dryrun else (1.0, 1.25, 1.5, 2.0),
    )
    key = ("moe", world, tokens, d_model)
    curve = {}
    cap_iters = max(2, iters)
    while tuner.needs_trial(key, tuner.choose(key)):
        cf = tuner.choose(key)
        step = make_step("ab_captuned", capacity_factor=cf)
        out, st = step(params, xd, jnp.int32(0))
        _sync(out)
        t0 = time.perf_counter()
        for i in range(cap_iters):
            out, st = step(params, xd, jnp.int32(i + 1))
        _sync(out)
        secs = (time.perf_counter() - t0) / cap_iters
        hist = np.asarray(st.expert_tokens)
        tuner.observe_load(
            key, cf, hist, dropped=float(st.dropped),
            total=float(st.total), seconds=secs,
        )
        publish_moe(
            hist, float(st.dropped), float(st.total), capacity_factor=cf
        )
        curve[str(cf)] = {
            "drop_rate": round(tuner.drop_rate(key, cf), 4),
            "imbalance": round(tuner.imbalance(key, cf), 3),
            "ms_per_step": round(secs * 1e3, 3),
        }
    chosen = tuner.choose(key)
    emit(
        "ab_captuned",
        curve[str(chosen)]["ms_per_step"],
        [],
        {"intra_bytes": 0, "inter_bytes": 0},
        extra={
            "chosen_capacity_factor": chosen,
            "drop_curve": curve,
            "unit_note": "ms/step at the chosen factor",
        },
    )
    assert chosen in tuner.candidates
    # the curve is monotone where it must be: more capacity, fewer drops
    cands = sorted(float(c) for c in curve)
    drops = [curve[str(c)]["drop_rate"] for c in cands]
    assert all(a >= b - 1e-9 for a, b in zip(drops, drops[1:])), curve

    # structural gates (valid on every backend): the hier leg's
    # compiled program carries ONLY group-limited all_to_alls (intra
    # size-L legs + inter size-H legs), never a monolithic flat one;
    # the flat leg is exactly the monolithic baseline
    flat_sizes = results["ab_flat"]["sizes"]
    hier_sizes = results["ab_hier_int8"]["sizes"]
    assert flat_sizes and all(s == world for s in flat_sizes), flat_sizes
    assert hier_sizes and all(s < world for s in hier_sizes), hier_sizes
    assert {s for s in hier_sizes} <= {L, H}, hier_sizes
    # identical routing: the wire is downstream of the router by
    # construction — bitwise-equal expert histograms and drop counts
    np.testing.assert_array_equal(
        results["ab_flat"]["hist"], results["ab_hier_int8"]["hist"]
    )
    assert results["ab_flat"]["dropped"] == (
        results["ab_hier_int8"]["dropped"]
    )
    # outputs within the pre-registered bound (docs/perf.md): a few
    # quanta through the expert FFN on cross-slice tokens only
    a, b = results["ab_flat"]["out"], results["ab_hier_int8"]["out"]
    scale = float(np.abs(a).max())
    max_dev = float(np.abs(a - b).max())
    assert max_dev <= 0.15 * scale, (max_dev, scale)
    # the pre-registered DCN-byte prediction: >= 3x fewer inter-hop
    # expert-dispatch bytes for hier-int8 vs flat fp32
    ratio = results["ab_hier_int8"]["hops"]["inter_ratio_vs_flat"]
    assert ratio >= 3.0, results
    print(
        json.dumps(
            {
                "metric": "moe_ab_summary",
                "inter_ratio_hier_int8": ratio,
                "routing_identical": True,
                "max_output_dev_frac": round(max_dev / scale, 5),
                "chosen_capacity_factor": chosen,
                "gate": (
                    "inter expert-dispatch bytes drop >=3x, routing "
                    "bitwise identical, outputs within 0.15*scale"
                ),
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
