"""First contact with the chip: the quick-start trainer, end to end.

``python chip_smoke.py`` trains GPT-2 medium at full width (24 x 1024,
16 heads, vocab 50257, bf16 compute, flash attention at block 512,
remat on) at batch 8 per chip, seq 512, for a few optimizer steps on
seeded random tokens, through the entry points a user calls:
``hvd.init()`` -> ``hvd.mesh()`` -> ``hvd.broadcast_parameters`` ->
``hvd.DistributedOptimizer`` -> a ``jax.shard_map`` train step over
``hvd.WORLD_AXIS``. One process drives every local chip, so the same
file is the one-chip run and the four-chip run.

It exits non-zero unless the platform is a TPU, the compiled step holds
the Mosaic flash kernels (forward, dQ, dK/dV), the loss is finite at
every step and lower at the last step than at the first, nothing
compiles after step 1, and - on more than one device - the step holds a
world-spanning all-reduce, every device holds a full parameter copy and
1/world of the token batch. The step time it prints is a smoke reading,
not a benchmark metric. The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

``train_smoke`` is the body; tests drive it on the CPU mesh at
``TransformerConfig.tiny()``. The ``__main__`` path has no size or
platform switch.
"""

import dataclasses
import json
import re
import sys
import time
from functools import partial

import numpy as np

STEPS = 8  # step 1 warms; steps 2..8 are the timed window
BATCH = 8  # per chip
SEQ = 512
FLASH_KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")

# Every trace, lowering and backend compile of the process reports one
# of these (a persistent-cache hit still traces and lowers, so a second
# program cannot hide behind the cache).
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


def _spanning_allreduces_compiled(hlo_text: str, world: int) -> int:
    """All-reduces in optimized HLO text whose replica group holds the
    whole world, in either spelling XLA prints: ``{{0,1,2,3}}`` or the
    iota form ``[groups,size]<=[world]``."""
    n = 0
    for m in re.finditer(
        r"all-reduce(?:-start)?\(.*?replica_groups="
        r"(?:\{\{([\d,]+)\}|\[(\d+),(\d+)\]<=)",
        hlo_text,
    ):
        size = len(m.group(1).split(",")) if m.group(1) else int(m.group(3))
        n += size == world
    return n


def _host_sync(x):
    """Wait for ``x`` by moving one scalar of it to the host: a transfer
    of a value that depends on the whole timed loop cannot return early,
    whatever the runtime does with ``block_until_ready``. The leaf is
    sliced on the device first, so one scalar crosses."""
    import jax

    leaf = jax.tree.leaves(x)[0].reshape(-1)[:1]
    return float(np.asarray(leaf)[0])


def train_smoke(cfg, steps, batch=BATCH, seq=SEQ, op=None):
    """Run the quick-start trainer for ``steps`` optimizer steps of
    model config ``cfg`` on every local device; raise on any failed
    check; return the report dict (also printed line by line)."""
    import jax
    import jax.monitoring
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu import analysis
    from horovod_tpu.models import Transformer

    if steps < 2:
        raise ValueError("need a warm step and at least one timed step")
    compiles = []  # (jax.monitoring has no unregister; one list per call)
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, *args, **kw: (
            compiles.append(event) if event in _COMPILE_EVENTS else None
        )
    )
    hvd.init()
    mesh = hvd.mesh()
    world = hvd.size()
    if world != jax.device_count():
        raise RuntimeError(
            f"hvd.size()={world} but jax sees {jax.device_count()} devices"
        )
    reduce_op = hvd.Average if op is None else op
    print(f"chip_smoke: world={world} model=L{cfg.num_layers}xD{cfg.d_model}"
          f" heads={cfg.num_heads} vocab={cfg.vocab_size} batch/chip={batch}"
          f" seq={seq} remat={cfg.remat} op={reduce_op.name}")

    model = Transformer(cfg)
    rng = np.random.default_rng(0)
    shape = (world, batch, seq)
    rank_major = hvd.rank_sharding(mesh)
    toks = jax.device_put(
        rng.integers(0, cfg.vocab_size, size=shape).astype(np.int32),
        rank_major,
    )
    labels = jax.device_put(
        rng.integers(0, cfg.vocab_size, size=shape).astype(np.int32),
        rank_major,
    )
    params = jax.jit(
        lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((batch, seq), jnp.int32),
            train=False,
        )
    )()
    # Placed with the mesh sharding BEFORE the first call: a step fed
    # single-device arrays returns mesh-sharded ones, and the second
    # call would then be a different program.
    params = hvd.broadcast_parameters(params)
    # the quick start scales the rate by the world for an averaged
    # gradient; Adasum's combine is the scaling, so it keeps the base
    lr = 0.01 * (1 if reduce_op == hvd.Adasum else world)
    opt = hvd.DistributedOptimizer(
        optax.sgd(lr, momentum=0.9), op=reduce_op
    )
    opt_state = hvd.broadcast_optimizer_state(opt.init(params))
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P(), P(hvd.WORLD_AXIS), P(hvd.WORLD_AXIS)),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    def train_step(params, opt_state, tokens, labels):
        tokens, labels = tokens[0], labels[0]

        def loss_fn(p):
            logits = model.apply(p, tokens, train=True)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), labels
            ).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, jax.lax.pmean(loss, hvd.WORLD_AXIS)

    t0 = time.perf_counter()
    lowered = train_step.lower(params, opt_state, toks, labels)
    t1 = time.perf_counter()
    step = lowered.compile()
    compile_s = time.perf_counter() - t1
    print(f"chip_smoke: trace+lower {t1 - t0:.1f} s, compile {compile_s:.1f} s")

    stablehlo = lowered.as_text()
    hlo = step.as_text()
    mosaic = {
        k: stablehlo.count(f'kernel_name = "{k}"') for k in FLASH_KERNELS
    }
    mosaic["tpu_custom_call"] = hlo.count('custom_call_target="tpu_custom_call"')
    print(f"chip_smoke: mosaic kernels in the compiled step: {mosaic}")

    report = {
        "world": world,
        "compile_s": round(compile_s, 2),
        "mosaic": mosaic,
        "param_bytes": param_bytes,
    }
    if world > 1:
        graph = analysis.parse_module(lowered)
        spanning = [
            c for c in graph.collectives("all_reduce") if c.spans(world)
        ]
        ar_bytes = sum(c.operand_bytes for c in spanning)
        n_compiled = _spanning_allreduces_compiled(hlo, world)
        print(f"chip_smoke: world-spanning all-reduces: {len(spanning)} lowered"
              f" ({ar_bytes} B), {n_compiled} in the compiled HLO")
        if not spanning or not n_compiled:
            raise RuntimeError(
                f"no all-reduce spans all {world} devices in the step"
            )
        if op is None and ar_bytes < param_bytes:
            raise RuntimeError(
                f"world-spanning all-reduces carry {ar_bytes} B, less than"
                f" the {param_bytes} B of gradients"
            )
        devices = set(mesh.devices.flat)
        for leaf in jax.tree.leaves(params):
            shards = leaf.addressable_shards
            if {s.device for s in shards} != devices or any(
                s.data.shape != leaf.shape for s in shards
            ):
                raise RuntimeError(
                    "a parameter is not fully addressable on every device:"
                    f" {leaf.shape} {leaf.sharding}"
                )
        tok_shards = toks.addressable_shards
        if {s.device for s in tok_shards} != devices or any(
            s.data.shape != (1, batch, seq) for s in tok_shards
        ):
            raise RuntimeError(
                "each device should hold 1/world of the token batch, got "
                f"{[(str(s.device), s.data.shape) for s in tok_shards]}"
            )
        print(f"chip_smoke: full parameter copy and 1/{world} of the batch on"
              f" each of {len(devices)} devices")
        report["allreduce"] = {
            "lowered": len(spanning), "bytes": ar_bytes,
            "compiled": n_compiled,
        }

    t0 = time.perf_counter()
    params, opt_state, loss = step(params, opt_state, toks, labels)
    _host_sync(loss)  # also compiles sync's own tiny programs, once
    first_step_s = time.perf_counter() - t0
    losses = [loss]
    compiles_after_step1 = len(compiles)

    t0 = time.perf_counter()
    for _ in range(steps - 1):
        params, opt_state, loss = step(params, opt_state, toks, labels)
        losses.append(loss)
    jax.block_until_ready(loss)
    dt_block = time.perf_counter() - t0
    _host_sync(loss)  # a host transfer that depends on the whole chain
    dt_host = time.perf_counter() - t0

    recompiles = len(compiles) - compiles_after_step1
    losses = [float(x) for x in losses]
    step_ms = dt_block * 1e3 / (steps - 1)
    # block_until_ready is honest when the host transfer after it finds
    # nothing left to wait for
    sync_agree = dt_host - dt_block <= max(0.05 * dt_host, 0.02)
    print(f"chip_smoke: losses {[round(x, 4) for x in losses]}")
    print(f"chip_smoke: first step {first_step_s:.2f} s; steady"
          f" {step_ms:.1f} ms/step over {steps - 1} steps (smoke reading,"
          " not a metric)")
    print(f"chip_smoke: window by block_until_ready {dt_block * 1e3:.1f} ms,"
          f" by host transfer {dt_host * 1e3:.1f} ms -> "
          + ("agree" if sync_agree else "DISAGREE"))
    print(f"chip_smoke: compilations after step 1: {recompiles}")
    stats = jax.local_devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"chip_smoke: peak device memory "
              f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB")
    report.update(
        losses=losses, step_ms=round(step_ms, 2),
        first_step_s=round(first_step_s, 2), recompiles=recompiles,
        sync_agree=sync_agree,
        peak_bytes=stats.get("peak_bytes_in_use"),
    )
    if not all(np.isfinite(losses)):
        raise RuntimeError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"loss did not fall: {losses}")
    if recompiles:
        raise RuntimeError(f"{recompiles} compilations after step 1")
    if not sync_agree:
        raise RuntimeError(
            "block_until_ready returned before the device finished: "
            f"{dt_block:.3f} s vs {dt_host:.3f} s by host transfer"
        )
    return report


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax reports platform={dev.platform!r});"
              " this smoke only means something on the chip",
              file=sys.stderr)
        return 1
    import jaxlib

    # the repo's own modules before the first line of output: a copy of
    # this file alone fails here, having printed nothing
    from horovod_tpu.common import compile_cache
    from horovod_tpu.models import TransformerConfig

    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "not importable"
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    print(f"chip_smoke: device {device} jax {jax.__version__} jaxlib"
          f" {jaxlib.__version__} libtpu {libtpu_version}")
    print(f"chip_smoke: compile cache at {compile_cache.ensure()}")
    cfg = dataclasses.replace(TransformerConfig.gpt2_medium(), remat=True)
    report = train_smoke(cfg, STEPS)
    missing = [k for k in FLASH_KERNELS if not report["mosaic"][k]]
    if missing or not report["mosaic"]["tpu_custom_call"]:
        raise RuntimeError(
            "the compiled step holds no Mosaic custom call for "
            f"{missing or 'any kernel'}: attention fell off the flash path"
        )
    print("chip_smoke: report " + json.dumps(report))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
