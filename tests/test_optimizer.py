"""DistributedOptimizer / tape / broadcast-state tests.

Reference model: test/parallel/test_torch.py's DistributedOptimizer
step-equivalence-vs-manual-allreduce and broadcast_optimizer_state
round-trip tests [V] (SURVEY.md §4.1).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd_mod


def rank_major(fn, dtype=np.float32):
    return np.stack([np.asarray(fn(r), dtype=dtype) for r in range(8)])


def spmd(hvd, fn, in_specs, out_specs):
    return jax.jit(
        jax.shard_map(
            fn,
            mesh=hvd.mesh(),
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )
    )


def test_distributed_optimizer_equals_manual_allreduce(hvd):
    """One step of DistributedOptimizer(sgd) == sgd step on pmean'd grads."""
    opt = hvd_mod.DistributedOptimizer(optax.sgd(0.1))
    params = {"w": jnp.ones(4), "b": jnp.zeros(2)}
    grads_rm = {
        "w": rank_major(lambda r: np.full(4, float(r))),
        "b": rank_major(lambda r: np.full(2, 2.0 * r)),
    }

    def step(g):
        state = opt.init(params)
        updates, _ = opt.update(g, state, params)
        return optax.apply_updates(params, updates)

    out = spmd(
        hvd,
        lambda g: jax.tree_util.tree_map(
            lambda x: x[None], step(jax.tree_util.tree_map(lambda x: x[0], g))
        ),
        (P(hvd_mod.WORLD_AXIS),),
        jax.tree_util.tree_map(lambda _: P(hvd_mod.WORLD_AXIS), params),
    )(grads_rm)
    # mean grad w = 3.5, b = 7.0 → params - 0.1*mean
    np.testing.assert_allclose(np.asarray(out["w"][0]), np.full(4, 1 - 0.35))
    np.testing.assert_allclose(
        np.asarray(out["b"][3]), np.full(2, -0.7), rtol=1e-6
    )
    # all ranks identical
    np.testing.assert_allclose(np.asarray(out["w"][5]), np.asarray(out["w"][0]))


@pytest.mark.parametrize("avg_agg", [False, True])
def test_backward_passes_per_step_accumulates(hvd, avg_agg):
    """k=2: first micro-step is a no-op; the second applies the SUM of the
    micro-grads (reference default) or the mean with
    average_aggregated_gradients=True."""
    opt = hvd_mod.DistributedOptimizer(
        optax.sgd(1.0),
        backward_passes_per_step=2,
        average_aggregated_gradients=avg_agg,
    )
    params = jnp.zeros(3)
    g1 = rank_major(lambda r: np.full(3, 1.0))
    g2 = rank_major(lambda r: np.full(3, 3.0))

    def run(both):
        ga, gb = both

        def body(g_pair):
            a, b = g_pair
            state = opt.init(params)
            u1, state = opt.update(a, state, params)
            p1 = optax.apply_updates(params, u1)
            u2, state = opt.update(b, state, p1)
            p2 = optax.apply_updates(p1, u2)
            return p1[None], p2[None]

        return body((ga[0], gb[0]))

    p1, p2 = spmd(
        hvd,
        run,
        ((P(hvd_mod.WORLD_AXIS), P(hvd_mod.WORLD_AXIS)),),
        (P(hvd_mod.WORLD_AXIS), P(hvd_mod.WORLD_AXIS)),
    )((g1, g2))
    np.testing.assert_allclose(np.asarray(p1[0]), np.zeros(3))  # no step yet
    # boundary: sum of micro-grads = 1+3 = 4 (mean = 2 when averaging)
    expected = -2.0 if avg_agg else -4.0
    np.testing.assert_allclose(np.asarray(p2[0]), np.full(3, expected))


def test_gradient_predivide_factor(hvd):
    """predivide f: sum(g/(n f)) * f == average — numerically equal path."""
    opt = hvd_mod.DistributedOptimizer(
        optax.sgd(1.0), gradient_predivide_factor=2.0
    )
    params = jnp.zeros(2)
    g = rank_major(lambda r: np.full(2, float(r)))

    def step(gr):
        state = opt.init(params)
        updates, _ = opt.update(gr[0], state, params)
        return optax.apply_updates(params, updates)[None]

    out = spmd(hvd, step, (P(hvd_mod.WORLD_AXIS),), P(hvd_mod.WORLD_AXIS))(g)
    np.testing.assert_allclose(np.asarray(out[0]), np.full(2, -3.5), rtol=1e-6)


def test_predivide_requires_average():
    with pytest.raises(ValueError):
        hvd_mod.DistributedOptimizer(
            optax.sgd(0.1), gradient_predivide_factor=2.0, op=hvd_mod.Sum
        )


def test_distributed_optimizer_adasum(hvd, rng):
    """op=Adasum runs and produces identical params on every rank."""
    opt = hvd_mod.DistributedOptimizer(optax.sgd(0.5), op=hvd_mod.Adasum)
    params = jnp.ones(4)
    g = rank_major(lambda r: rng.normal(size=4))

    def step(gr):
        state = opt.init(params)
        updates, _ = opt.update(gr[0], state, params)
        return optax.apply_updates(params, updates)[None]

    out = spmd(hvd, step, (P(hvd_mod.WORLD_AXIS),), P(hvd_mod.WORLD_AXIS))(g)
    for r in range(1, 8):
        np.testing.assert_allclose(
            np.asarray(out[r]), np.asarray(out[0]), rtol=1e-5, atol=1e-6
        )


def test_compression_fp16_roundtrip_in_optimizer(hvd):
    opt = hvd_mod.DistributedOptimizer(
        optax.sgd(1.0), compression=hvd_mod.Compression.fp16
    )
    params = jnp.zeros(3)
    g = rank_major(lambda r: np.full(3, float(r)))

    def step(gr):
        state = opt.init(params)
        updates, _ = opt.update(gr[0], state, params)
        p = optax.apply_updates(params, updates)
        return p[None]

    out = spmd(hvd, step, (P(hvd_mod.WORLD_AXIS),), P(hvd_mod.WORLD_AXIS))(g)
    assert out.dtype == jnp.float32  # decompressed back
    np.testing.assert_allclose(np.asarray(out[0]), np.full(3, -3.5), rtol=1e-3)


def test_value_and_grad_tape(hvd):
    """hvd.value_and_grad == DistributedGradientTape: grads averaged."""

    def loss(w, x):
        return jnp.sum(w * x)

    vg = hvd_mod.value_and_grad(loss)
    w = jnp.ones(3)
    x = rank_major(lambda r: np.full(3, float(r)))

    def step(xr):
        val, g = vg(w, xr[0])
        return val[None], g[None]

    vals, grads = spmd(
        hvd,
        step,
        (P(hvd_mod.WORLD_AXIS),),
        (P(hvd_mod.WORLD_AXIS), P(hvd_mod.WORLD_AXIS)),
    )(x)
    np.testing.assert_allclose(np.asarray(grads[0]), np.full(3, 3.5))
    np.testing.assert_allclose(np.asarray(grads[7]), np.full(3, 3.5))


def test_broadcast_parameters_replicates(hvd):
    params = {"w": np.arange(6.0, dtype=np.float32).reshape(2, 3)}
    out = hvd_mod.broadcast_parameters(params, root_rank=0)
    assert out["w"].sharding.is_fully_replicated
    np.testing.assert_allclose(np.asarray(out["w"]), params["w"])


def test_broadcast_optimizer_state_roundtrip(hvd):
    opt = optax.adam(1e-3)
    params = {"w": jnp.ones((3, 3))}
    state = opt.init(params)
    out = hvd_mod.broadcast_optimizer_state(state)
    leaves_in = jax.tree_util.tree_leaves(state)
    leaves_out = jax.tree_util.tree_leaves(out)
    assert len(leaves_in) == len(leaves_out)
    for a, b in zip(leaves_in, leaves_out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_broadcast_object_single_controller(hvd):
    obj = {"step": 7, "note": "hello"}
    assert hvd_mod.broadcast_object(obj) is obj


# ------------------------------------------------------ the exchange's plan


def _plans():
    from horovod_tpu.common import tracing

    return [r["tags"] for r in tracing.recorder().spans()
            if r["name"] == "hvd.exchange.plan"]


def _plan_params():
    # 8 x 16 + 16 + 16 x 4 + 2 x 128 = 464 float32 elements in 4 leaves
    return {"w": jnp.ones((8, 16)), "b": jnp.zeros((16,)),
            "v": jnp.ones((16, 4)), "u": jnp.ones((2, 128))}


def _plan_loss(p, x):
    return jnp.mean((jnp.tanh(x @ p["w"] + p["b"]) @ p["v"]) ** 2) + (
        jnp.sum(p["u"] ** 2))


@pytest.mark.parametrize("kwargs, want", [
    ({}, dict(bytes=464 * 4, wire="float32", buckets=0, collectives=4)),
    ({"overlap_buckets": 4, "overlap_min_bytes": 0},
     dict(bytes=464 * 4, wire="float32", buckets=4, collectives=4)),
    ({"compression": hvd_mod.Compression.bf16},
     dict(bytes=464 * 2, wire="bfloat16", buckets=0, collectives=4)),
    ({"compression": hvd_mod.Compression.int8},
     dict(bytes=464, wire="int8", buckets=0, collectives=4)),
], ids=["default", "overlap4", "bf16", "int8"])
def test_the_exchange_leaves_its_plan_and_counts_the_lowered_bytes(
        hvd, kwargs, want):
    """``hvd.exchange.plan``: one span a traced exchange, and its
    ``bytes`` are what the benchmark counts from the lowered step's
    world-spanning all-reduces (a quantized wire goes by all-to-all and
    all-gather, which that count does not see)."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark.lib import xtrace

    opt = hvd_mod.DistributedOptimizer(optax.sgd(0.1), **kwargs)
    params = _plan_params()
    state = opt.init(params)

    def step(params, state, x):
        grads = jax.grad(_plan_loss)(params, x[0])
        updates, state = opt.update(grads, state, params)
        return optax.apply_updates(params, updates), state

    x = jnp.ones((hvd.size(), 4, 8))
    before = len(_plans())
    lowered = spmd(hvd, step, (P(), P(), P(hvd_mod.WORLD_AXIS)),
                   (P(), P())).lower(params, state, x)
    (plan,) = _plans()[before:]
    assert plan == dict(want, world=8, op="average", leaves=4)
    if plan["wire"] != "int8":
        assert plan["bytes"] == xtrace.world_allreduce_bytes(
            lowered.as_text(), 8)
    # an eager call plans nothing: the span is trace time's
    assert len(_plans()) == before + 1


def test_the_tape_api_and_the_zero_optimizer_leave_plans_too(hvd):
    params = _plan_params()
    x = jnp.ones((hvd.size(), 4, 8))
    before = len(_plans())

    def tape(params, x):
        return hvd_mod.value_and_grad(_plan_loss)(params, x[0])[1]

    spmd(hvd, tape, (P(), P(hvd_mod.WORLD_AXIS)), P()).lower(params, x)
    zero = hvd_mod.ShardedDistributedOptimizer(optax.sgd(0.1), wire="bf16")
    state = zero.init(params)

    def step(params, state, x):
        grads = jax.grad(_plan_loss)(params, x[0])
        return zero.update(grads, state, params)

    spmd(hvd, step, (P(), zero.state_spec(), P(hvd_mod.WORLD_AXIS)),
         (P(), zero.state_spec())).lower(params, state, x)
    tape_plan, zero_plan = _plans()[before:]
    assert tape_plan == dict(world=8, op="average", leaves=4, bytes=464 * 4,
                             wire="float32", buckets=0, collectives=4)
    assert zero_plan == dict(world=8, op="average", leaves=4, bytes=464 * 2,
                             wire="bf16", buckets=0, collectives=1)
