"""Pallas kernel tests (interpret mode on the CPU test mesh).

The same kernel code lowers to Mosaic on real TPU: tests/test_tpu_compile.py
compiles it for a described v5e, and every cell of the benchmark runs
the flash kernels on the chip against a float32 reference.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import pallas_kernels as pk


def test_scale_cast_matches_reference(rng):
    x = jnp.asarray(rng.normal(size=777).astype(np.float32))
    out = pk.scale_cast(x, 0.5, jnp.bfloat16)
    assert out.dtype == jnp.bfloat16 and out.shape == x.shape
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(x) * 0.5, rtol=1e-2, atol=1e-3
    )


def test_scale_cast_identity_dtype(rng):
    x = jnp.asarray(rng.normal(size=(13, 17)).astype(np.float32))
    out = pk.scale_cast(x, 2.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 2.0, rtol=1e-6)


def test_int8_quantize_roundtrip_error_bound(rng):
    x = jnp.asarray(rng.normal(size=(33, 47)).astype(np.float32))
    values, scale = pk.int8_quantize(x, seed=1)
    assert values.dtype == jnp.int8
    back = pk.int8_dequantize(values, scale)
    # stochastic rounding: per-element error bounded by one quantum
    err = np.abs(np.asarray(back) - np.asarray(x)).max()
    assert err <= float(scale) * 1.01


def test_int8_quantize_unbiased(rng):
    x = jnp.full((64, 128), 0.3, jnp.float32)
    errs = []
    for seed in range(5):
        v, s = pk.int8_quantize(x, seed=seed)
        back = pk.int8_dequantize(v, s)
        errs.append(float(np.mean(np.asarray(back) - np.asarray(x))))
    # bias shrinks under averaging over seeds
    assert abs(np.mean(errs)) < float(s) * 0.1


def test_adasum_pallas_matches_jax_reference(rng):
    from horovod_tpu.ops.adasum import adasum_pair as ada_ref

    a = jnp.asarray(rng.normal(size=1000).astype(np.float32))
    b = jnp.asarray(rng.normal(size=1000).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(pk.adasum_pair(a, b)),
        np.asarray(ada_ref(a, b)),
        rtol=1e-4,
        atol=1e-5,
    )


def test_adasum_pallas_self_combine_identity(rng):
    a = jnp.asarray(rng.normal(size=300).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(pk.adasum_pair(a, a)), np.asarray(a), rtol=1e-5, atol=1e-6
    )


def test_int8_compressor_roundtrip(rng):
    from horovod_tpu.ops.compression import Compression

    x = jnp.asarray(rng.normal(size=(8, 32)).astype(np.float32))
    wire, ctx = Compression.int8.compress(x)
    assert wire.dtype == jnp.int8
    back = Compression.int8.decompress(wire, ctx)
    assert back.dtype == x.dtype
    _, scale = ctx
    assert np.abs(np.asarray(back) - np.asarray(x)).max() <= float(scale) * 1.01


def test_int8_compressor_passes_through_ints():
    from horovod_tpu.ops.compression import Compression

    x = jnp.arange(10, dtype=jnp.int32)
    wire, ctx = Compression.int8.compress(x)
    assert wire.dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(Compression.int8.decompress(wire, ctx)), np.asarray(x)
    )


def test_quantized_allreduce_on_mesh(hvd, rng):
    """int8-wire allreduce approximates the exact psum within quantization
    error, across an 8-device mesh."""
    from functools import partial

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.ops import traced

    mesh = hvd.mesh()
    per_rank = np.stack(
        [rng.normal(size=256).astype(np.float32) * (r + 1) for r in range(8)]
    )

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=P(hvd.WORLD_AXIS),
        out_specs=P(hvd.WORLD_AXIS),
        check_vma=False,
    )
    def qmean(x):
        return traced.quantized_allreduce(x[0], op=hvd.Average)[None]

    got = np.asarray(jax.jit(qmean)(jnp.asarray(per_rank)))
    want = per_rank.mean(axis=0)
    # every rank sees the same result
    for r in range(8):
        np.testing.assert_allclose(got[r], got[0], rtol=0, atol=0)
    # two quantization stages (per-chunk scatter + reduced-shard gather):
    # stage-1 error ≤ mean of per-rank quanta, stage-2 ≤ one quantum of
    # the reduced shard — bound generously at 3x the largest quantum.
    quantum = np.abs(per_rank).max() / 127.0
    assert np.abs(got[0] - want).max() <= 3 * quantum


def test_distributed_optimizer_int8_compression(hvd, rng):
    """DistributedOptimizer(compression=int8) routes through the
    quantized collective and still averages gradients correctly."""
    from functools import partial

    import optax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    opt = hvd.DistributedOptimizer(
        optax.sgd(1.0), compression=hvd.Compression.int8
    )
    mesh = hvd.mesh()
    per_rank = np.stack(
        [rng.normal(size=512).astype(np.float32) for _ in range(8)]
    )
    params = jnp.zeros(512, jnp.float32)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(hvd.WORLD_AXIS), P()),
        out_specs=P(),
        check_vma=False,
    )
    def step(g, p):
        state = opt.init(p)
        updates, _ = opt.update(g[0], state, p)
        return updates

    updates = np.asarray(jax.jit(step)(jnp.asarray(per_rank), params))
    want = per_rank.mean(axis=0)
    quantum = np.abs(per_rank).max() / 127.0
    # sgd(1.0) updates are -grad
    assert np.abs(-updates - want).max() <= 3 * quantum


def test_quantized_allreduce_rejects_min():
    from horovod_tpu.ops import traced

    with pytest.raises(ValueError):
        # op check happens before any collective; no mesh needed
        traced.quantized_allreduce(jnp.zeros(4), op="min")
