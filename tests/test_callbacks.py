"""Callback + SyncBatchNorm tests (ref test model: the Keras callback
coverage inside test/parallel/test_tensorflow_keras.py [V])."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P


def test_broadcast_global_variables_callback(hvd, rng):
    """All workers leave on_train_begin with rank 0's weights."""
    from horovod_tpu.callbacks import BroadcastGlobalVariablesCallback

    # Rank-dependent params: only rank 0's values must survive.
    params = {
        "w": hvd.shard_from_rank_fn(
            lambda r: np.full((4,), float(r), np.float32), hvd.mesh()
        )
    }
    cb = BroadcastGlobalVariablesCallback(root_rank=0)
    out = cb.on_train_begin(params)
    host = np.asarray(out["w"])
    np.testing.assert_allclose(host, 0.0)


def test_metric_average_callback(hvd, monkeypatch):
    """Scalar metrics are averaged across workers; strings untouched."""
    from horovod_tpu.callbacks import MetricAverageCallback

    cb = MetricAverageCallback()
    logs = {"loss": 2.0, "acc": 0.5, "note": "epoch done"}
    cb.on_epoch_end(0, logs)
    # single-controller world: average of identical values is identity,
    # but the value must round-trip through a real collective
    assert logs["loss"] == pytest.approx(2.0)
    assert logs["acc"] == pytest.approx(0.5)
    assert logs["note"] == "epoch done"


def test_warmup_callback_ramp(hvd):
    from horovod_tpu.callbacks import LearningRateWarmupCallback

    cb = LearningRateWarmupCallback(initial_lr=0.8, warmup_epochs=4)
    size = hvd.size()
    cb.on_epoch_begin(0)
    assert cb.current_lr == pytest.approx(0.8 / size)
    cb.on_epoch_begin(4)
    assert cb.current_lr == pytest.approx(0.8)
    # monotone ramp
    lrs = []
    for e in range(5):
        cb.on_epoch_begin(e)
        lrs.append(cb.current_lr)
    assert all(a <= b + 1e-12 for a, b in zip(lrs, lrs[1:]))


def test_warmup_multiplier_per_batch(hvd):
    from horovod_tpu.callbacks import LearningRateWarmupCallback

    cb = LearningRateWarmupCallback(
        initial_lr=1.0, warmup_epochs=2, steps_per_epoch=10
    )
    m0 = cb.multiplier(0, batch=0)
    m_half = cb.multiplier(0, batch=5)
    m1 = cb.multiplier(1, batch=0)
    assert m0 < m_half < m1 <= 1.0
    assert cb.multiplier(2, batch=0) == 1.0


def test_schedule_callback_piecewise():
    from horovod_tpu.callbacks import LearningRateScheduleCallback

    cb = LearningRateScheduleCallback(
        initial_lr=1.0, multiplier=0.1, start_epoch=30, end_epoch=60
    )
    cb.on_epoch_begin(0)
    assert cb.current_lr == pytest.approx(1.0)
    cb.on_epoch_begin(30)
    assert cb.current_lr == pytest.approx(0.1)
    cb.on_epoch_begin(60)  # out of range: keeps last value (ref behavior)
    assert cb.current_lr == pytest.approx(0.1)


def test_schedule_callback_callable_multiplier():
    from horovod_tpu.callbacks import LearningRateScheduleCallback

    cb = LearningRateScheduleCallback(
        initial_lr=2.0, multiplier=lambda e: 1.0 / (1 + e)
    )
    cb.on_epoch_begin(3)
    assert cb.current_lr == pytest.approx(2.0 / 4)


def test_callback_list_threads_state(hvd):
    from horovod_tpu.callbacks import (
        BroadcastGlobalVariablesCallback,
        CallbackList,
        LearningRateWarmupCallback,
    )

    cbs = CallbackList(
        [
            BroadcastGlobalVariablesCallback(),
            LearningRateWarmupCallback(0.1, warmup_epochs=2),
        ]
    )
    params = {"w": hvd.replicate(np.ones((2,), np.float32))}
    out = cbs.on_train_begin(params)
    assert out is not None and "w" in out
    out = cbs.on_epoch_begin(0, out)
    assert "w" in out


def test_warmup_schedule_pure(hvd):
    from horovod_tpu.callbacks import warmup_schedule

    size = hvd.size()
    sched = warmup_schedule(base_lr=0.8, warmup_steps=100)
    assert float(sched(0)) == pytest.approx(0.8 / size)
    assert float(sched(100)) == pytest.approx(0.8)
    assert float(sched(1000)) == pytest.approx(0.8)
    assert float(sched(50)) == pytest.approx(
        0.8 * size**0.5 / size, rel=1e-5
    )


def test_piecewise_schedule_pure():
    from horovod_tpu.callbacks import piecewise_schedule

    sched = piecewise_schedule(1.0, [(30, 0.1), (60, 0.01)])
    assert float(sched(0)) == pytest.approx(1.0)
    assert float(sched(30)) == pytest.approx(0.1)
    assert float(sched(59)) == pytest.approx(0.1)
    assert float(sched(61)) == pytest.approx(0.01)


def test_sync_batch_norm_global_stats(hvd, rng):
    """SyncBatchNorm inside shard_map normalizes with GLOBAL batch
    statistics: replicas with different data agree on mean/var (ref:
    test_torch.py's sync-BN equivalence-to-global-batch pattern [V])."""
    import horovod_tpu as hvd_pkg
    from jax import shard_map

    mesh = hvd.mesh()
    bn = hvd_pkg.SyncBatchNorm(axis_name=hvd.WORLD_AXIS)
    # per-rank batches with very different means
    data = np.stack(
        [rng.normal(loc=float(r), size=(4, 3)).astype(np.float32)
         for r in range(8)]
    )

    variables = bn.init(jax.random.PRNGKey(0), data[0])

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(hvd.WORLD_AXIS)),
        out_specs=P(hvd.WORLD_AXIS),
        check_vma=False,
    )
    def apply(vars_, x):
        y, _ = bn.apply(
            vars_, x[0], use_running_average=False,
            mutable=["batch_stats"],
        )
        return y[None]

    out = np.asarray(jax.jit(apply)(variables, jnp.asarray(data)))
    # global normalization: concatenating all shards gives ~zero mean,
    # ~unit variance per feature
    flat = out.reshape(-1, 3)
    np.testing.assert_allclose(flat.mean(axis=0), 0.0, atol=1e-4)
    np.testing.assert_allclose(flat.std(axis=0), 1.0, atol=1e-2)
    # and per-shard means are NOT zero (each shard is offset), proving
    # stats were global, not local
    per_shard_means = out.mean(axis=(1, 2))
    assert np.abs(per_shard_means).max() > 0.3


def test_sync_batch_norm_running_average_inference(hvd, rng):
    import horovod_tpu as hvd_pkg

    bn = hvd_pkg.SyncBatchNorm()  # no axis: plain BN on one device
    x = rng.normal(size=(16, 5)).astype(np.float32)
    variables = bn.init(jax.random.PRNGKey(0), x)
    y, mutated = bn.apply(
        variables, x, use_running_average=False, mutable=["batch_stats"]
    )
    # running stats moved toward batch stats
    assert not np.allclose(
        np.asarray(mutated["batch_stats"]["mean"]), 0.0
    )
    # inference path uses running stats without mutation
    y2 = bn.apply(
        {**variables, "batch_stats": mutated["batch_stats"]},
        x,
        use_running_average=True,
    )
    assert y2.shape == x.shape
