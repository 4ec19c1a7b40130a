"""MNIST data-parallel training — the canonical first example.

Parity with the reference's ``examples/pytorch/pytorch_mnist.py`` [V]
(BASELINE.json config #1): same 2-layer ConvNet capacity, same flow —
init, shard the data by rank, wrap the optimizer, broadcast initial
state, train, evaluate on rank 0.

TPU-native shape: one jit-compiled train step over the world mesh via
shard_map; the DistributedOptimizer's allreduce is an XLA collective
scheduled by the compiler, not a background thread.

Run (single host, 8-way CPU simulation):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/mnist.py --epochs 1

Run (TPU): python examples/mnist.py
"""

import argparse
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import MNISTConvNet


def synthetic_mnist(n: int, rng: np.random.Generator):
    """Deterministic stand-in for the MNIST download (this sandbox has
    no network; the reference example downloads via torchvision [V])."""
    x = rng.normal(size=(n, 28, 28, 1)).astype(np.float32)
    y = rng.integers(0, 10, size=(n,)).astype(np.int32)
    # Plant a learnable signal: mean intensity encodes the label.
    x += y[:, None, None, None].astype(np.float32) / 10.0
    return x, y


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--epochs", type=int, default=2)
    parser.add_argument("--batch-size", type=int, default=64,
                        help="per-replica batch size (ref default 64)")
    parser.add_argument("--lr", type=float, default=0.01)
    parser.add_argument("--steps-per-epoch", type=int, default=30)
    args = parser.parse_args()

    hvd.init()
    mesh = hvd.mesh()
    world = hvd.size()

    model = MNISTConvNet()
    # Horovod's LR scaling rule: scale by world size (ref docs [V]).
    opt = hvd.DistributedOptimizer(
        optax.sgd(args.lr * world, momentum=0.9), op=hvd.Average
    )

    rng = np.random.default_rng(hvd.rank())
    sample_x = jnp.zeros((args.batch_size, 28, 28, 1), jnp.float32)
    params = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        sample_x,
    )
    opt_state = opt.init(params)
    # Every replica starts from identical weights (ref:
    # hvd.broadcast_parameters / broadcast_optimizer_state [V]).
    params = hvd.broadcast_parameters(params, root_rank=0)
    opt_state = hvd.broadcast_optimizer_state(opt_state, root_rank=0)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P(), P(hvd.WORLD_AXIS), P(hvd.WORLD_AXIS), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    def train_step(params, opt_state, x, y, dropout_key):
        x, y = x[0], y[0]  # this replica's shard

        def loss_fn(p):
            logits = model.apply(
                p, x, train=True, rngs={"dropout": dropout_key}
            )
            one_hot = jax.nn.one_hot(y, 10)
            return optax.softmax_cross_entropy(logits, one_hot).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        # loss is per-replica; average it for logging
        loss = jax.lax.pmean(loss, hvd.WORLD_AXIS)
        return params, opt_state, loss

    step = jax.jit(train_step)
    for epoch in range(args.epochs):
        for it in range(args.steps_per_epoch):
            xs, ys = [], []
            for _ in range(world):
                x, y = synthetic_mnist(args.batch_size, rng)
                xs.append(x)
                ys.append(y)
            params, opt_state, loss = step(
                params,
                opt_state,
                jnp.asarray(np.stack(xs)),
                jnp.asarray(np.stack(ys)),
                jax.random.fold_in(
                    jax.random.PRNGKey(2), epoch * 10_000 + it
                ),
            )
        if hvd.rank() == 0:
            print(f"epoch {epoch}: loss {float(loss):.4f}")

    if hvd.rank() == 0:
        x, y = synthetic_mnist(256, np.random.default_rng(999))
        logits = jax.jit(lambda p, x: model.apply(p, x, train=False))(
            params, jnp.asarray(x)
        )
        acc = float((np.argmax(np.asarray(logits), -1) == y).mean())
        print(f"eval accuracy {acc:.3f}")


if __name__ == "__main__":
    main()
