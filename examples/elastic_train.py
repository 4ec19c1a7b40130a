"""Elastic training example — survive worker churn with commit/restore.

Parity with the reference's elastic examples
(ref: examples/elastic/pytorch/pytorch_mnist_elastic.py [V] and the
``hvd.elastic.run`` + ``State`` protocol, SURVEY.md §3.4): train under a
decorator that catches peer failures, rolls state back to the last
``commit()``, re-rendezvouses, and resumes.

On TPU, "membership changed" means slice re-acquisition rather than
NCCL communicator rebuild, but the user-facing protocol is identical.

Run under the elastic launcher:
    python -m horovod_tpu.runner -np 2 --placement per-slot \
        --  python examples/elastic_train.py
or single-process: python examples/elastic_train.py
"""


import numpy as np
import optax
import jax
import jax.numpy as jnp

import horovod_tpu as hvd
from horovod_tpu.models import MNISTConvNet


def main():
    hvd.init()
    model = MNISTConvNet()
    opt = hvd.DistributedOptimizer(optax.sgd(0.02, momentum=0.9))

    sample = jnp.zeros((32, 28, 28, 1), jnp.float32)
    params = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        sample,
    )
    opt_state = opt.init(params)

    # State holds everything that must survive a membership change
    # (ref: hvd.elastic.TorchState [V]; here JaxState snapshots pytrees
    # to host on commit). batch tracks progress so a restore resumes
    # where the last commit left off.
    state = hvd.elastic.JaxState(
        params=params, opt_state=opt_state, batch=0, epoch=0
    )

    rng = np.random.default_rng(0)

    from functools import partial

    from jax.sharding import PartitionSpec as P

    # The DistributedOptimizer's allreduce needs the mesh axis bound, so
    # the step runs under shard_map; each rank trains on its own shard
    # of the batch (rank-major leading axis, like examples/mnist.py).
    @partial(
        jax.shard_map,
        mesh=hvd.mesh(),
        in_specs=(P(), P(), P(hvd.WORLD_AXIS), P(hvd.WORLD_AXIS), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    def train_step(params, opt_state, x, y, key):
        x, y = x[0], y[0]

        def loss_fn(p):
            logits = model.apply(p, x, train=True, rngs={"dropout": key})
            return optax.softmax_cross_entropy(
                logits, jax.nn.one_hot(y, 10)
            ).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, jax.lax.pmean(loss, hvd.WORLD_AXIS)

    train_step = jax.jit(train_step)

    @hvd.elastic.run
    def train(state):
        loss = None  # may resume at an epoch boundary with no new batch
        while state.epoch < 2:
            while state.batch < 20:
                world = hvd.size()
                x = rng.normal(size=(world, 8, 28, 28, 1)).astype(np.float32)
                y = rng.integers(0, 10, size=(world, 8)).astype(np.int32)
                state.params, state.opt_state, loss = train_step(
                    state.params,
                    state.opt_state,
                    jnp.asarray(x),
                    jnp.asarray(y),
                    jax.random.fold_in(
                        jax.random.PRNGKey(3), state.epoch * 1000 + state.batch
                    ),
                )
                state.batch += 1
                if state.batch % 10 == 0:
                    # Checkpoint-in-memory: a failure after this point
                    # rolls back here, not to the epoch start.
                    state.commit()
            if hvd.rank() == 0 and loss is not None:
                print(f"epoch {state.epoch}: loss {float(loss):.4f}")
            state.batch = 0
            state.epoch += 1
            state.commit()

    train(state)
    if hvd.rank() == 0:
        print("elastic training complete")


if __name__ == "__main__":
    main()
