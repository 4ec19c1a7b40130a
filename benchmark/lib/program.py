"""The system under test, built through the entry points its users call:
``hvd.init``, ``DistributedOptimizer`` and the quick start's step, whatever
the model. With ``models/<family>.py`` the only files of the yardstick that
import the program."""

from functools import partial

import jax
import jax.numpy as jnp


def per_chip_loss(logits, labels):
    """Mean cross entropy over this chip's rows and positions."""
    import optax

    return optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), labels).mean()


def init_training(model, traffic: dict):
    """``hvd.init()`` and the quick start's optimizer:
    ``(hvd, mesh, DistributedOptimizer)``."""
    import optax

    import horovod_tpu as hvd

    hvd.init()
    o = traffic["optimizer"]
    if o["kind"] != "sgd":
        raise ValueError(f"optimizer kind {o['kind']!r} is not known here")
    reduce_op = {"Average": hvd.Average, "Adasum": hvd.Adasum}[o["op"]]
    opt = hvd.DistributedOptimizer(
        optax.sgd(o["lr"], momentum=o["momentum"]), op=reduce_op)
    return hvd, hvd.mesh(), opt


def place_training_state(hvd, opt, params):
    """Parameters and optimizer state on the mesh before the first call (a
    step fed single-device arrays returns mesh-sharded ones, and the second
    call would be another program). ``init`` shares one zero array between
    its two counters, which a donated call refuses: every leaf is copied."""
    params = hvd.broadcast_parameters(params)
    state = hvd.broadcast_optimizer_state(opt.init(params))
    return params, jax.tree.map(jnp.copy, state)


def make_train_step(hvd, model, opt, mesh):
    """The quick start's step (``chip_smoke.train_smoke``): ``shard_map``
    over ``hvd.WORLD_AXIS``, parameters and state donated."""
    import optax
    from jax.sharding import PartitionSpec as P

    @partial(jax.jit, donate_argnums=(0, 1))
    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(), P(), P(hvd.WORLD_AXIS), P(hvd.WORLD_AXIS)),
        out_specs=(P(), P(), P()), check_vma=False,
    )
    def train_step(params, opt_state, tokens, labels):
        tokens, labels = tokens[0], labels[0]

        def loss_fn(p):
            return per_chip_loss(model.apply(p, tokens, train=True), labels)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, jax.lax.pmean(loss, hvd.WORLD_AXIS)

    return train_step


def momentum_trace(opt_state):
    """The momentum buffer in a ``DistributedOptimizer(optax.sgd)`` state:
    after the first step it is the gradient as the optimizer got it."""
    import optax

    return optax.tree_utils.tree_get(opt_state, "trace")
