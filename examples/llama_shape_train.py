"""Training a Llama/Mistral-shaped model on padded batches.

Demonstrates the modern-LM kernel surface in one script: RoPE + grouped
-query attention + causal sliding window + native right-padding, all
through the Pallas flash kernels, under hvd data parallelism. The
reference has no model zoo at all — this is the capability a user
migrating a modern LM stack needs (SURVEY.md §2.6 beyond-parity).

Run (8-way CPU simulation; interpret kernels unless flash is forced):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/llama_shape_train.py --steps 8
Run (TPU): same script; flash kernels engage automatically.
"""

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import Transformer, TransformerConfig


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--seq-len", type=int, default=64)
    parser.add_argument("--batch-per-rank", type=int, default=2)
    args = parser.parse_args()

    hvd.init()
    mesh = hvd.mesh()
    world = hvd.size()

    cfg = dataclasses.replace(
        TransformerConfig.tiny(causal=True),
        rope=True,            # rotary positions, no learned table
        num_kv_heads=2,       # grouped-query attention
        sliding_window=16,    # causal band
        max_len=args.seq_len,
    )
    model = Transformer(cfg)
    b, t = args.batch_per_rank, args.seq_len
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (world, b, t)), jnp.int32
    )
    # right-padded batch: lengths in [3t/4, t]
    lengths = jnp.asarray(
        rng.integers(3 * t // 4, t + 1, (world, b)), jnp.int32
    )
    params = model.init(
        jax.random.PRNGKey(0), tokens[0], train=False
    )
    params = hvd.broadcast_parameters(params)
    opt = hvd.DistributedOptimizer(optax.adam(1e-3))
    opt_state = opt.init(params)

    from functools import partial

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(), P(), P(hvd.WORLD_AXIS), P(hvd.WORLD_AXIS)),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    def train_step(params, opt_state, tokens, lengths):
        tokens, lengths = tokens[0], lengths[0]
        labels = jnp.roll(tokens, -1, axis=1)

        def loss_fn(p):
            logits = model.apply(
                p, tokens, train=True, lengths=lengths,
                rngs={"dropout": jax.random.PRNGKey(1)},
            )
            per_tok = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), labels
            )
            # next-token targets: position lengths-1 would read its
            # label FROM the padding (and t-1 wraps), so the loss mask
            # stops one short of the valid length
            valid = jnp.arange(t)[None, :] < (lengths[:, None] - 1)
            return jnp.sum(jnp.where(valid, per_tok, 0.0)) / jnp.sum(valid)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, jax.lax.pmean(loss, hvd.WORLD_AXIS)

    step = jax.jit(train_step)
    losses = []
    for _ in range(args.steps):
        params, opt_state, loss = step(params, opt_state, tokens, lengths)
        losses.append(float(loss))
    print(f"llama-shape loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    assert losses[-1] < losses[0], "training must reduce the loss"


if __name__ == "__main__":
    main()
