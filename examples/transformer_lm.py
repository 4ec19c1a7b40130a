"""Multi-axis-parallel transformer LM training.

This goes beyond the reference's capability surface (Horovod is
data-parallel only — SURVEY.md §2.6): one mesh carrying dp x pp x ep x
sp x tp simultaneously, with ring attention for the sequence axis,
GPipe-style microbatching for the pipeline axis, and expert-parallel
MoE over all_to_all — the collective the reference ships as a bare
primitive [V] is here the backbone of a parallelism strategy.

Run (8-way CPU simulation):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/transformer_lm.py --dp 2 --sp 2 --tp 2
Run (TPU pod): choose axes to match the slice.
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.parallel import MeshSpec
from horovod_tpu.parallel.transformer import (
    ParallelTransformerConfig,
    make_sharded_params,
    make_train_step,
)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--dp", type=int, default=2)
    parser.add_argument("--pp", type=int, default=1)
    parser.add_argument("--ep", type=int, default=1)
    parser.add_argument("--sp", type=int, default=2)
    parser.add_argument("--tp", type=int, default=2)
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--seq-len", type=int, default=128)
    parser.add_argument("--batch-per-dp", type=int, default=4)
    args = parser.parse_args()

    spec = MeshSpec(
        dp=args.dp, pp=args.pp, ep=args.ep, sp=args.sp, tp=args.tp
    )
    if spec.size != len(jax.devices()):
        raise SystemExit(
            f"mesh {spec} needs {spec.size} devices; "
            f"{len(jax.devices())} visible"
        )
    mesh = spec.build()

    cfg = ParallelTransformerConfig(
        vocab_size=512,
        num_layers=2 * max(args.pp, 1),
        d_model=128,
        num_heads=max(4, args.tp),
        d_ff=256,
        max_len=args.seq_len,
        n_experts=2 * max(args.ep, 1),
        n_microbatches=2,
        learning_rate=0.1,
    )
    params = make_sharded_params(cfg, mesh, jax.random.PRNGKey(0))
    step = make_train_step(cfg, mesh)

    rng = np.random.default_rng(0)
    global_batch = args.batch_per_dp * args.dp * max(args.ep, 1)
    # A learnable synthetic language: next token = (token + 1) % K.
    base = rng.integers(0, cfg.vocab_size - 1, size=(global_batch, 1))
    seq = (base + np.arange(args.seq_len + 1)[None, :]) % cfg.vocab_size
    tokens = jnp.asarray(seq[:, :-1], jnp.int32)
    labels = jnp.asarray(seq[:, 1:], jnp.int32)

    losses = []
    for i in range(args.steps):
        params, loss = step(params, tokens, labels)
        losses.append(float(loss))
        print(f"step {i}: loss {losses[-1]:.4f}")
    if losses[-1] < losses[0]:
        print("loss decreased — parallel training works")
    else:
        raise SystemExit("loss did not decrease")


if __name__ == "__main__":
    main()
