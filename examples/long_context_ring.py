"""Long-context training with the flash-block ring (sequence parallel).

The scaling story the reference cannot tell (Horovod is data-parallel
only — SURVEY.md §5.7): a context too long for ONE chip's memory,
sharded over the `sp` mesh axis, trained with EXACT attention. Each
hop of the ring runs the Pallas flash kernels on (q, k_hop, v_hop) and
merges the normalized partials online — per-chip attention memory is
O(T_local·Dh) + VMEM tiles, independent of the full context length; no
score matrix ever reaches HBM.

Run (8-way CPU simulation — interpret-mode kernels, logic only):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/long_context_ring.py --seq-len 2048
Run (TPU slice): sp = number of chips; the same script, real kernels.
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.parallel import ring_flash_attention


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--seq-len", type=int, default=2048,
                        help="FULL context length (sharded over all devices)")
    parser.add_argument("--d-model", type=int, default=64)
    parser.add_argument("--heads", type=int, default=4)
    parser.add_argument("--steps", type=int, default=10)
    args = parser.parse_args()

    devices = jax.devices()
    sp = len(devices)
    if args.seq_len % sp:
        raise SystemExit(f"--seq-len must divide by {sp} devices")
    mesh = Mesh(np.asarray(devices), ("sp",))
    t_local = args.seq_len // sp
    d, h = args.d_model, args.heads
    hd = d // h
    print(f"{args.seq_len} tokens over {sp} chips -> {t_local}/chip")

    rng = np.random.default_rng(0)
    params = {
        "wqkv": jnp.asarray(rng.normal(size=(d, 3, h, hd)) * 0.05,
                            jnp.float32),
        "wo": jnp.asarray(rng.normal(size=(h, hd, d)) * 0.05, jnp.float32),
    }
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    @jax.jit
    def train_step(params, opt_state, x, y):
        def loss_fn(p):
            def fwd(x, y):
                qkv = jnp.einsum("btd,dchx->btchx", x, p["wqkv"])
                q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
                a = ring_flash_attention(q, k, v, "sp", causal=True)
                out = jnp.einsum("bthx,hxd->btd", a, p["wo"])
                # mean over the GLOBAL sequence: local sum / global count
                err = jnp.sum((out - y) ** 2)
                return lax.psum(err, "sp") / (y.shape[0] * args.seq_len * d)

            return jax.shard_map(
                fwd, mesh=mesh,
                in_specs=(P(None, "sp"), P(None, "sp")),
                out_specs=P(),
                check_vma=False,
            )(x, y)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    x = jnp.asarray(rng.normal(size=(2, args.seq_len, d)), jnp.float32)
    y = jnp.roll(x, -1, axis=1)  # predict-next as a regression toy
    losses = []
    for _ in range(args.steps):
        params, opt_state, loss = train_step(params, opt_state, x, y)
        losses.append(float(loss))
    print(f"loss {losses[0]:.5f} -> {losses[-1]:.5f}")
    assert losses[-1] < losses[0], "training must reduce the loss"


if __name__ == "__main__":
    main()
