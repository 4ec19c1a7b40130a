"""``drive.py`` on the ``sdar_moe`` test cell
(``data/BENCHMARK.sdar_moe.json``):

    python3 tests/benchmark/drive_sdar_moe.py <cell> <trace 0|1> <seconds> [fault]

The faults of this family's own, planted under the harness in the timed
path (the program; the reference stays whole): ``leak_own_clean`` (a noised
query also sees the clean copy of its own block), ``causal_in_block``
(inside a block a query sees only what is not after it),
``positions_2l`` (the rotation takes positions 0..2L-1), ``no_weight`` (the
loss leaves 1/t out), ``half_blocks`` (the loss leaves the second half of
every row's blocks out), ``no_gate_norm`` (the gates are not renormalised
over the chosen), ``top_k_less_1`` (the last choice gets no weight). On the
CPU the model runs its dense path, so the mask faults are planted in
``transformer.block_diffusion_mask``. ``drive.py``'s ``state_unchanged``
does not reach this loop's step."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import drive  # noqa: E402

drive.MANIFEST = os.path.join(drive.DATA, "BENCHMARK.sdar_moe.json")


def _mask(fault):
    import jax.numpy as jnp

    def mask(length, block):
        at = jnp.arange(2 * length)
        i, j = (at % length)[:, None], (at % length)[None, :]
        noised_q, noised_k = (at < length)[:, None], (at < length)[None, :]
        bi, bj = i // block, j // block
        within = (j <= i) if fault == "causal_in_block" else True
        own_clean = (bj <= bi) if fault == "leak_own_clean" else (bj < bi)
        return jnp.where(
            noised_q, jnp.where(noised_k, (bi == bj) & within, own_clean),
            ~noised_k & ((bj < bi) | ((bj == bi) & within)))

    return mask


def plant(fault):
    from benchmark.lib import manifest
    from horovod_tpu.models import transformer
    from horovod_tpu.parallel import moe

    family = manifest.load_module("models", "sdar_moe")
    loss, route = family.per_chip_loss, moe.route_top_k
    if fault in ("leak_own_clean", "causal_in_block"):
        transformer.block_diffusion_mask = _mask(fault)
    elif fault == "positions_2l":
        rope = transformer.apply_rope
        transformer.apply_rope = lambda x, base, offset=0, period=None: rope(
            x, base, offset)
    elif fault == "no_weight":
        family.per_chip_loss = lambda logits, tokens, w: loss(
            logits, tokens, (w > 0).astype(w.dtype))
    elif fault == "half_blocks":
        def half(logits, tokens, w):
            import jax.numpy as jnp

            kept = jnp.arange(w.shape[1]) < w.shape[1] // 2
            return 2 * loss(logits, tokens, jnp.where(kept, w, 0))

        family.per_chip_loss = half
    elif fault == "no_gate_norm":
        moe.route_top_k = lambda *a, **kw: route(*a, **dict(kw, norm=False))
    elif fault == "top_k_less_1":
        def less_1(logits, select_bias, top_k, **kw):
            import jax.numpy as jnp

            chosen, gates = route(logits, select_bias, top_k,
                                  **dict(kw, norm=False))
            gates = jnp.where(jnp.arange(top_k) < top_k - 1, gates, 0.0)
            return chosen, gates / (
                jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)

        moe.route_top_k = less_1
    elif fault:
        raise SystemExit(f"no fault {fault!r}")


drive.plant = plant

if __name__ == "__main__":
    drive.main()
