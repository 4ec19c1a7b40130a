"""``drive.py`` on the ``afmoe`` test cell (``data/BENCHMARK.afmoe.json``):

    python3 tests/benchmark/drive_afmoe.py <cell> <trace 0|1> <seconds> [fault]

The fault of this family's own, planted under the harness in the timed
path: ``top1_routing`` (the program's router takes one expert a token
where the configuration says two). ``drive.py``'s own faults work too."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import drive  # noqa: E402

drive.MANIFEST = os.path.join(drive.DATA, "BENCHMARK.afmoe.json")
_plant = drive.plant


def plant(fault):
    if fault != "top1_routing":
        return _plant(fault)
    from horovod_tpu.parallel import moe

    real = moe.route_top_k

    def one_expert(logits, select_bias, top_k, **kw):
        import jax.numpy as jnp

        chosen, gates = real(logits, select_bias, 1, **kw)
        # the layer's shapes stay: the other choices are the first again,
        # with no weight
        return (jnp.repeat(chosen, top_k, axis=-1),
                jnp.where(jnp.arange(top_k) == 0, gates, 0.0))

    moe.route_top_k = one_expert


drive.plant = plant

if __name__ == "__main__":
    drive.main()
