"""``drive.py`` on the ``deepseek_v3`` test cell
(``data/BENCHMARK.deepseek_v3.json``):

    python3 tests/benchmark/drive_deepseek_v3.py <cell> <trace 0|1> <seconds> [fault]

The fault of this family's own, planted under the harness in the timed
path: ``rope_half_split`` (the program rotates the pairs (x[i], x[i + d/2])
where the configuration says they are interleaved). ``drive_afmoe.py``'s
``top1_routing`` and ``drive.py``'s own faults work too."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import drive  # noqa: E402
import drive_afmoe  # noqa: E402,F401 - its plant() wraps drive's

drive.MANIFEST = os.path.join(drive.DATA, "BENCHMARK.deepseek_v3.json")
_plant = drive.plant


def plant(fault):
    if fault != "rope_half_split":
        return _plant(fault)
    from horovod_tpu.models import transformer

    real = transformer.apply_rope
    transformer.apply_rope = lambda x, base=10000.0, offset=0, **kw: real(
        x, base, offset)


drive.plant = plant

if __name__ == "__main__":
    drive.main()
