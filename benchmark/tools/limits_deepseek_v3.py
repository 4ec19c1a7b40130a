"""Upper readings for the limits of a cell of the ``deepseek_v3`` family, on
the chip and at the cell's own size: ``tools/limits_afmoe.py`` as it is (it
reads the faults, the routes and the held experts from the cell's own
reference and configuration), run for this family:

    python3 benchmark/tools/limits_deepseek_v3.py --workload <cell> \
        --seeds 1,2 [--controls fp8] [--faults scale_nope,..] \
        [--out chiprun_out/limits]

The faults are ``references/deepseek_v3.py: FAULTS``: the scores scaled by
128^-0.5 for 192^-0.5, the rotation half-split where the pairs are
interleaved, the rotated part left out of the key, the latent's norm left
out, top-5 for top-6, ``routed_scaling_factor`` left out, the shared
experts left out; beside them half of the batch and the control in fp8."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import limits_afmoe  # noqa: E402

if __name__ == "__main__":
    sys.exit(limits_afmoe.main())
