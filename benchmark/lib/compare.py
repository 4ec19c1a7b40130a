"""The comparison that decides ``correct``: each number compared, beside the
limit it is held to (``limits/<cell>.json``, ``manifest.Cell.limits``)."""

import sys

import numpy as np

# a leaf whose reference gradient is under this share of the median leaf's
# moves by round-off alone; it is left out of the change
ZERO_GRAD_SHARE = 1e-3


def _worst(prog, ref, floor):
    gap = np.abs(prog - ref) / np.maximum(ref, floor)
    i = int(np.argmax(gap))
    return float(gap[i]), i


def training_gaps(prog: dict, ref: dict) -> dict:
    """Gaps between the program's first three steps and the reference's.
    Both hold ``losses`` [3], ``grad_norms`` and ``change_norms`` [leaves]:
    each step's loss, the norm of the first gradient as the optimizer got
    it, and the norm of the parameters' change over the three steps, leaf
    by leaf. A gap is between the two norms of a leaf, measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger, and the worst leaf counts."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    gp, gr = np.asarray(prog["grad_norms"]), np.asarray(ref["grad_norms"])
    cp, cr = np.asarray(prog["change_norms"]), np.asarray(ref["change_norms"])
    loss_gaps = np.abs(lp - lr) / np.abs(lr)
    grad_gap, grad_leaf = _worst(gp, gr, np.median(gr))
    moved = gr >= ZERO_GRAD_SHARE * np.median(gr)
    change_gap, change_leaf = _worst(cp[moved], cr[moved],
                                     np.median(cr[moved]))
    own = moved & (cr > 0)
    return {
        # read, not compared: the worst leaf against its own norm alone
        "_grad_gap_own": float(np.max(np.abs(gp - gr)[own] / gr[own])),
        "_change_gap_own": float(np.max(np.abs(cp - cr)[own] / cr[own])),
        "loss1_gap": float(loss_gaps[0]),
        "loss3_gap": float(loss_gaps[1:].max()),
        "grad_gap": grad_gap,
        "change_gap": change_gap,
        "_grad_leaf": grad_leaf,
        "_change_leaf": int(np.flatnonzero(moved)[change_leaf]),
    }


def judge(numbers: dict, limits: dict):
    """``(correct, compared)``: every number that has a limit, as
    ``{name: {"value": v, "limit": l}}``; correct when each is at or under
    its limit (and is a number)."""
    compared = {}
    ok = True
    for name, limit in limits.items():
        value = float(numbers[name])
        compared[name] = {"value": value, "limit": limit}
        ok = ok and bool(np.isfinite(value)) and value <= limit
    return ok, compared


def print_compared(compared: dict, correct: bool, file=None) -> None:
    """The run's last lines on standard error."""
    file = file or sys.stderr
    for name, c in compared.items():
        verdict = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"compared {name} = {c['value']:.6g} limit {c['limit']:.6g}"
              f" {verdict}", file=file)
    print(f"correct = {str(correct).lower()}", file=file, flush=True)
