"""The comparisons that decide ``correct``.

Training: the program's first three steps against the reference's, from
the same weights on the same batches.  Each step's loss; the first
gradient, worked out from each side's state after one step as
``(p0 - p1) / lr``; and the change ``p3 - p0`` after three steps.  The
gradient and the change are compared leaf by leaf as the gap between the
two sides' norms, over the larger of that leaf's reference norm and the
median leaf's, and the worst leaf counts.  Leaves whose reference
gradient is under a thousandth of the median leaf's (a phase screen in
front of ``|.|`` detection moves by round-off alone) are left out.

Serving: every served row of a sample drawn from the seed against the
reference's output for the same features, as the largest absolute error
in the row over the row's largest reference output; and the number of
requests due in the window that were never served.
"""

from __future__ import annotations

import numpy as np

#: a leaf whose reference gradient norm is under this share of the median
#: leaf's takes no part in the gradient and change comparisons
NULL_LEAF = 1e-3


def _norms(leaves) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(a, np.float64)))
                     for a in leaves])


def _gap(got: np.ndarray, want: np.ndarray, keep: np.ndarray) -> float:
    scale = np.maximum(want, np.median(want[keep]))
    return float(np.max((np.abs(got - want) / scale)[keep]))


def train_gaps(p0, prog: tuple, ref: tuple, lr: float) -> dict:
    """``p0``: the starting leaves; ``prog``/``ref``: (leaves after step 1,
    leaves after step 3, the three losses) of each side."""
    p0 = [np.asarray(a, np.float64) for a in p0]

    def grads(p1):
        return _norms([(a - np.asarray(b, np.float64)) / lr
                       for a, b in zip(p0, p1)])

    def change(p3):
        return _norms([np.asarray(b, np.float64) - a
                       for a, b in zip(p0, p3)])

    g_ref = grads(ref[0])
    keep = g_ref >= NULL_LEAF * np.median(g_ref)
    l_prog = np.asarray(prog[2], np.float64)
    l_ref = np.asarray(ref[2], np.float64)
    return {
        "loss_gap": float(np.max(np.abs(l_prog - l_ref) / np.abs(l_ref))),
        "grad_gap": _gap(grads(prog[0]), g_ref, keep),
        "update_gap": _gap(change(prog[1]), change(ref[1]), keep),
        "leaves_left_out": int(np.sum(~keep)),
    }


def row_gap(got, want) -> float:
    """Largest per-row error over the row's largest reference output."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    err = np.max(np.abs(got - want), axis=-1)
    return float(np.max(err / np.max(np.abs(want), axis=-1)))
