"""Model operations per sample, for the whole step's share of the peak.

Forward and backward of the model as it is defined, with no padding, no
recompute and no optimizer update.
"""

from __future__ import annotations

from counts import deepgrid as g


def deepgrid_train(d) -> float:
    return g.forward_flops_per_row(d) + g.backward_flops_per_row(d)


def deepgrid_serve(d) -> float:
    return g.forward_flops_per_row(d)


def rfnn_train(d) -> float:
    """784 -> d dense with leaky-ReLU, the d x d mesh with its output
    screen and detector, d -> classes dense, softmax cross-entropy.  The
    backward skips the input's gradient, which nothing uses."""
    i, h, c, cells = d["d_in"], d["d"], d["classes"], d["cells"]
    fwd = 2 * i * h + 2 * h + 28 * cells + 9 * h + 2 * h * c + c + 4 * c
    bwd = 2 * i * h + 2 * h + 60 * cells + 9 * h + 4 * h * c + c + 2 * c
    return fwd + bwd
