"""Work of one n-channel mesh kernel call, counted from programmed cells.

``d``: ``n``, ``cells`` (programmed by the plan) and ``batch``.  A cell on
a row is 28 FLOP forward and 60 backward (see ``counts/deepgrid.py``);
a row's state is n complex values, 8 B each, in and out; each cell's
coefficients are 8 floats.
"""

from __future__ import annotations


def state_bytes_per_row(d) -> float:
    return 8 * d["n"]


def weight_bytes(d) -> float:
    return 32 * d["cells"]
