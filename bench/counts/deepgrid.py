"""Work of the deep tiled network, counted from its programmed cells.

``d``: ``layers``, ``to``, ``ti``, ``n`` (tile size), ``cells_v``/
``cells_u`` (cells each mesh plan programs) and ``batch`` (rows in one
call).  Identity cells that pad a mesh to the grid's common column count
are not work and are not counted.

One cell on one row: the 2x2 complex matrix times a complex pair is 4
complex multiplies and 2 complex adds, 28 FLOP.  Its backward, without
recomputing the forward state: the cotangent through the adjoint (28) and
the cell's coefficient gradient, 4 complex multiply-adds (32).  Per tile
and row: the input screen, the attenuation and the digital scale are
complex scalings of n channels (6n FLOP each) and the row combine adds n
complex values (2n); per output row the detector's |.| is 3 FLOP a channel.
Bytes are what one call must move to or from HBM: complex inputs (8 B a
channel), real detected outputs (4 B), the residual stage planes (two
complex n-vectors a tile), and 8 floats for each programmed cell.
"""

from __future__ import annotations


def _cells(d):
    return d["layers"] * d["to"] * d["ti"], d["cells_v"] + d["cells_u"]


def forward_flops_per_row(d) -> float:
    tiles, cells = _cells(d)
    n = d["n"]
    return tiles * (28 * cells + 20 * n) + d["layers"] * d["to"] * 3 * n


def backward_flops_per_row(d) -> float:
    tiles, cells = _cells(d)
    n = d["n"]
    return tiles * (60 * cells + 40 * n) + d["layers"] * d["to"] * 6 * n


def weight_bytes(d) -> float:
    tiles, cells = _cells(d)
    return tiles * (32 * cells + 24 * d["n"])


def io_bytes_per_row(d) -> float:
    return 8 * d["ti"] * d["n"] + 4 * d["to"] * d["n"]


def stage_bytes_per_row(d) -> float:
    tiles, _ = _cells(d)
    return tiles * 16 * d["n"]
