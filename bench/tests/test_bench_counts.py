"""The benchmark's operation and byte counts against hand counts at a
tiny plan, and its own mesh layouts."""

import pytest

from counts import (deepgrid_bwd_kernel, deepgrid_fwd_kernel,
                    deepgrid_kernel, mesh_bwd_kernel, mesh_kernel, model)
from reference import physics

# one layer, one 4x4 tile, a Reck plan of 6 cells in each mesh, 2 rows
TINY = {"layers": 1, "to": 1, "ti": 1, "n": 4, "cells_v": 6, "cells_u": 6,
        "batch": 2}


def test_layouts():
    lay = physics.reck(4)[0]
    assert (lay.n_cells, lay.top.shape) == (6, (5, 2))
    lay = physics.reck(16)[0]
    assert (lay.n_cells, lay.top.shape) == (120, (29, 8))
    lay = physics.clements(8)
    assert (lay.n_cells, lay.top.shape) == (28, (8, 4))


def test_deep_grid_forward_by_hand():
    # a row: 12 cells x 28 = 336; input screen, attenuation and scale on
    # 4 channels, 3 x 24 = 72; the row combine 8; |.| on 4 channels 12
    flops, nbytes = deepgrid_kernel.count(TINY)
    assert flops == 2 * (336 + 72 + 8 + 12)
    # rows: 4 complex in (32 B) + 4 real out (16 B); weights: 12 cells of
    # 8 floats (384 B) and 24 floats of gains (96 B)
    assert nbytes == 2 * 48 + 480


def test_deep_grid_training_kernels_by_hand():
    flops, nbytes = deepgrid_fwd_kernel.count(TINY)
    assert flops == 2 * 428
    assert nbytes == 2 * (48 + 64) + 480      # + two complex 4-vectors
    flops, nbytes = deepgrid_bwd_kernel.count(TINY)
    # a row: 12 cells x 60 = 720; gains 2 x 80 = 160; |.| 24
    assert flops == 2 * (720 + 160 + 24)
    assert nbytes == 2 * (96 + 64) + 3 * 480


def test_mesh_kernels_by_hand():
    d = {"n": 8, "cells": 28, "batch": 10}
    assert mesh_kernel.count(d) == (10 * 28 * 28, 10 * 128 + 28 * 32)
    assert mesh_bwd_kernel.count(d) == (10 * 28 * 60, 10 * 192 + 3 * 896)


def test_model_counts_by_hand():
    assert model.deepgrid_train(dict(TINY, batch=1)) == 428 + 904
    d = {"d_in": 784, "d": 8, "classes": 10, "cells": 28}
    fwd = 12544 + 16 + 784 + 72 + 160 + 10 + 40
    bwd = 12544 + 16 + 1680 + 72 + 320 + 10 + 20
    assert model.rfnn_train(d) == pytest.approx(fwd + bwd)
