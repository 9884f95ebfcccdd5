"""Procedural 28x28 ten-class digits, the benchmark's stand-in for MNIST.

A copy of the repository's ``repro.data.digits`` renderer, kept here so that
the benchmark's inputs do not move when the program's data code does:
digit glyphs on a 7x5 seed bitmap, upsampled to 28x28, with random affine
jitter (shift, rotation, scale), stroke-thickness variation and pixel
noise.  Deterministic in (seed, index); labels are balanced.
"""

from __future__ import annotations

import numpy as np
from scipy import ndimage

# 7x5 seed glyphs for digits 0-9
_GLYPHS = {
    0: ["01110", "10001", "10011", "10101", "11001", "10001", "01110"],
    1: ["00100", "01100", "00100", "00100", "00100", "00100", "01110"],
    2: ["01110", "10001", "00001", "00110", "01000", "10000", "11111"],
    3: ["11110", "00001", "00001", "01110", "00001", "00001", "11110"],
    4: ["00010", "00110", "01010", "10010", "11111", "00010", "00010"],
    5: ["11111", "10000", "11110", "00001", "00001", "10001", "01110"],
    6: ["00110", "01000", "10000", "11110", "10001", "10001", "01110"],
    7: ["11111", "00001", "00010", "00100", "01000", "01000", "01000"],
    8: ["01110", "10001", "10001", "01110", "10001", "10001", "01110"],
    9: ["01110", "10001", "10001", "01111", "00001", "00010", "01100"],
}


def _base_image(digit: int) -> np.ndarray:
    g = np.array([[float(c) for c in row] for row in _GLYPHS[digit]])
    img = np.kron(g, np.ones((3, 4)))              # 21 x 20
    out = np.zeros((28, 28))
    out[3:24, 4:24] = img
    return out


def _render(digit: int, rng: np.random.Generator) -> np.ndarray:
    img = _base_image(digit)
    # stroke thickness
    if rng.random() < 0.5:
        img = ndimage.grey_dilation(img, size=(2, 2))
    # affine jitter
    angle = rng.uniform(-18, 18)
    img = ndimage.rotate(img, angle, reshape=False, order=1)
    zoom = rng.uniform(0.85, 1.15)
    zoomed = ndimage.zoom(img, zoom, order=1)
    canvas = np.zeros((28, 28))
    h, w = zoomed.shape
    if h >= 28:
        o = (h - 28) // 2
        canvas = zoomed[o:o + 28, o:o + 28]
    else:
        o = (28 - h) // 2
        canvas[o:o + h, o:o + w] = zoomed
    shift = rng.integers(-2, 3, size=2)
    canvas = np.roll(canvas, shift, axis=(0, 1))
    # blur + noise
    canvas = ndimage.gaussian_filter(canvas, rng.uniform(0.4, 0.9))
    canvas = canvas + rng.normal(0, 0.08, canvas.shape)
    return np.clip(canvas, 0.0, 1.0)


def pool(n: int, seed: int):
    """``n`` digits rendered from ``seed``: (x [n, 784] f32 in [0, 1],
    y [n] int32), labels balanced and in a seeded order."""
    xs = np.empty((n, 784), np.float32)
    ys = np.empty((n,), np.int32)
    for i in range(n):
        rng = np.random.default_rng((seed, 1, i))
        xs[i] = _render(i % 10, rng).reshape(-1)
        ys[i] = i % 10
    perm = np.random.default_rng((seed, 1, 999)).permutation(n)
    return xs[perm], ys[perm]
