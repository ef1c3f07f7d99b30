"""Seeded stereo pairs: textured, with a true shift between the two views.

Uniform noise would defeat the wire's compression and give the correlation
lookup nothing to find, so a pair is a few octaves of smooth noise plus fine
grain, and the right view is the left one shifted by a disparity that differs
from band to band.  Values are whole numbers in [0, 255], kept as uint8 (a
quarter of the float32 a request carries) and widened when sent.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np


def _octave(rng, h: int, w: int, cell: int) -> np.ndarray:
    gh, gw = -(-h // cell) + 1, -(-w // cell) + 1
    g = rng.random((gh, gw, 3), dtype=np.float32)
    g = np.repeat(np.repeat(g, cell, axis=0), cell, axis=1)
    k = max(cell // 2, 1)                      # box blur takes the blocks' edges off
    c = np.cumsum(np.cumsum(np.pad(g, ((k, 0), (k, 0), (0, 0))), 0), 1)
    g = (c[k:, k:] - c[:-k, k:] - c[k:, :-k] + c[:-k, :-k]) / (k * k)
    return g[:h, :w]


def make_pair(seed: int, index: int, hw: Tuple[int, int],
              max_disp: int = 48) -> Tuple[np.ndarray, np.ndarray]:
    """Pair ``index`` of the stream ``seed``: (left, right), uint8 (H,W,3)."""
    h, w = hw
    rng = np.random.default_rng([int(seed), int(index), 0x9A1B])
    wide = w + max_disp
    tex = sum(a * _octave(rng, h, wide, c)
              for a, c in ((0.5, 64), (0.3, 16), (0.15, 4)))
    tex = tex + 0.05 * rng.random((h, wide, 3), dtype=np.float32)
    tex = np.clip(tex * 255.0, 0, 255).astype(np.uint8)
    left = tex[:, :w]
    right = np.empty_like(left)
    edges = np.linspace(0, h, 7).astype(int)
    for a, b in zip(edges[:-1], edges[1:]):
        d = int(rng.integers(2, max_disp + 1))
        right[a:b] = tex[a:b, d:d + w]          # right view sees the scene d px on
    return left, right


def make_pool(seed: int, n: int, hw: Tuple[int, int]) -> List[Tuple]:
    return [make_pair(seed, i, hw) for i in range(n)]
