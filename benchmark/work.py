"""Operations and bytes, counted from a configuration's shapes alone.

Nothing here reads the program, XLA's cost model or a trace, so the count
stays right when a kernel or a fusion is replaced.  A convolution is
2*k*k*C_in*C_out*H_out*W_out; the correlation is the paper's algorithm (one
all-pairs volume per pair, then 2r+1 two-tap reads a level an iteration);
norms, activations and resizes are not counted (they are not what a peak in
FLOP/s is about).

``kernel_*`` functions give one call of a named kernel as the program's
algorithm has it, for roofline shares: operations, bytes, and the least
time at the chip's peaks with the bound that binds.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Tuple

FEATURE_DIM = 256


def peaks(device_kind: str) -> Dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmark/peaks.json")
    return table[device_kind]


def conv_flops(k: int, cin: int, cout: int, h: int, w: int) -> float:
    return 2.0 * k * k * cin * cout * h * w


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def level_shapes(cfg: Dict, hw: Tuple[int, int]) -> List[Tuple[int, int]]:
    """(H, W) after conv1, layer2, layer3 (the field), layer4, layer5."""
    d = cfg["n_downsample"]
    h, w = hw
    out = []
    for stride in (1 + (d > 2), 1 + (d > 1), 1 + (d > 0), 2, 2):
        h, w = _cdiv(h, stride), _cdiv(w, stride)
        out.append((h, w))
    return out


def _block(cin: int, cout: int, stride: int, hw) -> float:
    f = conv_flops(3, cin, cout, *hw) + conv_flops(3, cout, cout, *hw)
    if stride != 1 or cin != cout:
        f += conv_flops(1, cin, cout, *hw)
    return f


def trunk_flops(cfg: Dict, hw) -> float:
    """conv1 .. layer3 of one encoder over one image."""
    d = cfg["n_downsample"]
    s1, s2, s3 = level_shapes(cfg, hw)[:3]
    return (conv_flops(7, 3, 64, *s1)
            + _block(64, 64, 1, s1) * 2
            + _block(64, 96, 1 + (d > 1), s2) + _block(96, 96, 1, s2)
            + _block(96, 128, 1 + (d > 0), s3) + _block(128, 128, 1, s3))


def encoder_flops(cfg: Dict, hw) -> float:
    """Everything before the loop, for one pair."""
    n = cfg["n_gru_layers"]
    hd = cfg["hidden_dims"]
    _, _, s3, s4, s5 = level_shapes(cfg, hw)
    if cfg.get("shared_backbone"):
        f = 2 * trunk_flops(cfg, hw)
        f += 2 * (_block(128, 128, 1, s3)
                  + conv_flops(3, 128, FEATURE_DIM, *s3))
    else:
        f = 3 * trunk_flops(cfg, hw)            # context once, features twice
        f += 2 * conv_flops(1, 128, FEATURE_DIM, *s3)
    f += 2 * (_block(128, 128, 1, s3) + conv_flops(3, 128, hd[0], *s3))
    f += conv_flops(3, hd[0], 3 * hd[0], *s3)
    if n >= 2:
        f += _block(128, 128, 2, s4) + _block(128, 128, 1, s4)
        f += 2 * (_block(128, 128, 1, s4) + conv_flops(3, 128, hd[1], *s4))
        f += conv_flops(3, hd[1], 3 * hd[1], *s4)
    if n >= 3:
        f += _block(128, 128, 2, s5) + _block(128, 128, 1, s5)
        f += 2 * conv_flops(3, 128, hd[2], *s5)
        f += conv_flops(3, hd[2], 3 * hd[2], *s5)
    f += 2.0 * s3[0] * s3[1] * s3[1] * FEATURE_DIM      # the volume
    return f


def gru_flops(hidden: int, cin: int, hw) -> float:
    """One level, once: the z, r and q convolutions."""
    return 3 * conv_flops(3, hidden + cin, hidden, *hw)


def iteration_flops(cfg: Dict, hw) -> float:
    """One refinement iteration of one pair."""
    n, sf = cfg["n_gru_layers"], cfg.get("slow_fast_gru", False)
    hd = cfg["hidden_dims"]
    _, _, s3, s4, s5 = level_shapes(cfg, hw)
    planes = cfg["corr_levels"] * (2 * cfg["corr_radius"] + 1)
    f = 4.0 * s3[0] * s3[1] * planes                     # the lookup's taps
    f += (conv_flops(1, planes, 64, *s3) + conv_flops(3, 64, 64, *s3)
          + conv_flops(7, 2, 64, *s3) + conv_flops(3, 64, 64, *s3)
          + conv_flops(3, 128, 126, *s3))
    f += gru_flops(hd[0], 128 + (hd[1] if n > 1 else 0), s3)
    if n >= 2:
        f += (1 + bool(sf)) * gru_flops(
            hd[1], hd[0] + (hd[2] if n == 3 else 0), s4)
    if n == 3:
        f += (1 + 2 * bool(sf)) * gru_flops(hd[2], hd[1], s5)
    f += conv_flops(3, hd[0], 256, *s3) + conv_flops(3, 256, 2, *s3)
    return f


def mask_flops(cfg: Dict, hw) -> float:
    s3 = level_shapes(cfg, hw)[2]
    ff = 4 ** cfg["n_downsample"]
    return (conv_flops(3, cfg["hidden_dims"][0], 256, *s3)
            + conv_flops(1, 256, 9 * ff, *s3) + 2.0 * 9 * ff * s3[0] * s3[1])


def pair_flops(cfg: Dict, hw, iters: int) -> float:
    """One served pair: the mask and the upsampling once, after the loop."""
    return (encoder_flops(cfg, hw) + iters * iteration_flops(cfg, hw)
            + mask_flops(cfg, hw))


# ------------------------------------------------------------------- kernels

def kernel_corr_lookup(cfg: Dict, hw, batch: int) -> Dict:
    """One call of the on-demand correlation lookup over ``batch`` pairs:
    each left feature row against the four pooled right rows (a matmul),
    then the hat-weighted reduction to 2r+1 taps a level (4 operations a
    swept element: subtract, hat, multiply, add).  Bytes: both feature maps
    in float32 (the published code widens them), the coordinates, and the
    taps written in the compute type."""
    s3 = level_shapes(cfg, hw)[2]
    h, w = s3
    widths = [w]
    for _ in range(cfg["corr_levels"] - 1):
        widths.append(widths[-1] // 2)
    w2 = sum(widths)
    k = 2 * cfg["corr_radius"] + 1
    rows = batch * h
    flops = 2.0 * rows * w * w2 * FEATURE_DIM + 4.0 * rows * w * k * w2
    out_bytes = 2 if cfg.get("compute_dtype", "bfloat16") == "bfloat16" else 4
    nbytes = (rows * w * FEATURE_DIM * 4 + rows * w2 * FEATURE_DIM * 4
              + rows * w * 4
              + rows * w * cfg["corr_levels"] * k * out_bytes)
    return {"flops": flops, "bytes": float(nbytes)}


def least_seconds(work: Dict, pk: Dict) -> Tuple[float, str]:
    """The least time the chip could take, and which bound gives it."""
    tc = work["flops"] / pk["bf16_flops_per_s"]
    tb = work["bytes"] / pk["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tb else (tb, "bytes")
