"""Sliding-window classifier scoring — Pallas TPU kernel.

TPU adaptation of the paper's FaceDet benchmark (Rosetta Viola-Jones):
the FPGA pipeline evaluates a feature cascade over every sliding window,
holding the image in on-chip BRAM (the paper credits exactly this for
the FPGA win at 640x480).  The TPU analogue keeps the *whole image
resident in VMEM* (300 KB-1.2 MB << 16 MB) and turns window scoring
into MXU matmuls: each grid step gathers a tile of windows (im2col in
VMEM via dynamic_slice) and scores them against all feature templates
at once.  The cascade's early-exit becomes a post-hoc threshold on the
host side of the function boundary — uniform MXU work beats the skipped-
window savings of the FPGA pipeline (hardware-adaptation delta,
DESIGN.md §2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _window_kernel(img_ref, w_ref, o_ref, *, win: int, stride: int,
                   block_wy: int, block_wx: int):
    gy = pl.program_id(0)
    gx = pl.program_id(1)
    img = img_ref[...].astype(jnp.float32)    # full image in VMEM
    w = w_ref[...].astype(jnp.float32)        # (F, win*win)
    F = w.shape[0]
    y0 = gy * block_wy * stride
    x0 = gx * block_wx * stride
    rows = []
    for wy in range(block_wy):
        for wx in range(block_wx):
            patch = jax.lax.dynamic_slice(
                img, (y0 + wy * stride, x0 + wx * stride), (win, win))
            rows.append(patch.reshape(win * win))
    patches = jnp.stack(rows)                  # (block_wy*block_wx, win*win)
    scores = jax.lax.dot_general(patches, w, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    o_ref[...] = scores.reshape(block_wy, block_wx, F)


def window_scores(img: jax.Array, feats: jax.Array, *, win: int = 24,
                  stride: int = 4, block_wy: int = 4, block_wx: int = 4,
                  interpret: bool = False) -> jax.Array:
    """img: (H, W) f32; feats: (F, win*win) -> (ny, nx, F) scores."""
    H, W = img.shape
    F = feats.shape[0]
    ny = (H - win) // stride + 1
    nx = (W - win) // stride + 1

    def largest_divisor(n: int, at_most: int) -> int:
        for b in range(min(at_most, n), 0, -1):
            if n % b == 0:
                return b
        return 1

    block_wy = largest_divisor(ny, block_wy)
    block_wx = largest_divisor(nx, block_wx)

    kernel = functools.partial(_window_kernel, win=win, stride=stride,
                               block_wy=block_wy, block_wx=block_wx)
    return pl.pallas_call(
        kernel,
        grid=(ny // block_wy, nx // block_wx),
        in_specs=[
            pl.BlockSpec((H, W), lambda gy, gx: (0, 0)),
            pl.BlockSpec((F, feats.shape[1]), lambda gy, gx: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_wy, block_wx, F),
                               lambda gy, gx: (gy, gx, 0)),
        out_shape=jax.ShapeDtypeStruct((ny, nx, F), jnp.float32),
        interpret=interpret,
        name="window_scores",
    )(img, feats)
