"""Hamming-distance matrix for KNN digit recognition — Pallas TPU kernel.

The paper's DigitRec benchmark (Rosetta [FPGA'18]) is K-nearest-
neighbours over 196-bit digit bitvectors with Hamming distance — the
function Xar-Trek offloads to the FPGA.  The TPU adaptation keeps the
bit-packed layout (uint32 words) and computes the full test x train
distance matrix with XOR + popcount in VMEM tiles; the cheap top-k over
train items stays on the host side of the function boundary (ops.py),
matching the paper's self-contained-function migration model.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _hamming_kernel(t_ref, r_ref, o_ref):
    t = t_ref[...]                            # (bt, W) uint32
    r = r_ref[...]                            # (bn, W) uint32
    x = jax.lax.population_count(t[:, None, :] ^ r[None, :, :])
    o_ref[...] = jnp.sum(x.astype(jnp.int32), axis=-1)


def hamming_distances(test: jax.Array, train: jax.Array, *,
                      block_t: int = 128, block_n: int = 512,
                      interpret: bool = False) -> jax.Array:
    """test: (Nt, W) uint32; train: (Nn, W) uint32 -> (Nt, Nn) int32."""
    Nt, W = test.shape
    Nn = train.shape[0]
    block_t = min(block_t, Nt)
    block_n = min(block_n, Nn)
    assert Nt % block_t == 0 and Nn % block_n == 0
    return pl.pallas_call(
        _hamming_kernel,
        grid=(Nt // block_t, Nn // block_n),
        in_specs=[
            pl.BlockSpec((block_t, W), lambda ti, ni: (ti, 0)),
            pl.BlockSpec((block_n, W), lambda ti, ni: (ni, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, block_n), lambda ti, ni: (ti, ni)),
        out_shape=jax.ShapeDtypeStruct((Nt, Nn), jnp.int32),
        interpret=interpret,
        name="hamming_distances",
    )(test, train)
