"""Pallas TPU kernel: bit-packed pair intersection counting (LIST-PAIRS core).

Posting lists are packed 32 documents per uint32 word (data/index.py
``incidence_bitpacked``). The intersection size of two posting lists is
Σ_w popcount(w_i & w_j) — the VPU path: 32× less HBM traffic than a bf16
incidence tile, no MXU involvement, exact integer counts.

Grid = (M/blk_m, N/blk_n, W/blk_w), word dimension innermost/sequential, the
(blk_m, blk_n) int32 accumulator resident in VMEM. The output block is
lane-aligned (blk_m a multiple of 8, blk_n of 128), which the TPU compiler
requires; the (blk_m, blk_n, blk_w) AND intermediate is 512 KB at the
default (8, 128, 128) blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _bitpair_kernel(wi_ref, wj_ref, out_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    both = jnp.bitwise_and(wi_ref[...][:, None, :], wj_ref[...][None, :, :])
    out_ref[...] += jax.lax.population_count(both).astype(jnp.int32).sum(axis=-1)


@functools.partial(
    jax.jit, static_argnames=("blk_m", "blk_n", "blk_w", "interpret")
)
def bitpair_kernel(
    rows_i: jax.Array,
    rows_j: jax.Array,
    *,
    blk_m: int = 8,
    blk_n: int = 128,
    blk_w: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """rows_i: (M, W), rows_j: (N, W) uint32; dims multiples of block sizes
    (ops.bitpair_popcount pads). Returns int32 (M, N)."""
    m, w = rows_i.shape
    n, _ = rows_j.shape
    grid = (m // blk_m, n // blk_n, w // blk_w)
    return pl.pallas_call(
        _bitpair_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((blk_m, blk_w), lambda i, j, k: (i, k)),
            pl.BlockSpec((blk_n, blk_w), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((blk_m, blk_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(rows_i, rows_j)
