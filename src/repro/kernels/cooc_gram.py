"""Pallas TPU kernel: tiled co-occurrence Gram matmul (the LIST-BLOCKS core).

Computes C[I,J] = B[:,I]ᵀ B[:,J] for 0/1 incidence tiles streamed HBM→VMEM.
Grid = (M/blk_m, N/blk_n, D/blk_d) with the document (contraction) dimension
innermost and sequential; the (blk_m, blk_n) f32 output tile stays resident
in VMEM across the contraction and is written once — mirroring LIST-BLOCKS'
write-once accumulator discipline (no merge phase).

MXU alignment: blk_m, blk_n multiples of 128 (lane), blk_d multiple of 8
(sublane, f32). 0/1 values are exact in bf16/f32; accumulation is f32, exact
below 2²⁴ documents per call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gram_kernel(bi_ref, bj_ref, out_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # (blk_d, blk_m)ᵀ @ (blk_d, blk_n) on the MXU, f32 accumulate
    out_ref[...] += jax.lax.dot_general(
        bi_ref[...],
        bj_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@functools.partial(
    jax.jit, static_argnames=("blk_m", "blk_n", "blk_d", "interpret")
)
def cooc_gram_kernel(
    b_i: jax.Array,
    b_j: jax.Array,
    *,
    blk_m: int = 128,
    blk_n: int = 128,
    blk_d: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """b_i: (D, M), b_j: (D, N) 0/1 tiles; D, M, N multiples of the block
    sizes (ops.cooc_gram pads). Returns f32 (M, N)."""
    d, m = b_i.shape
    _, n = b_j.shape
    grid = (m // blk_m, n // blk_n, d // blk_d)
    return pl.pallas_call(
        _gram_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((blk_d, blk_m), lambda i, j, k: (k, i)),
            pl.BlockSpec((blk_d, blk_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((blk_m, blk_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(b_i, b_j)
