"""Jit'd public wrappers for the Pallas kernels.

Each op pads its inputs to kernel block multiples, dispatches to the Pallas
kernel (compiled Mosaic on a TPU; off it the interpreter runs the kernel
body for correctness validation — ``repro.runtime.device.interpret``), and
slices the result back.
``use_kernel=False`` routes to the pure-jnp oracle in ref.py — the oracle IS
the reference semantics, so both paths are interchangeable.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.bitpair import bitpair_kernel
from repro.kernels.cooc_gram import cooc_gram_kernel
from repro.kernels.segment_cooc import segment_hist_kernel
from repro.runtime.device import interpret as _interpret


def _pad_to(x: jax.Array, axis: int, mult: int, value=0) -> jax.Array:
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths, constant_values=value)


def cooc_gram(
    b_i,
    b_j,
    *,
    use_kernel: bool = True,
    blk_m: int = 128,
    blk_n: int = 128,
    blk_d: int = 256,
) -> jax.Array:
    """Gram tile C = b_iᵀ b_j for 0/1 incidence tiles (D, M), (D, N) → f32 (M, N)."""
    b_i = jnp.asarray(b_i, dtype=jnp.float32)
    b_j = jnp.asarray(b_j, dtype=jnp.float32)
    if not use_kernel:
        return ref.cooc_gram_ref(b_i, b_j)
    m, n = b_i.shape[1], b_j.shape[1]
    b_i = _pad_to(_pad_to(b_i, 0, blk_d), 1, blk_m)
    b_j = _pad_to(_pad_to(b_j, 0, blk_d), 1, blk_n)
    out = cooc_gram_kernel(
        b_i, b_j, blk_m=blk_m, blk_n=blk_n, blk_d=blk_d, interpret=_interpret()
    )
    return out[:m, :n]


def bitpair_popcount(
    rows_i,
    rows_j,
    *,
    use_kernel: bool = True,
    blk_m: int = 8,
    blk_n: int = 128,
    blk_w: int = 128,
) -> jax.Array:
    """Intersection counts over uint32 bitmaps (M, W), (N, W) → int32 (M, N)."""
    rows_i = jnp.asarray(np.ascontiguousarray(rows_i), dtype=jnp.uint32)
    rows_j = jnp.asarray(np.ascontiguousarray(rows_j), dtype=jnp.uint32)
    if not use_kernel:
        return ref.bitpair_popcount_ref(rows_i, rows_j)
    m, n = rows_i.shape[0], rows_j.shape[0]
    rows_i = _pad_to(_pad_to(rows_i, 0, blk_m), 1, blk_w)
    rows_j = _pad_to(_pad_to(rows_j, 0, blk_n), 1, blk_w)
    out = bitpair_kernel(
        rows_i, rows_j, blk_m=blk_m, blk_n=blk_n, blk_w=blk_w, interpret=_interpret()
    )
    return out[:m, :n]


def segment_hist(
    ids,
    seg,
    *,
    num_rows: int,
    vocab: int,
    use_kernel: bool = True,
    blk_v: int = 128,
    blk_l: int = 512,
) -> jax.Array:
    """Batched LIST-SCAN histogram: (L,) ids + (L,) segment ids (−1 = pad)
    → int32 (num_rows, vocab)."""
    ids = jnp.asarray(ids, dtype=jnp.int32)
    seg = jnp.asarray(seg, dtype=jnp.int32)
    if not use_kernel:
        return ref.segment_hist_ref(ids, seg, num_rows, vocab)
    ids = _pad_to(ids, 0, blk_l, value=-1)
    seg = _pad_to(seg, 0, blk_l, value=-1)
    vpad = vocab + ((-vocab) % blk_v)
    out = segment_hist_kernel(
        ids,
        seg,
        num_rows=num_rows,
        vocab=vpad,
        blk_v=blk_v,
        blk_l=blk_l,
        interpret=_interpret(),
    )
    return out[:, :vocab].astype(jnp.int32)
