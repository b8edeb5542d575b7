"""Pallas TPU kernel: batched top-k neighbour selection on gathered CSR rows.

The serving hot path (store/query.py) gathers each queried term's merged
neighbour row, on the device, from the int32 pages of its row cache into a
rectangular ``(B, L)`` tile padded with id -1, and ranks the ``L``
candidates per row by count, PMI, or Dice. The reference implementation
scores the tile and calls ``jax.lax.top_k`` in one jitted function. Here
XLA scores the tile with the reference's own expressions, fused with the
padding, and one Pallas launch selects the top k, streaming the candidate
axis through VMEM (``topk_gather`` takes the same tile as host arrays):

    for each (blk_b, BLK_L) column tile of ids and scores, in order:
        merge with the running top-k (k rounds of row-max,
                                      first-argmax, mask)
    →  (B, k)

The scores stay outside the kernel on purpose: XLA computes ``log`` and
float division the same way in both programs, so the scores the kernel
returns are the reference's, bit for bit, on every backend; the kernel only
compares them.

The grid is ``(B / blk_b, L / BLK_L)``; the column axis is sequential
("arbitrary"), and the running top-k of each row block — ids, scores and
the column each came from — stays resident in VMEM across it. VMEM use is
therefore set by ``BLK_L`` and ``k`` and does not grow with ``L``: a head
term whose row holds a large share of the vocabulary compiles like any
other.

Selection is k rounds of masked row-max over the running entries plus the
new tile. Each round takes the maximum score and, among the entries
achieving it, the one from the **lowest column** — exactly
``jax.lax.top_k``'s tie rule — then retires it. Running entries always come
from earlier columns than the tile, so after the last tile the running list
is the global top-k in ``lax.top_k`` order, and results are bit-identical
to the reference on every path (tests/test_topk_gather.py asserts this,
across several column tiles, with ``interpret=True``). The rounds are a
loop, not unrolled, so the program's size does not grow with ``k``.

Scores (df = document frequency, D = total documents):
    count  c(t, n)                        — exact int32 ranking
    pmi    log(c · D / (df_t · df_n))    — pointwise mutual information
    dice   2c / (df_t + df_n)            — Dice coefficient

Padding slots carry id -1 / count 0 and score 0 (count) or -inf (pmi/dice),
matching the reference scorer, so rows shorter than ``k`` surface id -1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime.device import interpret as _interpret

LANE = 128  # TPU lane width: pad the candidate axis to a multiple of this
# candidate columns per grid step: two (8, 2048) 32-bit input tiles, double
# buffered, take 256 KB of VMEM whatever the row length
BLK_L = 2048

_INT_MIN = jnp.iinfo(jnp.int32).min
_INT_MAX = jnp.iinfo(jnp.int32).max
# columns of not-yet-filled running slots: above every real column (a store
# row is shorter than 2**30), distinct per slot, so an empty slot loses every
# tie and two empty slots never tie with each other
_EMPTY_COL = 1 << 30


def _scores(ids, cnts, df_t, df_n, *, score: str, num_docs: int):
    """Score a padded (B, L) candidate tile; same expressions (and the same
    dtypes, op for op) as the reference scorer in store/query.py."""
    valid = ids >= 0
    if score == "count":
        return jnp.where(valid, cnts, 0).astype(jnp.int32)
    if score == "pmi":
        s = jnp.log(
            cnts.astype(jnp.float32)
            * jnp.float32(num_docs)
            / (df_t.astype(jnp.float32) * df_n.astype(jnp.float32))
        )
        return jnp.where(valid, s, -jnp.inf)
    if score == "dice":
        s = 2.0 * cnts.astype(jnp.float32) / (df_t + df_n).astype(jnp.float32)
        return jnp.where(valid, s, -jnp.inf)
    raise ValueError(f"unknown score {score!r}; have ('count', 'pmi', 'dice')")


def _topk_gather_kernel(
    ids_ref,
    s_ref,
    top_ids_ref,
    top_s_ref,
    top_col_ref,
    *,
    k: int,
    score: str,
):
    l_blk = pl.program_id(1)
    ids = ids_ref[...]  # (blk_b, blk_l) int32, -1 padding
    s = s_ref[...]
    # below every score: the value of empty and retired entries
    fill = _INT_MIN if score == "count" else -jnp.inf
    blk_b, blk_l = ids.shape
    k_pad = top_ids_ref.shape[1]
    slot = jax.lax.broadcasted_iota(jnp.int32, (blk_b, k_pad), 1)

    # the running top-k lives in the output blocks (and the column scratch):
    # their block index ignores the column axis, so they stay in VMEM
    @pl.when(l_blk == 0)
    def _init():
        top_ids_ref[...] = jnp.full((blk_b, k_pad), -1, jnp.int32)
        top_s_ref[...] = jnp.full((blk_b, k_pad), fill, top_s_ref.dtype)
        top_col_ref[...] = _EMPTY_COL + slot

    run_ids = top_ids_ref[...]
    run_s = top_s_ref[...]
    run_col = top_col_ref[...]
    col = l_blk * blk_l + jax.lax.broadcasted_iota(jnp.int32, (blk_b, blk_l), 1)

    def select(r, carry):
        # a retired (or never eligible) entry carries column _INT_MAX
        rcol, tcol, new_ids, new_s, new_col = carry
        rm = jnp.where(rcol != _INT_MAX, run_s, fill)
        tm = jnp.where(tcol != _INT_MAX, s, fill)
        m = jnp.maximum(
            jnp.max(rm, axis=1, keepdims=True), jnp.max(tm, axis=1, keepdims=True)
        )
        # the lowest column achieving the max — lax.top_k's tie rule
        c = jnp.minimum(
            jnp.min(jnp.where(rm == m, rcol, _INT_MAX), axis=1, keepdims=True),
            jnp.min(jnp.where(tm == m, tcol, _INT_MAX), axis=1, keepdims=True),
        )
        run_pick = rcol == c
        pick = tcol == c
        sel = jnp.maximum(
            jnp.max(jnp.where(run_pick, run_ids, _INT_MIN), axis=1, keepdims=True),
            jnp.max(jnp.where(pick, ids, _INT_MIN), axis=1, keepdims=True),
        )
        here = slot == r
        return (
            jnp.where(run_pick, _INT_MAX, rcol),
            jnp.where(pick, _INT_MAX, tcol),
            jnp.where(here, sel, new_ids),
            jnp.where(here, m, new_s),
            jnp.where(here, c, new_col),
        )

    # k rounds; running slots past k are lane padding, never candidates
    rcol = jnp.where(slot < k, run_col, _INT_MAX)
    carry = (rcol, col, run_ids, run_s, run_col)
    _, _, new_ids, new_s, new_col = jax.lax.fori_loop(0, k, select, carry)

    top_ids_ref[...] = new_ids
    top_s_ref[...] = new_s
    top_col_ref[...] = new_col


@functools.partial(
    jax.jit,
    static_argnames=("num_docs", "score", "k", "blk_b", "interpret"),
)
def _topk_gather(
    ids, cnts, df_t, df_n, *, num_docs, score, k, blk_b, interpret
):
    B, L = ids.shape
    # one column tile when the row fits, else whole BLK_L tiles
    tile_l = min(BLK_L, max(LANE, -(-L // LANE) * LANE))
    L_pad = -(-L // tile_l) * tile_l
    B_pad = -(-B // blk_b) * blk_b
    ids = jnp.pad(ids, ((0, B_pad - B), (0, L_pad - L)), constant_values=-1)
    cnts = jnp.pad(cnts, ((0, B_pad - B), (0, L_pad - L)))
    df_n = jnp.pad(df_n, ((0, B_pad - B), (0, L_pad - L)), constant_values=1)
    df_t = jnp.pad(df_t, ((0, B_pad - B), (0, 0)), constant_values=1)

    k_pad = -(-k // LANE) * LANE  # lane-aligned running top-k / output tile
    s = _scores(ids, cnts, df_t, df_n, score=score, num_docs=num_docs)
    kernel = functools.partial(_topk_gather_kernel, k=k, score=score)
    row_tile = pl.BlockSpec((blk_b, tile_l), lambda b, l: (b, l))
    top_tile = pl.BlockSpec((blk_b, k_pad), lambda b, l: (b, 0))
    top_ids, top_s = pl.pallas_call(
        kernel,
        grid=(B_pad // blk_b, L_pad // tile_l),
        in_specs=[row_tile, row_tile],
        out_specs=[top_tile, top_tile],
        out_shape=[
            jax.ShapeDtypeStruct((B_pad, k_pad), jnp.int32),
            jax.ShapeDtypeStruct((B_pad, k_pad), s.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((blk_b, k_pad), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(ids, s)
    return top_ids[:B, :k], top_s[:B, :k]


def topk_gather(
    ids,
    cnts,
    df_t,
    df_n,
    *,
    num_docs: int,
    score: str = "count",
    k: int = 10,
    blk_b: int = 8,
    interpret: bool | None = None,
):
    """Top-k neighbours of a gathered candidate tile, fully on-device.

    Args:
        ids:   (B, L) int candidate term IDs, padded with -1.
        cnts:  (B, L) int pair counts (0 in padding slots).
        df_t:  (B,) or (B, 1) int document frequency of each queried term.
        df_n:  (B, L) int document frequency of each candidate (>= 1).
        num_docs: total documents in the store (a per-store constant — it is
            baked into the jitted launch, not shipped per call).
        score: "count" | "pmi" | "dice".
        k:     neighbours to return; must be <= L.
        blk_b: query rows per grid step.
        interpret: run the Pallas interpreter instead of compiling (None =
            the platform decides: compiled on a TPU, interpreted elsewhere,
            which is how CPU CI exercises the kernel).

    Returns:
        (top_ids (B, k) int32, top_scores (B, k) int32 or float32) — rows
        with fewer than k candidates padded with id -1 (score 0 for count,
        -inf otherwise). Bit-identical to the reference scorer.

    Example::

        ids  = np.array([[4, 9, -1, -1]])   # one row, two real candidates
        cnts = np.array([[3, 7,  0,  0]])
        top_ids, top_s = topk_gather(ids, cnts, np.array([5]),
                                     np.maximum(ids, 1), num_docs=100, k=2)
        # top_ids -> [[9, 4]], top_s -> [[7, 3]]
    """
    if score not in ("count", "pmi", "dice"):
        raise ValueError(f"unknown score {score!r}; have ('count', 'pmi', 'dice')")
    ids = jnp.asarray(np.asarray(ids), dtype=jnp.int32)
    cnts = jnp.asarray(np.asarray(cnts), dtype=jnp.int32)
    df_t = jnp.asarray(np.asarray(df_t), dtype=jnp.int32).reshape(ids.shape[0], 1)
    df_n = jnp.asarray(np.asarray(df_n), dtype=jnp.int32)
    if not 1 <= k <= ids.shape[1]:
        raise ValueError(f"k={k} must be in [1, L={ids.shape[1]}]")
    if interpret is None:
        interpret = _interpret()
    return _topk_gather(
        ids, cnts, df_t, df_n,
        num_docs=int(num_docs), score=score, k=int(k),
        blk_b=int(blk_b), interpret=bool(interpret),
    )
