"""Distributed Gram accumulation: C = Σ_s B_sᵀ B_s over document shards.

Device layout (launch/mesh.py):
  * documents shard over ("pod", "data")  — rows of B,
  * vocabulary shards over "model"        — columns of B and of C.

Each device holds B_local of shape (D_local, V_local). To form its strip of
C it needs every other model-rank's column block as the right operand. Two
schedules are provided:

* ``gram_allgather`` — paper-faithful LIST-BLOCKS schedule: materialize the
  full right operand with one all-gather over "model", one big matmul, then
  reduce-scatter partials over the document axes. Simple, but the all-gather
  is a bandwidth burst that cannot overlap the matmul.

* ``gram_ring`` — beyond-paper schedule: rotate column blocks around the
  "model" axis with collective-permute, accumulating one (V_local × V_local)
  block-product per step. Communication of step k+1 overlaps the matmul of
  step k (the compiler can double-buffer the permute), peak memory drops from
  O(V) to O(V_local) per device, and total bytes moved are identical.
  This is the schedule hill-climbed in EXPERIMENTS.md §Perf.

Both return the device-local strip of the *global* Gram matrix: shape
(V_local, V) rows scattered over the document axes for the final write-out.
Exactness: f32 accumulation, exact for per-shard doc counts < 2²⁴.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



def _local_gram_allgather(B_local: jax.Array, *, model_axis: str, doc_axes) -> jax.Array:
    B_all = jax.lax.all_gather(B_local, model_axis, axis=1, tiled=True)  # (D_loc, V)
    partial = jnp.einsum(
        "di,dj->ij", B_local, B_all, preferred_element_type=jnp.float32
    )  # (V_loc, V)
    return jax.lax.psum_scatter(partial, doc_axes, scatter_dimension=0, tiled=True)


def _local_gram_ring(
    B_local: jax.Array, *, model_axis: str, doc_axes, n: int
) -> jax.Array:
    my = jax.lax.axis_index(model_axis)
    v_loc = B_local.shape[1]

    # STATIC python loop (n is a trace-time constant): every ring step
    # appears in the HLO — cost analysis counts all n block-matmuls (a
    # fori_loop body would be counted once), and the compiler can pipeline
    # step k's permute against step k+1's matmul
    acc = jnp.zeros((v_loc, v_loc * n), dtype=jnp.float32)
    acc = jax.lax.pcast(acc, tuple(doc_axes) + (model_axis,), to="varying")
    buf = B_local
    for k in range(n):
        src = (my + k) % n  # global block id currently held in buf
        part = jnp.einsum(
            "di,dj->ij", B_local, buf, preferred_element_type=jnp.float32
        )
        acc = jax.lax.dynamic_update_slice(acc, part, (0, src * v_loc))
        if k + 1 < n:
            # pass buf one hop left so rank r receives block (r + k + 1) next
            buf = jax.lax.ppermute(
                buf, model_axis, perm=[((i + 1) % n, i) for i in range(n)]
            )
    return jax.lax.psum_scatter(acc, doc_axes, scatter_dimension=0, tiled=True)


def make_distributed_gram(
    mesh: Mesh,
    *,
    schedule: str = "ring",
    model_axis: str = "model",
):
    """Build a jit'd distributed Gram op over ``mesh``.

    Input: global incidence matrix B (D, V) sharded (doc_axes, model).
    Output: global C (V, V) with rows sharded over doc_axes and columns
    over nothing (each row strip is fully accumulated).
    """
    doc_axes = tuple(a for a in mesh.axis_names if a != model_axis)
    fn = {"allgather": _local_gram_allgather, "ring": _local_gram_ring}[schedule]
    kwargs = dict(model_axis=model_axis, doc_axes=doc_axes)
    if schedule == "ring":
        # ring length must be a trace-time constant (static python loop)
        kwargs["n"] = dict(mesh.shape)[model_axis]
    local = functools.partial(fn, **kwargs)

    shard = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(doc_axes, model_axis),),
        out_specs=P((model_axis,) + doc_axes, None),
    )
    return jax.jit(shard)


def gram_reference(B: jnp.ndarray) -> jnp.ndarray:
    """Single-device oracle for the distributed schedules."""
    return jnp.einsum("di,dj->ij", B, B, preferred_element_type=jnp.float32)
