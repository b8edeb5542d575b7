import os
import sys

# Tests must see exactly ONE device (the dry-run sets its own 512-device flag
# in a separate process). Keep threads bounded for CI stability.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Entry points and serving workers place JAX's persistent compilation cache
# in the checkout (repro.runtime.device.configure_compile_cache). Test runs
# compile for the CPU from several xdist workers at once; keep them off it.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def lexsort_sym_reference(row_ptr, cols, counts, V):
    """The pre-refactor in-memory symmetric-adjacency build (doubled COO +
    lexsort) — the byte-identity oracle for csr_store._write_symmetric's
    external-memory two-pass build."""
    import numpy as np

    rows = np.repeat(
        np.arange(V, dtype=np.int32), np.diff(row_ptr).astype(np.int64)
    )
    r2 = np.concatenate([rows, cols])
    c2 = np.concatenate([cols, rows])
    v2 = np.concatenate([counts, counts])
    order = np.lexsort((c2, r2))
    sym_ptr = np.zeros(V + 1, dtype=np.int64)
    np.cumsum(np.bincount(r2, minlength=V), out=sym_ptr[1:])
    return sym_ptr, c2[order].astype(np.int32), v2[order]
