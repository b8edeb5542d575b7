"""Every Pallas kernel on the main path compiles for a TPU v5e at the widths
the system runs, with no chip attached: the TPU compiler refuses what the
interpreter accepts (unaligned blocks, more VMEM than a kernel may use).

The v5e:2x2 topology is described inside a fixture, never at import: only
one process may load the TPU library at a time, so the test worker given
this file loads it and every other worker collects the same tests."""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip can be written to the persistent cache
    # but never read back here: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel, not XLA
    return compiled


@pytest.mark.parametrize("L", [128, 65_536, 1_048_576])
@pytest.mark.parametrize("score", ["count", "pmi", "dice"])
def test_topk_gather_compiles_at_any_row_length(one_chip, L, score):
    from repro.kernels.topk_gather import _topk_gather

    B, i32 = 32, jnp.int32
    fn = functools.partial(
        _topk_gather, num_docs=10_000, score=score, k=10, blk_b=8,
        interpret=False,
    )
    _compile(fn, one_chip, ((B, L), i32), ((B, L), i32), ((B, 1), i32),
             ((B, L), i32))


def test_cooc_gram_compiles_at_freq_split_tile(one_chip):
    from repro.core.specs import get_spec
    from repro.kernels.cooc_gram import cooc_gram_kernel

    kw = get_spec("freq-split").defaults()
    tile = ((kw["doc_tile"], kw["head"]), jnp.float32)  # (2048, 1024)
    _compile(functools.partial(cooc_gram_kernel, interpret=False), one_chip,
             tile, tile)


def test_segment_hist_compiles_at_full_head_vocab(one_chip):
    from repro.kernels.segment_cooc import segment_hist_kernel

    fn = functools.partial(segment_hist_kernel, num_rows=64, vocab=65_536,
                           interpret=False)
    _compile(fn, one_chip, ((8192,), jnp.int32), ((8192,), jnp.int32))


def test_bitpair_compiles_at_default_blocks(one_chip):
    from repro.kernels.bitpair import bitpair_kernel

    rows = ((1024, 512), jnp.uint32)
    _compile(functools.partial(bitpair_kernel, interpret=False), one_chip,
             rows, rows)


# the served 65,536-term store's page pool: ids, counts and df of 4,096 rows
# of 32 pages of 2,048 candidates
POOL = ((2 + 4096 * 32, 3 * 2048), jnp.int32)


@pytest.mark.parametrize("B, pages", [(1, 1), (64, 32)])
def test_paged_topk_compiles_at_served_pool(one_chip, B, pages):
    from repro.store.query import _topk_pages

    fn = functools.partial(
        _topk_pages, num_docs=10_000, score="pmi", k=10, kernel="pallas",
        interpret=False,
    )
    i32 = jnp.int32
    _compile(fn, one_chip, POOL, ((B, pages), i32), ((B,), i32))


def test_page_upload_donates_the_pool(one_chip):
    """The scatter aliases the pool's buffer to its output and copies it
    not: an upload writes its pages in place."""
    from repro.store.query import _upload_pages

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in (POOL, ((4,), jnp.int32), ((4, 3 * 2048), jnp.int32))]
    compiled = _upload_pages.lower(*args).compile()
    text = compiled.as_text()
    assert "input_output_alias={ {}: (0, {}, may-alias) }" in text
    pool_copies = [line for line in text.splitlines()
                   if ("copy(" in line or "copy-start" in line)
                   and f"s32[{POOL[0][0]},{POOL[0][1]}]" in line.split("=")[0]]
    assert not pool_copies
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
