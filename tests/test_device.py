"""One process per TPU chip: the device rules a parent checks before it
spawns chip users, and the refusal to fall back to the CPU on a TPU host.
A host with a chip is simulated by patching the PCI chip count."""

import os

import pytest

from repro.runtime import device


@pytest.fixture
def tpu_host(monkeypatch):
    monkeypatch.setattr(device, "tpu_chips", lambda: 1)


def test_cpu_host_has_no_chip_and_interprets():
    assert device.tpu_chips() == 0  # JAX_PLATFORMS=cpu excludes the TPU
    assert device.platform() == "cpu"
    assert device.interpret() and not device.use_kernels()
    device.check_chip_owner(4, "test")  # no chip: any number of processes


def test_tpu_host_refuses_cpu_fallback(tpu_host):
    with pytest.raises(RuntimeError, match="another process holds the chip"):
        device.platform()
    assert device.use_kernels()


def test_tpu_host_allows_one_chip_user(tpu_host, monkeypatch):
    monkeypatch.setattr(device, "backend_started", lambda: False)
    device.check_chip_owner(1, "test")
    with pytest.raises(RuntimeError, match="chip belongs to one process"):
        device.check_chip_owner(2, "test")


def test_tpu_host_refuses_spawn_from_chip_holder(tpu_host, monkeypatch):
    monkeypatch.setattr(device, "backend_started", lambda: True)
    with pytest.raises(RuntimeError, match="already brought up JAX"):
        device.check_chip_owner(1, "test")


def test_server_with_two_workers_refused_on_tpu_host(tpu_host, tmp_path):
    from repro.core.cooc import count_to_store
    from repro.data.corpus import synthetic_zipf_collection
    from repro.store import CoocServer

    c = synthetic_zipf_collection(30, vocab=64, mean_len=8, seed=0)
    count_to_store("list-scan", c, str(tmp_path / "s"))
    server = CoocServer(str(tmp_path / "s"), workers=2)
    with pytest.raises(RuntimeError, match="CoocServer: 2 processes"):
        server.start()
    assert not server._procs  # refused before any worker was spawned


def test_parallel_kernel_ingest_refused_on_tpu_host(tpu_host, tmp_path):
    from repro.core.plan import CountJob, ParallelExecutor, Planner
    from repro.data.corpus import synthetic_zipf_collection
    from repro.data.preprocess import remap_df_descending

    c, _ = remap_df_descending(
        synthetic_zipf_collection(40, vocab=5000, mean_len=8, seed=0)
    )
    job = CountJob(collection=c, output="store", method="freq-split",
                   out_path=str(tmp_path / "s"), df_descending=True,
                   num_shards=2)
    plan = Planner().plan(job)
    assert plan.method_kwargs["use_kernel"] is True  # the platform decided
    assert plan.sink_policy == "spill"
    with pytest.raises(RuntimeError, match="kernel method 'freq-split'"):
        ParallelExecutor(num_workers=2).execute(plan, out_dir=str(tmp_path / "w"))


def test_serve_builds_in_a_child_on_tpu_host(monkeypatch):
    """With workers on a TPU host the store is built in a process that
    exits before they start, never in the serving parent."""
    from repro.launch import cooc_serve

    def parent_build(*a, **kw):
        raise AssertionError("the serving parent built the store")

    monkeypatch.setattr(cooc_serve, "tpu_chips", lambda: 1)
    monkeypatch.setattr(cooc_serve, "_build_or_open", parent_build)
    stats = cooc_serve.serve(docs=60, vocab=128, queries=20, batch=10,
                             workers=1, clients=1)
    assert stats["workers"] == 1 and stats["num_docs"] == 60
    assert stats["build_s"] > 0


def test_compile_cache_prefers_the_environment(monkeypatch, tmp_path):
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert device.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_compile_cache_defaults_into_the_checkout(monkeypatch):
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    old = jax.config.jax_compilation_cache_dir
    try:
        path = device.configure_compile_cache()
        assert path == device.CACHE_DIR and path.endswith(".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        # exported, so spawned children land in the same directory
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == path
    finally:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        jax.config.update("jax_compilation_cache_dir", old)
