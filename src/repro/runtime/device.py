"""Which device a process computes on, and who may own it.

A TPU chip belongs to one process at a time: the first process that brings
up JAX's TPU backend holds the chip until it exits, and a second one cannot
get it. What the second one sees depends on ``JAX_PLATFORMS``: where it
names the platforms, start-up fails ("Unable to initialize backend 'tpu'",
a libtpu lockfile error); where it is unset, the TPU backend fails quietly,
the process comes up on the CPU and Pallas kernels would silently run under
the interpreter. This module keeps those rules in one place:

* :func:`tpu_chips` reads how many TPU chips the host has from PCI ids,
  without starting a JAX backend — so a parent that spawns chip users can
  plan for the chip without taking it;
* :func:`platform` is the platform this process computes on, and raises
  instead of returning ``"cpu"`` on a host whose chip it failed to get;
* :func:`use_kernels` / :func:`interpret` derive the kernel choice from the
  platform (compiled Pallas on a TPU, jnp references / interpreter off it);
* :func:`memory_bytes` is the device's memory, where it reports it;
* :func:`check_chip_owner` is called before spawning processes that need
  the chip and raises when more than one process would need it;
* :func:`configure_compile_cache` places JAX's persistent compilation cache
  (every entry point and spawned worker calls it).
"""

from __future__ import annotations

import os

# the fixed in-checkout cache directory used when JAX_COMPILATION_CACHE_DIR
# is unset: <checkout>/.jax_cache (this file is src/repro/runtime/device.py)
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def tpu_chips() -> int:
    """TPU chips this process may use: 0 when ``JAX_PLATFORMS`` excludes
    the TPU, otherwise the chips attached to the host over PCI. Starts no
    JAX backend."""
    wanted = os.environ.get("JAX_PLATFORMS", "")
    if wanted and "tpu" not in wanted.split(","):
        return 0
    from jax._src import hardware_utils

    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


def backend_started() -> bool:
    """Whether this process has already brought up a JAX backend."""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def platform() -> str:
    """The platform this process computes on (``jax.default_backend()``).

    Raises ``RuntimeError`` on a host with TPU chips where JAX came up on
    another platform: the chip is held by another process, and carrying on
    would run every kernel on the host instead."""
    import jax

    name = jax.default_backend()
    if name != "tpu" and tpu_chips():
        raise RuntimeError(
            f"this host has {tpu_chips()} TPU chip(s) but JAX came up on "
            f"{name!r}: another process holds the chip (one process per chip)"
        )
    return name


def use_kernels() -> bool:
    """Default kernel choice for counting methods: compiled Pallas kernels
    on a TPU host, the jnp references elsewhere. Decided from the host's
    chips, so a planning process stays off the backend."""
    return tpu_chips() > 0


def interpret() -> bool:
    """Whether Pallas kernels in this process run under the interpreter
    (every platform but the TPU)."""
    return platform() != "tpu"


def memory_bytes() -> int | None:
    """Bytes of memory of this process's first device, where the device
    reports them (a TPU does; the CPU does not: ``None``)."""
    import jax

    stats = jax.devices()[0].memory_stats()
    return int(stats["bytes_limit"]) if stats and "bytes_limit" in stats else None


def check_chip_owner(processes: int, what: str) -> None:
    """Raise before spawning ``processes`` children that each need JAX's
    device, when that would put more than one process on a TPU chip: more
    than one child, or a parent that already holds the chip itself."""
    if not tpu_chips():
        return
    if processes > 1:
        raise RuntimeError(
            f"{what}: {processes} processes would each need the TPU, and a "
            "chip belongs to one process; use 1"
        )
    if backend_started():
        raise RuntimeError(
            f"{what}: this process already brought up JAX and holds the "
            "TPU, so a spawned process cannot get it; spawn before any JAX "
            "work in this process"
        )


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, wins and nothing else is set.
    Otherwise the cache lives at the fixed :data:`CACHE_DIR` inside the
    checkout, and the variable is exported so spawned children that only
    inherit the environment use the same directory. Every compilation is
    cached (no minimum compile time), so a second run finds each kernel."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        os.environ["JAX_COMPILATION_CACHE_DIR"] = path
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
