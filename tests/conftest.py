"""Test harness: an 8-device virtual CPU mesh.

The reference had no tests at all (SURVEY §4); its only harness was the
single-process no-op fallback in every collective. JAX makes real distributed
testing cheap: ``--xla_force_host_platform_device_count=8`` gives eight CPU
"devices" in one process, and the exact same ``shard_map``/``psum`` code path
that runs over TPU ICI runs over them.

This must run before jax initializes its backends, hence module-import time.
"""

import os

# Force CPU even when the environment pre-sets a TPU platform: tests exercise
# the distributed code path on 8 virtual devices, which needs the host
# platform. replace=False keeps a user-supplied device-count flag; the
# helper also covers the jax-already-imported case via jax.config.
from network_distributed_pytorch_tpu.hostenv import force_cpu_devices  # noqa: E402

# collective_timeout_s: XLA:CPU's default 40 s rendezvous-terminate
# deadline aborts the whole process when a heavy multi-device program's
# serialized per-device computes (8 devices, possibly 1 core) keep the
# last participant away too long — observed on the full suite at
# test_exact_cifar10_fsdp_strategy. 120 s sufficed for the suite alone
# but still aborted when ANOTHER jax process shared the single core
# (reproduced twice with a concurrent jax process); 300 s/600 s
# absorbs that while a genuine deadlock still dies in ten minutes.
force_cpu_devices(8, replace=False, collective_timeout_s=300)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: the suite is hundreds of small XLA compiles;
# caching serialized executables across runs cuts re-run wall time sharply
# (first run pays, repeats hit). XLA:CPU AOT entries bake in the compiling
# host's CPU features and can SIGILL if replayed on a lesser machine, so the
# cache directory is keyed by a fingerprint of this host's feature set — a
# different machine/image gets a fresh cache instead of stale executables.
# Safe to delete .xla_cache_tests/ anytime.
def _host_fingerprint() -> str:
    import hashlib
    import platform as _platform

    # machine + processor brand (NOT platform.platform(): that embeds the
    # kernel build string, which would invalidate the whole cache on every
    # routine kernel update); on hosts without /proc/cpuinfo (macOS) the
    # processor string still separates e.g. Rosetta from native
    feat = "|".join((_platform.machine(), _platform.processor()))
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    feat += line
                    break
    except OSError:
        pass
    return hashlib.sha256(feat.encode()).hexdigest()[:12]


# yields to JAX_COMPILATION_CACHE_DIR like every other entry point, and as
# the process's first caller it is the one that places the cache
from network_distributed_pytorch_tpu.hostenv import (  # noqa: E402
    configure_compile_cache,
)

configure_compile_cache(
    default_dir=os.path.join(
        os.path.dirname(os.path.dirname(__file__)),
        ".xla_cache_tests",
        _host_fingerprint(),
    )
)
# the suite is hundreds of sub-second compiles: keep those out of its cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs
