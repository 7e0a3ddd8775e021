"""Host-platform pinning and the compile cache, importable BEFORE jax.

Every CPU-mesh entry point (the test conftest, the multi-process rendezvous
workers, the driver's multichip dryrun, study scripts) needs the same
pre-import dance: ``JAX_PLATFORMS=cpu`` plus an
``--xla_force_host_platform_device_count`` flag, applied before jax's first
backend init. Every entry point that compiles (``launch.py`` workers,
``chip_smoke.py``, ``bench.py`` children, the test conftest) places its
persistent compile cache through :func:`configure_compile_cache`. This
module imports no jax at module scope (and the package ``__init__`` imports
nothing), so it is safe at the very top of any script.
"""

from __future__ import annotations

import os
import re
import sys
from typing import Optional

_COUNT_FLAG = r"--xla_force_host_platform_device_count=\d+\s*"
_TIMEOUT_FLAGS = (
    r"--xla_cpu_collective_call_(?:warn_stuck|terminate)_timeout_seconds=\d+\s*"
)


def _xla_flag_supported(flag_name: str) -> bool:
    """Whether this jaxlib registers ``flag_name`` — unknown names in
    ``XLA_FLAGS`` are FATAL (``parse_flags_from_env.cc`` aborts the process
    at first backend init), so optional flags must be probed, not guessed.

    There is no query API, but every registered flag's name string is
    embedded in the jaxlib binary; a substring scan of ``xla_extension`` is
    cheap (one mmap'd pass) and errs on the safe side: a flag the scan
    can't find is never appended.
    """
    try:
        import importlib.util
        import mmap

        spec = importlib.util.find_spec("jaxlib")
        if spec is None or not spec.submodule_search_locations:
            return False
        root = spec.submodule_search_locations[0]
        for fname in os.listdir(root):
            if not fname.startswith("xla_extension"):
                continue
            with open(os.path.join(root, fname), "rb") as f:
                with mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ) as mm:
                    if mm.find(flag_name.encode()) != -1:
                        return True
        return False
    except (OSError, ValueError, ImportError):
        return False


def force_cpu_devices(
    n: Optional[int] = 8,
    replace: bool = True,
    collective_timeout_s: Optional[int] = None,
) -> None:
    """Pin jax to the host (CPU) platform with ``n`` virtual devices.

    ``n=None`` REMOVES any device-count flag (one real device per process —
    the multi-process rendezvous world). ``replace=False`` keeps a
    pre-existing count flag (so a caller's own ``XLA_FLAGS`` wins). If jax
    is already imported, the platform config is updated directly too (the
    env var alone would be too late).

    ``collective_timeout_s`` raises XLA:CPU's collective-rendezvous
    warn/terminate deadlines (default 20 s/40 s). On a host with fewer
    cores than virtual devices the per-device compute of one step runs
    SERIALLY, so a heavy step can legitimately keep the last participant
    thread away past 40 s and the default deadline kills the process
    ("Expected N threads to join the rendezvous") — raise it for big-model
    CPU-mesh runs.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    had_count = "xla_force_host_platform_device_count" in flags
    if n is None:
        flags = re.sub(_COUNT_FLAG, "", flags)
    elif replace or not had_count:
        flags = re.sub(_COUNT_FLAG, "", flags).strip()
        flags += f" --xla_force_host_platform_device_count={n}"
    if collective_timeout_s is not None and _xla_flag_supported(
        "xla_cpu_collective_call_warn_stuck_timeout_seconds"
    ):
        flags = re.sub(_TIMEOUT_FLAGS, "", flags).strip()  # no duplicates
        flags += (
            f" --xla_cpu_collective_call_warn_stuck_timeout_seconds={collective_timeout_s}"
            f" --xla_cpu_collective_call_terminate_timeout_seconds={2 * collective_timeout_s}"
        )
    os.environ["XLA_FLAGS"] = flags.strip()
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_platforms", "cpu")


COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


_cache_dir: Optional[str] = None  # where this process's cache was placed


def configure_compile_cache(default_dir: Optional[str] = None) -> str:
    """Place this process's persistent XLA compile cache; returns its path.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads the variable
    itself and no code sets a directory. Where it is not, the cache is
    ``default_dir`` or ``<checkout>/.xla_cache`` — fixed and git-ignored,
    never derived from a temp name, pid or time, because the path is part
    of the cache key. Every executable is persisted, whatever it took to
    compile, so a second run of the same program adds no entry.

    The first caller in a process places the cache; a later call
    (``launch.main`` under the test conftest) changes nothing and returns
    where it is. Imports jax but initialises no backend.
    """
    global _cache_dir
    if _cache_dir is not None:
        return _cache_dir
    import jax

    _cache_dir = os.environ.get(COMPILE_CACHE_ENV)
    if not _cache_dir:
        _cache_dir = default_dir or os.path.join(CHECKOUT, ".xla_cache")
        os.makedirs(_cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", _cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return _cache_dir


def compile_cache_entries(cache_dir: str) -> int:
    """Number of entries in the compile cache (0 for a directory not yet
    created) — what ``chip_smoke.py`` and ``bench.py`` report before and
    after their compiles."""
    if not os.path.isdir(cache_dir):
        return 0
    return sum(1 for name in os.listdir(cache_dir) if not name.startswith("."))


_GOOGLE_PCI_VENDOR = "0x1ae0"


def local_tpu_chips(dev: str = "/dev", sys_root: str = "/sys") -> int:
    """TPU chips this host hands to a process, counted without importing
    jax — what a supervising parent, which must never open a chip itself,
    can know about the host it is about to spawn workers on. A chip is a
    ``/dev/accel<N>`` node (v4 and older) or a ``/dev/vfio/<group>`` whose
    IOMMU group holds a Google PCI function (v5e: the sealed one-chip
    machine shows all four functions on PCI but only one vfio group). 0 on
    a host with no chip (every CPU test host)."""
    try:
        nodes = os.listdir(dev)
    except OSError:
        return 0
    count = sum(1 for node in nodes if re.fullmatch(r"accel\d+", node))
    try:
        groups = [g for g in os.listdir(os.path.join(dev, "vfio")) if g.isdigit()]
    except OSError:
        groups = []
    for group in groups:
        functions = os.path.join(sys_root, "kernel", "iommu_groups", group, "devices")
        try:
            for function in os.listdir(functions):
                with open(os.path.join(functions, function, "vendor")) as f:
                    if f.read().strip() == _GOOGLE_PCI_VENDOR:
                        count += 1
                        break
        except OSError:
            continue
    return count


def one_chip_env(chip: int) -> dict:
    """Environment that makes libtpu open exactly chip ``chip`` as an
    isolated one-chip slice — one process per chip. Set in the CHILD's
    environment before it starts (libtpu reads it at backend init); each
    process gets its own controller port so co-hosted slices do not
    collide."""
    port = 8476 + chip
    return {
        "TPU_VISIBLE_CHIPS": str(chip),
        "TPU_VISIBLE_DEVICES": str(chip),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_MESH_CONTROLLER_ADDRESS": f"localhost:{port}",
        "TPU_MESH_CONTROLLER_PORT": str(port),
    }
