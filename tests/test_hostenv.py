"""hostenv: the pre-jax host set-up every entry point shares.

``force_cpu_devices`` — pure env-var manipulation, no jax needed — for the
four caller profiles: conftest (keep user flag), dryrun (replace),
multiprocess worker (remove), study (raise the collective-rendezvous
deadlines). ``configure_compile_cache`` — the one place a compile-cache
directory is set. ``local_tpu_chips`` / ``one_chip_env`` — what a
supervising parent may know and do about chips without opening one."""

import importlib
import os
import subprocess
import sys

from network_distributed_pytorch_tpu import hostenv

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _clean(monkeypatch):
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")


def test_sets_platform_and_count(monkeypatch):
    _clean(monkeypatch)
    import os

    hostenv.force_cpu_devices(8)
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=8" in os.environ["XLA_FLAGS"]


def test_replace_false_keeps_existing(monkeypatch):
    _clean(monkeypatch)
    import os

    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    hostenv.force_cpu_devices(8, replace=False)
    assert "count=4" in os.environ["XLA_FLAGS"]
    assert "count=8" not in os.environ["XLA_FLAGS"]
    hostenv.force_cpu_devices(8, replace=True)
    assert "count=8" in os.environ["XLA_FLAGS"]
    assert "count=4" not in os.environ["XLA_FLAGS"]


def test_none_removes_count(monkeypatch):
    _clean(monkeypatch)
    import os

    monkeypatch.setenv(
        "XLA_FLAGS", "--foo=1 --xla_force_host_platform_device_count=8 --bar=2"
    )
    hostenv.force_cpu_devices(n=None)
    assert "device_count" not in os.environ["XLA_FLAGS"]
    assert "--foo=1" in os.environ["XLA_FLAGS"]  # unrelated flags kept
    assert "--bar=2" in os.environ["XLA_FLAGS"]


def test_collective_timeout_flags(monkeypatch):
    """The timeout flags are appended only when the installed jaxlib
    registers them — an unknown name in XLA_FLAGS aborts the process at
    backend init, so on older jaxlibs suppression IS the correct output."""
    _clean(monkeypatch)
    import os

    hostenv.force_cpu_devices(8, collective_timeout_s=600)
    flags = os.environ["XLA_FLAGS"]
    supported = hostenv._xla_flag_supported(
        "xla_cpu_collective_call_warn_stuck_timeout_seconds"
    )
    assert (
        "--xla_cpu_collective_call_warn_stuck_timeout_seconds=600" in flags
    ) is supported
    assert (
        "--xla_cpu_collective_call_terminate_timeout_seconds=1200" in flags
    ) is supported


def test_collective_timeout_flags_forced_supported(monkeypatch):
    """With the probe forced true, both deadlines are appended and
    de-duplicated on re-entry."""
    _clean(monkeypatch)
    import os

    monkeypatch.setattr(hostenv, "_xla_flag_supported", lambda name: True)
    hostenv.force_cpu_devices(8, collective_timeout_s=600)
    hostenv.force_cpu_devices(8, collective_timeout_s=600)
    flags = os.environ["XLA_FLAGS"]
    assert flags.count("warn_stuck_timeout_seconds=600") == 1
    assert flags.count("terminate_timeout_seconds=1200") == 1


def test_updates_config_when_jax_imported(monkeypatch):
    _clean(monkeypatch)
    import jax  # the test suite has jax imported already

    jax.config.update("jax_platforms", "cpu")  # conftest state
    hostenv.force_cpu_devices(8)
    assert jax.config.jax_platforms == "cpu"


def test_module_importable_without_jax_side_effects():
    """The module must not import jax at MODULE scope (it runs pre-init,
    at the very top of every entry script). ``configure_compile_cache``
    imports jax inside the function, so the check is structural (AST), not
    textual: no top-level jax/jaxlib import, and importing the module in a
    fresh process must not pull jax into sys.modules."""
    import ast

    src = importlib.util.find_spec(
        "network_distributed_pytorch_tpu.hostenv"
    ).origin
    with open(src) as f:
        tree = ast.parse(f.read(), filename=src)
    for node in tree.body:  # module scope only, by design
        if isinstance(node, ast.Import):
            assert not any(
                a.name.split(".")[0] in ("jax", "jaxlib")
                for a in node.names
            ), f"module-scope jax import at line {node.lineno}"
        elif isinstance(node, ast.ImportFrom):
            assert (node.module or "").split(".")[0] not in (
                "jax", "jaxlib",
            ), f"module-scope jax import at line {node.lineno}"
    proc = subprocess.run(
        [
            sys.executable, "-c",
            "import sys; from network_distributed_pytorch_tpu import "
            "hostenv; sys.exit(1 if any(m.split('.')[0] in ('jax', "
            "'jaxlib') for m in sys.modules) else 0)",
        ],
        capture_output=True,
    )
    assert proc.returncode == 0, proc.stderr.decode()


# --- configure_compile_cache: one cache, placeable from outside -------------

_CACHE_PROBE = (
    "import os, json, jax;"
    "from network_distributed_pytorch_tpu import hostenv;"
    "before = jax.config.jax_compilation_cache_dir;"
    "got = hostenv.configure_compile_cache();"
    "floor = jax.config.jax_persistent_cache_min_compile_time_secs;"
    "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.5);"
    "again = hostenv.configure_compile_cache('/elsewhere');"
    "from jax._src import xla_bridge;"
    "print(json.dumps({'before': before, 'got': got, 'again': again,"
    " 'config': jax.config.jax_compilation_cache_dir, 'floor': floor,"
    " 'floor_again': jax.config.jax_persistent_cache_min_compile_time_secs,"
    " 'backends': len(xla_bridge._backends)}))"
)


def _cache_probe(env_extra):
    import json

    env = {k: v for k, v in os.environ.items() if k != hostenv.COMPILE_CACHE_ENV}
    env.update(env_extra, PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], env=env, cwd="/",
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_compile_cache_yields_to_the_variable(tmp_path):
    """Variable set: jax reads it itself and the helper sets no directory —
    the config holds exactly what jax took from the environment."""
    placed = str(tmp_path / "placed")
    out = _cache_probe({hostenv.COMPILE_CACHE_ENV: placed})
    assert out["before"] == placed  # jax's own read of the variable
    assert out["got"] == out["again"] == out["config"] == placed
    assert out["floor"] == 0  # every executable persisted there too
    assert out["backends"] == 0  # placing the cache initialises no backend
    assert not os.path.exists(os.path.join(REPO, ".xla_cache", "placed"))


def test_compile_cache_defaults_to_checkout_and_first_caller_wins():
    """Variable unset: ``<checkout>/.xla_cache`` — fixed, never derived from
    a temp name, pid or time — and a second call leaves it where it is,
    floor included (the test conftest raises its own after placing)."""
    out = _cache_probe({})
    assert out["before"] is None
    assert out["got"] == os.path.join(REPO, ".xla_cache")
    assert out["again"] == out["config"] == out["got"]
    assert (out["floor"], out["floor_again"]) == (0, 0.5)
    assert out["backends"] == 0


def test_only_the_helper_sets_a_compile_cache_dir():
    """``git grep jax_compilation_cache_dir``: one code site besides tests."""
    proc = subprocess.run(
        ["git", "grep", "-l", "jax_compilation_cache_dir", "--", "*.py"],
        cwd=REPO, capture_output=True, text=True,
    )
    if proc.returncode not in (0, 1):  # not a git checkout (the chip copy)
        import pytest

        pytest.skip("not a git repository")
    sites = {p for p in proc.stdout.split() if not p.startswith("tests/")}
    assert sites == {"network_distributed_pytorch_tpu/hostenv.py"}


# --- chips, counted and pinned without jax ----------------------------------


def _fake_host(tmp_path, vfio_groups=(), accel=(), vendor="0x1ae0"):
    dev, sysfs = tmp_path / "dev", tmp_path / "sys"
    (dev / "vfio").mkdir(parents=True)
    (dev / "vfio" / "vfio").touch()  # the container node is not a chip
    for n in accel:
        (dev / f"accel{n}").touch()
    for g in vfio_groups:
        (dev / "vfio" / str(g)).touch()
    for g in range(4):  # PCI shows every function, visible to us or not
        fn = sysfs / "kernel" / "iommu_groups" / str(g) / "devices" / f"0000:00:0{g}.0"
        fn.mkdir(parents=True)
        (fn / "vendor").write_text(vendor + "\n")
    return str(dev), str(sysfs)


def test_local_tpu_chips_counts_visible_vfio_groups(tmp_path):
    """v5e: the sealed one-chip machine lists four Google functions on PCI
    but exposes ONE vfio group — one chip, not four (observed on the chip,
    PR 21: /dev/vfio/3 alone; the four-chip host has /dev/vfio/0..3)."""
    assert hostenv.local_tpu_chips(*_fake_host(tmp_path / "one", [3])) == 1
    assert hostenv.local_tpu_chips(*_fake_host(tmp_path / "four", range(4))) == 4
    assert hostenv.local_tpu_chips(*_fake_host(tmp_path / "v4", accel=range(4))) == 4
    # a vfio group that holds someone else's device is not a TPU
    other = _fake_host(tmp_path / "gpu", [0, 1], vendor="0x10de")
    assert hostenv.local_tpu_chips(*other) == 0
    assert hostenv.local_tpu_chips(str(tmp_path / "absent"), str(tmp_path)) == 0


def test_one_chip_env_is_distinct_per_chip():
    a, b = hostenv.one_chip_env(0), hostenv.one_chip_env(3)
    assert a["TPU_VISIBLE_CHIPS"] == "0" and b["TPU_VISIBLE_CHIPS"] == "3"
    assert a["TPU_PROCESS_BOUNDS"] == a["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    # co-hosted one-chip slices must not share a controller port
    assert a["TPU_MESH_CONTROLLER_PORT"] != b["TPU_MESH_CONTROLLER_PORT"]
