"""``chip_smoke.py`` off the chip: it must refuse, quickly, and print no
result — nothing in it or under it may turn a missing TPU into a CPU run.
(Its passing run is the chip's job: ``python chip_smoke.py`` there.)"""

import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, script], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=120,
    )


def test_chip_smoke_refuses_the_cpu():
    proc = _run(REPO, SMOKE)
    assert proc.returncode != 0
    assert proc.stdout == ""  # no result line of any kind
    assert "no TPU" in proc.stderr and "'cpu'" in proc.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """The script without the program proves nothing and must say so: a
    directory that holds ``chip_smoke.py`` and nothing else of the repo."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc = _run(str(tmp_path), str(tmp_path / "chip_smoke.py"))
    assert proc.returncode != 0 and proc.stdout == ""


def test_chip_smoke_never_chooses_a_platform():
    with open(SMOKE) as f:
        source = f.read()
    assert "jax_platforms" not in source
    assert 'environ["JAX_PLATFORMS"]' not in source
    assert "force_cpu_devices" not in source


def test_verdict_line_has_exactly_the_contract_keys():
    """The driver reads the last line of stdout: ``ok`` and ``device``
    (``platform``, ``kind``, ``count``) and no other key. The detail goes on
    the ``{"smoke": ...}`` line before it."""
    import importlib.util
    import json

    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    line = module.verdict_line("tpu", "TPU v5 lite", 1)
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    with open(SMOKE) as f:
        source = f.read()
    # the verdict is the last thing main() writes
    assert source.rindex("verdict_line(") > source.rindex('{"smoke": result}')
