"""Launcher parser: mpirun-style env-var defaults (the reference documents
the OMPI_COMM_WORLD_* path, ddp_guide/run_script.py:8-22); the one-process-
per-chip rules of the supervising parent (it initialises no backend, pins
N>1 rendezvous-free workers one per chip, refuses N>1 training workers on a
TPU host) — plus a slow-marked end-to-end CLI drive of an experiment
subcommand."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_var_rank_defaults(monkeypatch):
    monkeypatch.setenv("OMPI_COMM_WORLD_RANK", "3")
    monkeypatch.setenv("OMPI_COMM_WORLD_SIZE", "4")
    from network_distributed_pytorch_tpu.launch import build_parser

    args = build_parser().parse_args(["bare_init"])
    assert args.process_id == 3
    assert args.num_processes == 4
    # explicit flags still win
    args = build_parser().parse_args(["bare_init", "--process-id", "1"])
    assert args.process_id == 1


def test_config_from_args_overrides():
    from network_distributed_pytorch_tpu.launch import build_parser, config_from_args

    args = build_parser().parse_args(
        ["powersgd_cifar10", "--lr", "0.01", "--reducer-rank", "8", "--epochs", "2"]
    )
    cfg = config_from_args(args)
    assert cfg.learning_rate == 0.01
    assert cfg.reducer_rank == 8
    assert cfg.training_epochs == 2


@pytest.mark.parametrize(
    "flag,value",
    [("--comm-chunks", "4"), ("--comm-strategy", "ring"), ("--compress-impl", "pallas")],
)
def test_retired_flags_are_rejected(capsys, flag, value):
    """The knobs of the chunked collective engine and the fused compress
    kernels are gone: passing one is a usage error, never a silent no-op."""
    from network_distributed_pytorch_tpu.launch import build_parser

    with pytest.raises(SystemExit) as err:
        build_parser().parse_args(["powersgd_cifar10", flag, value])
    assert err.value.code == 2
    assert f"unrecognized arguments: {flag}" in capsys.readouterr().err


@pytest.mark.slow
def test_cli_drives_experiment_end_to_end():
    """The L5 surface the reference launches with run_script.py: ONE
    subprocess runs `python -m ...launch exact_cifar10 --preset small
    --epochs 1` on the 8-virtual-device CPU mesh (synthetic fallback data)
    and reports a finite mean loss plus the wire-byte accounting — the
    launcher -> config -> experiment -> trainer wiring end to end."""
    import re
    import subprocess
    import sys
    env = dict(os.environ)
    # launch.py defaults rank/world-size from these (the mpirun path the
    # tests above pin); inherited values would make the child rendezvous
    env.pop("OMPI_COMM_WORLD_RANK", None)
    env.pop("OMPI_COMM_WORLD_SIZE", None)
    env["JAX_PLATFORMS"] = "cpu"
    # INHERIT the harness XLA_FLAGS (conftest's hostenv already put the
    # 8-device count AND the raised collective-rendezvous deadlines in
    # os.environ — overwriting would revert the child to the default 40 s
    # terminate deadline that aborts this workload class on a 1-core
    # host); only a standalone invocation without them needs a fallback
    if "xla_force_host_platform_device_count" not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
            + " --xla_cpu_collective_call_warn_stuck_timeout_seconds=300"
            + " --xla_cpu_collective_call_terminate_timeout_seconds=600"
        ).strip()
    # share the suite's persistent compile cache: jax reads these env vars
    # at config init, so the child amortizes the 8-way shard_map compile
    # across runs like the in-process tests do
    import conftest

    cache = getattr(conftest, "_cache", None)
    if cache:
        env.setdefault("JAX_COMPILATION_CACHE_DIR", cache)
        env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
        env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    proc = subprocess.run(
        [sys.executable, "-u", "-m", "network_distributed_pytorch_tpu.launch",
         "exact_cifar10", "--preset", "small", "--epochs", "1",
         "--log-every", "0"],
        capture_output=True, text=True, timeout=540, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    m = re.search(
        r"epoch 0: mean loss ([\d.]+), ([\d.]+) MB communicated", proc.stdout
    )
    assert m, proc.stdout[-2000:]
    assert float(m.group(1)) < 10.0  # finite, sane cross-entropy
    assert float(m.group(2)) > 0.0  # bits accounting reported (SURVEY C9)


# --- one process per chip ---------------------------------------------------

_PARENT_PROBE = """
import json, sys
from network_distributed_pytorch_tpu import hostenv, launch
hostenv.local_tpu_chips = lambda: {chips}
launch.build_parser()
seen = {{}}
import network_distributed_pytorch_tpu.resilience.supervisor as sup
class FakeSupervisor:
    run_id = None
    def __init__(self, argv_for_rank, world_size, **kw):
        seen.update(world=world_size, pin_chips=kw["pin_chips"])
    def run(self):
        return sup.SupervisorResult(success=True, world_size=seen["world"],
                                    total_restarts=0, degraded=False)
sup.Supervisor = FakeSupervisor
try:
    launch.main({argv})
except SystemExit as e:
    seen["refused"] = str(e)
from jax._src import xla_bridge
seen["backends"] = len(xla_bridge._backends)
print(json.dumps(seen))
"""


def _parent(argv, chips):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", _PARENT_PROBE.format(argv=argv, chips=chips)],
        env=env, capture_output=True, text=True, timeout=120, cwd="/",
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_import_parser_and_supervising_parent_initialise_no_backend():
    """A chip belongs to one process: a parent that touched a backend would
    hold it and its worker would fail or hang. Importing ``launch``,
    building the parser and running the whole ``--supervise`` parent path
    must leave ``xla_bridge._backends`` empty."""
    seen = _parent(["serve_gpt", "--supervise", "--num-processes", "1"], chips=4)
    assert seen["backends"] == 0
    assert seen == {"world": 1, "pin_chips": False, "backends": 0}


def test_supervise_pins_rendezvous_free_workers_one_per_chip():
    seen = _parent(["serve_gpt", "--supervise", "--num-processes", "2"], chips=4)
    assert seen == {"world": 2, "pin_chips": True, "backends": 0}
    # no chips on the host (every CPU test host): nothing to pin
    seen = _parent(["serve_gpt", "--supervise", "--num-processes", "2"], chips=0)
    assert seen["pin_chips"] is False


def test_supervise_refuses_training_workers_sharing_a_tpu_host():
    """N>1 training workers would each open every chip. The refusal names
    the reason instead of letting the second worker crash on a busy chip."""
    seen = _parent(
        ["powersgd_imdb", "--supervise", "--num-processes", "2"], chips=4
    )
    assert "world" not in seen and seen["backends"] == 0
    assert "a chip belongs to one process" in seen["refused"]
    assert "--num-processes 1" in seen["refused"]
    # more workers than chips is refused too, whatever the experiment
    seen = _parent(["serve_gpt", "--supervise", "--num-processes", "8"], chips=4)
    assert "this host has 4 TPU chip(s)" in seen["refused"]
    # off-TPU the CPU rendezvous world is untouched
    seen = _parent(
        ["powersgd_imdb", "--supervise", "--num-processes", "2"], chips=0
    )
    assert seen["world"] == 2 and seen["pin_chips"] is False


def test_supervisor_exports_one_chip_env_per_rank(tmp_path):
    """``pin_chips``: worker rank r starts with the environment that makes
    libtpu open only chip r — or ``device_ranks[r]`` under a fleet lease."""
    from network_distributed_pytorch_tpu.resilience.supervisor import Supervisor

    def argv_for_rank(rank, world, incarnation):
        code = (
            "import os, json; print(json.dumps({k: os.environ.get(k) for k in"
            " ('TPU_VISIBLE_CHIPS', 'TPU_PROCESS_BOUNDS', 'RESILIENCE_RANK')}))"
        )
        return [sys.executable, "-c", code]

    for device_ranks, expected in ((None, ["0", "1"]), ([2, 3], ["2", "3"])):
        logs = tmp_path / f"logs-{expected[0]}"
        result = Supervisor(
            argv_for_rank, world_size=2, pin_chips=True,
            device_ranks=device_ranks, log_dir=str(logs),
        ).run()
        assert result.success
        for rank, chip in enumerate(expected):
            env = json.loads((logs / f"rank{rank}.0.log").read_text())
            assert env["TPU_VISIBLE_CHIPS"] == chip
            assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
            assert env["RESILIENCE_RANK"] == str(rank)
    # default: an unpinned worker's environment is left alone
    logs = tmp_path / "logs-unpinned"
    Supervisor(argv_for_rank, world_size=1, log_dir=str(logs)).run()
    env = json.loads((logs / "rank0.0.log").read_text())
    assert env["TPU_VISIBLE_CHIPS"] == os.environ.get("TPU_VISIBLE_CHIPS")

