"""Every cell's whole step, at its real size, compiled for v5e chips on the
CPU host by the installed libtpu (no chip, no chip time): what the chip's
compiler would refuse is refused here, and ``memory_analysis()`` says what
each cell's batch costs in device memory. One file, the topology in a module
fixture (only one process may load libtpu; see the on-chip-measurement guide).
"""

import json
import os

import jax
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from benchmark import cells, compose

FLOOR_BYTES = 0.25 * 16.9e9  # a cell under a quarter of a chip's memory is refused
LIMIT_BYTES = 16.9e9  # bytes_limit of one v5e chip as its allocator reports it


@pytest.fixture(scope="module")
def v5e_devices():
    from jax.experimental import topologies

    try:
        topology = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {type(e).__name__}: {e}")
    # a CPU host can write an AOT TPU executable to the cache but not read it back
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
    yield list(topology.devices)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)


def compile_cell(name, devices, per_chip_batch=None):
    from network_distributed_pytorch_tpu.parallel import make_mesh

    cell = cells.cell(name)
    mesh = make_mesh(devices=devices[: cell["entry"]["chips"]])
    cfg = compose.resolved(cell["config"], cell["workload"], rehearsal=False)
    if per_chip_batch:
        cfg["per_chip_batch"] = per_chip_batch
    step, state, batch = cells.module("builders", cell["config"]["builder"]).abstract(cfg, 0, mesh)
    # one sharding per TrainState field, a prefix of the state's tree
    state = type(state)(*[
        jax.tree_util.tree_map(lambda x, s=s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), field)
        for field, s in zip(state, step.state_shardings)
    ])
    data = NamedSharding(mesh, PartitionSpec("data"))
    batch = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=data), batch
    )
    compiled = step.fn.lower(state, batch).compile()
    return cell, step, compiled


@pytest.mark.parametrize("name", [w["name"] for w in cells.manifest()["workloads"]])
def test_cell_compiles_for_v5e_and_fits(v5e_devices, monkeypatch, name, capsys):
    from network_distributed_pytorch_tpu.utils.hlo_audit import hlo_text_of_compiled

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # "auto" resolves as on the chip
    batch = int(os.environ.get("BENCHMARK_AOT_BATCH", "0")) or None
    cell, step, compiled = compile_cell(name, v5e_devices, batch)
    memory = compiled.memory_analysis()
    program = (
        memory.argument_size_in_bytes + memory.temp_size_in_bytes
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
        + memory.generated_code_size_in_bytes
    )
    with capsys.disabled():
        print(f"\n{name}: per chip {json.dumps({'program_bytes': program, 'temp': memory.temp_size_in_bytes, 'arguments': memory.argument_size_in_bytes, 'code': memory.generated_code_size_in_bytes})}")
    hlo = hlo_text_of_compiled(compiled)
    assert "tpu_custom_call" in hlo  # the Pallas kernels are in the program
    assert FLOOR_BYTES <= program <= LIMIT_BYTES, program
    if cell["entry"]["chips"] > 1:
        audit = step.ledger.reconcile(hlo)
        assert audit["exact"] and audit["hlo_collective_count"] > 0, audit
