"""Worker for the true multi-process rendezvous test (run as a subprocess).

Each of N OS processes rendezvouses via ``jax.distributed.initialize`` on
CPU (1 local device each — the reference's one-rank-per-process world,
``ddp_guide/run_script.py:4-23``), builds the global ``data`` mesh, assembles
its local batch shard into the global batch with
``multihost.global_batch_from_local``, and runs ExactReducer training steps.
Prints the per-step global losses and the first parameter element so the
parent can assert equality with a single-process run.

Usage: python multiprocess_worker.py <coordinator_port> <process_id> <num_processes>
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# must happen before jax import: 1 CPU device per process, no TPU plugin
from network_distributed_pytorch_tpu.hostenv import force_cpu_devices  # noqa: E402

force_cpu_devices(n=None)

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from network_distributed_pytorch_tpu.data.multihost import (  # noqa: E402
    global_batch_from_local,
    global_state_from_host,
)
from network_distributed_pytorch_tpu.parallel import (  # noqa: E402
    ExactReducer,
    PowerSGDReducer,
)
from network_distributed_pytorch_tpu.parallel.mesh import (  # noqa: E402
    DistributedConfig,
    initialize_distributed,
    make_mesh,
    shutdown_distributed,
)
from network_distributed_pytorch_tpu.parallel.trainer import (  # noqa: E402
    TrainState,
    make_train_step,
    stateless_loss,
)


def main() -> int:
    port, pid, nproc = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
    initialize_distributed(
        DistributedConfig(
            num_processes=nproc,
            process_id=pid,
            coordinator_address=f"localhost:{port}",
            timeout_seconds=60,
        )
    )
    assert jax.process_count() == nproc, jax.process_count()
    assert len(jax.local_devices()) == 1
    assert jax.device_count() == nproc
    mesh = make_mesh()

    # deterministic toy regression, same on every process (shared seed — the
    # reference's DataPartitioner seed-1234 convention)
    rng = np.random.RandomState(1234)
    w_true = rng.randn(16, 4).astype(np.float32)
    x = rng.randn(8 * nproc, 16).astype(np.float32)
    y = x @ w_true
    params = {"w": np.zeros((16, 4), np.float32), "b": np.zeros((4,), np.float32)}

    def loss(p, batch):
        xb, yb = batch
        import jax.numpy as jnp

        return jnp.mean((xb @ p["w"] + p["b"] - yb) ** 2)

    # THIS process's shard of the batch (rank-partitioned, like
    # DataPartitioner.use(rank))
    lo, hi = 8 * pid, 8 * (pid + 1)
    batch = global_batch_from_local((x[lo:hi], y[lo:hi]), mesh)

    results = {}
    for name, reducer, algo in (
        ("exact", ExactReducer(), "sgd"),
        # the flagship compressed path: EF chain + warm-start Q across
        # REAL process boundaries
        ("powersgd", PowerSGDReducer(
            random_seed=1234, compression_rank=2, matricize="last"
        ), "ef_momentum"),
    ):
        step = make_train_step(
            stateless_loss(loss), reducer, params, learning_rate=0.05,
            momentum=0.9, algorithm=algo, mesh=mesh, donate_state=False,
        )
        state = step.init_state(params)
        state = global_state_from_host(
            state,
            TrainState(
                params=P(), momenta=P(), memories=P("data"),
                reducer_state=P(), model_state=P("data"),
            ),
            mesh,
        )
        losses = []
        for _ in range(3):
            state, l = step(state, batch)
            losses.append(float(l))
        w0 = float(np.asarray(jax.device_get(state.params["w"]))[0, 0])
        results[name] = (losses, w0)

    # DiLoCo round across REAL process boundaries: per-worker inner state,
    # PowerSGD-compressed outer deltas, one compiled shard_map round
    from network_distributed_pytorch_tpu.parallel import make_diloco_train_fn
    from network_distributed_pytorch_tpu.parallel.localsgd import DiLoCoState

    diloco = make_diloco_train_fn(
        stateless_loss(loss), params, inner_learning_rate=0.05,
        sync_every=2, inner_algorithm="sgd_plain", mesh=mesh,
        donate_state=False,
        reducer=PowerSGDReducer(
            random_seed=1234, compression_rank=2, matricize="last"
        ),
    )
    dstate = global_state_from_host(
        diloco.init_state(params),
        DiLoCoState(
            params=P(), outer_momenta=P(), inner_opt=P("data"),
            memories=P("data"), reducer_state=P(), model_state=P("data"),
        ),
        mesh,
    )
    # two DISTINCT inner-step batches (reversed rows for step 2) so the
    # sync_every scan is falsifiable — identical steps would mask a batch-
    # threading regression
    stacked = tuple(
        np.stack([a, a[::-1]]) for a in (x, y)
    )
    dbatches = global_state_from_host(
        stacked, (P(None, "data"), P(None, "data")), mesh
    )
    dlosses = []
    for _ in range(2):
        dstate, dl = diloco(dstate, dbatches)
        dlosses.extend(float(v) for v in np.asarray(jax.device_get(dl)))
    dw0 = float(np.asarray(jax.device_get(dstate.params["w"]))[0, 0])
    results["diloco"] = (dlosses, dw0)

    for name, (losses, w0) in results.items():
        print(
            f"RESULT kind={name} pid={pid} "
            f"losses={','.join(f'{v:.8f}' for v in losses)} w00={w0:.8f}",
            flush=True,
        )
    shutdown_distributed()
    return 0


if __name__ == "__main__":
    sys.exit(main())
