"""Jax worker for supervisor kill-and-resume tests (run as a subprocess).

One rank of a real training run: SmallCNN + PowerSGD ef_momentum through
``resilient_train_loop`` with committed checkpoints, a heartbeat file, a
JSONL event log, and an optional chaos plan. On completion writes a result
JSON holding sha256 digests of the final params and EF memories, so the
parent can assert a killed-and-resumed run is bit-identical to an
uninterrupted one.

Usage::

    python supervised_worker.py --rank R --world W --epochs N \
        --ckpt-dir D --result F [--heartbeat-dir D] [--chaos-plan F] \
        [--event-log F] [--step-retries K] [--guard-batches]
"""

import argparse
import hashlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# must happen before jax import: CPU backend, no TPU plugin
from network_distributed_pytorch_tpu.hostenv import force_cpu_devices  # noqa: E402

force_cpu_devices(n=1)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from network_distributed_pytorch_tpu.experiments.common import (  # noqa: E402
    resilient_train_loop,
)
from network_distributed_pytorch_tpu.models import SmallCNN  # noqa: E402
from network_distributed_pytorch_tpu.observe import (  # noqa: E402
    telemetry_for_run,
)
from network_distributed_pytorch_tpu.parallel import (  # noqa: E402
    PowerSGDReducer,
    make_mesh,
)
from network_distributed_pytorch_tpu.parallel.trainer import (  # noqa: E402
    make_train_step,
    stateless_loss,
)
from network_distributed_pytorch_tpu.resilience import (  # noqa: E402
    ChaosPlan,
    incarnation_from_env,
)
from network_distributed_pytorch_tpu.utils import (  # noqa: E402
    cross_entropy_loss,
)
from network_distributed_pytorch_tpu.utils.failure import (  # noqa: E402
    HeartbeatMonitor,
)

IMG = (8, 8, 3)


def _setup():
    model = SmallCNN(width=4)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, *IMG)))["params"]

    def lf(p, b):
        x, y = b
        return cross_entropy_loss(model.apply({"params": p}, x), y)

    mesh = make_mesh()
    step = make_train_step(
        stateless_loss(lf),
        PowerSGDReducer(random_seed=7, compression_rank=2, matricize="last"),
        params, learning_rate=0.05, momentum=0.9, algorithm="ef_momentum",
        mesh=mesh, donate_state=False,
    )
    return step, params


def _batches(epoch, steps=4):
    rng = np.random.RandomState(1000 + epoch)
    means = np.random.RandomState(999).randn(10, *IMG)
    for _ in range(steps):
        y = rng.randint(0, 10, 32)
        x = means[y] + 0.5 * rng.randn(32, *IMG)
        yield jnp.asarray(x, jnp.float32), jnp.asarray(y)


def _digest(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(tree):
        h.update(np.ascontiguousarray(jax.device_get(leaf)).tobytes())
    return h.hexdigest()


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--rank", type=int, default=0)
    p.add_argument("--world", type=int, default=1)
    p.add_argument("--epochs", type=int, default=4)
    p.add_argument("--ckpt-dir", required=True)
    p.add_argument("--result", required=True)
    p.add_argument("--heartbeat-dir", default=None)
    p.add_argument("--chaos-plan", default=None)
    p.add_argument("--event-log", default=None)
    p.add_argument("--step-retries", type=int, default=0)
    p.add_argument("--guard-batches", action="store_true")
    args = p.parse_args()

    incarnation = incarnation_from_env()
    plan = ChaosPlan.load(args.chaos_plan) if args.chaos_plan else None
    telemetry = telemetry_for_run(event_log=args.event_log)
    hb = (
        HeartbeatMonitor(
            args.heartbeat_dir, process_id=args.rank,
            num_processes=args.world, incarnation=incarnation,
        )
        if args.heartbeat_dir
        else None
    )

    step, params = _setup()
    state, _, start_epoch = resilient_train_loop(
        step, step.init_state(params), _batches, args.epochs,
        checkpoint_dir=args.ckpt_dir, rank=args.rank,
        heartbeat=hb, telemetry=telemetry, run_name="supervised",
        chaos_plan=plan, incarnation=incarnation,
        step_retries=args.step_retries, guard_batches=args.guard_batches,
        expected_batch=32 if args.guard_batches else None,
    )
    telemetry.close()

    with open(args.result, "w") as f:
        json.dump(
            {
                "rank": args.rank,
                "incarnation": incarnation,
                "start_epoch": start_epoch,
                "params_digest": _digest(state.params),
                "memories_digest": _digest(state.memories),
            },
            f,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
