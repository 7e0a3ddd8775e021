"""What every builder shares: the reducer and the jitted step, put together
from the program's public pieces the way ``experiments/powersgd_*.run`` do.

The ``run`` functions themselves cannot be called: they take no hook that
stops on a clock and hand back no step (PERF.md, Open questions).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Iterator, NamedTuple

import jax


class Built(NamedTuple):
    """What a builder hands the harness."""

    step: Any  # parallel.trainer.CompiledStep
    state: Any  # TrainState, placed over the mesh
    batches: Callable[[int], Iterator[Any]]  # pass number -> host batches (global)
    pool: Any  # the sample pool (for the padding share)
    samples_per_step: int


def resolved(config: Dict, workload: Dict, rehearsal: bool) -> Dict:
    """The configuration a run uses: the config file, the cell's reducer if
    it names one, and in rehearsal the tiny sizes both files carry."""
    cfg = dict(config)
    if rehearsal:
        cfg.update(config.get("rehearsal", {}))
    if "reducer" in workload:
        cfg["reducer"] = workload["reducer"]
    cfg["per_chip_batch"] = int(workload["per_chip_batch"])
    cfg["traffic"] = dict(workload["traffic"])
    if rehearsal:
        over = dict(workload.get("rehearsal", {}))
        cfg["per_chip_batch"] = int(over.pop("per_chip_batch", cfg["per_chip_batch"]))
        cfg["traffic"].update(over)
    return cfg


def experiment_config(cfg: Dict, seed: int, n_chips: int):
    from network_distributed_pytorch_tpu.utils.config import ExperimentConfig

    return ExperimentConfig(
        seed=seed,
        learning_rate=cfg["learning_rate"],
        momentum=cfg["momentum"],
        global_batch_size=cfg["per_chip_batch"] * n_chips,
        reducer_rank=cfg["reducer"].get("rank", 0),
        reuse_query=cfg["reducer"].get("reuse_query", True),
        compute_dtype=cfg["compute_dtype"],
        orthogonalize_impl=cfg.get("orthogonalize_impl", "auto"),
        log_every=0,
    )


def make_reducer(cfg: Dict, exp_config):
    from network_distributed_pytorch_tpu.experiments.common import (
        exact_reducer_kwargs,
        powersgd_reducer_kwargs,
    )
    from network_distributed_pytorch_tpu.parallel import ExactReducer, PowerSGDReducer

    spec = cfg["reducer"]
    if spec["kind"] == "powersgd":
        return PowerSGDReducer(
            random_seed=exp_config.seed,
            compression_rank=spec["rank"],
            reuse_query=spec.get("reuse_query", True),
            matricize=spec.get("matricize", "last"),
            **powersgd_reducer_kwargs(exp_config),
        )
    if spec["kind"] == "exact":
        return ExactReducer(**exact_reducer_kwargs(exp_config))
    raise ValueError(f"unknown reducer kind {spec['kind']!r}")


def make_step(loss_fn, reducer, params, cfg: Dict, mesh):
    from network_distributed_pytorch_tpu.parallel.trainer import make_train_step

    return make_train_step(
        loss_fn, reducer, params,
        learning_rate=cfg["learning_rate"], momentum=cfg["momentum"],
        algorithm=cfg["algorithm"], mesh=mesh,
    )


def endless(batches_for_epoch: Callable[[int], Iterator[Any]]):
    """One pass of ``train_loop`` that never reaches an epoch boundary: the
    program's per-epoch generator, pass after pass, each reshuffled as the
    program reshuffles it. The window's clock ends the loop."""

    def gen(first_pass: int):
        epoch = first_pass
        while True:
            yield from batches_for_epoch(epoch)
            epoch += 1

    return gen


def init_on_device(init_fn, seed: int):
    """Weights from the seed in one jitted call on the device."""
    return jax.jit(init_fn)(jax.random.PRNGKey(seed))
