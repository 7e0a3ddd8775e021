"""L5 — launcher CLI.

Mirrors the reference's per-rank ``run_script.py`` launchers: ``-rank``
(``ddp_guide/run_script.py:27-28``), ``-world_size`` / ``-init_method``
(``ddp_powersgd_distillBERT_IMDb/run_script.py:27-31``), which mutate the
config and call the experiment lifecycle. One launcher serves every
experiment (the reference copies the script four times); the ``cuda_rnak``
typo and hard-coded lab IPs are not reproduced (SURVEY §7).

Usage::

    python -m network_distributed_pytorch_tpu.launch powersgd_cifar10 \
        --process-id 0 --num-processes 1 --preset small --epochs 1
"""

from __future__ import annotations

import argparse
import sys

from . import hostenv
from .experiments import (
    bandwidth_study,
    bare_init,
    diloco_cifar10,
    exact_cifar10,
    gpt_generate,
    gpt_lm,
    gpt_moe,
    gpt_pp,
    gpt_sp,
    gpt_tp,
    imdb_baseline,
    powersgd_afmoe,
    powersgd_cifar10,
    powersgd_imdb,
    powersgd_lfm2,
    powersgd_mellum,
    powersgd_nemotron,
    powersgd_phi4flash,
    powersgd_qwen3_next,
    powersgd_sdar,
    serve_gpt,
)
from .experiments.lm import LM_EXPERIMENTS
from .observe import RawEvent, StreamJsonSink, Telemetry
from .parallel.mesh import DistributedConfig, initialize_distributed
from .utils.config import ExperimentConfig

EXPERIMENTS = {
    "bare_init": bare_init.run,
    "exact_cifar10": exact_cifar10.run,
    "diloco_cifar10": diloco_cifar10.run,
    "powersgd_cifar10": powersgd_cifar10.run,
    "powersgd_imdb": powersgd_imdb.run,
    "powersgd_nemotron": powersgd_nemotron.run,
    "powersgd_afmoe": powersgd_afmoe.run,
    "powersgd_qwen3_next": powersgd_qwen3_next.run,
    "powersgd_lfm2": powersgd_lfm2.run,
    "powersgd_mellum": powersgd_mellum.run,
    "powersgd_phi4flash": powersgd_phi4flash.run,
    "powersgd_sdar": powersgd_sdar.run,
    "imdb_baseline": imdb_baseline.run,
    "bandwidth_study": bandwidth_study.run,
    "gpt_lm": gpt_lm.run,
    "gpt_pp": gpt_pp.run,
    "gpt_sp": gpt_sp.run,
    "gpt_tp": gpt_tp.run,
    "gpt_moe": gpt_moe.run,
    "gpt_generate": gpt_generate.run,
    "serve_gpt": serve_gpt.run,
}

# experiments whose ranks share no collective (serve_gpt ranks share only the
# file spool): no jax.distributed rendezvous, and the only ones a supervisor
# may run as several one-chip workers on one TPU host
RENDEZVOUS_FREE = ("bare_init", "serve_gpt")


def build_parser() -> argparse.ArgumentParser:
    import os

    # mpirun-style launch (the reference documents the same env-var path,
    # ``ddp_guide/run_script.py:8-22``): OMPI_COMM_WORLD_RANK/SIZE become the
    # flag defaults, so `mpirun -np N python -m ...launch exp` just works.
    env_rank = int(os.environ.get("OMPI_COMM_WORLD_RANK", 0))
    env_size = int(os.environ.get("OMPI_COMM_WORLD_SIZE", 1))

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("experiment", choices=sorted(EXPERIMENTS))
    # the reference's -rank / -world_size / -init_method flags
    p.add_argument("--process-id", type=int, default=env_rank, help="rank of this host process")
    p.add_argument("--num-processes", type=int, default=env_size, help="world size (host processes)")
    p.add_argument("--coordinator", type=str, default=None, help="host:port rendezvous")
    p.add_argument("--seed", type=int, default=714)
    p.add_argument("--epochs", type=int, default=None)
    p.add_argument("--global-batch", type=int, default=None)
    p.add_argument("--lr", type=float, default=None)
    p.add_argument("--momentum", type=float, default=None)
    p.add_argument("--reducer-rank", type=int, default=None)
    p.add_argument(
        "--accum-steps", type=int, default=None,
        help="gradient-accumulation microbatches per step"
             " (cifar and imdb experiments)",
    )
    p.add_argument(
        "--max-grad-norm", type=float, default=None,
        help="clip the reduced update to this global norm"
             " (cifar/imdb experiments)",
    )
    p.add_argument(
        "--bucket-bytes", type=int, default=None,
        help="bucketed backward overlap (cifar exact-DDP experiments): pack"
             " gradients into ~B-byte buckets in backward production order,"
             " one fenced collective each, so early buckets' wire time"
             " overlaps the rest of the backward (DESIGN.md: raw speed)",
    )
    p.add_argument(
        "--orthogonalize-impl", choices=["auto", "xla", "pallas"],
        default=None,
        help="PowerSGD Gram-Schmidt engine ('auto': the Pallas VMEM kernel"
             " on TPU, the XLA fori_loop elsewhere)",
    )
    p.add_argument(
        "--attn-impl", choices=["auto", "einsum", "flash"], default=None,
        help="attention engine override for the transformer experiments"
             " ('auto': flash on TPU, einsum elsewhere; unset = each"
             " model's own default, which is also 'auto')",
    )
    p.add_argument(
        "--remat", action="store_true",
        help="rematerialize transformer blocks in the backward pass"
             " (gpt_lm, powersgd_imdb)",
    )
    p.add_argument(
        "--scan-layers", action="store_true",
        help="gpt_lm only: run decoder blocks as one lax.scan with stacked"
             " params — ~n_layers× smaller HLO and compile time, same math",
    )
    p.add_argument(
        "--health-every", type=int, default=None,
        help="emit a TrainHealthEvent (grad norm, EF memory norm, PowerSGD"
             " relative compression error) every N steps via the separately"
             " jitted health probe — the live plane's NaN-precursor feed"
             " (cifar experiments; 0/unset = never, zero overhead)",
    )
    p.add_argument("--preset", choices=["small", "full"], default="small")
    p.add_argument("--data-dir", type=str, default="./data")
    p.add_argument("--dtype", choices=["float32", "bfloat16"], default="float32")
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--max-steps-per-epoch", type=int, default=None)
    p.add_argument(
        "--strategy", choices=["ddp", "fsdp"], default="ddp",
        help="exact_cifar10 only: replicated DDP or ZeRO-3 fully-sharded",
    )
    p.add_argument(
        "--data-shards", type=int, default=1,
        help="gpt_pp only: compose data parallelism over the pipeline "
             "(mesh ('data','pipe'))",
    )
    p.add_argument(
        "--pp-reducer", choices=["exact", "powersgd"], default="exact",
        help="gpt_pp only: cross-shard gradient reduction when "
             "--data-shards > 1",
    )
    p.add_argument(
        "--model-shards", type=int, default=4,
        help="gpt_tp only: tensor-parallel shards (mesh ('data','model'))",
    )
    p.add_argument(
        "--tp-reducer", choices=["exact", "powersgd"], default="exact",
        help="gpt_tp only: data-axis gradient reduction when devices >"
             " --model-shards",
    )
    p.add_argument(
        "--sync-every", type=int, default=8,
        help="diloco_cifar10 only: local steps per outer sync round",
    )
    p.add_argument(
        "--fragments", type=int, default=1,
        help="diloco_cifar10 only: >1 switches to streaming DiLoCo"
             " (round-robin fragment sync)",
    )
    p.add_argument(
        "--diloco-reducer", choices=["exact", "powersgd"], default="exact",
        help="diloco_cifar10 only: compression of the outer parameter delta",
    )
    p.add_argument(
        "--experts-per-device", type=int, default=1,
        help="gpt_moe only: local experts per device (total = devices x this)",
    )
    p.add_argument(
        "--moe-reducer", choices=["exact", "powersgd"], default="exact",
        help="gpt_moe only: reduction for the replicated (non-expert) params",
    )
    p.add_argument(
        "--moe-top-k", type=int, default=1,
        help="gpt_moe only: experts per token (1=Switch, 2=GShard-style)",
    )
    p.add_argument(
        "--vocab-parallel", action="store_true",
        help="gpt_tp only: shard the tied token table over vocab rows and"
             " compute the CE without materializing full-vocab logits",
    )
    p.add_argument(
        "--checkpoint-dir", type=str, default=None,
        help="gpt_pp/gpt_sp: save the carry per epoch and resume the newest;"
             " exact_cifar10 (ddp): run through resilient_train_loop —"
             " committed per-epoch checkpoints, verified resume, and the"
             " --chaos-plan injection point; serve_gpt: hot-load model"
             " params from the newest committed training checkpoint",
    )
    p.add_argument(
        "--max-new-tokens", type=int, default=64,
        help="gpt_generate: decode length; serve_gpt: per-request decode"
             " budget cap (uniform in [2, this])",
    )
    p.add_argument(
        "--temperature", type=float, default=0.0,
        help="gpt_generate only: 0 = greedy",
    )
    # --- serve_gpt (serving/ continuous-batching engine) ------------------
    p.add_argument(
        "--slots", type=int, default=None,
        help="serve_gpt only: static batch slots of the continuous-batching"
             " engine (default 4)",
    )
    p.add_argument(
        "--requests", type=int, default=None,
        help="serve_gpt only: simulated requests in the Poisson workload"
             " (default 16)",
    )
    p.add_argument(
        "--request-rate", type=float, default=None,
        help="serve_gpt only: Poisson arrival rate in requests/s"
             " (default 64)",
    )
    p.add_argument(
        "--spool-dir", type=str, default=None,
        help="serve_gpt only: shared file-spool request queue — the elastic"
             " fleet mode; combine with --supervise --num-processes N for"
             " mid-decode fail-over (dead ranks' in-flight requests are"
             " re-queued on the survivors)",
    )
    p.add_argument(
        "--engine", type=str, default=None, choices=("slot", "paged"),
        help="serve_gpt only: KV cache engine — 'slot' (dense per-slot"
             " cache) or 'paged' (block-pool cache with copy-on-write"
             " prefix sharing; default slot)",
    )
    p.add_argument(
        "--block-len", type=int, default=None,
        help="serve_gpt only (--engine paged): tokens per KV block"
             " (default 16)",
    )
    p.add_argument(
        "--n-blocks", type=int, default=None,
        help="serve_gpt only (--engine paged): KV pool size in blocks"
             " (default: dense-equivalent bytes, slots * max_len/block_len"
             " + 1)",
    )
    p.add_argument(
        "--no-prefix-sharing", action="store_true",
        help="serve_gpt only (--engine paged): disable copy-on-write"
             " prompt-prefix sharing",
    )
    p.add_argument(
        "--spec-k", type=int, default=None,
        help="serve_gpt only (--engine paged): speculative decoding window"
             " — draft proposes K-1 tokens, target verifies all K in one"
             " batched step (default off)",
    )
    p.add_argument("--json", action="store_true", help="print the summary as JSON")
    p.add_argument(
        "--chaos-plan", type=str, default=None,
        help="JSON fault schedule (resilience.chaos.ChaosPlan) injected into"
             " experiments that run through resilient_train_loop; forwarded"
             " to workers under --supervise",
    )
    p.add_argument(
        "--adaptive-comm", action="store_true",
        help="exact_cifar10 (ddp) only: degraded-fabric survival — collective"
             " deadline watchdogs around every fenced collective plus the"
             " closed-loop reducer fallback ladder (resilience.controller);"
             " --chaos-plan then drives comm-layer faults in-process, no"
             " checkpoint_dir needed",
    )
    p.add_argument(
        "--comm-fabric", type=str, default=None,
        choices=("1GbE", "10GbE", "100GbE", "ICI(v5e)"),
        help="--adaptive-comm: fabric whose modeled line rate"
             " (utils.bandwidth.FABRICS_BYTES_PER_S) budgets the collective"
             " deadlines (default ICI(v5e)); --plan: the fabric whose"
             " tuned best pick is applied",
    )
    p.add_argument(
        "--plan", type=str, default=None,
        help="tuned per-fabric plan file from scripts/plan.py (the offline"
             " what-if cost model): apply its predicted-best comm knobs for"
             " --comm-fabric (explicit CLI knobs still win), and under"
             " --adaptive-comm reorder the fallback ladder predicted-best-"
             "first (cifar experiments)",
    )
    # --- supervised elastic launch (resilience.supervisor) ---------------
    # these flags configure the PARENT only and are stripped from the
    # worker command lines (_SUPERVISOR_FLAGS below)
    p.add_argument(
        "--supervise", action="store_true",
        help="run as the supervising parent: spawn --num-processes copies of"
             " this command (one per rank), restart crashed/hung ranks with"
             " bounded backoff, degrade to a shrunk world when a rank is"
             " permanently gone",
    )
    p.add_argument(
        "--max-restarts", type=int, default=3,
        help="supervise: restarts per rank before it is declared dead",
    )
    p.add_argument(
        "--restart-backoff", type=float, default=0.25,
        help="supervise: base seconds of the bounded exponential backoff",
    )
    p.add_argument(
        "--heartbeat-dir", type=str, default=None,
        help="supervise: shared heartbeat directory for hang detection"
             " (workers must beat it, e.g. via resilient_train_loop)",
    )
    p.add_argument(
        "--heartbeat-timeout", type=float, default=None,
        help="supervise: seconds without a beat before a rank is killed"
             " and restarted",
    )
    p.add_argument(
        "--term-grace", type=float, default=5.0,
        help="supervise: seconds between SIGTERM and SIGKILL on every"
             " supervisor-initiated kill — the window a worker's"
             " PreemptionGuard has to commit an emergency checkpoint",
    )
    p.add_argument(
        "--min-world-size", type=int, default=1,
        help="supervise: smallest world a degraded restart may shrink to"
             " (the quorum planner's --min-world floor)",
    )
    p.add_argument(
        "--mesh-shape", type=str, default=None,
        help="supervise: the world's mesh shape as DATAxFSDPxTENSOR (e.g."
             " 2x1x2; product must equal --num-processes). Degraded"
             " restarts then go through the quorum planner — trade TP"
             " degree for DP first — instead of only shrinking the data"
             " axis; workers read the shape from RESILIENCE_MESH",
    )
    p.add_argument(
        "--correlation-window", type=float, default=2.0,
        help="supervise: hard deaths of >= 2 distinct ranks within this"
             " many seconds are classified as one correlated incident"
             " (zone outage) and replanned as a whole",
    )
    p.add_argument(
        "--no-degraded", action="store_true",
        help="supervise: declare the run dead instead of shrinking the"
             " world when a rank exhausts its restarts",
    )
    p.add_argument(
        "--worker-log-dir", type=str, default=None,
        help="supervise: per-rank-per-incarnation worker stdout logs",
    )
    p.add_argument(
        "--metrics-port", type=int, default=None,
        help="supervise + --run-dir: serve the live telemetry plane's"
             " Prometheus-text /metrics endpoint on this port (0 ="
             " ephemeral; the bound port is advertised in"
             " <run-dir>/metrics_port). Unset = live plane off",
    )
    p.add_argument(
        "--alert-restart-after", type=int, default=0,
        help="supervise live plane: restart a rank after this many"
             " sustained CRITICAL alerts attributed to it (the NaN-"
             "precursor path; restarts spend the ordinary restart budget;"
             " 0 = log-only)",
    )
    p.add_argument(
        "--event-log", type=str, default=None,
        help="append structured JSONL telemetry (steps, wire ledger, compile"
             " audits) to this path; read it back with scripts/report.py",
    )
    p.add_argument(
        "--run-dir", type=str, default=None,
        help="run-level observability directory: the supervising parent"
             " writes the run manifest (observe.runlog) and its own event"
             " shard there, each worker appends events_rank<R>.jsonl; merge"
             " with scripts/report.py --run-dir (use a FRESH directory per"
             " run)",
    )
    p.add_argument(
        "--trace-dir", type=str, default=None,
        help="capture a jax.profiler trace of the run under this directory",
    )
    p.add_argument(
        "--audit-wire", action="store_true", default=None,
        help="force the compile-time analytic-vs-HLO wire audit (default:"
             " on whenever --event-log is set)",
    )
    return p


def apply_plan(cfg: ExperimentConfig, args) -> None:
    """Apply a scripts/plan.py plan file's predicted-best comm knobs for
    the launch fabric onto ``cfg``. Explicit CLI knobs win over the plan;
    the plan wins over the dataclass defaults. A plan naming a different
    reducer family than the launched experiment only warns — the
    experiment choice stays the user's (under --adaptive-comm the
    reordered fallback ladder can still walk to the compressed rung)."""
    import json

    from .observe import costmodel

    with open(args.plan, "r", encoding="utf-8") as fh:
        plan = json.load(fh)
    fabric = args.comm_fabric or cfg.comm_fabric
    slot = (plan.get("fabrics") or {}).get(fabric)
    if not isinstance(slot, dict):
        sys.stderr.write(
            f"# launch: plan {args.plan} has no fabric {fabric!r};"
            " knobs unchanged\n"
        )
        cfg.plan_path = args.plan
        return
    best = costmodel.canonical_config((slot.get("best") or {}).get("config"))
    if args.bucket_bytes is None and best["bucket_bytes"]:
        cfg.bucket_bytes = best["bucket_bytes"]
    if args.reducer_rank is None and best["reducer_rank"]:
        cfg.reducer_rank = best["reducer_rank"]
    plan_reducer = best["reducer"]
    exp_reducer = (
        "powersgd" if "powersgd" in args.experiment else "exact"
    )
    if plan_reducer != exp_reducer:
        sys.stderr.write(
            f"# launch: plan's best pick for {fabric} uses the"
            f" {plan_reducer!r} reducer but {args.experiment!r} runs"
            f" {exp_reducer!r} — comm knobs applied, reducer unchanged\n"
        )
    cfg.plan_path = args.plan


def config_from_args(args) -> ExperimentConfig:
    cfg = ExperimentConfig(
        seed=args.seed,
        process_id=args.process_id,
        num_processes=args.num_processes,
        coordinator_address=args.coordinator,
        compute_dtype=args.dtype,
        log_every=args.log_every,
    )
    if args.epochs is not None:
        cfg.training_epochs = args.epochs
    if args.global_batch is not None:
        cfg.global_batch_size = args.global_batch
    if args.lr is not None:
        cfg.learning_rate = args.lr
    if args.momentum is not None:
        cfg.momentum = args.momentum
    if args.reducer_rank is not None:
        cfg.reducer_rank = args.reducer_rank
    if args.accum_steps is not None:
        cfg.accum_steps = args.accum_steps
    if args.max_grad_norm is not None:
        cfg.max_grad_norm = args.max_grad_norm
    if args.bucket_bytes is not None:
        cfg.bucket_bytes = args.bucket_bytes
    if args.orthogonalize_impl is not None:
        cfg.orthogonalize_impl = args.orthogonalize_impl
    if args.attn_impl is not None:
        cfg.attn_impl = args.attn_impl
    cfg.event_log = args.event_log
    cfg.trace_dir = args.trace_dir
    cfg.audit_wire = args.audit_wire
    cfg.chaos_plan = args.chaos_plan
    cfg.adaptive_comm = args.adaptive_comm
    if args.comm_fabric is not None:
        cfg.comm_fabric = args.comm_fabric
    if args.health_every is not None:
        cfg.health_every = args.health_every
    return cfg


# supervisor-parent-only flags, stripped from worker command lines
# (value-taking unless marked boolean)
_SUPERVISOR_FLAGS = {
    "--supervise": False,
    "--max-restarts": True,
    "--restart-backoff": True,
    "--heartbeat-timeout": True,
    "--term-grace": True,
    "--min-world-size": True,
    "--mesh-shape": True,
    "--correlation-window": True,
    "--no-degraded": False,
    "--worker-log-dir": True,
    "--metrics-port": True,
    "--alert-restart-after": True,
    # re-appended per worker with the supervisor's own numbering
    "--process-id": True,
    "--num-processes": True,
}


def parse_mesh_shape(spec: str) -> dict:
    """``DATAxFSDPxTENSOR`` (or the two-axis shorthand ``DATAxTENSOR``)
    into a mesh-axes dict for :class:`SupervisorConfig`."""
    try:
        degrees = [int(p) for p in spec.lower().replace("×", "x").split("x")]
    except ValueError:
        degrees = []
    if len(degrees) == 2:
        data, fsdp, tensor = degrees[0], 1, degrees[1]
    elif len(degrees) == 3:
        data, fsdp, tensor = degrees
    else:
        raise ValueError(
            f"--mesh-shape must look like DATAxFSDPxTENSOR (e.g. 2x1x2) or"
            f" DATAxTENSOR (e.g. 2x2), got {spec!r}"
        )
    return {"data": data, "fsdp": fsdp, "tensor": tensor}


def worker_argv_base(argv) -> list:
    """The launch argv with supervisor-only flags (and any explicit rank/
    world-size) removed — what every worker command line starts from."""
    out, skip = [], False
    for a in argv:
        if skip:
            skip = False
            continue
        flag = a.split("=", 1)[0]
        if flag in _SUPERVISOR_FLAGS:
            skip = _SUPERVISOR_FLAGS[flag] and "=" not in a
            continue
        out.append(a)
    return out


def _supervise(args, argv) -> dict:
    """Run as the supervising parent: every worker is this same CLI with
    ``--process-id``/``--num-processes`` rewritten per (rank, world)."""
    import os

    from .observe import MarkerEvent, telemetry_for_run
    from .observe import runlog as _runlog
    from .resilience.supervisor import Supervisor, SupervisorConfig

    base = worker_argv_base(argv)

    # one process per chip: N>1 workers on a TPU host would each open every
    # chip (make_mesh() takes all visible devices) and all but the first
    # would die or hang on a busy chip. The parent counts chips from their
    # device nodes — it must never initialise a backend itself — and pins
    # each worker to
    # its own chip, which only rendezvous-free experiments can use: a
    # training job drives all chips of a host from ONE process.
    chips = hostenv.local_tpu_chips() if args.num_processes > 1 else 0
    if chips and args.experiment not in RENDEZVOUS_FREE:
        raise SystemExit(
            f"launch: refusing --supervise --num-processes"
            f" {args.num_processes} for {args.experiment!r} on a host with"
            f" {chips} TPU chip(s): a chip belongs to one process, and one"
            " process already shards a training step over every local chip."
            " Use --num-processes 1 here (N>1 is for one process per HOST,"
            f" or for {', '.join(RENDEZVOUS_FREE)} workers pinned one per"
            " chip)."
        )
    if chips and args.num_processes > chips:
        raise SystemExit(
            f"launch: refusing --num-processes {args.num_processes}: this"
            f" host has {chips} TPU chip(s) and each worker needs its own"
        )

    def argv_for_rank(rank: int, world: int, incarnation: int) -> list:
        return [
            sys.executable, "-m", "network_distributed_pytorch_tpu.launch",
            *base, "--process-id", str(rank), "--num-processes", str(world),
        ]

    # with a run dir, the parent's own events land in the conventional
    # supervisor shard so the merged timeline includes the failure domain
    event_log = args.event_log
    if args.run_dir and not event_log:
        event_log = os.path.join(args.run_dir, _runlog.SUPERVISOR_LOG)
    telemetry = telemetry_for_run(event_log=event_log)
    with telemetry:
        supervisor = Supervisor(
            argv_for_rank,
            world_size=args.num_processes,
            config=SupervisorConfig(
                max_restarts=args.max_restarts,
                backoff_base_s=args.restart_backoff,
                heartbeat_dir=args.heartbeat_dir,
                heartbeat_timeout_s=args.heartbeat_timeout,
                term_grace_s=args.term_grace,
                allow_degraded=not args.no_degraded,
                min_world_size=args.min_world_size,
                seed=args.seed,
                metrics_port=args.metrics_port,
                alert_restart_after=args.alert_restart_after,
                mesh_axes=(
                    parse_mesh_shape(args.mesh_shape)
                    if args.mesh_shape else None
                ),
                correlation_window_s=args.correlation_window,
            ),
            telemetry=telemetry,
            log_dir=args.worker_log_dir,
            run_dir=args.run_dir,
            pin_chips=bool(chips),
        )
        if args.run_dir:
            telemetry.emit(
                MarkerEvent(
                    kind="run_start", run_id=supervisor.run_id or "",
                    world_size=args.num_processes,
                )
            )
        result = supervisor.run()
    summary = {
        "supervised": True,
        "experiment": args.experiment,
        "success": result.success,
        "world_size": result.world_size,
        "total_restarts": result.total_restarts,
        "degraded": result.degraded,
        "reason": result.reason,
        "final_mesh": result.final_mesh,
    }
    if args.run_dir:
        summary["run_dir"] = args.run_dir
        summary["run_id"] = supervisor.run_id
    if args.json:
        Telemetry([StreamJsonSink(sys.stdout)]).emit(RawEvent(summary))
    if not result.success:
        raise SystemExit(3)
    return summary


def main(argv=None) -> dict:
    raw = argv if argv is not None else sys.argv[1:]
    if raw and raw[0] == "fleet":
        # the fleet control plane: gang-schedule spooled job manifests
        # over a fixed chip inventory (resilience.scheduler owns the CLI;
        # jax-free, so intercept BEFORE the experiment parser and its
        # choices= validation)
        from .resilience import scheduler as _scheduler

        return {"fleet_rc": _scheduler.main(raw[1:])}
    args = build_parser().parse_args(argv)
    if args.metrics_port is not None and not (args.supervise and args.run_dir):
        raise ValueError("--metrics-port requires --supervise and --run-dir")
    if args.alert_restart_after and not args.supervise:
        raise ValueError("--alert-restart-after requires --supervise")
    if args.mesh_shape and not args.supervise:
        raise ValueError("--mesh-shape requires --supervise")
    if args.supervise:
        return _supervise(args, argv if argv is not None else sys.argv[1:])
    # worker path only (the supervising parent stays off jax config): one
    # persistent compile cache, placeable through JAX_COMPILATION_CACHE_DIR
    hostenv.configure_compile_cache()
    if args.run_dir:
        # a worker rank of a run-dir launch: derive this rank's event shard,
        # and make sure the run env is present so telemetry_for_run leads
        # the shard with the run_start marker (supervised workers inherit
        # the env from the parent — setdefault keeps the parent's run id)
        import os

        from .observe import runlog as _runlog

        os.environ.setdefault(_runlog.ENV_RUN_DIR, args.run_dir)
        os.environ.setdefault(
            _runlog.ENV_RUN_ID, _runlog.default_run_id(args.run_dir)
        )
        os.environ.setdefault("RESILIENCE_RANK", str(args.process_id))
        os.environ.setdefault("RESILIENCE_WORLD", str(args.num_processes))
        if not args.event_log:
            args.event_log = _runlog.shard_path(args.run_dir, args.process_id)
    cfg = config_from_args(args)
    if args.plan is not None:
        if args.experiment not in ("exact_cifar10", "powersgd_cifar10"):
            raise ValueError(
                f"--plan is not supported by {args.experiment!r}"
                " (supported: exact_cifar10, powersgd_cifar10)"
            )
        apply_plan(cfg, args)

    # reject silently-ignored flags BEFORE any rendezvous: a pure-CLI error
    # must not burn a multi-host allocation on a doomed jax.distributed join
    _ACCUM_OK = ("exact_cifar10", "powersgd_cifar10", "powersgd_imdb", "imdb_baseline")
    _REMAT_OK = ("gpt_lm", "powersgd_imdb")
    if cfg.accum_steps > 1 and args.experiment not in _ACCUM_OK:
        raise ValueError(
            f"--accum-steps is not supported by {args.experiment!r}"
            f" (supported: {', '.join(_ACCUM_OK)})"
        )
    if cfg.max_grad_norm is not None and args.experiment not in _ACCUM_OK:
        raise ValueError(
            f"--max-grad-norm is not supported by {args.experiment!r}"
            f" (supported: {', '.join(_ACCUM_OK)})"
        )
    if cfg.adaptive_comm and args.experiment != "exact_cifar10":
        raise ValueError(
            f"--adaptive-comm is not supported by {args.experiment!r}"
            " (supported: exact_cifar10)"
        )
    if (
        args.comm_fabric is not None
        and not cfg.adaptive_comm
        and args.plan is None
    ):
        raise ValueError("--comm-fabric requires --adaptive-comm or --plan")
    if args.remat and args.experiment not in _REMAT_OK:
        raise ValueError(
            f"--remat is not supported by {args.experiment!r}"
            f" (supported: {', '.join(_REMAT_OK)})"
        )
    if args.scan_layers and args.experiment != "gpt_lm":
        raise ValueError(
            f"--scan-layers is not supported by {args.experiment!r}"
            " (supported: gpt_lm)"
        )
    for flag, val in (
        ("--slots", args.slots), ("--requests", args.requests),
        ("--request-rate", args.request_rate),
        ("--spool-dir", args.spool_dir),
        ("--engine", args.engine), ("--block-len", args.block_len),
        ("--n-blocks", args.n_blocks),
        ("--no-prefix-sharing", args.no_prefix_sharing or None),
        ("--spec-k", args.spec_k),
    ):
        if val is not None and args.experiment != "serve_gpt":
            raise ValueError(
                f"{flag} is not supported by {args.experiment!r}"
                " (supported: serve_gpt)"
            )

    # multi-host rendezvous before any experiment touches devices
    # (the reference's setup() does the same before run_task()).
    # serve_gpt ranks share only the file spool — no collectives, and a
    # rendezvous would couple the fleet's fate to its slowest/dead rank,
    # exactly what the elastic spool exists to avoid
    if args.num_processes > 1 and args.experiment not in RENDEZVOUS_FREE:
        initialize_distributed(
            DistributedConfig(
                process_id=cfg.process_id,
                num_processes=cfg.num_processes,
                coordinator_address=cfg.coordinator_address,
                timeout_seconds=cfg.timeout_seconds,
            )
        )

    fn = EXPERIMENTS[args.experiment]
    kwargs = {"config": cfg}
    if args.experiment == "diloco_cifar10":
        kwargs.update(preset=args.preset, data_dir=args.data_dir,
                      max_steps_per_epoch=args.max_steps_per_epoch,
                      sync_every=args.sync_every, fragments=args.fragments,
                      reducer=args.diloco_reducer)
        if args.lr is not None:
            # --lr names the INNER rate here (see diloco_cifar10.run)
            kwargs.update(inner_learning_rate=args.lr)
    elif args.experiment in ("exact_cifar10", "powersgd_cifar10"):
        kwargs.update(preset=args.preset, data_dir=args.data_dir,
                      max_steps_per_epoch=args.max_steps_per_epoch)
        if args.experiment == "exact_cifar10":
            kwargs.update(strategy=args.strategy,
                          checkpoint_dir=args.checkpoint_dir)
    elif args.experiment in ("powersgd_imdb", "imdb_baseline"):
        kwargs.update(preset=args.preset,
                      data_dir=None if args.data_dir == "./data" else args.data_dir,
                      max_steps_per_epoch=args.max_steps_per_epoch)
        if args.experiment == "powersgd_imdb":
            kwargs.update(remat=args.remat)
    elif args.experiment == "gpt_generate":
        kwargs.update(preset=args.preset, max_new_tokens=args.max_new_tokens,
                      temperature=args.temperature)
    elif args.experiment == "serve_gpt":
        kwargs.update(preset=args.preset,
                      slots=args.slots if args.slots is not None else 4,
                      requests=args.requests
                      if args.requests is not None else 16,
                      request_rate=args.request_rate
                      if args.request_rate is not None else 64.0,
                      max_new_tokens=args.max_new_tokens,
                      checkpoint_dir=args.checkpoint_dir,
                      spool_dir=args.spool_dir,
                      engine=args.engine if args.engine is not None
                      else "slot",
                      block_len=args.block_len
                      if args.block_len is not None else 16,
                      n_blocks=args.n_blocks,
                      prefix_sharing=not args.no_prefix_sharing,
                      spec_k=args.spec_k if args.spec_k is not None else 0)
    elif args.experiment == "bandwidth_study":
        kwargs.update(preset=args.preset)
    elif args.experiment in LM_EXPERIMENTS:
        kwargs.update(preset=args.preset,
                      max_steps_per_epoch=args.max_steps_per_epoch)
    elif args.experiment in ("gpt_lm", "gpt_pp", "gpt_sp", "gpt_tp", "gpt_moe"):
        kwargs.update(preset=args.preset, max_steps_per_epoch=args.max_steps_per_epoch)
        if args.experiment == "gpt_lm":
            kwargs.update(remat=args.remat, scan_layers=args.scan_layers)
        if args.experiment == "gpt_pp":
            kwargs.update(data_shards=args.data_shards, reducer=args.pp_reducer)
        if args.experiment == "gpt_tp":
            kwargs.update(model_shards=args.model_shards, reducer=args.tp_reducer,
                          vocab_parallel=args.vocab_parallel)
        if args.experiment == "gpt_moe":
            kwargs.update(experts_per_device=args.experts_per_device,
                          reducer=args.moe_reducer, top_k=args.moe_top_k)
        if args.experiment in ("gpt_pp", "gpt_sp"):
            kwargs.update(checkpoint_dir=args.checkpoint_dir)

    result = fn(**kwargs)
    if args.json:
        # driver-facing contract: RawEvent keeps the payload verbatim, so the
        # line is byte-identical to the historical print(json.dumps(...))
        Telemetry([StreamJsonSink(sys.stdout)]).emit(RawEvent(result))
    return result


if __name__ == "__main__":
    main()
