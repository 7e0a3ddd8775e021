"""Experiment configuration.

One typed dataclass replacing the reference's four module-level mutable
``config`` dicts (``ddp_guide/ddp_init.py:9-17``,
``ddp_powersgd_guide_cifar10/ddp_init.py:22-37``,
``ddp_powersgd_distillBERT_IMDb/ddp_init.py:23-39``) — same key set, renamed
to JAX terms where the torch term has no TPU meaning (``cuda_rank`` dropped;
``distributed_backend`` is always XLA; ``init_method`` →
``coordinator_address``).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional


@dataclass
class ExperimentConfig:
    # rendezvous (reference: seed/rank/n_workers/init_method keys)
    seed: int = 714
    process_id: int = 0
    num_processes: int = 1
    coordinator_address: Optional[str] = None
    timeout_seconds: int = 600

    # optimization (reference: learning_rate/momentum/nesterov/... keys)
    learning_rate: float = 0.001
    momentum: float = 0.9
    nesterov: bool = False  # declared-but-unused in the reference too (ddp_init.py:33)
    training_epochs: int = 100
    global_batch_size: int = 256

    # compression (reference: reducer_rank)
    reducer_rank: int = 4
    reuse_query: bool = True

    # TPU-native extras
    compute_dtype: str = "float32"  # "bfloat16" for MXU mixed precision
    log_every: int = 10
    accum_steps: int = 1  # gradient accumulation microbatches per step
    max_grad_norm: Optional[float] = None  # global-norm gradient clipping
    # DDP-style backward-order gradient buckets for the exact reducer
    # (parallel.comm.bucket_assignments): target bytes per bucket; each
    # bucket's collective launches as soon as the backward pass has
    # produced its gradients. None = one monolithic packed collective.
    bucket_bytes: Optional[int] = None

    # kernel implementation overrides (DESIGN.md "Raw speed"). "auto"
    # resolves per backend at construction: Pallas kernels on TPU, the XLA
    # reference lowerings on CPU (where Pallas would only run interpreted).
    # orthogonalize_impl: "auto" | "xla" | "pallas" — PowerSGD Gram-Schmidt
    orthogonalize_impl: str = "auto"
    # attn_impl: None = keep each model's own default ("auto" → flash on
    # TPU, einsum elsewhere); "einsum" | "flash" | "auto" to force
    attn_impl: Optional[str] = None

    # observability (observe/): structured JSONL run log, jax.profiler trace
    # directory, and the compile-time wire-ledger-vs-HLO audit. audit_wire
    # None = audit iff an event log is being written (the audit costs one
    # extra XLA compile, so it follows the "this run is being recorded"
    # signal unless forced).
    event_log: Optional[str] = None
    trace_dir: Optional[str] = None
    audit_wire: Optional[bool] = None
    # training-health sampling cadence (observe.events.TrainHealthEvent):
    # every N steps the loop dispatches the separately jitted health probe
    # (CompiledStep.health_fn — one extra fwd+bwd plus a collective-free
    # diagnostic compression round; see DESIGN.md "health sampling cost").
    # 0 = never sample (the probe is never dispatched, zero overhead).
    health_every: int = 0

    # resilience (resilience/): path to a JSON fault schedule
    # (resilience.chaos.ChaosPlan) for experiments running through
    # resilient_train_loop — deterministic fault injection for chaos drills
    chaos_plan: Optional[str] = None
    # degraded-fabric survival (resilience.controller, DESIGN.md): run the
    # closed-loop fallback controller — collective deadline watchdogs
    # around every fenced collective plus the epoch-boundary reducer
    # fallback ladder. exact_cifar10 ddp only.
    adaptive_comm: bool = False
    # the fabric whose FABRICS_BYTES_PER_S line rate models the collective
    # deadline budget (utils.bandwidth keys: "1GbE", "10GbE", "100GbE",
    # "ICI(v5e)")
    comm_fabric: str = "ICI(v5e)"
    # tuned per-fabric plan file from scripts/plan.py (``launch.py --plan``):
    # its best-pick knobs for ``comm_fabric`` are applied at launch, and
    # under adaptive_comm the fallback ladder is reordered predicted-best-
    # first (resilience.controller.ladder_from_plan). None = hand-set knobs
    # and the static DEFAULT_LADDER order.
    plan_path: Optional[str] = None
