"""Continuous-batching GPT serving (beyond parity): the ``serving/``
slot engine as a launcher entry point.

The reference stops at training; the north star's "heavy traffic from
millions of users" needs an inference path. This experiment boots a GPT
decoder (freshly initialized, or hot-loaded from the newest committed
TRAINING checkpoint via ``serving.cache.restore_serving_params``), draws
a deterministic Poisson workload, and serves it through
``serving.engine.SlotEngine`` — iteration-level continuous batching over
``slots`` static batch slots, one compiled decode step for the run — or,
with ``--engine paged``, through ``serving.engine.PagedEngine``: the
block-pool paged KV cache (copy-on-write prefix sharing, optional
speculative decoding via ``--spec-k``), bitwise-identical tokens at a
fraction of the dense cache's HBM.

Two serving modes:

- **in-process** (default): open-loop wall-clock replay of the workload
  against the local engine (``serving.frontend.replay``).
- **spool** (``--spool-dir``): the elastic fleet mode. Every rank
  idempotently enqueues the same deterministic workload into the shared
  ``FileSpool``, then runs the claim/step/complete loop
  (``serve_from_spool``). Ranks share ONLY the spool directory — no
  collectives, no rendezvous — so under ``launch.py --supervise`` a rank
  death mid-decode degrades the world and the restart's orphan re-queue
  moves its in-flight requests onto the survivors.

Every terminal request emits one ``observe.RequestEvent`` (queue /
prefill / decode / total latencies); ``scripts/report.py`` renders the
per-run SLO table from those and ``scripts/gate.py`` gates on the p99
decode ms/token.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..models.gpt import gpt_small, gpt_tiny
from ..utils.config import ExperimentConfig
from .common import device_fields


def run(
    config: Optional[ExperimentConfig] = None,
    preset: str = "small",
    slots: int = 4,
    requests: int = 16,
    request_rate: float = 64.0,
    max_new_tokens: int = 16,
    checkpoint_dir: Optional[str] = None,
    spool_dir: Optional[str] = None,
    max_wall_s: float = 120.0,
    engine: str = "slot",
    block_len: int = 16,
    n_blocks: Optional[int] = None,
    prefix_sharing: bool = True,
    spec_k: int = 0,
) -> Dict:
    from ..observe import NoteEvent, telemetry_from_config
    from ..serving import (
        WorkloadConfig,
        poisson_workload,
        replay,
        slo_summary,
    )
    from ..serving.engine import (
        PagedEngine,
        SlotEngine,
        padded_static_decode_steps,
    )

    config = config or ExperimentConfig()
    if engine not in ("slot", "paged"):
        raise ValueError(f"engine must be 'slot' or 'paged', got {engine!r}")
    if slots < 1:
        raise ValueError(f"slots must be >= 1, got {slots}")
    if requests < 1:
        raise ValueError(f"requests must be >= 1, got {requests}")
    if max_new_tokens < 2:
        raise ValueError(
            f"max_new_tokens must be >= 2 for serving, got {max_new_tokens}"
        )

    vocab = 64 if preset == "small" else 1024
    p_lo, p_hi = (4, 12) if preset == "small" else (8, 32)
    workload = WorkloadConfig(
        n_requests=requests,
        rate_rps=request_rate,
        prompt_len=(p_lo, p_hi),
        max_new_tokens=(2, max_new_tokens),
        vocab=vocab,
        seed=config.seed,
    )
    # cache capacity covers the longest possible request; every admission
    # prefills at this capacity so outputs are comparable bit-for-bit with
    # a sequential generate(cache_len=max_len) reference. The paged engine
    # wants a whole number of KV blocks.
    max_len = p_hi + max_new_tokens
    if engine == "paged":
        max_len = ((max_len + block_len - 1) // block_len) * block_len

    make = gpt_tiny if preset == "small" else gpt_small
    model = make(
        vocab_size=vocab, max_position_embeddings=max_len,
        dtype=jnp.dtype(config.compute_dtype),
    )
    params = model.init(
        jax.random.PRNGKey(config.seed), jnp.zeros((1, max_len), jnp.int32)
    )["params"]

    telemetry = telemetry_from_config(config)
    # in-process live-plane adapter: every RequestEvent the engine emits
    # also lands in a MetricRegistry (serving SLO split — queue / decode /
    # total summaries, ms-per-token histogram), so an embedding process can
    # serve /metrics straight off this registry with no run dir at all
    from ..observe.live import MetricRegistry, MetricSink

    registry = MetricRegistry()
    telemetry.add_sink(MetricSink(registry))
    try:
        ckpt_step = None
        if checkpoint_dir is not None:
            from ..serving.cache import restore_serving_params

            restored = restore_serving_params(
                checkpoint_dir, params, telemetry=telemetry, label="serve_gpt"
            )
            if restored is None:
                telemetry.emit(
                    NoteEvent(
                        f"serve_gpt: no restorable checkpoint under"
                        f" {checkpoint_dir}; serving fresh params"
                    )
                )
            else:
                params, ckpt_step = restored

        if engine == "paged":
            # speculative decoding self-drafts here: a freshly-initialized
            # independent draft would propose noise (accept rate ~1/vocab),
            # so the mechanical demo uses the target as its own draft —
            # bitwise-accept semantics are what is being exercised, and a
            # real deployment swaps in a distilled gpt_tiny-class draft
            eng = PagedEngine(
                model.config, params, n_slots=slots, max_len=max_len,
                block_len=block_len, n_blocks=n_blocks,
                prefix_sharing=prefix_sharing,
                draft_config=model.config if spec_k >= 2 else None,
                draft_params=params if spec_k >= 2 else None,
                spec_k=spec_k,
                telemetry=telemetry, rank=config.process_id,
                label="serve_gpt",
            )
        else:
            eng = SlotEngine(
                model.config, params, n_slots=slots, max_len=max_len,
                telemetry=telemetry, rank=config.process_id,
                label="serve_gpt",
            )

        if spool_dir is not None:
            from ..resilience import incarnation_from_env
            from ..serving import FileSpool, serve_from_spool

            # every rank (and every restart) enqueues the same deterministic
            # workload — ensure() is idempotent, so exactly one copy lands
            spool = FileSpool(
                spool_dir, rank=config.process_id,
                incarnation=incarnation_from_env(),
            )
            spool.ensure(poisson_workload(workload))
            served = serve_from_spool(
                eng, spool, world=config.num_processes,
                max_wall_s=max_wall_s,
            )
            finished = served.pop("requests")
            mode: Dict = {"mode": "spool", **served}
        else:
            finished = replay(
                eng, poisson_workload(workload), max_wall_s=max_wall_s
            )
            mode = {"mode": "in_process"}

        # the continuous-batching claim, as numbers: ticks actually spent
        # vs what padded static batching would spend on the same workload
        # (decode lengths in arrival order — ids sort by arrival)
        decode_lengths = [
            len(r.tokens) for r in sorted(finished, key=lambda r: r.request_id)
        ]
        summary = {
            "experiment": "serve_gpt",
            "preset": preset,
            "slots": slots,
            "requests": requests,
            "request_rate": request_rate,
            "max_len": max_len,
            "checkpoint_step": ckpt_step,
            "engine": engine,
            "decode_steps": eng.decode_steps,
            "prefills": eng.prefills,
            "padded_static_decode_steps": padded_static_decode_steps(
                decode_lengths, slots
            ),
            "slo": slo_summary(finished),
            # the live registry's view of the same run — proves the
            # MetricSink path agrees with the post-hoc slo_summary
            "live_requests_total": registry.get_counter(
                "live_serving_requests_total", state="finished"
            ),
            **device_fields(attn_impl=model.config.attn_impl),
            **mode,
        }
        if engine == "paged":
            summary["kv"] = eng.kv_stats()
            if spec_k >= 2:
                stats = eng.stats()
                summary["spec"] = {
                    k: stats[k]
                    for k in (
                        "spec_k", "spec_rounds", "spec_proposed",
                        "spec_accepted", "spec_accept_rate",
                    )
                }
        return summary
    finally:
        telemetry.close()
