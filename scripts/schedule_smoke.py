"""CI schedule smoke (Round-6): AOT-compile one chunked step per reducer on
the CPU backend — NO execution — and assert the compiled executable still
carries the decomposed pipeline:

  1. compiled collective count == Σ ledger entry counts (the barrier-fenced
     chunks must not be re-fused into one blocking op), and
  2. HLO collective payload bytes == ledger bytes (per-chunk itemization
     stays byte-exact against the analytic bits_per_step model).

The bucketed check additionally asserts the DDP backward-overlap property:
with ``bucket_bytes`` splitting a 3-layer MLP's gradients into per-layer
buckets, the compiled module must INTERLEAVE reduce ops with backward
compute fusions (``overlap_report``'s ``sync_interleaved``) — i.e. bucket
0's collective launches before the earlier layers' gradients are even
produced, instead of all compute then one blocking comm tail.

Fails loudly on either drift — this is the cheap canary for an XLA upgrade
(or a comm.py edit) silently un-pipelining the chunk schedule. Runs in a
few seconds: tiny MLP, ``lower().compile()`` on abstract args only.

Invoked by run_tests.sh before the pytest tier with the same CPU/8-device
environment; standalone use needs that env too::

    env JAX_PLATFORMS=cpu \\
        XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python scripts/schedule_smoke.py
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


import jax
import jax.numpy as jnp

from network_distributed_pytorch_tpu.parallel import (
    ExactReducer,
    PowerSGDReducer,
    make_mesh,
)
from network_distributed_pytorch_tpu.parallel.trainer import (
    make_train_step,
    stateless_loss,
)
from network_distributed_pytorch_tpu.utils.hlo_audit import (
    audit_hlo,
    collective_summary,
    hlo_text_of_compiled,
)
from network_distributed_pytorch_tpu.utils.overlap import overlap_report


def check(label, reducer, params, mesh, loss=None, batch_abs=None,
          require_interleave=False):
    loss = loss or stateless_loss(
        lambda p, b: jnp.mean((b[0] @ p["w"] + p["b"] - b[1]) ** 2)
    )
    step = make_train_step(
        loss, reducer, params, 0.05, mesh=mesh, donate_state=False
    )
    state_abs = jax.eval_shape(step.init_state, params)
    batch_abs = batch_abs or (
        jax.ShapeDtypeStruct((16, 32), jnp.float32),
        jax.ShapeDtypeStruct((16, 16), jnp.float32),
    )
    hlo = hlo_text_of_compiled(step.fn.lower(state_abs, batch_abs).compile())
    summary = collective_summary(hlo)
    ledger_count = sum(e.count for e in step.ledger.entries)
    ledger_bytes = step.ledger.total_bytes()
    errors = []
    if summary["count"] != ledger_count:
        errors.append(
            f"collective count drifted: compiled {summary['count']} != "
            f"ledger {ledger_count} — the fenced chunks were re-fused "
            f"(by_kind: {summary['by_kind']})"
        )
    if int(summary["total_payload_bytes"]) != ledger_bytes:
        errors.append(
            f"payload bytes drifted: compiled {summary['total_payload_bytes']}"
            f" != ledger {ledger_bytes}"
        )
    rep = overlap_report(hlo)
    interleaved = rep["sync_interleaved"] or rep["n_overlapped"] >= 2
    if require_interleave and not rep["sync_interleaved"]:
        errors.append(
            "backward overlap lost: the bucketed reduce ops are NOT "
            "interleaved with compute fusions — "
            f"{rep['n_sync_collectives']} sync collectives, "
            f"{rep['n_sync_gaps_with_compute']} interior gaps with compute. "
            "The scheduler re-sank every bucket behind the full backward."
        )
    status = "ok" if not errors else "FAIL"
    sys.stderr.write(
        f"# schedule-smoke {label}: {status} — {summary['count']} collectives"
        f" ({summary['by_kind']}), {ledger_bytes} bytes,"
        f" interleaved={interleaved}\n"
    )
    return [f"{label}: {e}" for e in errors]


def _site_blocks(n_sites, inner_world):
    """Partition-id blocks per site in mesh-flatten (row-major) order —
    the id space HLO ``replica_groups`` are written in."""
    return [
        frozenset(range(s * inner_world, (s + 1) * inner_world))
        for s in range(n_sites)
    ]


def _cross_site_ops(hlo, sites):
    """Collectives whose (first) replica group is NOT contained in a single
    site's device block. ``group=None`` means all participants — cross-site
    by definition on a multi-site mesh."""
    out = []
    for op in audit_hlo(hlo):
        group = op.group
        if group is None or not any(set(group) <= s for s in sites):
            out.append(op)
    return out


def check_hierarchical(label="hierarchical-local-round"):
    """Round-18 geo canary: the two-level step's LOCAL round must compile to
    an HLO with no cross-site collective — every replica group confined to
    one site's block of the (dcn, ici) mesh — while the sync round really
    does carry an outer-axis op. And the step's ledger must be fully priced:
    every entry tagged ``inner.*``/``outer.*`` and the per-level byte totals
    byte-exact against the cost model's hierarchical predictor."""
    from network_distributed_pytorch_tpu.observe import costmodel
    from network_distributed_pytorch_tpu.parallel import (
        make_hierarchical_train_fn,
    )

    n_dcn, n_ici, sync = 2, 4, 4
    mesh2d = make_mesh(axis_sizes=(n_dcn, n_ici), axis_names=("dcn", "ici"))
    params = {"w": jnp.zeros((32, 16)), "b": jnp.zeros((16,))}

    def _loss(p, model_state, b):
        return jnp.mean((b[0] @ p["w"] + p["b"] - b[1]) ** 2), model_state

    step = make_hierarchical_train_fn(
        _loss, params, inner_learning_rate=0.05, sync_every=sync,
        mesh=mesh2d, outer_async=True, donate_state=False,
    )
    state_abs = jax.eval_shape(step.init_state, params)
    batches_abs = (
        jax.ShapeDtypeStruct((sync, 16, 32), jnp.float32),
        jax.ShapeDtypeStruct((sync, 16, 16), jnp.float32),
    )
    weights_abs = jax.ShapeDtypeStruct((sync,), jnp.float32)
    local_hlo = hlo_text_of_compiled(
        step.local_fn.lower(state_abs, batches_abs, weights_abs).compile()
    )
    sync_hlo = hlo_text_of_compiled(
        step.sync_fn.lower(state_abs, batches_abs, weights_abs).compile()
    )
    sites = _site_blocks(n_dcn, n_ici)
    errors = []
    crossers = _cross_site_ops(local_hlo, sites)
    if crossers:
        errors.append(
            "local round leaks onto the slow fabric: "
            f"{len(crossers)} cross-site collective(s) in its HLO — "
            + "; ".join(
                f"{op.kind} group={op.group}" for op in crossers[:4]
            )
        )
    n_local = len(audit_hlo(local_hlo))
    if n_local == 0:
        errors.append(
            "local round compiled to ZERO collectives — the inner exact "
            "all-reduce vanished, so the site-subset check proves nothing"
        )
    if not _cross_site_ops(sync_hlo, sites):
        errors.append(
            "sync round has NO cross-site collective — the outer reduction "
            "is gone (or the cross-site detector is blind)"
        )

    # ---- ledger pricing: no untagged bytes, per-level totals byte-exact
    # against the model. The trainer's inner.loss-sync scalar is the one
    # entry the wire predictor does not price; account for it exactly.
    by_level = {"inner": 0, "outer": 0}
    for e in step.ledger.entries:
        level = e.tag.split(".", 1)[0]
        if level not in by_level or "." not in e.tag:
            errors.append(
                f"unpriced ledger tag {e.tag!r} ({e.payload_bytes} bytes): "
                "every entry must carry an inner./outer. level prefix"
            )
            continue
        by_level[level] += e.payload_bytes
    loss_sync_bytes = sum(
        e.payload_bytes for e in step.ledger.entries
        if e.tag == "inner.loss-sync"
    )
    dense_bytes = step.ledger.dense_grad_bits // 8
    calib = costmodel.CostCalibration(
        step_time_s=0.01, compute_s=0.005,
        dense_bytes=float(dense_bytes), bytes_per_step=float(dense_bytes),
        n_workers=mesh2d.size,
    )
    pred = costmodel.predict(
        calib,
        {"reducer": "hierarchical", "sync_every": sync,
         "outer_async": 1, "sites": n_dcn},
        fabric="1GbE",
    )
    want_inner = int(round(pred["predicted_inner_bytes_per_step"] * sync))
    want_outer = int(round(pred["predicted_outer_bytes_per_step"] * sync))
    got_inner = by_level["inner"] - loss_sync_bytes
    if want_inner != got_inner:
        errors.append(
            f"inner level unpriced: model says {want_inner} bytes/round but "
            f"the ledger itemizes {got_inner} (+{loss_sync_bytes} loss-sync)"
        )
    if want_outer != by_level["outer"]:
        errors.append(
            f"outer level unpriced: model says {want_outer} bytes/round but "
            f"the ledger itemizes {by_level['outer']}"
        )
    status = "ok" if not errors else "FAIL"
    sys.stderr.write(
        f"# schedule-smoke {label}: {status} — {n_local} site-local"
        f" collectives, inner {got_inner}+{loss_sync_bytes}B/round,"
        f" outer {by_level['outer']}B/round priced on 1GbE\n"
    )
    return [f"{label}: {e}" for e in errors]


def main() -> int:
    mesh = make_mesh()
    params = {"w": jnp.zeros((32, 16)), "b": jnp.zeros((16,))}
    errors = []
    # K=3 on a 528-element gradient: ragged chunks (176 each here; the
    # reducers clamp if a payload is smaller than K)
    errors += check("exact-k3", ExactReducer(comm_chunks=3), params, mesh)
    errors += check(
        "powersgd-k2",
        PowerSGDReducer(
            random_seed=7, compression_rank=2, matricize="last", comm_chunks=2
        ),
        params,
        mesh,
    )
    # DDP backward-order buckets: 3-layer MLP so there are distinct backward
    # fusions per layer; bucket_bytes=8192 splits the 6 leaves into ~3
    # buckets in gradient-production order (last layer's grads first). The
    # compiled HLO must interleave the bucket collectives with that compute.
    deep_params = {
        "w1": jnp.zeros((32, 64)), "b1": jnp.zeros((64,)),
        "w2": jnp.zeros((64, 64)), "b2": jnp.zeros((64,)),
        "w3": jnp.zeros((64, 16)), "b3": jnp.zeros((16,)),
    }

    def _deep_loss(p, b):
        h = jnp.tanh(b[0] @ p["w1"] + p["b1"])
        h = jnp.tanh(h @ p["w2"] + p["b2"])
        return jnp.mean((h @ p["w3"] + p["b3"] - b[1]) ** 2)

    errors += check(
        "exact-bucketed",
        ExactReducer(bucket_bytes=8192),
        deep_params,
        mesh,
        loss=stateless_loss(_deep_loss),
        require_interleave=True,
    )
    # Round-18: the geo-resilient two-level round's HLO/ledger invariants
    errors += check_hierarchical()
    for e in errors:
        sys.stderr.write(f"# schedule-smoke ERROR: {e}\n")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
