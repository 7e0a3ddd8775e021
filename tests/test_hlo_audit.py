"""HLO collective audit: the analytic bytes-on-wire model must equal what
XLA actually compiled (SURVEY §7's 'honest accounting' hard part), and the
audit exposes the combiner's collective-count reduction."""

import jax
import jax.numpy as jnp
import pytest

from network_distributed_pytorch_tpu.models import SmallCNN
from network_distributed_pytorch_tpu.parallel import (
    ExactReducer,
    PowerSGDReducer,
    make_mesh,
)
from network_distributed_pytorch_tpu.parallel.trainer import (
    make_train_step,
    stateless_loss,
)
from network_distributed_pytorch_tpu.utils import cross_entropy_loss
from network_distributed_pytorch_tpu.utils.hlo_audit import (
    collective_summary,
    compiled_hlo_text,
)

IMG = (8, 8, 3)


def _setup():
    model = SmallCNN(width=4)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, *IMG)))["params"]

    def lf(p, b):
        x, y = b
        return cross_entropy_loss(model.apply({"params": p}, x), y)

    batch = (jnp.zeros((64, *IMG)), jnp.zeros((64,), jnp.int32))
    return params, stateless_loss(lf), batch


def _summary(reducer, algo):
    params, loss_fn, batch = _setup()
    mesh = make_mesh()
    step = make_train_step(
        loss_fn, reducer, params, 0.05, 0.9, algo, mesh=mesh, donate_state=False
    )
    state = step.init_state(params)
    txt = compiled_hlo_text(step.fn, state, batch)
    return step, collective_summary(txt)


def test_exact_hlo_payload_matches_analytic(devices):
    step, s = _summary(ExactReducer(), "sgd")
    # bits_per_step is the WHOLE step's wire cost (reducer payload + the
    # 4-byte loss pmean, trainer.LOSS_SYNC_BITS) — byte-exact vs compiled HLO
    assert s["total_payload_bytes"] == step.bits_per_step // 8
    # only all-reduces, and at most 2 (the gradient + the loss pmean —
    # whether the combiner merges them into one is toolchain-dependent)
    assert set(s["by_kind"]) == {"all-reduce"}
    assert 1 <= s["by_kind"]["all-reduce"] <= 2


def test_powersgd_hlo_payload_matches_analytic(devices):
    step, s = _summary(PowerSGDReducer(compression_rank=2, matricize="last"), "ef_momentum")
    assert s["total_payload_bytes"] == step.bits_per_step // 8
    # the P / rank-1 / Q / loss logical collectives compile to at most 4;
    # Q depends on allreduced-P so at least 2 remain after the combiner
    # (how much the rest merge is toolchain-dependent)
    assert 2 <= s["by_kind"]["all-reduce"] <= 4


def _vector_setup():
    """A model of vectors only: PowerSGD has no matrix to compress, and the
    whole gradient rides the rank-1 payload."""
    params = {"scale": jnp.ones((24,)), "shift": jnp.zeros((24,))}

    def lf(p, b):
        x, y = b
        pred = jnp.sum(x.reshape(x.shape[0], -1)[:, :24] * p["scale"] + p["shift"], -1)
        return jnp.mean((pred - y) ** 2)

    batch = (jnp.zeros((64, *IMG)), jnp.zeros((64,)))
    return params, stateless_loss(lf), batch


# reducer, algorithm, setup: every shape the ledger itemises
AUDIT_CONFIGS = {
    "exact-packed": (lambda: ExactReducer(), "sgd", _setup),
    "exact-unpacked": (lambda: ExactReducer(packed=False), "sgd", _setup),
    "exact-bucketed": (lambda: ExactReducer(bucket_bytes=60), "sgd", _setup),
    "psgd-r1": (lambda: PowerSGDReducer(compression_rank=1), "ef_momentum", _setup),
    "psgd-r4": (lambda: PowerSGDReducer(compression_rank=4), "ef_momentum", _setup),
    "psgd-r4-bf16-wire": (
        lambda: PowerSGDReducer(compression_rank=4, compression_dtype=jnp.bfloat16),
        "ef_momentum", _setup,
    ),
    "psgd-r4-2-rounds": (
        lambda: PowerSGDReducer(compression_rank=4, n_power_iterations=1),
        "ef_momentum", _setup,
    ),
    "psgd-r4-matricize-last": (
        lambda: PowerSGDReducer(compression_rank=4, matricize="last"),
        "ef_momentum", _setup,
    ),
    "psgd-r4-fresh-query": (
        lambda: PowerSGDReducer(compression_rank=4, reuse_query=False),
        "ef_momentum", _setup,
    ),
    "psgd-vectors-only": (
        lambda: PowerSGDReducer(compression_rank=4), "ef_momentum", _vector_setup,
    ),
}


@pytest.mark.parametrize("n_workers", [2, 4, 8])
@pytest.mark.parametrize("config", list(AUDIT_CONFIGS))
def test_compiled_collectives_equal_ledger(devices, config, n_workers):
    """The CPU twin of the four-chip cell's wire audit: the all-reduces in
    the optimised HLO carry the ledger's bytes exactly, in no more ops than
    the ledger has lines (the combiner may merge, nothing may be added)."""
    make_reducer, algo, setup = AUDIT_CONFIGS[config]
    params, loss_fn, batch = setup()
    step = make_train_step(
        loss_fn, make_reducer(), params, 0.05, 0.9, algo,
        mesh=make_mesh(devices=devices[:n_workers]), donate_state=False,
    )
    txt = compiled_hlo_text(step.fn, step.init_state(params), batch)
    audit = step.ledger.reconcile(txt)
    # XLA:CPU widens a bf16 all-reduce to f32 (the chip does not): there the
    # compiled bytes are the ledger's elements at four bytes each
    widened = sum(
        e.payload_bytes for e in step.ledger.entries if e.dtype == "bfloat16"
    )
    assert audit["delta_bytes"] == widened, audit
    assert (widened > 0) == (config == "psgd-r4-bf16-wire")
    assert set(audit["hlo_by_kind"]) == {"all-reduce"}, audit
    assert step.ledger.total_bits() == step.bits_per_step
    n_lines = sum(e.count for e in step.ledger.entries)
    assert 1 <= audit["hlo_collective_count"] <= n_lines, (audit, n_lines)


def test_full_step_with_batch_stats_no_unaccounted_collectives(devices):
    """Round-1 verdict item 4: the entire compiled train step — including a
    model WITH BatchNorm running stats in model_state — must contain no
    collective payload the analytic ``bits_per_step`` doesn't carry. BN stats
    stay per-worker (zero wire bytes, the reference's unsynced-BN torch-DDP
    semantics), so the only non-reducer collective is the scalar loss pmean."""
    from network_distributed_pytorch_tpu.experiments.common import (
        image_classifier_loss,
    )
    from network_distributed_pytorch_tpu.models import resnet18

    model = resnet18(num_classes=10, norm="batch", stem="cifar", width=8)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, *IMG)), train=True)
    loss_fn = image_classifier_loss(model, has_batch_stats=True)
    batch = (jnp.zeros((16, *IMG)), jnp.zeros((16,), jnp.int32))
    mesh = make_mesh()
    for reducer, algo in (
        (ExactReducer(), "sgd"),
        (PowerSGDReducer(compression_rank=2, matricize="last"), "ef_momentum"),
    ):
        step = make_train_step(
            loss_fn, reducer, variables["params"], 0.05, 0.9, algo,
            mesh=mesh, donate_state=False,
        )
        state = step.init_state(
            variables["params"],
            model_state={"batch_stats": variables["batch_stats"]},
        )
        s = collective_summary(compiled_hlo_text(step.fn, state, batch))
        assert s["total_payload_bytes"] == step.bits_per_step // 8, (
            algo, s["by_kind"], s["total_payload_bytes"], step.bits_per_step // 8
        )


def test_fsdp_hlo_payload_matches_analytic(devices):
    """ZeRO-3's compiled collectives: all-gather(params) + reduce-scatter
    (grads) payloads must equal the analytic 2x model (+ loss/model-state
    pmeans), with the grad reduce-scatter appearing as real reduce-scatter
    ops (psum_scatter from the AD transpose), not widened all-reduces."""
    from network_distributed_pytorch_tpu.parallel.fsdp import make_fsdp_train_step

    params, loss_fn, batch = _setup()
    mesh = make_mesh()
    step = make_fsdp_train_step(
        loss_fn, params, learning_rate=0.05, momentum=0.9, algorithm="sgd",
        mesh=mesh, donate_state=False,
    )
    state = step.init_state(params)
    txt = compiled_hlo_text(step.fn, state, batch)
    s = collective_summary(txt)

    assert s["by_kind"].get("reduce-scatter", 0) >= 1, s["by_kind"]
    assert s["by_kind"].get("all-gather", 0) >= 1, s["by_kind"]
    # analytic: gather + scatter of every padded leaf + the loss pmean
    # (LOSS_SYNC_BITS); model_state is {} here
    assert s["total_payload_bytes"] == step.bits_per_step // 8


def test_audit_parses_tpu_layout_annotations():
    """TPU HLO shapes carry tiling/memory-space layout suffixes
    ("{0:T(1024)S(1)}") — the audit must parse them (a v5e-compiled module
    previously audited as ZERO collectives)."""
    from network_distributed_pytorch_tpu.utils.hlo_audit import audit_hlo

    hlo = (
        "  %psum.1 = f32[219724]{0:T(1024)S(1)} all-reduce(%c), "
        "replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add\n"
        "  %ar = (f32[53130]{0:T(1024)S(1)}, f32[106280]{0:T(1024)S(1)}, "
        "f32[]{:T(128)}) all-reduce(%a, %b, %c), "
        "replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add\n"
    )
    ops = audit_hlo(hlo)
    assert len(ops) == 2
    assert ops[0].payload_bytes == 4 * 219724
    assert ops[1].payload_bytes == 4 * (53130 + 106280 + 1)


def test_audit_tuple_result_combiner_merged_mixed_dtypes():
    """A combiner-merged collective is ONE tuple-result op whose payload
    sums its components at each component's OWN dtype width — a bf16 buffer
    merged with f32 buffers must not be billed at 4 bytes/elem."""
    from network_distributed_pytorch_tpu.utils.hlo_audit import audit_hlo

    hlo = (
        "  %merged = (f32[100]{0}, bf16[50]{0}, f32[]) "
        "all-reduce(%a, %b, %c), replica_groups={{0,1,2,3}}, to_apply=%add\n"
    )
    ops = audit_hlo(hlo)
    assert len(ops) == 1
    op = ops[0]
    assert op.kind == "all-reduce"
    assert op.payload_bytes == 4 * 100 + 2 * 50 + 4
    assert op.dtype == "f32+bf16+f32"
    assert op.shape == ((100,), (50,), ())
    assert op.group == (0, 1, 2, 3) and op.group_size == 4


def test_audit_tuple_result_reduce_scatter_scales_by_group():
    """A tuple-result (combiner-merged) reduce-scatter's result is 1/N of
    each reduced buffer — the audit scales the SUMMED components by the
    replica-group size so the payload stays in the same convention as
    all-reduce (the logical buffer moved)."""
    from network_distributed_pytorch_tpu.utils.hlo_audit import audit_hlo

    hlo = (
        "  %rs = (f32[16]{0}, f32[8]{0}) reduce-scatter(%a, %b), "
        "replica_groups={{0,1,2,3}}, dimensions={0}, to_apply=%add\n"
    )
    ops = audit_hlo(hlo)
    assert len(ops) == 1
    assert ops[0].payload_bytes == (4 * 16 + 4 * 8) * 4
    assert ops[0].group_size == 4


def test_audit_async_start_form_counted_once():
    """The async `-start` form of a collective is audited like the sync op
    (same result type), and its `-done` line — which repeats no collective
    keyword with a payload — adds nothing."""
    from network_distributed_pytorch_tpu.utils.hlo_audit import (
        audit_hlo,
        collective_summary,
    )

    hlo = (
        "  %ar = f32[96]{0} all-reduce-start(%x), "
        "replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add\n"
        "  %ard = f32[96]{0} all-reduce-done(%ar)\n"
    )
    ops = audit_hlo(hlo)
    assert len(ops) == 1
    assert ops[0].payload_bytes == 4 * 96
    assert collective_summary(hlo)["by_kind"] == {"all-reduce": 1}
