"""Overlap analyzer: -start/-done window extraction from scheduled HLO."""

from network_distributed_pytorch_tpu.utils.overlap import overlap_report

_SCHEDULED_HLO = """\
HloModule jit_step, is_scheduled=true

ENTRY %main (p0: f32[64,32]) -> f32[64,32] {
  %p0 = f32[64,32]{1,0} parameter(0)
  %ar-start = f32[96]{0} all-reduce-start(%rank1buf), replica_groups={}, to_apply=%add
  %gs = f32[64,2]{1,0} fusion(%p0), kind=kLoop, calls=%gram_schmidt
  %qt = f32[32,2]{1,0} dot(%p0, %gs), lhs_contracting_dims={0}
  %ar-done = f32[96]{0} all-reduce-done(%ar-start)
  %ag-start = (f32[8],f32[64]) all-gather-start(%x), dimensions={0}
  %ag-done = f32[64]{0} all-gather-done(%ag-start)
  ROOT %out = f32[64,32]{1,0} fusion(%qt, %ar-done), kind=kOutput, calls=%f
}
"""


def test_overlap_report_synthetic():
    rep = overlap_report(_SCHEDULED_HLO)
    assert rep["scheduled"]
    assert rep["n_async_collectives"] == 2
    # the all-reduce window contains a fusion + a dot -> overlapped; the
    # all-gather window is empty -> not
    assert rep["n_overlapped"] == 1
    assert not rep["all_overlap"]
    ar = [c for c in rep["collectives"] if c["kind"] == "all-reduce"][0]
    assert ar["compute_ops_between"] == 2 and ar["ops_between"] == 2
    ag = [c for c in rep["collectives"] if c["kind"] == "all-gather"][0]
    assert ag["ops_between"] == 0


def test_overlap_report_on_real_cpu_hlo(devices):
    """CPU compiles synchronous collectives — the report must say so (zero
    async), never crash, on a real compiled PowerSGD step."""
    import jax.numpy as jnp

    from network_distributed_pytorch_tpu.parallel import PowerSGDReducer, make_mesh
    from network_distributed_pytorch_tpu.parallel.trainer import (
        make_train_step,
        stateless_loss,
    )
    from network_distributed_pytorch_tpu.utils.hlo_audit import compiled_hlo_text

    params = {"w": jnp.zeros((32, 16))}
    loss = stateless_loss(lambda p, b: jnp.mean((b[0] @ p["w"] - b[1]) ** 2))
    step = make_train_step(
        loss, PowerSGDReducer(compression_rank=2, matricize="last"), params,
        0.05, mesh=make_mesh(), donate_state=False,
    )
    state = step.init_state(params)
    batch = (jnp.zeros((16, 32)), jnp.zeros((16, 16)))
    rep = overlap_report(compiled_hlo_text(step.fn, state, batch))
    assert rep["scheduled"]
    assert rep["n_async_collectives"] == 0


def test_overlap_report_generic_async_wrapper():
    """XLA's generic `async-start`/`async-done` wrapper (what the TPU
    async-collective-fusion pass emits) is recognized and classified by the
    wrapped collective named on the line."""
    hlo = "\n".join([
        "HloModule m, is_scheduled=true",
        "ENTRY %main () -> f32[8] {",
        "  %p = f32[8]{0} parameter(0)",
        "  %ar = ((f32[8]), f32[8]) async-start(%p), calls=%wrapped_all-reduce.1",
        "  %f1 = f32[8]{0} fusion(%p), kind=kLoop",
        "  %d = f32[8]{0} dot(%f1, %f1)",
        "  %done = f32[8]{0} async-done(%ar)",
        "  ROOT %r = f32[8]{0} add(%done, %d)",
        "}",
    ])
    rep = overlap_report(hlo)
    assert rep["n_async_collectives"] == 1
    assert rep["collectives"][0]["kind"] == "all-reduce"
    assert rep["n_overlapped"] == 1  # the fusion + dot sit inside the window
    assert rep["collectives"][0]["compute_ops_between"] == 2


def test_overlap_report_start_done_pairing_by_name():
    """-done pairs with ITS -start by operand name, not by order: with two
    interleaved windows, each window's op count comes from its own span,
    and a -done naming an unknown op is ignored rather than crashing."""
    hlo = "\n".join([
        "HloModule m, is_scheduled=true",
        "ENTRY %main () -> f32[8] {",
        "  %a-start = f32[96]{0} all-reduce-start(%x), to_apply=%add",
        "  %b-start = (f32[8],f32[8]) all-gather-start(%y), dimensions={0}",
        "  %f1 = f32[8]{0} fusion(%y), kind=kLoop",
        "  %a-done = f32[96]{0} all-reduce-done(%a-start)",
        "  %orphan = f32[8]{0} all-gather-done(%never-started)",
        "  %d = f32[8]{0} dot(%f1, %f1)",
        "  %b-done = f32[8]{0} all-gather-done(%b-start)",
        "}",
    ])
    rep = overlap_report(hlo)
    assert rep["n_async_collectives"] == 2
    ar = [c for c in rep["collectives"] if c["kind"] == "all-reduce"][0]
    ag = [c for c in rep["collectives"] if c["kind"] == "all-gather"][0]
    # the all-reduce window holds only the all-gather-start + fusion; the
    # all-gather window additionally spans the -done/orphan/dot lines
    assert ar["compute_ops_between"] == 1
    assert ag["compute_ops_between"] == 2
    assert rep["n_overlapped"] == 2 and rep["all_overlap"]


def test_overlap_report_copy_windows_counted():
    """The TPU memory scheduler's copy-start/copy-done DMA prefetch windows
    are counted (with/without compute inside) but never listed as async
    collectives — on v5e they ARE the visible latency hiding."""
    hlo = "\n".join([
        "HloModule m, is_scheduled=true",
        "ENTRY %main () -> f32[8] {",
        "  %c1 = (f32[8],f32[8],u32[],u32[]) copy-start(%p)",
        "  %f = f32[8]{0} fusion(%p), kind=kLoop",
        "  %c1d = f32[8]{0} copy-done(%c1)",
        "  %c2 = (f32[8],f32[8],u32[],u32[]) copy-start(%q)",
        "  %c2d = f32[8]{0} copy-done(%c2)",
        "}",
    ])
    rep = overlap_report(hlo)
    assert rep["n_async_collectives"] == 0
    assert rep["collectives"] == []
    assert rep["n_async_copy_windows"] == 2
    assert rep["n_copy_windows_with_compute"] == 1


def test_overlap_report_async_compute_wrapper_skipped():
    """A generic async-start wrapping NON-collective work (no collective
    kind named on the line) must be dropped at its -done, not reported as
    an async collective — and must not shadow a real window around it."""
    hlo = "\n".join([
        "HloModule m, is_scheduled=true",
        "ENTRY %main () -> f32[8] {",
        "  %p = f32[8]{0} parameter(0)",
        "  %ac = ((f32[8]), f32[8]) async-start(%p), calls=%wrapped_fusion.3",
        "  %ar-start = f32[96]{0} all-reduce-start(%p), to_apply=%add",
        "  %f1 = f32[8]{0} fusion(%p), kind=kLoop",
        "  %acd = f32[8]{0} async-done(%ac)",
        "  %ar-done = f32[96]{0} all-reduce-done(%ar-start)",
        "}",
    ])
    rep = overlap_report(hlo)
    # only the real collective window is reported; the compute wrapper is
    # skipped silently (its window would otherwise double-count the fusion)
    assert rep["n_async_collectives"] == 1
    assert rep["collectives"][0]["kind"] == "all-reduce"
    assert rep["collectives"][0]["name"] == "ar-start"
    assert rep["n_overlapped"] == 1


_CHUNKED_SYNC_HLO = """\
HloModule jit_step, is_scheduled=true

%wrapped_ar (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  ROOT %inner = f32[8]{0} all-reduce(%x), to_apply=%add
}

ENTRY %main (p0: f32[24]) -> f32[24] {
  %p0 = f32[24]{0} parameter(0)
  %ar.1 = f32[8]{0} all-reduce(%s0), replica_groups={}, to_apply=%add
  %retire.1 = f32[8]{0} fusion(%ar.1), kind=kLoop, calls=%unpack1
  %ar.2 = f32[8]{0} all-reduce(%s1), replica_groups={}, to_apply=%add
  %retire.2 = f32[8]{0} fusion(%ar.2), kind=kLoop, calls=%unpack2
  %ar.3 = f32[8]{0} all-reduce(%s2), replica_groups={}, to_apply=%add
  ROOT %out = f32[24]{0} fusion(%retire.1, %retire.2, %ar.3), kind=kOutput
}
"""


def test_overlap_report_sync_interleave_fields():
    """Synchronous chunk collectives (the CPU backend, Round-6 pipeline)
    are listed in schedule order with the compute between each and the
    next; only INTERIOR gaps count toward the interleave verdict, and the
    all-reduce inside the non-ENTRY wrapper computation is not counted."""
    rep = overlap_report(_CHUNKED_SYNC_HLO)
    assert rep["n_sync_collectives"] == 3
    names = [op["name"] for op in rep["sync_collectives"]]
    assert names == ["ar.1", "ar.2", "ar.3"]
    gaps = [op["compute_ops_after"] for op in rep["sync_collectives"]]
    # ar.1 -> retire.1; ar.2 -> retire.2; ar.3 -> the ROOT fusion (tail)
    assert gaps == [1, 1, 1]
    assert rep["n_sync_gaps_with_compute"] == 2  # interior gaps only
    assert rep["sync_interleaved"]


def test_overlap_report_sync_single_collective_not_interleaved():
    """One collective cannot interleave with itself: compute after the
    LAST collective proves nothing, so the verdict stays False."""
    hlo = "\n".join([
        "HloModule m, is_scheduled=true",
        "ENTRY %main () -> f32[8] {",
        "  %ar = f32[8]{0} all-reduce(%p), to_apply=%add",
        "  %f = f32[8]{0} fusion(%ar), kind=kLoop",
        "}",
    ])
    rep = overlap_report(hlo)
    assert rep["n_sync_collectives"] == 1
    assert rep["n_sync_gaps_with_compute"] == 0
    assert not rep["sync_interleaved"]


def test_overlap_report_sync_ignores_start_done_forms():
    """The sync matcher must not re-count async -start/-done pairs (the
    kind is followed by '-start('/'-done(' there, never '(')."""
    hlo = "\n".join([
        "HloModule m, is_scheduled=true",
        "ENTRY %main () -> f32[8] {",
        "  %ar-start = f32[96]{0} all-reduce-start(%x), to_apply=%add",
        "  %f1 = f32[8]{0} fusion(%x), kind=kLoop",
        "  %ar-done = f32[96]{0} all-reduce-done(%ar-start)",
        "}",
    ])
    rep = overlap_report(hlo)
    assert rep["n_async_collectives"] == 1
    assert rep["n_sync_collectives"] == 0
    assert not rep["sync_interleaved"]
