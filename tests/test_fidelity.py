"""The gradient-fidelity plane: per-group compression audit, the
ledger<->fidelity join, EF-growth tracking, the accuracy-per-byte
frontier, the streaming detectors that page on it, and the controller's
fidelity ascend.

Two invariants are pinned as EQUALITY, not closeness, because they are
correctness facts rather than estimates (DESIGN.md guarantee classes):
every exact reducer layout (flat / chunked / bucketed) reports
identically-zero relative error, and every fidelity group's wire tag is
byte-priced by the same reducer's ledger entries (an orphan group is a
broken join, not a tolerance question). Everything numeric about lossy
reducers stays in the sampled merge-tolerance class.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from network_distributed_pytorch_tpu.observe.events import FidelityEvent
from network_distributed_pytorch_tpu.observe.fidelity import (
    FidelityTracker,
    fidelity_summary,
    frontier_from_events,
)
from network_distributed_pytorch_tpu.observe.health import (
    DetectorConfig,
    EfBlowupDetector,
    FidelityCollapseDetector,
    HealthMonitor,
)
from network_distributed_pytorch_tpu.observe.ledger import (
    reducer_ledger_entries,
)
from network_distributed_pytorch_tpu.observe.live import (
    MetricRegistry,
    ingest_record,
)
from network_distributed_pytorch_tpu.parallel import (
    ExactReducer,
    HierarchicalReducer,
    PowerSGDReducer,
    make_mesh,
)
from network_distributed_pytorch_tpu.parallel.hierarchical import (
    replica_drift_stats,
)
from network_distributed_pytorch_tpu.resilience import (
    FallbackController,
    Rung,
)


def _template():
    """A CNN-ish mix (matches test_reducers): high-rank + rank-1 leaves."""
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    return [
        jax.random.normal(ks[0], (8, 3, 3, 3)),
        jax.random.normal(ks[1], (16, 8)),
        jax.random.normal(ks[2], (16,)),
        jax.random.normal(ks[3], (10, 16)),
        jax.random.normal(ks[4], (10,)),
    ]


def _get(stats):
    """device_get + plain floats, the host side of the health probe."""
    return {
        g: {k: float(v) for k, v in vals.items()}
        for g, vals in jax.device_get(stats).items()
    }


# ---------------------------------------------------------------------------
# satellite: exact reducers report identically zero, hierarchical reports
# the OUTER stage's error
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "reducer",
    [
        ExactReducer(),
        ExactReducer(bucket_bytes=512),
        ExactReducer(packed=False),
    ],
    ids=["flat", "bucketed", "unpacked"],
)
def test_exact_compression_error_identically_zero(reducer):
    send = _template()
    err = float(reducer.compression_error({}, send, None))
    assert err == 0.0  # equality: exactness is a fact, not an estimate
    for vals in _get(reducer.fidelity_stats({}, send)).values():
        assert vals["rel_error"] == 0.0
        assert vals["cosine_sim"] == 1.0
        assert vals["quantized_share"] == 0.0


def test_powersgd_rel_error_positive_and_consistent():
    send = _template()
    reducer = PowerSGDReducer(random_seed=7, compression_rank=1)
    state = reducer.init(send)
    flat = float(reducer.compression_error(state, send, None))
    assert flat > 0.0  # rank-1 of real matrices must lose something
    stats = _get(reducer.fidelity_stats(state, send))
    grouped = [v["rel_error"] for g, v in stats.items() if g != "powersgd.rank1"]
    assert all(e > 0.0 for e in grouped)
    assert stats["powersgd.rank1"]["rel_error"] == 0.0  # exact fallthrough
    for vals in stats.values():
        assert -1.0 <= vals["cosine_sim"] <= 1.0 + 1e-6


def test_hierarchical_reports_outer_error_not_inner(devices):
    """The hierarchical probe must surface the slow-fabric compressor's own
    distortion — not the inner exact stage's zero."""
    mesh2d = make_mesh(axis_sizes=(2, 4), axis_names=("dcn", "ici"))
    outer = PowerSGDReducer(random_seed=3, compression_rank=1)
    hier = HierarchicalReducer(outer, mesh2d, "ici", "dcn")
    send = _template()
    state = hier.init(send)
    hier_err = float(hier.compression_error(state, send))
    outer_err = float(outer.compression_error(state, send, None))
    assert hier_err == outer_err > 0.0  # delegation, not re-derivation
    stats = _get(hier.fidelity_stats(state, send))
    inner = {g: v for g, v in stats.items() if g.startswith("inner.")}
    outer_groups = {g: v for g, v in stats.items() if g.startswith("outer.")}
    assert inner and outer_groups
    assert all(v["rel_error"] == 0.0 for v in inner.values())
    assert any(v["rel_error"] > 0.0 for v in outer_groups.values())


def test_exact_in_exact_hierarchy_all_groups_zero(devices):
    mesh2d = make_mesh(axis_sizes=(2, 4), axis_names=("dcn", "ici"))
    hier = HierarchicalReducer(ExactReducer(), mesh2d, "ici", "dcn")
    send = _template()
    assert float(hier.compression_error(hier.init(send), send)) == 0.0
    for vals in _get(hier.fidelity_stats(hier.init(send), send)).values():
        assert vals["rel_error"] == 0.0


def test_powersgd_bf16_wire_flags_quantized_share():
    send = _template()
    bf16 = PowerSGDReducer(compression_rank=2, compression_dtype="bfloat16")
    fp32 = PowerSGDReducer(compression_rank=2)
    s16 = _get(bf16.fidelity_stats(bf16.init(send), send))
    s32 = _get(fp32.fidelity_stats(fp32.init(send), send))
    assert all(v["quantized_share"] == 1.0 for v in s16.values())
    assert all(v["quantized_share"] == 0.0 for v in s32.values())


def test_fidelity_stats_jit_safe_static_keys():
    """The probe runs inside a separately-jitted health fn: group keys must
    be static (host strings), values traced scalars."""
    send = _template()
    reducer = PowerSGDReducer(random_seed=5, compression_rank=2)
    state = reducer.init(send)

    @jax.jit
    def probe(send):
        return reducer.fidelity_stats(state, send, None, None)

    stats = _get(probe(send))
    assert set(stats) == set(reducer.fidelity_group_tags(send))


def test_make_health_fn_nests_fidelity_with_legacy_flat_keys(devices):
    """The health probe adds the per-group ``fidelity`` sub-dict WITHOUT
    touching the flat legacy keys the event schema already promises."""
    from network_distributed_pytorch_tpu.parallel.trainer import (
        make_health_fn,
        make_train_step,
        stateless_loss,
    )

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    params = {"w": jax.random.normal(k1, (32, 16))}
    loss = stateless_loss(
        lambda p, b: jnp.mean((b[0] @ p["w"] - b[1]) ** 2)
    )
    reducer = PowerSGDReducer(compression_rank=2, matricize="last")
    step = make_train_step(
        loss, reducer, params, 0.05, mesh=None, donate_state=False
    )
    state = step.init_state(params)
    batch = (jax.random.normal(k2, (16, 32)), jax.random.normal(k3, (16, 16)))
    health = make_health_fn(loss, reducer)  # mesh=None: collective-free
    out = jax.device_get(health(state, batch))
    flat = {"grad_norm", "ef_memory_norm", "powersgd_rel_error", "loss"}
    assert flat <= set(out)
    fid = out["fidelity"]
    assert set(fid) == set(reducer.fidelity_group_tags(params))
    for vals in fid.values():
        assert {"rel_error", "cosine_sim", "ef_norm", "quantized_share"} <= set(
            vals
        )
    assert any(float(v["rel_error"]) > 0.0 for v in fid.values())


# ---------------------------------------------------------------------------
# satellite: the ledger<->fidelity join — every group's tag is byte-priced
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "make_reducer,axis",
    [
        (lambda: ExactReducer(), "data"),
        (lambda: ExactReducer(bucket_bytes=512), "data"),
        (lambda: PowerSGDReducer(compression_rank=2), "data"),
        (
            lambda: PowerSGDReducer(
                compression_rank=2, compression_dtype="bfloat16"
            ),
            "data",
        ),
    ],
    ids=["exact-flat", "exact-bucketed", "powersgd", "powersgd-bf16"],
)
def test_fidelity_groups_join_wire_ledger(make_reducer, axis):
    reducer = make_reducer()
    send = _template()
    tags = reducer.fidelity_group_tags(send)
    assert tags  # every reducer must declare its groups
    priced = {
        e.tag for e in reducer_ledger_entries(reducer, send, axis, n_workers=2)
    }
    orphans = {g: t for g, t in tags.items() if t not in priced}
    assert not orphans, f"fidelity tags not byte-priced: {orphans} vs {priced}"
    # the stats dict and the tag map must agree on the group universe
    state = reducer.init(send) if hasattr(reducer, "init") else {}
    assert set(_get(reducer.fidelity_stats(state, send))) == set(tags)


def test_hierarchical_fidelity_groups_join_ledger(devices):
    mesh2d = make_mesh(axis_sizes=(2, 4), axis_names=("dcn", "ici"))
    hier = HierarchicalReducer(
        PowerSGDReducer(compression_rank=2), mesh2d, "ici", "dcn"
    )
    send = _template()
    tags = hier.fidelity_group_tags(send)
    priced = {e.tag for e in hier.ledger_entries(send, n_workers=2)}
    orphans = {g: t for g, t in tags.items() if t not in priced}
    assert not orphans, f"hierarchical tags not priced: {orphans} vs {priced}"
    assert any(g.startswith("outer.") for g in tags)
    assert any(g.startswith("inner.") for g in tags)


def test_tracker_events_join_ledger_and_flag_orphans():
    """FidelityEvents carry the reducer's tag for known groups; an unknown
    group rides its own key so the join test sees it loudly."""
    reducer = PowerSGDReducer(compression_rank=2)
    send = _template()
    tags = reducer.fidelity_group_tags(send)
    tracker = FidelityTracker(tags, rank=0, label="t")
    stats = _get(reducer.fidelity_stats(reducer.init(send), send))
    events = tracker.events(4, stats, epoch=1)
    priced = {
        e.tag for e in reducer_ledger_entries(reducer, send, "data", n_workers=2)
    }
    assert events and all(ev.tag in priced for ev in events)
    assert all(ev.step == 4 and ev.epoch == 1 and ev.rank == 0 for ev in events)
    orphan = tracker.events(5, {"mystery.group": {"rel_error": 0.5}})
    assert orphan[0].tag == "mystery.group"  # not silently dropped


# ---------------------------------------------------------------------------
# the tracker: EF growth and drift attachment
# ---------------------------------------------------------------------------


def test_tracker_ef_growth_rate():
    tracker = FidelityTracker({"g": "g"})
    (first,) = tracker.events(0, {"g": {"ef_norm": 2.0}})
    assert first.ef_growth == 0.0  # no previous sample
    (second,) = tracker.events(1, {"g": {"ef_norm": 3.0}})
    assert second.ef_growth == pytest.approx(0.5)
    (third,) = tracker.events(2, {"g": {"ef_norm": 1.5}})
    assert third.ef_growth == pytest.approx(-0.5)
    # a dead-zero previous EF norm must not divide: growth clamps to 0
    tracker2 = FidelityTracker()
    tracker2.events(0, {"g": {"ef_norm": 0.0}})
    (ev,) = tracker2.events(1, {"g": {"ef_norm": 1.0}})
    assert ev.ef_growth == 0.0


def test_tracker_attaches_drift_scalars():
    tracker = FidelityTracker({"a": "a", "b": "b"})
    events = tracker.events(
        0,
        {"a": {"rel_error": 0.1}, "b": {"rel_error": 0.2}},
        drift={"replica_drift": 0.25, "anchor_drift": 0.5},
    )
    assert [e.group for e in events] == ["a", "b"]  # sorted, stable
    assert all(e.replica_drift == 0.25 for e in events)
    assert all(e.anchor_drift == 0.5 for e in events)


def test_replica_drift_stats_zero_for_agreeing_replicas():
    same = {"w": jnp.ones((4, 3, 2))}
    d = {k: float(v) for k, v in replica_drift_stats(same).items()}
    assert d["replica_drift"] == pytest.approx(0.0, abs=1e-6)
    assert d["anchor_drift"] == 0.0  # no anchors given
    walked = {"w": jnp.stack([jnp.ones((3, 2)), jnp.full((3, 2), 3.0)])}
    d2 = {k: float(v) for k, v in replica_drift_stats(walked).items()}
    assert d2["replica_drift"] > 0.0
    anchors = {"w": jnp.ones((3, 2))}
    d3 = replica_drift_stats(walked, anchors)
    assert float(d3["anchor_drift"]) > 0.0


# ---------------------------------------------------------------------------
# summary: per-group aggregation and worst-group blame
# ---------------------------------------------------------------------------


def _fid_rec(step, group, rel, tag=None, ef=0.0, **kw):
    return FidelityEvent(
        step=step, group=group, tag=tag or group, rel_error=rel,
        ef_norm=ef, **kw
    ).record()


def test_summary_blames_sustained_worst_group_by_mean():
    records = []
    for s in range(10):
        records.append(_fid_rec(s, "steady", 0.3))
        # one spectacular spike, otherwise clean: mean ~0.1 < 0.3
        records.append(_fid_rec(s, "spiky", 1.0 if s == 0 else 0.0))
    summary = fidelity_summary(records)
    assert summary["samples"] == 20
    assert summary["worst_group"] == "steady"  # sustained beats spike
    assert summary["rel_error"] == pytest.approx(0.3)
    assert summary["groups"]["spiky"]["max_rel_error"] == 1.0
    assert summary["groups"]["spiky"]["mean_rel_error"] == pytest.approx(0.1)


def test_summary_tracks_ef_and_drift_extremes():
    records = [
        _fid_rec(0, "g", 0.1, ef=1.0, ef_growth=0.0, replica_drift=0.1),
        _fid_rec(2, "g", 0.2, ef=5.0, ef_growth=4.0, replica_drift=0.4),
        _fid_rec(4, "g", 0.1, ef=2.0, ef_growth=-0.6, replica_drift=0.2),
    ]
    s = fidelity_summary(records)
    g = s["groups"]["g"]
    assert (g["first_step"], g["last_step"]) == (0, 4)
    assert g["max_ef_norm"] == 5.0 and g["last_ef_norm"] == 2.0
    assert g["max_ef_growth"] == 4.0
    assert s["replica_drift"]["max"] == pytest.approx(0.4)
    assert s["replica_drift"]["last"] == pytest.approx(0.2)


def test_summary_empty_and_non_fidelity_records():
    s = fidelity_summary([{"event": "step", "step": 1}])
    assert s["samples"] == 0 and s["worst_group"] is None
    assert s["rel_error"] == 0.0


# ---------------------------------------------------------------------------
# the accuracy-per-byte frontier
# ---------------------------------------------------------------------------


def _step_rec(step, epoch, loss, byts):
    return {
        "event": "step", "step": step, "epoch": epoch, "loss": loss,
        "bits_cumulative": byts * 8,
    }


def _policy_rec(epoch, action, before, after, idx):
    return {
        "event": "policy", "epoch": epoch, "action": action,
        "rung_before": before, "rung_after": after, "rung_index_after": idx,
    }


def test_frontier_segments_by_rung_and_prices_bytes():
    records = [
        _step_rec(s, s // 4, 1.0 / (s + 1), (s + 1) * 100) for s in range(12)
    ]
    records.append(_policy_rec(2, "ascend", "compress", "baseline", 0))
    f = frontier_from_events(records)
    assert f["steps"] == 12 and f["total_bytes"] == 1200
    assert [r["rung"] for r in f["rungs"]] == ["compress", "baseline"]
    first, second = f["rungs"]
    # boundary: first step whose epoch >= 2 -> step 8
    assert (first["start_step"], first["end_step"]) == (0, 7)
    assert (second["start_step"], second["end_step"]) == (8, 11)
    assert first["bytes"] + second["bytes"] == f["total_bytes"]
    assert second["bytes_cumulative_end"] == 1200
    # the toy loss 1/(s+1) is monotone decreasing: both drops positive
    assert first["loss_drop"] > 0 and second["loss_drop"] > 0
    assert second["loss_drop_per_gb"] == pytest.approx(
        second["loss_drop"] / (second["bytes"] / 1e9)
    )


def test_frontier_without_policies_is_one_run_segment():
    records = [_step_rec(s, 0, 1.0 - 0.1 * s, (s + 1) * 10) for s in range(5)]
    f = frontier_from_events(records)
    assert [r["rung"] for r in f["rungs"]] == ["run"]
    assert f["rungs"][0]["steps"] == 5


def test_frontier_dedups_multirank_merge():
    """A merged run-dir replays every rank's StepEvents and PolicyEvents;
    the frontier must count each step and transition once."""
    base = [_step_rec(s, s // 2, 1.0 / (s + 1), (s + 1) * 10) for s in range(6)]
    pol = [_policy_rec(1, "ascend", "compress", "baseline", 0)]
    doubled = base + pol + base + pol  # rank 0 + rank 1 shards interleaved
    f = frontier_from_events(doubled)
    assert f["steps"] == 6
    assert len(f["rungs"]) == 2
    assert f["total_bytes"] == 60


def test_frontier_empty():
    f = frontier_from_events([])
    assert f == {
        "rungs": [], "total_bytes": 0, "final_loss": None, "steps": 0
    }


# ---------------------------------------------------------------------------
# streaming detectors
# ---------------------------------------------------------------------------


def test_fidelity_collapse_floor_and_sustain():
    det = FidelityCollapseDetector(DetectorConfig())
    # clean samples under the absolute floor never fire
    for _ in range(10):
        assert det.observe(0.02) is None
    # one degraded sample: sustain=2 holds fire
    assert det.observe(0.2) is None
    alert = det.observe(0.2)
    assert alert is not None and alert.alert == "fidelity_collapse"
    assert alert.severity == "warn"  # 0.2 < the 0.5 critical absolute


def test_fidelity_collapse_critical_past_absolute():
    det = FidelityCollapseDetector(DetectorConfig())
    det.observe(0.02)
    det.observe(20.0)
    alert = det.observe(20.0)
    assert alert is not None and alert.severity == "critical"


def test_fidelity_collapse_baseline_frozen_while_firing():
    cfg = DetectorConfig()
    det = FidelityCollapseDetector(cfg)
    for _ in range(5):
        det.observe(0.01)
    base = det._ewma.mean
    det.observe(5.0)
    det.observe(5.0)  # fires; collapsed samples must not raise the envelope
    assert det._ewma.mean == base


def test_fidelity_collapse_fires_on_zero_baseline_group():
    """An exact group's baseline is identically zero — the absolute floor
    must still catch error materializing out of nowhere."""
    det = FidelityCollapseDetector(DetectorConfig())
    for _ in range(4):
        assert det.observe(0.0) is None
    det.observe(0.3)
    assert det.observe(0.3) is not None


def test_ef_blowup_needs_nonzero_baseline():
    det = EfBlowupDetector(DetectorConfig())
    for _ in range(10):
        assert det.observe(0.0) is None
    # even a jump from dead zero never fires (exact groups)
    assert det.observe(100.0) is None


def test_ef_blowup_warn_and_critical_bands():
    cfg = DetectorConfig()
    det = EfBlowupDetector(cfg)
    for _ in range(max(cfg.ef_min_obs, cfg.ef_sustain) + 1):
        assert det.observe(1.0) is None
    for _ in range(cfg.ef_sustain - 1):
        det.observe(cfg.ef_factor * 1.0 + 1.0)
    warn = det.observe(cfg.ef_factor * 1.0 + 1.0)
    assert warn is not None and warn.severity == "warn"
    det2 = EfBlowupDetector(cfg)
    for _ in range(cfg.ef_min_obs + 1):
        det2.observe(1.0)
    for _ in range(cfg.ef_sustain - 1):
        det2.observe(cfg.ef_critical_factor * 2.0)
    crit = det2.observe(cfg.ef_critical_factor * 2.0)
    assert crit is not None and crit.severity == "critical"


def test_monitor_keys_fidelity_detectors_per_group():
    mon = HealthMonitor(DetectorConfig())
    # group a collapses; group b stays clean — only a's detector may fire
    fired = []
    for step in range(8):
        fired += mon.observe_fidelity("a", 5.0 if step >= 2 else 0.01, step=step)
        fired += mon.observe_fidelity("b", 0.01, step=step)
    assert fired and all(a.message.startswith("group a:") for a in fired)
    assert mon.fired_by_kind().get("fidelity_collapse", 0) >= 1


# ---------------------------------------------------------------------------
# live plane gauges
# ---------------------------------------------------------------------------


def test_ingest_fidelity_record_sets_labeled_gauges():
    reg = MetricRegistry()
    rec = FidelityEvent(
        step=3, group="powersgd.g0:16x8r2", tag="powersgd.P",
        rel_error=0.25, cosine_sim=0.9, ef_norm=1.5, ef_growth=0.1,
        quantized_share=1.0, replica_drift=0.05, anchor_drift=0.01,
        rank=1,
    ).record()
    # the record's own rank wins over the shard-fallback argument
    ingest_record(reg, rec, rank=7)
    labels = {"rank": "1", "group": "powersgd.g0:16x8r2"}
    assert reg.get_gauge("live_fidelity_rel_error", **labels) == 0.25
    assert reg.get_gauge("live_ef_norm", **labels) == 1.5
    assert reg.get_gauge("live_ef_growth", **labels) == pytest.approx(0.1)
    assert reg.get_gauge("live_fidelity_cosine_sim", **labels) == 0.9
    # drift scalars are whole-state: rank-labeled, ungrouped
    assert reg.get_gauge("live_replica_drift", rank="1") == 0.05
    assert reg.get_gauge("live_anchor_drift", rank="1") == 0.01


# ---------------------------------------------------------------------------
# the controller's fidelity ascend
# ---------------------------------------------------------------------------


def _ladder():
    return [Rung("baseline", {}), Rung("compress", {"reducer": "powersgd"})]


def test_fidelity_alert_ascends_any_severity():
    c = FallbackController(ladder=_ladder(), start_index=1)
    d = c.nudge("fidelity_collapse", epoch=0, severity="warn")
    assert d is not None and d.action == "ascend"
    assert d.trigger == "alert:fidelity_collapse:warn"
    assert c.rung.name == "baseline"
    assert c.nudged_epoch == 0


def test_ef_blowup_alert_ascends_too():
    c = FallbackController(ladder=_ladder(), start_index=1)
    d = c.nudge("ef_blowup", epoch=2, severity="critical")
    assert d is not None and d.action == "ascend"


def test_fidelity_ascend_holds_at_top_rung():
    c = FallbackController(ladder=_ladder(), start_index=0)
    assert c.nudge("fidelity_collapse", epoch=0, severity="critical") is None
    assert c.rung.name == "baseline"
    # the no-op must NOT spend the epoch's nudge budget
    assert c.nudged_epoch is None


def test_one_fidelity_nudge_per_epoch():
    ladder = _ladder() + [Rung("compress-low", {})]
    c = FallbackController(ladder=ladder, start_index=2)
    assert c.nudge("fidelity_collapse", epoch=1, severity="warn") is not None
    assert c.nudge("fidelity_collapse", epoch=1, severity="warn") is None
    assert c.index == 1  # one rung, not two
    assert c.nudge("fidelity_collapse", epoch=2, severity="warn") is not None
    assert c.index == 0
