"""Fallback-controller and deadline-derivation units (jax-free, fast).

The degraded-fabric policy layer (``resilience.controller``) is pure
host-side bookkeeping, so every behavior the e2e chaos tests rely on is
pinned here without a backend: the ladder's documented order, the
descend/ascend hysteresis (consecutive evidence; the indeterminate middle
band resets both streaks), the bandwidth-collapse trigger relative to the
per-rung learned best, PolicyEvent emission, and the collective-deadline
budget (modeled time vs measured p50 vs the floor).
"""

import pytest

from network_distributed_pytorch_tpu.observe import MemorySink, Telemetry
from network_distributed_pytorch_tpu.resilience import (
    DEFAULT_LADDER,
    EpochHealth,
    FallbackController,
    Rung,
    derive_collective_deadline,
)


def _health(epoch=0, achieved=0.0, expiries=0, degraded=0, stragglers=0):
    return EpochHealth(
        epoch=epoch, step_p50_s=0.01, achieved_bytes_per_s=achieved,
        deadline_expiries=expiries, degraded_steps=degraded,
        stragglers=stragglers,
    )


# ---- ladder shape ----------------------------------------------------------


def test_default_ladder_documented_order():
    names = [r.name for r in DEFAULT_LADDER]
    assert names == [
        "baseline", "compress", "compress-low-rank", "localsgd",
        "hierarchical", "hierarchical-async",
    ]
    # baseline overrides nothing; the first descent is the first rung that
    # changes the bytes; each compression rung names the reducer; the
    # localsgd rung widens the sync period; the bottom two rungs go
    # two-level (and finally async) — the geo-resilient end of the ladder
    assert DEFAULT_LADDER[0].overrides == {}
    for rung in DEFAULT_LADDER[1:4]:
        assert rung.overrides["reducer"] == "powersgd"
    assert DEFAULT_LADDER[2].overrides["reducer_rank"] < (
        DEFAULT_LADDER[1].overrides["reducer_rank"]
    )
    assert "sync_every" not in DEFAULT_LADDER[2].overrides
    assert DEFAULT_LADDER[3].overrides["sync_every"] > 1
    for rung in DEFAULT_LADDER[4:]:
        assert rung.overrides["reducer"] == "hierarchical"
    assert DEFAULT_LADDER[5].overrides.get("outer_async")
    assert (
        DEFAULT_LADDER[5].overrides["sync_every"]
        > DEFAULT_LADDER[4].overrides["sync_every"]
    )


@pytest.mark.parametrize("rung", ["baseline", "compress", "compress-low-rank"])
def test_every_default_rung_builds_a_step(devices, rung):
    """The rungs one flat CPU mesh can build, through the step factory the
    adaptive loop is driven with: a rung's overrides are knobs a reducer
    still takes, and the step it builds runs and prices its wire bytes."""
    import numpy as np
    from test_chaos import _adaptive_setup, _batches

    step_factory, params = _adaptive_setup()
    overrides = next(r.overrides for r in DEFAULT_LADDER if r.name == rung)
    step = step_factory(dict(overrides))
    expected = "powersgd" if overrides.get("reducer") == "powersgd" else "exact"
    assert expected in step.comm_config["reducer"]
    assert step.comm_config.get("reducer_rank") == overrides.get("reducer_rank")
    _, loss = step(step.init_state(params), next(_batches(0)))
    assert np.isfinite(float(loss))
    assert step.ledger.total_bits() == step.bits_per_step > 0


def test_ladder_validation():
    with pytest.raises(ValueError, match="at least one rung"):
        FallbackController(ladder=[])
    with pytest.raises(ValueError, match="outside ladder"):
        FallbackController(start_index=len(DEFAULT_LADDER))


# ---- descend / ascend walking ----------------------------------------------


def test_descends_in_order_and_stops_at_bottom():
    c = FallbackController(descend_after=1)
    seen = []
    for epoch in range(len(DEFAULT_LADDER) + 2):
        d = c.observe(_health(epoch=epoch, expiries=1))
        if d is not None:
            assert d.action == "descend"
            assert d.rung_index_after == d.rung_index_before + 1
            assert d.overrides == DEFAULT_LADDER[d.rung_index_after].overrides
            seen.append((d.rung_before, d.rung_after))
    # walked every edge exactly once, then held at the bottom rung
    assert seen == [
        (a.name, b.name) for a, b in zip(DEFAULT_LADDER, DEFAULT_LADDER[1:])
    ]
    assert c.rung.name == "hierarchical-async"


def test_descend_requires_consecutive_degraded_epochs():
    c = FallbackController(descend_after=2)
    assert c.observe(_health(epoch=0, degraded=1)) is None
    # an indeterminate epoch (no faults, no bandwidth evidence) resets the
    # streak — a move needs CONSECUTIVE evidence
    assert c.observe(_health(epoch=1)) is None
    assert c.observe(_health(epoch=2, degraded=1)) is None
    d = c.observe(_health(epoch=3, degraded=1))
    assert d is not None and d.action == "descend"
    assert "degraded_steps" in d.trigger


def test_ascend_requires_consecutive_healthy_epochs():
    c = FallbackController(start_index=1, recover_after=2)
    # first healthy epoch seeds the rung's best and starts the streak
    assert c.observe(_health(epoch=0, achieved=100.0)) is None
    # indeterminate (achieved in the middle band) resets the streak
    assert c.observe(_health(epoch=1, achieved=60.0)) is None
    assert c.observe(_health(epoch=2, achieved=100.0)) is None
    d = c.observe(_health(epoch=3, achieved=95.0))
    assert d is not None and d.action == "ascend"
    assert d.rung_index_after == 0
    assert "recovered" in d.trigger
    # at the top rung, healthy epochs never ascend past the ladder
    c2 = FallbackController(recover_after=1)
    assert c2.observe(_health(epoch=0, achieved=10.0)) is None
    assert c2.observe(_health(epoch=1, achieved=10.0)) is None
    assert c2.index == 0


def test_bandwidth_collapse_is_a_degraded_trigger():
    c = FallbackController(descend_after=1, degrade_factor=0.5)
    assert c.observe(_health(epoch=0, achieved=100.0)) is None  # seeds best
    d = c.observe(_health(epoch=1, achieved=40.0))  # < 0.5 x best
    assert d is not None and d.action == "descend"
    assert "achieved_bytes_per_s" in d.trigger
    # per-rung best: the NEW rung has no history, so the same 40 B/s is
    # indeterminate there (seeds that rung's best instead of triggering)
    assert c.observe(_health(epoch=2, achieved=40.0)) is None
    assert c.index == 1


def test_every_fault_counter_triggers_degraded():
    for kw in ({"expiries": 1}, {"degraded": 2}, {"stragglers": 3}):
        c = FallbackController(descend_after=1)
        d = c.observe(_health(**kw))
        assert d is not None and d.action == "descend", kw


# ---- PolicyEvent emission --------------------------------------------------


def test_record_emits_policy_event_with_byte_claims():
    sink = MemorySink()
    c = FallbackController(
        descend_after=1, telemetry=Telemetry([sink]), rank=3
    )
    d = c.observe(_health(epoch=5, expiries=2))
    c.record(d, predicted_bytes_per_step=1348.0, realized_bytes_per_step=4428.0)
    events = [r for r in sink.records if r.get("event") == "policy"]
    assert len(events) == 1
    (e,) = events
    assert e["action"] == "descend"
    assert e["epoch"] == 5
    assert e["rung_before"] == "baseline" and e["rung_after"] == "compress"
    assert e["overrides"] == {"reducer": "powersgd", "reducer_rank": 4}
    assert e["predicted_bytes_per_step"] == 1348.0
    assert e["realized_bytes_per_step"] == 4428.0
    assert e["rank"] == 3
    assert "deadline_expiries" in e["trigger"]
    assert c.decisions == [d]


def test_custom_ladder_and_overrides_copying():
    ladder = [Rung("a", {}), Rung("b", {"bucket_bytes": 2})]
    c = FallbackController(ladder=ladder, descend_after=1)
    d = c.observe(_health(expiries=1))
    d.overrides["bucket_bytes"] = 999  # mutating the decision's copy...
    assert c.overrides == {"bucket_bytes": 2}  # ...never reaches the rung


# ---- collective-deadline derivation ----------------------------------------


def test_deadline_floor_dominates_tiny_payloads():
    # a few bytes on ICI models out at microseconds; the floor holds
    assert derive_collective_deadline(16, 8, "ICI(v5e)", floor_s=0.25) == 0.25


def test_deadline_measured_p50_dominates_optimistic_model():
    # the model says microseconds; the fabric measurably delivers 100ms —
    # the deadline follows the measurement times the slack
    budget = derive_collective_deadline(
        16, 8, "ICI(v5e)", measured_p50_s=0.1, slack=4.0, floor_s=0.05
    )
    assert budget == pytest.approx(0.4)


def test_deadline_model_scales_with_payload_and_fabric():
    from network_distributed_pytorch_tpu.observe.analytics import (
        _load_utils_module,
    )

    bw = _load_utils_module("bandwidth")
    payload = 100 * (1 << 20)  # 100 MB on 1GbE: seconds, far above floor
    budget = derive_collective_deadline(
        payload, 8, "1GbE", slack=2.0, floor_s=0.05
    )
    assert budget == pytest.approx(
        bw.allreduce_time_s(payload, 8, "1GbE") * 2.0
    )
    # a faster fabric derives a tighter deadline for the same payload
    assert budget > derive_collective_deadline(
        payload, 8, "100GbE", slack=2.0, floor_s=0.05
    )


# ---- mid-epoch alert nudges (the live plane's entry point) -----------------


def test_nudge_critical_descends_immediately():
    c = FallbackController(descend_after=3)  # boundary would need 3 epochs
    d = c.nudge("grad_spike", epoch=2, severity="critical")
    assert d is not None and d.action == "descend"
    assert d.trigger == "alert:grad_spike:critical"
    assert d.epoch == 2
    assert c.index == 1
    assert c.nudged_epoch == 2


def test_nudge_comm_shaped_warn_descends_immediately():
    for alert in ("bandwidth_collapse", "step_time_drift"):
        c = FallbackController(descend_after=3)
        d = c.nudge(alert, epoch=0, severity="warn")
        assert d is not None and d.trigger == f"alert:{alert}:warn"


def test_nudge_other_warn_precharges_streak():
    c = FallbackController(descend_after=2)
    # a non-comm warn returns no decision but pre-charges the streak:
    # the next degraded boundary epoch descends one epoch sooner
    assert c.nudge("grad_spike", epoch=0, severity="warn") is None
    assert c.index == 0
    d = c.observe(_health(epoch=0, degraded=1))
    assert d is not None and d.action == "descend"


def test_nudge_at_most_one_descend_per_epoch():
    c = FallbackController()
    assert c.nudge("grad_spike", epoch=1, severity="critical") is not None
    # same epoch: the decision budget is spent (even for a comm alert)
    assert c.nudge("bandwidth_collapse", epoch=1, severity="warn") is None
    assert c.index == 1
    # a later epoch spends its own budget
    assert c.nudge("grad_spike", epoch=2, severity="critical") is not None
    assert c.index == 2


def test_nudged_epoch_boundary_observe_is_noop():
    c = FallbackController(descend_after=1)
    assert c.nudge("grad_spike", epoch=3, severity="critical") is not None
    # the SAME epoch's boundary verdict must not double-move on the same
    # evidence, no matter how degraded the numbers look
    assert c.observe(_health(epoch=3, expiries=5, degraded=9)) is None
    assert c.index == 1
    # the NEXT epoch's boundary owns its decision again
    d = c.observe(_health(epoch=4, degraded=1))
    assert d is not None and d.rung_index_after == 2


def test_nudge_at_bottom_rung_holds():
    c = FallbackController(start_index=len(DEFAULT_LADDER) - 1)
    assert c.nudge("grad_spike", epoch=0, severity="critical") is None
    assert c.index == len(DEFAULT_LADDER) - 1
    # the budget was NOT spent by the refused move
    assert c.nudged_epoch is None


def test_nudge_descend_emits_policy_event_with_alert_trigger():
    sink = MemorySink()
    telemetry = Telemetry([sink])
    c = FallbackController(telemetry=telemetry, rank=0)
    d = c.nudge("bandwidth_collapse", epoch=0, severity="critical")
    c.record(d, predicted_bytes_per_step=10.0, realized_bytes_per_step=100.0)
    telemetry.close()
    recs = [r for r in sink.records if r["event"] == "policy"]
    assert len(recs) == 1
    assert recs[0]["trigger"] == "alert:bandwidth_collapse:critical"
    assert recs[0]["action"] == "descend"
