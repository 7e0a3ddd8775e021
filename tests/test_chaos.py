"""Chaos matrix + checkpoint hardening.

The fault-injection half of the resilience story: every recoverable fault
kind in ``resilience.chaos`` is injected into a real (small) training run
and the run must complete with the documented recovery — and, for the
state-preserving faults, land on EXACTLY the parameters of a clean run.
The checkpoint tests prove the commit protocol: a torn directory is never
selected, a bit-flip is caught by checksums at restore, and ``restore_latest``
falls back to the previous good step with a telemetry trail.
"""

import collections
import json
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from network_distributed_pytorch_tpu.experiments.common import (
    resilient_train_loop,
)
from network_distributed_pytorch_tpu.models import SmallCNN
from network_distributed_pytorch_tpu.observe import MemorySink, Telemetry
from network_distributed_pytorch_tpu.parallel import PowerSGDReducer, make_mesh
from network_distributed_pytorch_tpu.parallel.trainer import (
    make_train_step,
    stateless_loss,
)
from network_distributed_pytorch_tpu.resilience import (
    COMM_FAULTS,
    CORRELATED_FAULTS,
    FAULT_KINDS,
    INJECTION_SITES,
    PROCESS_FAULTS,
    ChaosPlan,
    ChaosStep,
    ChaosTransientError,
    CheckpointUnwritableError,
    CollectiveWatchdog,
    CommDeadlineGuard,
    CommEscalationError,
    CommFaultInjector,
    FallbackController,
    FaultSpec,
    GuardedStep,
    NonFiniteLossError,
    PreemptionGuard,
    Rung,
    chaos_batches,
    check_fault_registry,
    guarded_batches,
)
from network_distributed_pytorch_tpu.resilience.chaos import (
    bitflip_checkpoint,
    make_checkpoint_unwritable,
    restore_checkpoint_writable,
    tear_checkpoint,
)
from network_distributed_pytorch_tpu.utils import cross_entropy_loss
from network_distributed_pytorch_tpu.utils.checkpoint import (
    COMMITTED_MARKER,
    CHECKSUM_MANIFEST,
    committed_step_paths,
    gc_checkpoints,
    is_committed,
    latest_step_path,
    read_topology,
    restore_latest,
    save_checkpoint,
    verify_checkpoint,
)

IMG = (8, 8, 3)
EPOCHS = 2
BATCH = 32


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


def _batches(epoch, steps=3):
    rng = np.random.RandomState(1000 + epoch)
    means = np.random.RandomState(999).randn(10, *IMG)
    for _ in range(steps):
        y = rng.randint(0, 10, BATCH)
        x = means[y] + 0.5 * rng.randn(BATCH, *IMG)
        yield jnp.asarray(x, jnp.float32), jnp.asarray(y)


def _telemetry():
    sink = MemorySink()
    return Telemetry([sink]), sink


def _kinds(sink):
    return [r.get("kind") for r in sink.records if r.get("event") == "failure"]


def _run(tmp_path, name, plan=None, **kw):
    step, params = _setup()
    telemetry, sink = _telemetry()
    state, _, _ = resilient_train_loop(
        step, step.init_state(params), _batches, EPOCHS,
        checkpoint_dir=str(tmp_path / name), telemetry=telemetry,
        run_name=name, chaos_plan=plan, **kw,
    )
    return state, sink


def _assert_params_equal(a, b):
    for x, y in zip(
        jax.tree_util.tree_leaves(a.params), jax.tree_util.tree_leaves(b.params)
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# chaos matrix: every recoverable fault kind x its documented recovery
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize(
    "kind", ["loader_bad_batch", "loader_short_batch"]
)
def test_chaos_matrix_loader_faults_dropped(devices, tmp_path, kind):
    """A poisoned/short batch is injected, detected, and dropped; the run
    completes on the remaining batches."""
    plan = ChaosPlan([FaultSpec(kind=kind, step=1)], seed=3)
    state, sink = _run(
        tmp_path, f"chaos-{kind}", plan=plan,
        guard_batches=True, expected_batch=BATCH,
    )
    kinds = _kinds(sink)
    assert "chaos_injected" in kinds
    assert "bad_batch_dropped" in kinds
    assert all(
        np.isfinite(np.asarray(l)).all()
        for l in jax.tree_util.tree_leaves(state.params)
    )


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["step_transient", "step_nan"])
def test_chaos_matrix_step_faults_retried_bit_exact(devices, tmp_path, kind):
    """A transient step error / NaN loss is retried without advancing state,
    so the final parameters are BIT-IDENTICAL to a clean run."""
    clean, _ = _run(tmp_path, "clean")
    plan = ChaosPlan([FaultSpec(kind=kind, step=2)], seed=3)
    state, sink = _run(
        tmp_path, f"chaos-{kind}", plan=plan, step_retries=2,
    )
    kinds = _kinds(sink)
    assert "chaos_injected" in kinds
    assert "retry" in kinds
    _assert_params_equal(state, clean)
    for a, b in zip(
        jax.tree_util.tree_leaves(state.memories),
        jax.tree_util.tree_leaves(clean.memories),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["ckpt_torn", "ckpt_bitflip"])
def test_chaos_matrix_checkpoint_faults_fall_back(devices, tmp_path, kind):
    """A corrupted newest checkpoint is skipped at resume; the run restarts
    from the previous good epoch and still finishes all epochs."""
    plan = ChaosPlan([FaultSpec(kind=kind, step=1)], seed=3)
    # run 2 epochs, corrupting the epoch-1 checkpoint after it lands
    _run(tmp_path, "chaos-ckpt", plan=plan)
    root = str(tmp_path / "chaos-ckpt")
    if kind == "ckpt_torn":
        # torn: no marker -> not even listed as committed
        assert latest_step_path(root) == os.path.join(root, "step_0")
    else:
        # bitflip: still committed, only checksums can catch it
        assert latest_step_path(root) == os.path.join(root, "step_1")
        ok, reason = verify_checkpoint(os.path.join(root, "step_1"))
        assert not ok and "checksum mismatch" in reason

    # resume: falls back to step_0, re-trains epoch 1, emits the fallback
    step, params = _setup()
    telemetry, sink = _telemetry()
    state, _, start_epoch = resilient_train_loop(
        step, step.init_state(params), _batches, EPOCHS,
        checkpoint_dir=root, telemetry=telemetry, run_name="resume",
    )
    assert start_epoch == 1
    if kind == "ckpt_bitflip":
        assert "checkpoint_fallback" in _kinds(sink)
    # the re-save replaced the corrupt step_1 with a good one
    ok, reason = verify_checkpoint(os.path.join(root, "step_1"))
    assert ok, reason


@pytest.mark.slow
def test_chaos_full_matrix_combined(devices, tmp_path):
    """All recoverable fault kinds in ONE run — recoveries compose."""
    plan = ChaosPlan(
        [
            FaultSpec(kind="loader_bad_batch", step=0),
            FaultSpec(kind="loader_short_batch", step=3),
            FaultSpec(kind="step_transient", step=1),
            FaultSpec(kind="step_nan", step=2),
        ],
        seed=5,
    )
    state, sink = _run(
        tmp_path, "combined", plan=plan, step_retries=2,
        guard_batches=True, expected_batch=BATCH,
    )
    kinds = _kinds(sink)
    assert kinds.count("chaos_injected") == 4
    assert "bad_batch_dropped" in kinds and "retry" in kinds


# ---------------------------------------------------------------------------
# preemption grace: SIGTERM -> emergency checkpoint -> mid-epoch resume
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_preempt_grace_checkpoint_and_midepoch_resume(devices, tmp_path):
    """A ``proc_preempt`` fault SIGTERMs the process mid-epoch; the
    installed guard turns it into an emergency COMMITTED checkpoint at the
    next step boundary (epoch cursor recorded), the loop stops early, and
    the resumed run re-enters the SAME epoch at the right step — landing
    bit-identical to an uninterrupted run."""
    clean, _ = _run(tmp_path, "preempt-clean")

    plan = ChaosPlan([FaultSpec(kind="proc_preempt", step=1)], seed=3)
    step, params = _setup()
    telemetry, sink = _telemetry()
    root = str(tmp_path / "preempt")
    with PreemptionGuard(telemetry=telemetry) as guard:
        resilient_train_loop(
            step, step.init_state(params), _batches, EPOCHS,
            checkpoint_dir=root, telemetry=telemetry, run_name="preempt",
            chaos_plan=plan, preemption_guard=guard,
        )
    assert guard.checkpoint_saved
    kinds = _kinds(sink)
    assert "chaos_injected" in kinds
    assert "preempt_notice" in kinds
    assert "preempt_checkpoint" in kinds
    # the emergency save carries the mid-epoch cursor: 2 of 3 steps done
    cursor = read_topology(os.path.join(root, "step_0"))["epoch_cursor"]
    assert cursor == {"epoch": 0, "batches_done": 2}

    step2, params2 = _setup()
    telemetry2, sink2 = _telemetry()
    resumed, _, start_epoch = resilient_train_loop(
        step2, step2.init_state(params2), _batches, EPOCHS,
        checkpoint_dir=root, telemetry=telemetry2, run_name="resume",
    )
    assert start_epoch == 0  # the preempted epoch, not the next one
    msg = next(
        r["message"] for r in sink2.records if r.get("kind") == "resumed"
    )
    assert "+2 steps" in msg
    _assert_params_equal(resumed, clean)
    for a, b in zip(
        jax.tree_util.tree_leaves(resumed.memories),
        jax.tree_util.tree_leaves(clean.memories),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# chaos primitives (fast, no training loop)
# ---------------------------------------------------------------------------

def test_fault_kinds_include_proc_preempt():
    assert "proc_preempt" in PROCESS_FAULTS
    FaultSpec(kind="proc_preempt", step=0)  # accepted, not "unknown kind"


def test_preemption_guard_turns_sigterm_into_flag():
    prev = signal.getsignal(signal.SIGTERM)
    telemetry, sink = _telemetry()
    with PreemptionGuard(telemetry=telemetry, rank=1) as guard:
        assert not guard.requested
        os.kill(os.getpid(), signal.SIGTERM)  # the process survives this
        assert guard.requested
    assert signal.getsignal(signal.SIGTERM) == prev  # disposition restored
    notices = [r for r in sink.records if r.get("kind") == "preempt_notice"]
    assert len(notices) == 1
    assert notices[0]["rank"] == 1

def test_chaos_plan_roundtrip_and_once_semantics(tmp_path):
    plan = ChaosPlan(
        [
            FaultSpec(kind="proc_kill", step=2, rank=1),
            FaultSpec(kind="step_nan", step=2, rank=None, incarnation=None),
        ],
        seed=9,
    )
    path = plan.save(str(tmp_path / "plan.json"))
    loaded = ChaosPlan.load(path)
    assert loaded.seed == 9
    assert [f.kind for f in loaded.faults] == ["proc_kill", "step_nan"]

    # rank filter: rank 0 at step 2 only matches the any-rank spec
    spec = loaded.pop(("step_nan",), 2, rank=0, incarnation=5)
    assert spec is not None and spec.kind == "step_nan"
    # once-per-spec: the same trigger never fires twice
    assert loaded.pop(("step_nan",), 2, rank=0, incarnation=5) is None
    # incarnation filter: the default-0 proc_kill won't fire in life 1
    assert loaded.pop(("proc_kill",), 2, rank=1, incarnation=1) is None
    assert loaded.pop(("proc_kill",), 2, rank=1, incarnation=0) is not None


def test_fault_spec_rejects_unknown_kind():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec(kind="meteor_strike", step=0)


def test_chaos_plan_load_time_validation(tmp_path):
    """Satellite: a malformed plan refuses at LOAD time, naming the
    offending entry index — not a crash hours later at injection time."""
    with pytest.raises(ValueError, match=r"fault\[1\] must be an object"):
        ChaosPlan.from_json(
            {"faults": [{"kind": "proc_kill", "step": 0}, "zap"]}
        )
    with pytest.raises(
        ValueError, match=r"fault\[0\] invalid: unknown fault kind"
    ):
        ChaosPlan.from_json({"faults": [{"kind": "meteor", "step": 0}]})
    with pytest.raises(ValueError, match=r"fault\[0\] invalid"):
        ChaosPlan.from_json(
            {"faults": [{"kind": "proc_kill", "step": 0, "at_rank": 1}]}
        )
    with pytest.raises(
        ValueError, match=r"fault\[2\] invalid: step must be an int"
    ):
        ChaosPlan.from_json({"faults": [
            {"kind": "proc_kill", "step": 0},
            {"kind": "step_nan", "step": 1},
            {"kind": "proc_exit", "step": "soon"},
        ]})
    with pytest.raises(
        ValueError, match=r"fault\[0\] invalid: payload\['ranks'\]"
    ):
        ChaosPlan.from_json({"faults": [
            {"kind": "zone_outage", "step": 0, "payload": {"ranks": []}}
        ]})
    # ChaosPlan.load routes files through the same validation
    path = tmp_path / "bad_plan.json"
    path.write_text(json.dumps({"faults": [{"kind": "meteor", "step": 0}]}))
    with pytest.raises(ValueError, match=r"fault\[0\]"):
        ChaosPlan.load(str(path))


def test_correlated_faults_registered_and_zone_matching():
    assert set(CORRELATED_FAULTS) == {"zone_outage", "host_flap"}
    for kind in CORRELATED_FAULTS:
        assert INJECTION_SITES[kind] == "process"
    assert INJECTION_SITES["ckpt_unwritable"] == "checkpoint"
    # payload["ranks"] overrides the rank field: every zone member matches
    spec = FaultSpec(kind="zone_outage", step=3, payload={"ranks": [2, 3]})
    assert spec.matches(3, 2, 0) and spec.matches(3, 3, 0)
    assert not spec.matches(3, 0, 0)
    assert not spec.matches(2, 2, 0)  # wrong step
    # host_flap matches every incarnation; the worker's flaps cap decides
    # which lives actually die
    flap = FaultSpec(kind="host_flap", step=1, rank=0, incarnation=None)
    assert flap.matches(1, 0, 0) and flap.matches(1, 0, 5)


def test_chaos_step_transient_and_nan(devices):
    calls = []

    class FakeStep:
        bits_per_step = 123

        def __call__(self, state, batch):
            calls.append(batch)
            return state + 1, 0.5

    plan = ChaosPlan(
        [
            FaultSpec(kind="step_transient", step=0),
            FaultSpec(kind="step_nan", step=1),
        ]
    )
    telemetry, sink = _telemetry()
    wrapped = ChaosStep(FakeStep(), plan, telemetry=telemetry)
    assert wrapped.bits_per_step == 123  # delegation
    with pytest.raises(ChaosTransientError):
        wrapped(0, "b0")
    # step_nan: state NOT advanced, loss non-finite, inner never called
    state, loss = wrapped(0, "b1")
    assert state == 0 and np.isnan(loss)
    assert calls == []
    # past the schedule, the real step runs
    state, loss = wrapped(0, "b2")
    assert state == 1 and calls == ["b2"]
    assert _kinds(sink).count("chaos_injected") == 2


def test_guarded_step_retries_nan_without_advancing(devices):
    attempts = []

    class FlakyStep:
        def __call__(self, state, batch):
            attempts.append(state)
            if len(attempts) == 1:
                return state + 100, jnp.float32(float("nan"))
            return state + 1, jnp.float32(0.25)

    telemetry, sink = _telemetry()
    guarded = GuardedStep(
        FlakyStep(), retries=2, backoff_seconds=0.0, telemetry=telemetry
    )
    state, loss = guarded(0, None)
    assert state == 1  # poisoned +100 update was discarded
    assert attempts == [0, 0]  # same inputs replayed
    assert "retry" in _kinds(sink)


def test_guarded_step_exhausted_raises(devices):
    class AlwaysNaN:
        def __call__(self, state, batch):
            return state, jnp.float32(float("nan"))

    telemetry, _ = _telemetry()
    guarded = GuardedStep(
        AlwaysNaN(), retries=1, backoff_seconds=0.0, telemetry=telemetry
    )
    with pytest.raises(NonFiniteLossError):
        guarded(0, None)


def test_chaos_batches_poison_and_short(devices):
    def src(epoch):
        for _ in range(2):
            yield (np.zeros((8, 4), np.float32), np.zeros((8,), np.int32))

    plan = ChaosPlan(
        [
            FaultSpec(kind="loader_bad_batch", step=0),
            FaultSpec(kind="loader_short_batch", step=1),
        ],
        seed=2,
    )
    telemetry, sink = _telemetry()
    out = list(chaos_batches(src, plan, telemetry=telemetry)(0))
    assert np.isnan(np.asarray(out[0][0])).any()
    assert np.asarray(out[1][0]).shape[0] == 4  # halved leading dim
    assert np.asarray(out[1][1]).shape[0] == 4

    # guarded_batches drops exactly the two poisoned ones
    plan2 = ChaosPlan(
        [
            FaultSpec(kind="loader_bad_batch", step=0),
            FaultSpec(kind="loader_short_batch", step=1),
        ],
        seed=2,
    )
    poisoned = chaos_batches(src, plan2, telemetry=telemetry)
    guarded = guarded_batches(poisoned, expected_batch=8, telemetry=telemetry)
    assert list(guarded(0)) == []
    assert _kinds(sink).count("bad_batch_dropped") == 2


# ---------------------------------------------------------------------------
# checkpoint hardening: the commit protocol
# ---------------------------------------------------------------------------

def _tree(v: float):
    return {
        "w": np.full((16, 8), v, np.float32),
        "b": np.arange(8, dtype=np.float32) * v,
    }


def test_commit_protocol_artifacts(devices, tmp_path):
    root = str(tmp_path / "ck")
    final = save_checkpoint(root, _tree(1.0), step=0)
    assert final == os.path.join(os.path.abspath(root), "step_0")
    assert is_committed(final)
    assert os.path.isfile(os.path.join(final, CHECKSUM_MANIFEST))
    with open(os.path.join(final, COMMITTED_MARKER)) as f:
        assert json.load(f)["step"] == 0
    ok, reason = verify_checkpoint(final)
    assert ok, reason
    # no leftover tmp dirs
    assert not [n for n in os.listdir(root) if n.startswith("_tmp.")]


def test_save_checkpoint_unwritable_raises_typed(devices, tmp_path):
    """Satellite: a persistently unwritable checkpoint root raises the
    TYPED ``CheckpointUnwritableError`` from ``save_checkpoint`` — the
    fail-fast signal the supervisor turns into a hard stop instead of a
    restart storm. The blocker here is a parent path that is a file
    (errno ENOTDIR), which fails even for root — chmod tricks do not."""
    blocker = tmp_path / "ckroot"
    blocker.write_text("not a directory")
    with pytest.raises(CheckpointUnwritableError, match="unwritable"):
        save_checkpoint(str(blocker / "ck"), _tree(1.0), step=0)
    # OSError so orbax/IO handlers see it, NOT RuntimeError so the
    # transient-retry wrappers (GuardedStep) can never swallow it
    assert issubclass(CheckpointUnwritableError, OSError)
    assert not issubclass(CheckpointUnwritableError, RuntimeError)


def test_make_checkpoint_unwritable_roundtrip(tmp_path):
    root = tmp_path / "ck"
    root.mkdir()
    make_checkpoint_unwritable(str(root))
    assert (os.stat(root).st_mode & 0o777) == 0o500
    restore_checkpoint_writable(str(root))
    assert (os.stat(root).st_mode & 0o777) == 0o700


def test_abort_before_commit_leaves_only_tmp(devices, tmp_path):
    """The mid-save crash seam: data written, commit never ran — readers
    must see NO checkpoint at all."""
    root = str(tmp_path / "ck")
    tmp = save_checkpoint(root, _tree(1.0), step=0, _abort_before_commit=True)
    assert os.path.basename(tmp).startswith("_tmp.")
    assert os.path.isdir(tmp)
    assert not os.path.isdir(os.path.join(root, "step_0"))
    assert latest_step_path(root) is None
    assert restore_latest(root, _tree(0.0)) is None


def test_torn_checkpoint_never_selected(devices, tmp_path):
    root = str(tmp_path / "ck")
    save_checkpoint(root, _tree(1.0), step=0)
    save_checkpoint(root, _tree(2.0), step=1)
    tear_checkpoint(os.path.join(root, "step_1"))
    assert latest_step_path(root) == os.path.join(
        os.path.abspath(root), "step_0"
    )
    restored = restore_latest(root, _tree(0.0))
    assert restored is not None
    state, step = restored
    assert step == 0
    np.testing.assert_array_equal(state["w"], _tree(1.0)["w"])


def test_bitflip_caught_by_checksums_with_fallback_event(devices, tmp_path):
    root = str(tmp_path / "ck")
    save_checkpoint(root, _tree(1.0), step=0)
    save_checkpoint(root, _tree(2.0), step=1)
    bitflip_checkpoint(os.path.join(root, "step_1"), seed=4)
    # still committed — only verification can tell
    assert latest_step_path(root) == os.path.join(
        os.path.abspath(root), "step_1"
    )
    telemetry, sink = _telemetry()
    restored = restore_latest(root, _tree(0.0), telemetry=telemetry, label="t")
    assert restored is not None
    state, step = restored
    assert step == 0
    np.testing.assert_array_equal(state["w"], _tree(1.0)["w"])
    fallbacks = [
        r for r in sink.records
        if r.get("event") == "failure" and r.get("kind") == "checkpoint_fallback"
    ]
    assert len(fallbacks) == 1
    assert "checksum mismatch" in fallbacks[0]["message"]


def test_manifest_catches_extra_and_missing_files(devices, tmp_path):
    root = str(tmp_path / "ck")
    final = save_checkpoint(root, _tree(1.0), step=0)
    with open(os.path.join(final, "smuggled.bin"), "wb") as f:
        f.write(b"x")
    ok, reason = verify_checkpoint(final)
    assert not ok and "unmanifested" in reason
    os.remove(os.path.join(final, "smuggled.bin"))
    with open(os.path.join(final, CHECKSUM_MANIFEST)) as f:
        victim = sorted(json.load(f))[0]
    os.remove(os.path.join(final, victim))
    ok, reason = verify_checkpoint(final)
    assert not ok and "missing file" in reason


def test_gc_keep_last(devices, tmp_path):
    root = str(tmp_path / "ck")
    for s in range(4):
        save_checkpoint(root, _tree(float(s)), step=s)
    # a foreign abandoned tmp dir gets collected too
    os.makedirs(os.path.join(root, "_tmp.step_9.99999"))
    deleted = gc_checkpoints(root, keep_last=2)
    kept = [s for s, _ in committed_step_paths(root)]
    assert kept == [3, 2]
    assert any("_tmp.step_9" in d for d in deleted)

    # keep_last threaded through save_checkpoint
    save_checkpoint(root, _tree(9.0), step=4, keep_last=2)
    assert [s for s, _ in committed_step_paths(root)] == [4, 3]
    with pytest.raises(ValueError):
        gc_checkpoints(root, keep_last=0)


def test_restore_latest_empty_root(devices, tmp_path):
    assert restore_latest(str(tmp_path / "nope"), _tree(0.0)) is None


# ---------------------------------------------------------------------------
# degraded-fabric survival: comm-layer faults, watchdogs, fallback ladder
# ---------------------------------------------------------------------------


def _info(phase="launch", payload=4096, device=0, tag="grads"):
    return {
        "tag": tag, "payload_bytes": payload, "phase": phase,
        "device_index": device,
    }


def test_comm_fault_registry_bijection():
    assert set(COMM_FAULTS) == {
        "comm_throttle", "comm_stall", "comm_flap", "comm_slow_edge",
        "comm_partition", "comm_heal",
    }
    for kind in COMM_FAULTS:
        assert kind in FAULT_KINDS
        assert INJECTION_SITES[kind] == "comm-hook"
        FaultSpec(kind=kind, step=0)  # accepted, not "unknown kind"
    # every kind has a site and every site names a kind — both directions
    check_fault_registry()
    assert set(INJECTION_SITES) == set(FAULT_KINDS)


def test_comm_fault_injector_throttle_lifecycle():
    plan = ChaosPlan([
        FaultSpec(kind="comm_throttle", step=1, payload={
            "bytes_per_s": 1e6, "max_sleep_s": 0.04, "duration_steps": 2,
        }),
    ])
    telemetry, sink = _telemetry()
    inj = CommFaultInjector(plan, rank=0, telemetry=telemetry)
    inj.advance(0)
    assert not inj.throttled
    inj.advance(1)
    assert inj.throttled
    assert "chaos_injected" in _kinds(sink)
    # wrong device / retire phase: filtered, no sleep
    import time as _t
    t0 = _t.monotonic()
    inj(_info(device=1))
    inj(_info(phase="retire"))
    assert _t.monotonic() - t0 < 0.02
    # matching launch: sleeps min(payload/rate, max_sleep) = the clamp
    t0 = _t.monotonic()
    inj(_info(payload=10_000_000))
    assert _t.monotonic() - t0 >= 0.03
    # expires at step 1 + duration_steps
    inj.advance(2)
    assert inj.throttled
    inj.advance(3)
    assert not inj.throttled
    assert "comm_fault_cleared" in _kinds(sink)


def test_comm_fault_injector_stall_fires_once():
    plan = ChaosPlan([
        # "chunk": a plan written while payloads were split into chunk
        # collectives still parses; the key is ignored
        FaultSpec(kind="comm_stall", step=0, payload={
            "stall_seconds": 0.05, "chunk": 1,
        }),
    ])
    inj = CommFaultInjector(plan, rank=0)
    inj.advance(0)
    assert inj.stall_pending
    import time as _t
    t0 = _t.monotonic()
    inj(_info(phase="retire"))  # only a launch stalls
    assert _t.monotonic() - t0 < 0.02
    t0 = _t.monotonic()
    inj(_info())
    assert _t.monotonic() - t0 >= 0.04
    assert not inj.stall_pending  # one collective hangs, ONCE
    t0 = _t.monotonic()
    inj(_info())
    assert _t.monotonic() - t0 < 0.02


def test_comm_flap_defaults_to_clearing():
    plan = ChaosPlan([FaultSpec(kind="comm_flap", step=2)])
    inj = CommFaultInjector(plan, rank=0)
    inj.advance(2)
    assert inj.throttled
    inj.advance(4)
    assert inj.throttled
    inj.advance(5)  # default clears_after=3
    assert not inj.throttled


def test_collective_watchdog_expiry_and_epoch_counters():
    import time as _t

    telemetry, sink = _telemetry()
    with CollectiveWatchdog(
        n_workers=8, slack=1.0, floor_s=0.05, escalate_after=2,
        telemetry=telemetry, rank=0, label="t",
    ) as wd:
        # clean window: launch then retire inside the budget
        wd.begin_attempt()
        wd(_info(phase="launch"))
        wd(_info(phase="retire"))
        assert not wd.expired_this_attempt
        wd.note_step(False)
        # blown window: the retire never comes before the deadline
        wd.begin_attempt()
        wd(_info(phase="launch", tag="powersgd.P"))
        _t.sleep(0.15)
        assert wd.expired_this_attempt
        assert wd.fired and wd.fired[-1]["tag"] == "powersgd.P"
        # hooks from other devices never arm rank 0's timer
        wd.begin_attempt()
        wd(_info(phase="launch", device=3))
        _t.sleep(0.08)
        assert not wd.expired_this_attempt
        # escalation streak: K consecutive degraded steps
        wd.note_step(True)
        assert not wd.should_escalate()
        wd.note_step(True)
        assert wd.should_escalate()
        counters = wd.take_epoch()
        assert counters == {"deadline_expiries": 1, "degraded_steps": 2}
        # epoch counters reset; the consecutive streak survives the epoch
        assert wd.take_epoch() == {"deadline_expiries": 0, "degraded_steps": 0}
        assert wd.should_escalate()
    deadline_events = [
        r for r in sink.records if r.get("kind") == "comm_deadline"
    ]
    assert len(deadline_events) == 1
    assert deadline_events[0]["label"] == "powersgd.P"


class _ScriptedWatchdog:
    """CommDeadlineGuard contract double: expiry verdicts per attempt."""

    escalate_after = 3

    def __init__(self, verdicts):
        self._verdicts = list(verdicts)
        self._current = False
        self.noted = []

    def begin_attempt(self):
        self._current = self._verdicts.pop(0) if self._verdicts else False

    @property
    def expired_this_attempt(self):
        return self._current

    def note_step(self, degraded):
        self.noted.append(degraded)

    def should_escalate(self):
        return self.noted[-3:] == [True, True, True]


def test_comm_deadline_guard_retry_then_degrade():
    calls = []

    class Step:
        bits_per_step = 64

        def __call__(self, state, batch):
            calls.append(state)
            return state + 1, 0.5

    telemetry, sink = _telemetry()
    wd = _ScriptedWatchdog([False, True, False, True, True])
    guard = CommDeadlineGuard(Step(), wd, telemetry=telemetry, label="t")
    assert guard.bits_per_step == 64  # delegation
    # attempt 1 clean: one call, not degraded
    state, _ = guard(0, None)
    assert state == 1 and calls == [0]
    # attempt expired -> retried IN PLACE on the same inputs -> clean
    state, _ = guard(state, None)
    assert state == 2 and calls == [0, 1, 1]
    kinds = _kinds(sink)
    assert kinds.count("comm_step_retry") == 1
    assert "comm_degraded" not in kinds
    # expired twice: the (late but valid) state is kept, step marked degraded
    state, _ = guard(state, None)
    assert state == 3
    assert "comm_degraded" in _kinds(sink)
    assert wd.noted == [False, False, True]


def test_comm_deadline_guard_escalates_past_runtime_error_handlers():
    class Step:
        def __call__(self, state, batch):
            return state + 1, 0.5

    wd = _ScriptedWatchdog([True, True] * 6)  # every attempt expires
    guard = CommDeadlineGuard(Step(), wd)
    guard(0, None)
    guard(0, None)
    with pytest.raises(CommEscalationError):
        guard(0, None)
    # an escalation must pass through GuardedStep/retry_transient, which
    # catch RuntimeError — so it must not BE one
    assert not issubclass(CommEscalationError, RuntimeError)


def _hooked_reducer(name):
    """(reducer, mesh, the axes it reduces over) for one fence-hook case."""
    from network_distributed_pytorch_tpu.parallel import (
        ExactReducer,
        HierarchicalReducer,
    )

    if name == "hierarchical":
        mesh = make_mesh(axis_sizes=(2, 4), axis_names=("dcn", "ici"))
        outer = PowerSGDReducer(random_seed=3, compression_rank=2)
        return HierarchicalReducer(outer, mesh), mesh, ("dcn", "ici")
    reducer = {
        "exact": ExactReducer,
        "exact-bucketed": lambda: ExactReducer(bucket_bytes=60),
        "powersgd": lambda: PowerSGDReducer(random_seed=3, compression_rank=2),
    }[name]()
    return reducer, make_mesh(), "data"


@pytest.mark.parametrize(
    "name", ["exact", "exact-bucketed", "powersgd", "hierarchical"]
)
def test_fence_hooks_see_every_collective(devices, name):
    """Every payload a reducer puts on the wire passes the fence hooks once
    (a launch and a retire per ledger line, tag and bytes the ledger's own),
    the callbacks stay outside the math (bitwise the unhooked results), and
    with no hook registered the traced program carries no callback."""
    from jax.sharding import PartitionSpec as P

    from network_distributed_pytorch_tpu.parallel import comm

    reducer, mesh, axes = _hooked_reducer(name)
    shapes = [(8, 3, 3, 3), (16, 8), (16,), (10, 16), (10,)]
    sends = [
        jax.random.normal(jax.random.PRNGKey(i), (8,) + shape)
        for i, shape in enumerate(shapes)
    ]
    template = [s[0] for s in sends]
    state = reducer.init(template)
    spec = P(axes)

    def body(state, *send):
        _, out, mem, _ = reducer.reduce(state, [s[0] for s in send], axes)
        return [o[None] for o in out], [m[None] for m in mem]

    def build():
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P(),) + (spec,) * len(sends),
            out_specs=([spec] * len(sends), [spec] * len(sends)),
        ))

    unhooked_jaxpr = str(jax.make_jaxpr(build())(state, *sends))
    assert "callback" not in unhooked_jaxpr
    baseline = build()(state, *sends)
    seen = []
    comm.add_fence_hook(seen.append)
    try:
        assert comm.fence_hooks_active()
        assert "callback" in str(jax.make_jaxpr(build())(state, *sends))
        hooked = jax.block_until_ready(build()(state, *sends))
    finally:
        comm.remove_fence_hook(seen.append)
    assert not comm.fence_hooks_active()
    assert str(jax.make_jaxpr(build())(state, *sends)) == unhooked_jaxpr
    for a, b in zip(
        jax.tree_util.tree_leaves(baseline), jax.tree_util.tree_leaves(hooked)
    ):
        np.testing.assert_array_equal(
            np.asarray(a).view(np.uint32), np.asarray(b).view(np.uint32)
        )
    entries = reducer.ledger_entries(template, axis="data")
    for phase in ("launch", "retire"):
        mine = [
            i for i in seen if i["device_index"] == 0 and i["phase"] == phase
        ]
        assert {(i["tag"], i["payload_bytes"]) for i in mine} == {
            (e.tag, e.payload_bytes) for e in entries
        }
        # once per collective: rank 0 along a collective's axis is one
        # device of each group that reduces over it
        assert collections.Counter(i["tag"] for i in mine) == {
            e.tag: mesh.size // mesh.shape[e.axis] for e in entries
        }


# -- the e2e matrix: fault -> watchdog/controller -> documented recovery ----


def _adaptive_setup():
    model = SmallCNN(width=4)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, *IMG)))["params"]

    def lf(p, b):
        x, y = b
        return cross_entropy_loss(model.apply({"params": p}, x), y)

    mesh = make_mesh()

    def step_factory(overrides):
        if overrides.get("reducer") == "powersgd":
            reducer = PowerSGDReducer(
                random_seed=7,
                compression_rank=overrides.get("reducer_rank", 2),
                matricize="last",
            )
        else:
            from network_distributed_pytorch_tpu.parallel import ExactReducer

            reducer = ExactReducer()
        return make_train_step(
            stateless_loss(lf), reducer, params, learning_rate=0.05,
            momentum=0.9, algorithm="ef_momentum", mesh=mesh,
            donate_state=False,
        )

    return step_factory, params


def _policy_records(sink):
    return [r for r in sink.records if r.get("event") == "policy"]


def _step_losses(sink):
    return [r["loss"] for r in sink.records if r.get("event") == "step"]


def _bits_deltas(sink):
    bits = [
        r["bits_cumulative"] for r in sink.records
        if r.get("event") == "step" and "bits_cumulative" in r
    ]
    return [b - a for a, b in zip(bits, bits[1:])]


@pytest.mark.slow
def test_comm_throttle_walks_ladder_down_and_back(devices):
    """The tentpole e2e: a mid-run throttle degrades achieved bandwidth ->
    the controller descends to the compressed rung (reducer actually
    switched, wire bytes/step measurably reduced per the ledger) with a
    typed PolicyEvent; the fault clears -> the ladder walks back up; the
    loss stays finite and nothing restarts."""
    from network_distributed_pytorch_tpu.experiments.common import (
        adaptive_train_loop,
    )

    step_factory, params = _adaptive_setup()
    telemetry, sink = _telemetry()
    plan = ChaosPlan([
        FaultSpec(kind="comm_throttle", step=6, payload={
            "bytes_per_s": 2e4, "max_sleep_s": 0.15, "duration_steps": 6,
        }),
    ])
    injector = CommFaultInjector(plan, rank=0, telemetry=telemetry)
    controller = FallbackController(
        ladder=[
            Rung("exact", {}),
            Rung("powersgd", {"reducer": "powersgd", "reducer_rank": 2}),
        ],
        descend_after=1, recover_after=2, telemetry=telemetry,
    )
    state, logger, controller = adaptive_train_loop(
        step_factory, params, None, _batches, 10, controller,
        injector=injector, telemetry=telemetry,
        # the throttle's per-chunk sleep (0.15s) must degrade bandwidth
        # WITHOUT tripping the deadline watchdog — that's the stall test
        deadline_floor_s=0.5,
    )
    policies = _policy_records(sink)
    descents = [p for p in policies if p["action"] == "descend"]
    ascents = [p for p in policies if p["action"] == "ascend"]
    assert descents and ascents
    assert descents[0]["rung_after"] == "powersgd"
    assert "achieved_bytes_per_s" in descents[0]["trigger"]
    # the descent's byte claim: the compressed rung sheds real ledger bytes
    assert (
        descents[0]["predicted_bytes_per_step"]
        < descents[0]["realized_bytes_per_step"]
    )
    # ...and the ledger the logger charged agrees: compressed steps cost
    # measurably fewer wire bits than exact steps
    deltas = set(_bits_deltas(sink))
    assert len(deltas) == 2 and min(deltas) < max(deltas)
    kinds = _kinds(sink)
    assert "chaos_injected" in kinds
    assert "comm_fault_cleared" in kinds
    assert "worker_restart" not in kinds  # recovery happened in-place
    assert controller.index == 0  # recovered all the way back to exact
    losses = _step_losses(sink)
    assert losses and np.isfinite(losses).all()
    assert all(
        np.isfinite(np.asarray(l)).all()
        for l in jax.tree_util.tree_leaves(state.params)
    )


@pytest.mark.slow
def test_comm_flap_recovers_in_place_without_escalation(devices):
    """A transient flap throttles a few steps then self-clears; the run
    absorbs it with no deadline expiry, no escalation, no restart — the
    flap lifecycle is visible as injected -> cleared telemetry."""
    from network_distributed_pytorch_tpu.experiments.common import (
        adaptive_train_loop,
    )

    step_factory, params = _adaptive_setup()
    telemetry, sink = _telemetry()
    plan = ChaosPlan([
        FaultSpec(kind="comm_flap", step=4, payload={
            "bytes_per_s": 2e4, "max_sleep_s": 0.1, "clears_after": 3,
        }),
    ])
    injector = CommFaultInjector(plan, rank=0, telemetry=telemetry)
    controller = FallbackController(
        ladder=[Rung("exact", {})], telemetry=telemetry,
    )
    state, logger, _ = adaptive_train_loop(
        step_factory, params, None, _batches, 4, controller,
        injector=injector, telemetry=telemetry, deadline_floor_s=0.5,
    )
    kinds = _kinds(sink)
    assert "chaos_injected" in kinds
    assert "comm_fault_cleared" in kinds
    assert "comm_deadline" not in kinds  # under the deadline floor
    assert "worker_restart" not in kinds
    losses = _step_losses(sink)
    assert len(losses) == 12  # every step of every epoch completed
    assert np.isfinite(losses).all()


@pytest.mark.slow
def test_comm_stall_trips_deadline_step_retried_ledger_unchanged(devices):
    """One collective hangs past its deadline: the watchdog fires
    ``comm_deadline``, the guard retries the step in place (the stall is
    once-only, so the retry is clean), no escalation — and the wire ledger
    is bit-identical to a clean run's, because injection lives in a host
    callback, not in the graph."""
    from network_distributed_pytorch_tpu.experiments.common import (
        adaptive_train_loop,
    )

    step_factory, params = _adaptive_setup()
    telemetry, sink = _telemetry()
    plan = ChaosPlan([
        FaultSpec(kind="comm_stall", step=4, payload={
            "stall_seconds": 1.0, "chunk": 0,
        }),
    ])
    injector = CommFaultInjector(plan, rank=0, telemetry=telemetry)
    # single-rung ladder: the stalled epoch may NOT descend anywhere, so
    # every step must charge the exact reducer's ledger
    controller = FallbackController(
        ladder=[Rung("exact", {})], telemetry=telemetry,
    )
    state, logger, _ = adaptive_train_loop(
        step_factory, params, None, _batches, 3, controller,
        injector=injector, telemetry=telemetry,
        deadline_floor_s=0.2, deadline_slack=1.0, escalate_after=3,
    )
    kinds = _kinds(sink)
    assert "comm_deadline" in kinds
    assert "comm_step_retry" in kinds
    # the once-only stall clears on the retry: degraded never accumulates
    assert not any(k == "comm_degraded" for k in kinds)
    losses = _step_losses(sink)
    assert len(losses) == 9 and np.isfinite(losses).all()
    # ledger invariance: every step charged the same exact-reducer bits
    assert len(set(_bits_deltas(sink))) == 1
