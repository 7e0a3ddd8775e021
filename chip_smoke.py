#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no children. On a TPU it (1) checks the two Pallas kernels the
main path selects there against their XLA references, compiled, at the run's
real shapes; (2) checks that a host timing ending in ``block_until_ready``
observes completion; (3) drives the paper's fourth guide through the normal
entry point — ``launch.main(["powersgd_imdb", "--preset", "full", ...])``:
DistilBERT-base (6 layers, dim 768, 12 heads, FFN 3072, vocab 30522,
sequence 256), PowerSGD rank 16 with error-feedback momentum, 16 sequences
per chip, bf16 compute, synthetic IMDb from the seed — for 8 steps, and
checks what came out. With four chips it also checks that the state, the
batches and the allocator's bytes are spread over all four, and that the
compiled step's all-reduce bytes equal the wire ledger.

Without a TPU it exits non-zero at once and prints no result: nothing in it
or under it can turn a missing chip into a CPU run. On success stdout ends
with two JSON lines: first ``{"smoke": {...}}``, the bounded detail (widths,
losses, kernel errors, resolved kernels, compile cache; its timings are smoke
timings — set-up and a few warm steps — not metrics; ``"claim": null``), and
LAST the verdict, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as jax reports it. The detail is also written to
``chiprun_out/chip_smoke.json``.

    python chip_smoke.py          # on the chip; writes chiprun_out/
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out")
EVENT_LOG = os.path.join(OUT_DIR, "smoke_events.jsonl")

SEQ_LEN = 256
PER_CHIP_BATCH = 16
RANK = 16
STEPS = 8
WIDTHS = {
    "n_layers": 6, "dim": 768, "n_heads": 12, "hidden_dim": 3072,
    "vocab_size": 30522,
}


class SmokeFailure(Exception):
    """A phase's result was wrong. Never caught: it ends the process with a
    traceback and a non-zero exit code, before any result line."""


def check(condition: bool, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


def require_chip():
    """The devices, or exit: jax must have found a TPU whose kind the peak
    table knows. This script never sets ``JAX_PLATFORMS`` itself."""
    import jax

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        sys.exit(
            f"chip_smoke: no TPU — jax.devices()[0] is {first.platform!r}"
            f" ({first.device_kind!r}, JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}). This check runs only on"
            " the chip; it does not fall back to the CPU."
        )
    from network_distributed_pytorch_tpu.observe.mfu import peak_flops

    peak_flops(first.device_kind, first.platform)  # raises for an unknown kind
    return devices


def _max_rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def check_flash_attention() -> dict:
    """Flash (Mosaic-compiled) vs fp32 einsum attention at the run's own
    attention shape — (16, 256, 12, 64) bf16 under the model's padding mask —
    forward and the gradients of all three operands."""
    import jax
    import jax.numpy as jnp

    from network_distributed_pytorch_tpu.ops.flash_attention import (
        flash_attention,
    )

    b, t, h, d = PER_CHIP_BATCH, SEQ_LEN, WIDTHS["n_heads"], 64
    kq, kk, kv, kw, kl = jax.random.split(jax.random.PRNGKey(0), 5)
    q, k, v = (
        jax.random.normal(key, (b, t, h, d), jnp.bfloat16)
        for key in (kq, kk, kv)
    )
    weight = jax.random.normal(kw, (b, t, h, d), jnp.float32)
    lengths = jax.random.randint(kl, (b,), t // 4, t + 1)
    # the mask exactly as models/distilbert.py hands it over in bf16: 0 for
    # tokens, finfo(float32).min rounded to bf16 (-inf) for padding
    attention_mask = jnp.arange(t)[None, :] < lengths[:, None]
    neg = jnp.asarray(jnp.finfo(jnp.float32).min, dtype=jnp.bfloat16)
    mask = jnp.where(attention_mask, 0.0, neg).astype(jnp.float32)

    def reference(q, k, v):
        with jax.default_matmul_precision("highest"):
            s = jnp.einsum(
                "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
            ) / math.sqrt(d)
            w = jax.nn.softmax(s + mask[:, None, None, :], axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32))

    def flash(q, k, v):
        return flash_attention(q, k, v, mask=mask, interpret=False)

    def scalar(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * weight)

    out = jax.jit(flash)(q, k, v)
    ref = jax.jit(reference)(q, k, v)
    grads = jax.jit(jax.grad(scalar(flash), argnums=(0, 1, 2)))(q, k, v)
    ref_grads = jax.jit(jax.grad(scalar(reference), argnums=(0, 1, 2)))(q, k, v)
    errs = {"forward": _max_rel_err(out, ref)}
    for name, got, want in zip(("dq", "dk", "dv"), grads, ref_grads):
        errs[name] = _max_rel_err(got, want)
    # bf16 outputs of fp32 arithmetic: a few bf16 ulps (2^-8) of the peak
    tolerance = 3e-2
    for name, err in errs.items():
        check(
            math.isfinite(err) and err <= tolerance,
            f"flash attention {name} differs from einsum by {err:.3g}"
            f" of peak (tolerance {tolerance})",
        )
    return {"shape": [b, t, h, d], "dtype": "bfloat16",
            "max_rel_err": {k: round(v, 5) for k, v in errs.items()},
            "tolerance": tolerance, "interpret": False}


def check_orthogonalize() -> dict:
    """Pallas Gram-Schmidt (Mosaic-compiled) vs ``ops.orthogonalize`` at the
    P-factor shapes DistilBERT-base produces at rank 16, and orthonormality
    of what the kernel returns."""
    import jax
    import jax.numpy as jnp

    from network_distributed_pytorch_tpu.ops.orthogonalize import orthogonalize
    from network_distributed_pytorch_tpu.ops.pallas_orthogonalize import (
        orthogonalize_pallas,
    )

    tolerance = 1e-3
    rows = []
    for n, r in ((30522, RANK), (3072, RANK), (768, RANK), (768, 2)):
        p = jax.random.normal(jax.random.PRNGKey(n + r), (n, r), jnp.float32)
        got = orthogonalize_pallas(p, interpret=False)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(orthogonalize)(p)
            gram = jnp.matmul(got.T, got)
        err = _max_rel_err(got, want)
        ortho = float(jnp.max(jnp.abs(gram - jnp.eye(r))))
        check(
            math.isfinite(err) and err <= tolerance,
            f"pallas orthogonalize ({n},{r}) differs from XLA by {err:.3g}"
            f" of peak (tolerance {tolerance})",
        )
        check(
            ortho <= tolerance,
            f"pallas orthogonalize ({n},{r}) columns are not orthonormal:"
            f" |QtQ - I| = {ortho:.3g}",
        )
        rows.append({"shape": [n, r], "max_rel_err": round(err, 7),
                     "orthonormality": round(ortho, 7)})
    return {"shapes": rows, "tolerance": tolerance, "interpret": False}


def check_sync() -> dict:
    """Does a host timing that ends in ``block_until_ready`` observe the
    device finishing? Time one ~50 ms program three ways: dispatch only,
    dispatch + ``block_until_ready``, dispatch + ``device_get`` of a scalar
    (``utils.timing.wait_result``). The two waits must agree and both must
    dwarf the dispatch."""
    import jax
    import jax.numpy as jnp

    from network_distributed_pytorch_tpu.utils.timing import wait_result

    x = jax.random.normal(jax.random.PRNGKey(1), (4096, 4096), jnp.bfloat16)

    @jax.jit
    def work(x):
        def body(_, a):
            return jnp.tanh(a @ x)
        return jnp.sum(jax.lax.fori_loop(0, 64, body, x).astype(jnp.float32))

    wait_result(work(x))  # compile + warm up

    def timed(finish) -> float:
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            finish(work(x))
            best = min(best, time.perf_counter() - t0)
            wait_result(work(x))  # drain before the next repeat
        return best

    dispatch_s = timed(lambda y: None)
    block_s = timed(lambda y: y.block_until_ready())
    get_s = timed(wait_result)
    check(
        block_s >= 0.5 * get_s and get_s >= 0.5 * block_s,
        f"block_until_ready ({block_s:.4f}s) and device_get ({get_s:.4f}s)"
        " disagree on when the program finished",
    )
    check(
        block_s >= 5 * dispatch_s,
        f"block_until_ready returned after {block_s:.4f}s, hardly later than"
        f" the dispatch alone ({dispatch_s:.4f}s): it does not observe"
        " completion",
    )
    return {"dispatch_only_s": round(dispatch_s, 5),
            "block_until_ready_s": round(block_s, 5),
            "device_get_s": round(get_s, 5)}


def run_training(n_devices: int) -> dict:
    """§A: the paper's PowerSGD-DistilBERT guide through ``launch.main``.
    The paper's parameters are passed explicitly: the CLI builds its config
    from the dataclass defaults, not from the experiment's."""
    from network_distributed_pytorch_tpu import launch

    if os.path.exists(EVENT_LOG):
        os.remove(EVENT_LOG)  # telemetry appends; one run per file
    return launch.main([
        "powersgd_imdb", "--preset", "full", "--dtype", "bfloat16",
        "--reducer-rank", str(RANK), "--lr", "5e-5",
        "--global-batch", str(PER_CHIP_BATCH * n_devices),
        "--epochs", "1", "--max-steps-per-epoch", str(STEPS),
        "--log-every", "1", "--event-log", EVENT_LOG, "--json",
    ])


def read_events() -> dict:
    by_kind: dict = {}
    with open(EVENT_LOG, encoding="utf-8") as f:
        for line in f:
            record = json.loads(line)
            by_kind.setdefault(record.get("event"), []).append(record)
    return by_kind


def _span_seconds(events: dict, name: str) -> float:
    return sum(
        s["dur_s"] for s in events.get("span", []) if s.get("name") == name
    )


def check_training(summary: dict, events: dict, devices) -> dict:
    n = len(devices)
    first = devices[0]
    steps = events.get("step", [])
    losses = [s["loss"] for s in steps]
    check(summary["steps"] == STEPS and len(steps) == STEPS,
          f"expected {STEPS} steps, summary says {summary['steps']} and the"
          f" event log holds {len(steps)}")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss in {losses}")
    check(summary["bits_per_step"] > 0
          and summary["bytes_communicated"] * 8
          == STEPS * summary["bits_per_step"],
          f"bytes_communicated {summary['bytes_communicated']} != {STEPS} x"
          f" bits_per_step {summary['bits_per_step']} / 8")
    # the summary names what actually ran
    expected = {
        "platform": first.platform, "device_kind": first.device_kind,
        "n_devices": n, "pallas_interpret": False,
        "model": WIDTHS, "seq_len": SEQ_LEN, "reducer_rank": RANK,
        "preset": "full",
    }
    for key, want in expected.items():
        check(summary.get(key) == want,
              f"summary[{key!r}] is {summary.get(key)!r}, expected {want!r}")
    for key in ("attn_impl", "orthogonalize_impl"):
        check(summary.get(key) in ("flash", "einsum", "pallas", "xla"),
              f"summary does not name the resolved {key}: {summary.get(key)!r}")
    check(not any(e.get("kind") == "audit_error" for e in events.get("failure", [])),
          f"the compile audit failed: {events.get('failure')}")
    compiles = events.get("compile", [])
    check(len(compiles) == 1, f"expected one CompileEvent, got {len(compiles)}")
    audit = compiles[0]
    ids = sorted(d.id for d in devices)
    memory = summary["device_memory"]
    check(sorted(row["id"] for row in memory) == ids
          and all(row.get("bytes_in_use", 0) > 0 for row in memory),
          f"not every device reports bytes in use: {memory}")
    if n > 1:
        # data-parallel for real: shards and bytes on every chip, and the
        # P, Q and rank-1 payloads in the compiled step's all-reduces
        for what in ("params", "memories", "batch"):
            check(summary["placement"][what] == ids,
                  f"{what} shards sit on devices {summary['placement'][what]},"
                  f" expected {ids}")
        check(audit["exact"] and audit["hlo_bytes"] == audit["analytic_bytes"]
              and audit["hlo_collective_count"] > 0,
              f"compiled all-reduce bytes do not match the wire ledger: {audit}")
    return {
        "steps": len(steps),
        "first_loss": round(losses[0], 6),
        "last_loss": round(losses[-1], 6),
        "smoke_timings_not_metrics": {
            # the audit AOT-compiles the step before step 0 runs it, so the
            # compile lands in audit_compile_s and not in the first step
            "audit_compile_s": round(_span_seconds(events, "audit/compile"), 3),
            "first_step_s": round(steps[0]["step_time_s"], 5),
            "mean_later_step_s": round(
                sum(s["step_time_s"] for s in steps[1:]) / (len(steps) - 1), 5
            ),
            "step_times_s": [round(s["step_time_s"], 5) for s in steps],
        },
        "bits_per_step": summary["bits_per_step"],
        "bytes_communicated": summary["bytes_communicated"],
        "attn_impl": summary["attn_impl"],
        "orthogonalize_impl": summary["orthogonalize_impl"],
        "host_data_tier": summary["host_data_tier"],
        "placement": summary.get("placement"),
        "device_bytes_in_use": [int(row["bytes_in_use"]) for row in memory],
        # on one chip XLA may drop single-participant collectives, so the
        # audit is recorded there and asserted only with n > 1
        "wire_audit": {
            k: audit.get(k)
            for k in ("exact", "analytic_bytes", "hlo_bytes",
                      "hlo_collective_count", "hlo_by_kind")
        },
    }


def verdict_line(platform: str, kind: str, count: int) -> str:
    """The last line of stdout: exactly the keys ``ok`` and ``device``
    (``platform``, ``kind``, ``count``), nothing else. Everything more a
    reader wants is on the ``{"smoke": ...}`` line before it."""
    return json.dumps({
        "ok": True,
        "device": {"platform": str(platform), "kind": str(kind),
                   "count": int(count)},
    })


def main() -> int:
    t_start = time.perf_counter()
    devices = require_chip()
    from network_distributed_pytorch_tpu import hostenv

    os.makedirs(OUT_DIR, exist_ok=True)
    cache_dir = hostenv.configure_compile_cache()
    entries_before = hostenv.compile_cache_entries(cache_dir)
    first = devices[0]
    result = {
        "device": {"platform": first.platform, "kind": first.device_kind,
                   "count": len(devices)},
        "model": {**WIDTHS, "seq_len": SEQ_LEN, "per_chip_batch": PER_CHIP_BATCH,
                  "reducer_rank": RANK, "dtype": "bfloat16"},
    }
    result["flash_attention"] = check_flash_attention()
    result["orthogonalize"] = check_orthogonalize()
    result["sync"] = check_sync()
    summary = run_training(len(devices))
    result.update(check_training(summary, read_events(), devices))
    result["compile_cache"] = {
        "dir": cache_dir,
        "from_env": bool(os.environ.get(hostenv.COMPILE_CACHE_ENV)),
        "entries_before": entries_before,
        "entries_after": hostenv.compile_cache_entries(cache_dir),
    }
    result["wall_s"] = round(time.perf_counter() - t_start, 1)
    result["claim"] = None
    # every phase above raised on failure, so reaching here is the pass
    with open(os.path.join(OUT_DIR, "chip_smoke.json"), "w") as f:
        json.dump(result, f, indent=1)
    sys.stdout.write(json.dumps({"smoke": result}) + "\n")
    sys.stdout.write(
        verdict_line(first.platform, first.device_kind, len(devices)) + "\n"
    )
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
