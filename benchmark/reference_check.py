"""The comparison that decides ``correct``: the system's first three steps
against the plain reference on the same batches.

The harness runs the warm-up steps itself, so the check costs the system no
second compile: it keeps, on the host, the initial parameters and warm-start
Q, and the state right after step 1 (fetched before the next call donates
it). After the window the plain reference (``benchmark/reference/``) repeats
those steps in float32 and the two are compared:

- losses of steps 1..3 (mean over workers);
- after step 1, per tensor, by its distance to the reference's: the reduced
  update (the momentum buffer, which after the first step IS the reducer's
  output: m = 0.9*0 + D), every worker's error memory, and the parameters'
  change (x0 - x1 = lr*(D + m) = 2*lr*D: the update rule itself);
- the bytes one worker sends, ledger against the reference's own count.

Tolerances, and why. The system computes the model in bf16 with fp32
accumulation and fp32 parameters, and its reducer's fp32 products at the
TPU's default precision; the reference is fp32 at "highest" throughout. Every
distance is taken over the norm of what the tensor's reducer was SENT (the
gradient's size): rounding error has the gradient's scale, while a residual
or a rank-r part can be a small remainder of it, whose own cosine then says
little (error memories of attention's value and output kernels agree to
0.92-0.98 by cosine and to 0.02-0.07 on the send's scale). Each tolerance is
about twice the worst of 46 runs on the chip (four workers' memories, each the
difference of a send and the shared update, sit three times further out than
one worker's). That is far from what the failures it guards against give: fp8
products carry 16 times bf16's rounding error (one worker's 0.045 would be
~0.7); no error feedback leaves the
memories 0.5-0.95 of the send away; no momentum term halves the parameters'
change (0.5); no orthogonalisation rescales the update by the factor's
singular values.

Two kinds of tensor cannot be compared by direction and are held to a norm
bound only: a tensor whose reference norm is negligible against the whole
(its direction is rounding noise: DistilBERT's key biases, whose gradient is
zero in exact arithmetic), and a tensor whose P factor is rank-deficient
(Gram-Schmidt then normalises rounding noise into a direction, in the
reference implementation as much as here: the two-label classifier's
gradient has rank 1 because its softmax gradient's columns sum to zero; a
residual branch behind a zero-initialised batch-norm scale has gradient 0).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

TOLERANCES = {
    # each about twice the worst of 46 runs on the chip (PR 22), given beside it
    # |loss_sys - loss_ref| <= loss_abs + loss_rel * |loss_ref|         worst 0.0068 relative
    "loss_rel": 0.015, "loss_abs": 2e-3,
    # distance to the reference over the norm of the tensor's send: each tensor, and all as one
    "update_each": 0.35, "update_all": 0.30,  # worst 0.173 and 0.132 (imdb_psgd16_b16)
    "memory_each": 0.30, "memory_all": 0.30,  # worst 0.149 and 0.139 (imdb_psgd16_b16_x4; one chip: 0.068, 0.045)
    # fp32 parameters hold an update of ~1e-7 of their size to a few digits only: all as one
    "change_all": 0.30,  # worst 0.134
    # a tensor whose send is under this share of all sends' norm is held to a norm bound only
    "negligible": 1e-3,
    # a P factor whose smallest singular value is under this share of its largest is rank-deficient
    "rank_deficient": 1e-4,
    # a tensor held to a bound only may be this many times its largest send's norm (plus the
    # negligible floor): Gram-Schmidt on a rank-deficient factor adds at most one more copy of it
    "loose_norm": 3.0,
}


class Kept:
    """What the check needs of the system, held on the host."""

    def __init__(self, state) -> None:
        import jax

        self.params0 = jax.device_get(state.params)
        self.q_memory0 = np.asarray(jax.device_get(state.reducer_state.q_memory))
        self.model_state0 = jax.device_get(state.model_state)
        self.first: Dict[str, Any] = {}

    def after_first_step(self, state) -> None:
        import jax

        self.first = jax.device_get(
            {"momenta": state.momenta, "memories": state.memories, "params": state.params}
        )


def _cos(a: np.ndarray, b: np.ndarray) -> float:
    na, nb = float(np.linalg.norm(a)), float(np.linalg.norm(b))
    if na == 0.0 or nb == 0.0:
        return 1.0 if na == nb else 0.0
    return float(np.vdot(a.ravel().astype(np.float64), b.ravel().astype(np.float64)) / (na * nb))


def rank_deficient(sends: List[np.ndarray], q: np.ndarray, matricize: str, tol: float) -> bool:
    """Is the mean P = M Q this tensor would produce short of full column rank?"""
    m = np.mean(sends, axis=0)
    m = m.reshape(-1, m.shape[-1]) if matricize == "last" else m.reshape(m.shape[0], -1)
    s = np.linalg.svd(m @ q, compute_uv=False)
    return bool(s[0] == 0.0 or s[-1] <= tol * s[0])


def compare_trees(name: str, got: List[np.ndarray], want: List[np.ndarray], scale: List[float],
                  loose: List[bool], paths: List[str], tol_each: float, tol_all: float,
                  bound: Optional[List[float]] = None) -> Dict:
    """Per tensor, the distance between the system's and the reference's,
    over ``scale``: the norm of what that tensor's reducer was sent (the
    gradient's own size, which is the size rounding error has; a residual or
    a rank-r part can be far smaller than the gradient it came from). And the
    same for all compared tensors taken as one vector. Cosines are reported
    beside them. ``bound`` is the size a bound-only tensor is held to (the
    largest send of any worker; ``scale`` if not given)."""
    tol = TOLERANCES
    bound = scale if bound is None else bound
    whole = float(np.sqrt(sum(s * s for s in scale)))
    floor = tol["negligible"] * whole
    worst, worst_cos, bad, skipped = 0.0, 1.0, [], 0
    dist2 = scale2 = dot = gg = ww = 0.0
    for path, g, w, s, is_loose, b in zip(paths, got, want, scale, loose, bound):
        ng = float(np.linalg.norm(g))
        if is_loose or s <= floor:
            skipped += 1
            if ng > tol["loose_norm"] * b + floor:
                bad.append(f"{path}: norm {ng:.3g} against a send of {b:.3g} (bound only)")
            continue
        d = float(np.linalg.norm(g.astype(np.float64) - w)) / s
        dist2 += (d * s) ** 2
        scale2 += s * s
        dot += float(np.vdot(g.astype(np.float64), w.astype(np.float64)))
        gg += ng * ng
        ww += float(np.vdot(w, w))
        worst, worst_cos = max(worst, d), min(worst_cos, _cos(g, w))
        if d > tol_each:
            bad.append(f"{path}: off by {d:.4f} of its send's norm (cosine {_cos(g, w):.5f})")
    off_all = float(np.sqrt(dist2 / scale2)) if scale2 > 0 else 0.0
    if off_all > tol_all:
        bad.append(f"all tensors as one: off by {off_all:.4f} of the sends' norm, over {tol_all}")
    return {
        "what": name, "ok": not bad, "worst_off": worst, "off_all": off_all,
        "worst_cosine": worst_cos, "cosine_all": float(dot / np.sqrt(gg * ww)) if gg > 0 and ww > 0 else 1.0,
        "norm_ratio_all": float(np.sqrt(gg / ww)) if ww > 0 else 1.0,
        "compared": len(got) - skipped, "bound_only": skipped, "failures": bad[:8],
    }


def compare(run, kept: Kept, warm_batches: List[Any], warm_losses: List[float], builder: str, chips: int) -> Dict:
    import jax

    from . import cells
    from .reference import ef_momentum

    cfg, tol = run.cfg, TOLERANCES
    if cfg["reducer"]["kind"] != "powersgd" or cfg["algorithm"] != "ef_momentum":
        raise SystemExit("benchmark: the plain reference covers PowerSGD under Algorithm 2 only")
    loss_and_grads = cells.module("reference", builder).make_loss_and_grads(cfg)
    per_worker = cfg["per_chip_batch"]

    def shard(batch, w):
        return jax.tree_util.tree_map(lambda x: x[w * per_worker:(w + 1) * per_worker], batch)

    step_batches = [[shard(b, w) for w in range(chips)] for b in warm_batches]
    # per-worker model state starts identical on every worker: take worker 0's
    model_state0 = jax.tree_util.tree_map(lambda x: x[0], kept.model_state0)
    out = ef_momentum.run(
        loss_and_grads, kept.params0, model_state0, kept.q_memory0, step_batches,
        cfg["reducer"], cfg["learning_rate"], cfg["momentum"],
    )
    first = out["after_first"]
    flat, _ = jax.tree_util.tree_flatten_with_path(kept.params0)
    paths = [jax.tree_util.keystr(k) for k, _ in flat]
    params0 = [np.asarray(x) for _, x in flat]
    leaves = lambda tree: [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]

    # which tensors can only be held to a norm bound
    mode = cfg["reducer"].get("matricize", "last")
    qs = iter(ef_momentum.unpack_qs(kept.q_memory0, params0, cfg["reducer"]["rank"], mode))
    loose = []
    for i, p in enumerate(params0):
        if p.ndim <= 1:
            loose.append(False)
            continue
        # after step 1 the send was the gradient: memory + update restores it
        sends = [first["memories"][w][i] + first["delta"][i] for w in range(chips)]
        loose.append(rank_deficient(sends, next(qs), mode, tol["rank_deficient"]))

    norm = lambda x: float(np.linalg.norm(x))
    worker_sends = [[norm(first["memories"][w][i] + first["delta"][i]) for i in range(len(params0))]
                    for w in range(chips)]
    mean_send = [float(np.mean([worker_sends[w][i] for w in range(chips)])) for i in range(len(params0))]
    max_send = [max(worker_sends[w][i] for w in range(chips)) for i in range(len(params0))]
    reports = [compare_trees(
        "reduced update (momentum after step 1)", leaves(kept.first["momenta"]), first["delta"],
        mean_send, loose, paths, tol["update_each"], tol["update_all"], max_send,
    )]
    sys_mem = leaves(kept.first["memories"])
    for w in range(chips):
        mine = [m[w] if m.shape != p.shape else m for m, p in zip(sys_mem, params0)]
        reports.append(compare_trees(
            f"error memory of worker {w}", mine, first["memories"][w], worker_sends[w], loose, paths,
            tol["memory_each"], tol["memory_all"], max_send,
        ))
    # x0 - x1 = lr * (D + m) = 2 * lr * D after the first step
    lr2 = 2.0 * cfg["learning_rate"]
    reports.append(compare_trees(
        "parameters' change over step 1",
        [a - b for a, b in zip(params0, leaves(kept.first["params"]))],
        [a - b for a, b in zip(params0, first["params"])],
        [lr2 * s for s in mean_send], loose, paths, np.inf, tol["change_all"],
        [lr2 * s for s in max_send],
    ))

    loss_ok = all(
        abs(s - r) <= tol["loss_abs"] + tol["loss_rel"] * abs(r)
        for s, r in zip(warm_losses, out["losses"])
    )
    # the ledger also prices the 4-byte loss all-reduce; the oracle prices the reducer alone
    wire_ok = run.wire_bytes_per_step - first["wire_bytes"] == 4
    for r in reports:
        print("benchmark: reference: " + ", ".join(f"{k}={v}" for k, v in r.items()), flush=True)
    print(f"benchmark: reference: losses system {warm_losses} reference {out['losses']}", flush=True)
    return {
        "ok": bool(loss_ok and all(r["ok"] for r in reports)),
        "wire_ok": bool(wire_ok),
        "report": {
            "losses_system": warm_losses, "losses_reference": out["losses"], "losses_ok": loss_ok,
            "wire_bytes_reference": first["wire_bytes"], "tensors": reports,
            "rank_deficient": [p for p, l in zip(paths, loose) if l][:12],
            "rank_deficient_count": int(sum(loose)),
        },
    }

