"""Which pass an op belongs to, read off the path jax already writes.

All three 8k configurations wrap whole blocks in ``nn.remat``, so a step runs
its forward twice and ``grads_ms`` is three passes in one number. jax puts the
pass on every op's path, and these are the forms it took in the cells' steps
compiled for v5e on the CPU host (``benchmark/tests/test_aot_v5e.py::compile_cell``,
PR 39; ``<M>`` the flax model, ``<layer>`` e.g. ``layer_3/mixer``):

- forward proper: ``jit(sharded_body)/step.grads/jvp(<M>)/<layer>/moe.experts/
  moe.products/jit(_rows_by_groups)/grouped_matmul/pallas_call`` (a scope
  stands bare under flax's ``jvp(<M>)``; in a step without flax it is the
  scope that is wrapped, ``step.grads/jvp(moe.experts)/...``);
- recomputation: ``.../step.grads/transpose(jvp(<M>))/step.grads/jvp(<M>)/
  checkpoint/rematted_computation/<layer>/attn.core/jit(flash_attention)/
  pallas_call``: it runs inside the backward's ``transpose(...)``, so
  ``rematted_computation`` is looked for first;
- backward: ``.../step.grads/transpose(jvp(<M>))/step.grads/jvp(<M>)/
  checkpoint/<layer>/...``; without ``remat`` (imdb) ``.../step.grads/
  transpose(jvp(<M>))/distilbert/<layer>/...``.

A kernel under a ``jax.custom_vjp`` falls where its rule runs: the backward
rules' kernels were all on backward paths (``.../checkpoint/<layer>/attn.core/
jit(flash_attention)/flash_attention_bwd/pallas_call``, ``.../moe.products/
jit(_rows_by_groups)/grouped_matmul_nt/pallas_call``, ``.../jit(_groups_of_rows)/
grouped_matmul_tn/pallas_call``, ``.../gdn.rule/jit(chunk_local_backward)/
gated_delta_chunk_local_bwd/pallas_call``), the forward kernels
(``flash_attention``'s, ``grouped_matmul``, ``gated_delta_chunk_local``) once
under ``jvp(<M>)/<layer>`` and once under ``rematted_computation``. The later
chunks of an expert layer sit under a ``jax.checkpoint`` of their own, so in
the backward their recomputation reads ``.../checkpoint/<layer>/moe.experts/
moe.overflow/.../closed_call/checkpoint/rematted_computation/cond/...``:
``remat``, as it is. XLA may merge a recomputed op with the forward's (the
router's ``top_k`` on the CPU); the merged op carries the forward's path.
``tests/test_moe_scopes.py`` and ``benchmark/tests/
test_pass_and_leaf_metrics.py`` hold jax to the two markers on compiled
programs.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional

PASSES = ("fwd", "remat", "bwd")
GRADS = "step.grads"  # the scope ``grads_ms`` reads: the three passes are its parts
# a named scope wherever it sits on a path: bare, or wrapped as in jvp(moe.route)
_SCOPE = re.compile(r"(?<![a-z_.])[a-z_]+\.[a-z_]+(?![a-z_.])")


def pass_of(op_name: str) -> str:
    """``"fwd"``, ``"remat"`` or ``"bwd"``."""
    if "rematted_computation" in op_name:
        return "remat"
    return "bwd" if "transpose(" in op_name else "fwd"


def pass_seconds(run, which: str, scope: str = GRADS) -> Optional[float]:
    """Device self time per step of the ops under ``scope`` (picked as
    ``grads_ms`` picks them) whose pass is ``which``."""
    if not run.trace:
        return None
    return run.trace.per_step(lambda o: scope in o.scopes and pass_of(o.op_name) == which)


def table(run) -> Dict[str, List[float]]:
    """``{innermost scope on the path: [fwd, remat, bwd]}`` in seconds per
    step (mean over chips) over the ops under ``step.grads``, largest first;
    an op under no scope of its own counts to ``step.grads``."""
    rows: Dict[str, List[float]] = {}
    chips = run.trace.chips
    for chip in chips:
        for o in chip.ops:
            if GRADS in o.scopes:
                row = rows.setdefault(_SCOPE.findall(o.op_name)[-1], [0.0, 0.0, 0.0])
                row[PASSES.index(pass_of(o.op_name))] += o.self_s / (chip.steps * len(chips))
    return dict(sorted(rows.items(), key=lambda kv: -sum(kv[1])))
