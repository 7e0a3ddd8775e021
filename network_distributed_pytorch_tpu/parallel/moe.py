"""Expert parallelism: two routed mixture-of-experts layers.

Which layer is which:

- :func:`switch_moe` — Switch/GShard routing over an ``expert`` mesh axis:
  softmax scores, top-1 (or top-k priority) dispatch through one-hot
  ``(T, E, C)`` masks, a fixed per-expert ``capacity`` whose overflow DROPS
  tokens, two ``lax.all_to_all`` hops, the Switch auxiliary loss. The router
  is exactly as wide as the experts the mesh holds. Used by ``gpt_moe``.
- :func:`held_experts_moe` — the layer of one expert-parallel rank of a
  larger deployment: it is told which expert ids it ``held``s, routes over
  ALL of the router's experts (sigmoid or softmax scores, top-k, renormalised, scaled),
  sorts the assignments that land on its own experts and computes their part
  of the result with grouped products over the sorted rows as they lie, each
  expert's after the last's (``ops.grouped_matmul``: Pallas kernels on TPU,
  ``lax.ragged_dot`` elsewhere). No capacity and no drops, no array of size
  tokens x experts x capacity, no padding between experts; what absent
  experts would add is left out (another rank's part). The experts are relu² (two stacked
  leaves) or gated silu (three), told apart by the operands. Its one caller
  among the models is ``models/layers.routed_experts``, for ``nemotron_h``
  (ungated, top 6 of 128), ``afmoe`` (gated, top 8 of 128), ``qwen3_next``
  (gated, softmax scores, top 10 of 512), ``lfm2`` (gated, top 4 of 64) and
  ``mellum`` (gated, softmax scores, top 8 of 64: one assignment in eight a
  held expert's, the heaviest load a rank sees, which sizes the first chunk).

``switch_moe``, in detail (beyond-parity capability, SURVEY §2.3: EP/MoE
absent from the reference). TPU-native design:

- experts live on an ``expert`` mesh axis: device i holds only its
  ``E/N`` experts' parameters (stacked expert params sharded on the leading
  axis) — model memory scales with the mesh;
- routing is the Mesh-TF/Switch dispatch-mask formulation: one-hot dispatch
  tensors and einsums, so the whole layer is static-shaped and jit-compiles
  (capacity-bounded; over-capacity tokens fall through on the residual path,
  standard Switch behavior);
- tokens physically move with TWO ``lax.all_to_all`` hops (to experts and
  back) — the TPU equivalent of the NCCL all-to-all an EP framework would
  use, riding ICI;
- returns the standard load-balancing auxiliary loss
  (``E · Σ_e fraction_e · prob_e``, Switch Transformer eq. 4) so trainers can
  regularize routing collapse.

Composes with the data axis the usual way: tokens are sharded over the SAME
devices that hold the experts (one mesh axis serves as both the token-batch
and expert shard axis).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.grouped_matmul import grouped_matmul, row_tiles
from ..ops.rows_to_tokens import rows_of_tokens, tokens_from_rows

PyTree = Any


class MoEOutput(NamedTuple):
    out: jax.Array          # (T, D) combined expert outputs (0 for dropped)
    aux_loss: jax.Array     # scalar load-balance loss (Switch eq. 4)
    dropped_fraction: jax.Array  # scalar: fraction of the t*top_k
    # (token, choice) ASSIGNMENTS over capacity — per-assignment, not
    # per-token, when top_k > 1 (a surviving primary + dropped secondary
    # contributes 1/2)


def switch_moe(
    x: jax.Array,
    router_kernel: jax.Array,
    expert_params: PyTree,
    expert_fn: Callable[[PyTree, jax.Array], jax.Array],
    axis_name: Optional[str],
    capacity: int,
    top_k: int = 1,
) -> MoEOutput:
    """Top-1 routed mixture-of-experts layer.

    Inside ``shard_map``: ``x`` is this device's ``(T, D)`` token shard,
    ``router_kernel`` ``(D, E)`` is replicated, and ``expert_params`` is this
    device's ``(E_local, ...)`` slice of the stacked expert parameters
    (sharded over ``axis_name``; total experts ``E = N · E_local``).
    ``expert_fn(params_of_one_expert, (tokens, D)) -> (tokens, D)``.
    ``capacity`` is per (expert, source-device): each device may send at most
    ``capacity`` tokens to each expert.

    ``axis_name=None`` is the single-process fallback (all experts local, no
    all-to-all) — the framework-wide convention (reference ``reducer.py:13-18``).

    ``top_k > 1`` switches to GShard-style multi-choice routing: each token
    is dispatched to its ``top_k`` experts, gates renormalized over the
    chosen experts, with PRIORITY dispatch — choice 0 claims capacity slots
    first, then choice 1 takes what remains (an over-capacity secondary
    choice drops while primaries survive). ``top_k=1`` is exactly the
    Switch behavior above (same gates, same aux loss, same drops).
    """
    t, d = x.shape
    n = 1 if axis_name is None else lax.axis_size(axis_name)
    e_local = jax.tree_util.tree_leaves(expert_params)[0].shape[0]
    e = n * e_local
    assert router_kernel.shape[1] == e, (
        f"router routes over {router_kernel.shape[1]} experts but the mesh"
        f" holds {e} ({n} devices x {e_local} local)"
    )

    assert 1 <= top_k <= e, (top_k, e)
    # --- routing (fp32 for a stable softmax) ------------------------------
    logits = x.astype(jnp.float32) @ router_kernel.astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_probs, topk_idx = jax.lax.top_k(probs, top_k)     # (T, K)
    gates = (
        topk_probs / jnp.sum(topk_probs, axis=-1, keepdims=True)
        if top_k > 1  # GShard renormalization over the chosen experts
        else topk_probs
    )

    # priority dispatch: a static unroll over choices (K is tiny); choice 0
    # claims capacity slots first via the running per-expert counts
    counts = jnp.zeros((e,), jnp.float32)
    dispatch = jnp.zeros((t, e, capacity), jnp.float32)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    kept = 0.0
    primary_onehot = None
    for k in range(top_k):
        oh = jax.nn.one_hot(topk_idx[:, k], e, dtype=jnp.float32)  # (T, E)
        if k == 0:
            primary_onehot = oh
        # position of each token within its expert's capacity buffer,
        # offset by the slots earlier choices already claimed
        pos = counts[None, :] + jnp.cumsum(oh, axis=0) - oh        # (T, E)
        pos_tok = jnp.sum(pos * oh, axis=-1)                       # (T,)
        keep_k = pos_tok < capacity
        d_k = (
            oh[:, :, None]
            * jax.nn.one_hot(
                pos_tok.astype(jnp.int32), capacity, dtype=jnp.float32
            )[:, None, :]
            * keep_k[:, None, None]
        )
        dispatch = dispatch + d_k
        combine = combine + d_k * gates[:, k][:, None, None]
        counts = counts + jnp.sum(oh * keep_k[:, None].astype(jnp.float32), axis=0)
        kept = kept + jnp.sum(keep_k.astype(jnp.float32))
    dropped_fraction = 1.0 - kept / (t * top_k)

    # load-balance aux loss BEFORE capacity drops, on the PRIMARY
    # assignment (Switch eq. 4; unchanged for top_k=1)
    fraction = jnp.mean(primary_onehot, axis=0)
    prob_mean = jnp.mean(probs, axis=0)
    aux_loss = e * jnp.sum(fraction * prob_mean)
    # (E, C, D) expert-major send buffer
    sent = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))

    # --- to experts: all_to_all over the mesh -----------------------------
    if axis_name is None:
        received = sent  # (E, C, D) — all experts local
    else:
        # expert-major (E, C, D) -> this device's experts with slots from
        # every source device, source-major: (E_local, N·C, D)
        received = lax.all_to_all(
            sent, axis_name, split_axis=0, concat_axis=1, tiled=True
        )

    # --- run the local experts -------------------------------------------
    processed = jax.vmap(expert_fn)(expert_params, received)

    # --- back to sources --------------------------------------------------
    if axis_name is None:
        returned = processed
    else:
        # source-major slots go back to their source; experts re-concatenate
        # expert-major: (E_local, N·C, D) -> (E, C, D), same layout as `sent`
        returned = lax.all_to_all(
            processed, axis_name, split_axis=1, concat_axis=0, tiled=True
        )

    out = jnp.einsum("tec,ecd->td", combine, returned).astype(x.dtype)
    return MoEOutput(out, aux_loss, dropped_fraction)


def stacked_expert_params(params_per_expert: list[PyTree]) -> PyTree:
    """Stack E per-expert pytrees with a leading expert axis — shard it over
    the ``expert`` mesh axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *params_per_expert)


def relu_squared(h: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(h))


# The router indexes T*top_k scalars by indices it has just computed. On the TPU a gather or a
# scatter of that many scalars takes 0.2-0.85 ms a call where its bytes need 0.01-0.1, and a sort
# of them 0.05-0.08 (PERF.md section 6, PR 39 and 40): so each such index is a compare against a
# small static range, or rides the one sort the layer makes anyway. Same values, to the bit
# (inside one jit XLA may add a token's k picks in another order: an ulp of a weight).


def _picked(scores: jax.Array, chosen: jax.Array) -> jax.Array:
    """``take_along_axis(scores, chosen, -1)``: (T, E), (T, K) -> (T, K).

    Of each sum over E one term is not zero, so it is the gather's value; XLA
    fuses the (T, K, E) compare and select into the reduce. jax's cotangent
    of it is the same compare summed over K (a token's choices are distinct:
    again one term a sum) where the gather's is a scatter into (T, E)."""
    is_chosen = chosen[:, :, None] == lax.iota(chosen.dtype, scores.shape[1])
    return jnp.sum(jnp.where(is_chosen, scores[:, None, :], 0.0), axis=-1)


def _slots(chosen: jax.Array, held: Sequence[int]) -> jax.Array:
    """Expert id -> its slot here, ``held.index(id)``, for (N,) ids; every
    absent expert shares the slot past the last. A compare a held id and a
    ``min`` over them, no (E,) table to look up."""
    ids, at = jnp.asarray(held, jnp.int32)[:, None], lax.iota(jnp.int32, len(held))[:, None]
    return jnp.min(jnp.where(chosen == ids, at, len(held)), axis=0)


def _counts(slots: jax.Array, n: int) -> jax.Array:
    """How many of ``slots`` are 0, 1 ... n - 1: ``zeros(n).at[slots].add(1)``
    without the scatter-add, whose every slot is a collision."""
    return jnp.sum(slots == lax.iota(jnp.int32, n)[:, None], axis=1, dtype=jnp.int32)


@jax.custom_vjp
def _sorted_by_slot(slots: jax.Array, weights: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``order = argsort(slots, stable=True)`` and ``weights[order]`` from one
    sort. Its key is slot-major with the position below it, a different key
    a position: sorted, it IS the stable order by slot, the positions are its
    remainders, and the sort has two operands where a stable sort of slots,
    positions and weights has three and a tie-break (``slots.max() *
    slots.size`` has to fit an int32: the caller's to see). The weights ride
    it as the payload.

    jax differentiates a sort's payload through a gather, whose cotangent is
    a scatter-add; here the cotangent rides a second sort, keyed on ``order``:
    a permutation, so sorting by it puts each cotangent back at its assignment."""
    n = slots.size
    key, weights = lax.sort((slots * n + lax.iota(jnp.int32, n), weights), num_keys=1, is_stable=False)
    return lax.rem(key, n), weights


def _sorted_by_slot_fwd(slots, weights):
    order, weights = _sorted_by_slot(slots, weights)
    return (order, weights), order


def _sorted_by_slot_bwd(order, cotangents):
    _, d_weights = lax.sort((order, cotangents[1]), num_keys=1)
    return None, d_weights


_sorted_by_slot.defvjp(_sorted_by_slot_fwd, _sorted_by_slot_bwd)


def chunk_rows(t: int, top_k: int, n_held: int, e: int, block_rows: int = 512) -> int:
    """Rows of one chunk of ``held_experts_moe``'s sorted assignments: the
    larger of ``t`` and 3/2 of the load an even router lands here,
    ``t*top_k*n_held/e``, in whole row tiles, never more than every
    assignment there can be. A rule over the layer's own shapes: a rank that
    holds a small share (0.31-0.5 t expected in the four models before
    mellum) keeps ``t`` rows, one that holds a large one (mellum's 16 of 64
    at top 8: 2 t) gets a first chunk its load fits, so that the later
    chunks stay the rare heavy load and not every step's path (an entered
    ``moe.overflow`` costs ~24 ms a layer, 17 of them bookkeeping over (T, D)
    and the weights' shapes: PERF.md section 6, PR 39). Why 3/2: Zipf token
    ids route by id, and the load was seen at 1.5x the expected in one
    trinity run of nine (a post-norm block under a balanced bias); where
    tokens route by their own ids it stays closer (lfm2 0.93-1.11x, PR 41;
    mellum 0.86-1.15x, 1.71-2.30 T where 2 T is expected, over 3 seeds x 3
    sequences x 4 layers on the chip at the published widths, and 72.7% of
    the chunk in a traced run's worst layer, PR 44). The rule answers an
    expected load, not a collapsed router: where every token picks the same
    experts (mellum with its embedding at 0.02: 0.14-2.97 T by seed) only
    the later chunks do."""
    most = t * min(top_k, n_held)
    expected = -(-3 * t * top_k * n_held // (2 * e))
    return -(-min(max(t, expected), most) // block_rows) * block_rows


def held_experts_moe(
    x: jax.Array,               # (T, D) tokens, compute dtype
    router_in: jax.Array,       # (T, D) what the router scores (fp32 where the caller has it)
    router_kernel: jax.Array,   # (D, E) over ALL experts of the model
    select_bias: jax.Array,     # (E,) added to the scores for SELECTION only
    w_in: jax.Array,            # (len(held), D, F) this rank's experts, stacked
    w_out: jax.Array,           # (len(held), F, D)
    held: Sequence[int],        # the expert ids that live here, in stacking order
    top_k: int,
    scaling: float = 1.0,
    axis_name: Optional[str] = None,
    block_rows: int = 512,
    w_gate: Optional[jax.Array] = None,  # (len(held), D, F): the experts are gated
    score: str = "sigmoid",     # "sigmoid": each expert scored alone | "softmax": over all E
) -> tuple[jax.Array, Dict[str, jax.Array]]:
    """This rank's part of a dropless top-k expert layer.

    Scores are ``sigmoid(router_in @ router_kernel)``, or with
    ``score="softmax"`` the softmax of those logits over the router's whole
    width, in fp32 at full
    precision (a top-k is discrete: a score rounded to bf16 picks other
    experts); each token takes the ``top_k`` largest of ``score +
    select_bias`` and weights them ``scaling * score_i / sum_topk score``.
    Of those T*top_k assignments the ones on a ``held`` expert are sorted by
    expert and computed as grouped products. The router indexes nothing by
    data (``_picked``, ``_slots``, ``_counts``, ``_sorted_by_slot`` above it:
    compares against static ranges and the payload of the one sort, the
    values of the gathers and scatter-adds they stand for); what is left
    indexed by data moves (rows, D), and is each other's transpose:
    ``ops.rows_to_tokens.rows_of_tokens`` brings the tokens to their rows (a
    gather, forward and as the combine's cotangent) and
    ``tokens_from_rows`` adds the weighted rows back into their tokens
    (forward and as the gather's cotangent: on TPU a Pallas kernel that
    holds a tile of tokens in VMEM and reads the rows that belong to it from
    where they lie, fp32 sums; ``.at[token].add`` elsewhere, which on the
    v5e is a loop over the rows, PR 45). The expert's form follows its
    operands: without ``w_gate`` it is ``w_out_e . relu(w_in_e . x)^2``
    (Nemotron-H), with it ``w_out_e . (silu(w_gate_e . x) * (w_in_e . x))``
    (afmoe's gate, up and down projections). The sorted rows stay as they lie,
    each expert's after the last's: the tokens are gathered in that order,
    the two (or three) products are ``ops.grouped_matmul`` over them with the
    experts' row counts as group sizes — row tiles of ``block_rows``, a tile
    two experts share visited once for each, each visit reading its expert's
    weights in place, a tile past the last assignment not visited at all —
    and the weighted rows are added back into their tokens. (``lax.ragged_dot``
    is the same product, and what this is on the CPU; on the v5e XLA's
    lowering of it took 17 ms a call at these shapes, PR 27. The layout that
    answered it then, every expert's rows padded to whole blocks and a
    block's weights picked by a one-hot product, computed 12,288 rows for
    3,000 to 4,400 and wrote a copy of the weights a block; the kernels
    visit 13 to 15 tiles of 512 and copy nothing, PR 32.)

    The work follows the assignments that landed here. They are taken in
    chunks whose size follows the expected load, T*top_k*len(held)/E, read
    from the shapes: ``chunk_rows`` gives a chunk the larger of T rows and
    1.5 times that load (0.31-0.5 T expected in nemotron_h, afmoe, qwen3_next
    and lfm2 as the benchmark cuts them, so T rows; 2 T in mellum's four-chip
    share, so 3 T). The first chunk runs outside any loop and is sized to
    hold them all; a heavier load goes on chunk after chunk (``lax.cond``
    into a ``lax.scan``, a chunk past the last assignment skipped) up to
    T*min(top_k, len(held)) rows — every assignment there can be — so none
    is ever dropped, and no array has a (tokens, experts, capacity) shape.

    Returns ``(out, counters)``: ``out`` (T, D) in ``x``'s dtype, and
    int32 counters of this call — ``held`` (len(held),) assignments per held
    expert, ``absent`` assignments on experts that live elsewhere, ``dropped``
    assignments on held experts that were not computed (always 0; counted
    from the rows the chunks covered, not assumed), ``row_tiles`` the row
    tiles one product of the first chunk visits (the row extent of its
    kernel's grid: work that follows the load). How many chunks held live
    rows is ``max(ceil(sum(held) / chunk_rows(...)), 1)``: a reader has both.

    ``axis_name`` is where the exchange between ranks would ride. Only the
    one-rank layer (``None``) exists, and every model that uses it runs it so:
    one rank of a deployment without its exchange; any other value raises.
    """
    if axis_name is not None:
        raise NotImplementedError("held_experts_moe has no exchange over a mesh axis yet")
    t, d = x.shape
    e = router_kernel.shape[1]
    n_held = len(held)
    assert w_in.shape[0] == w_out.shape[0] == n_held and 1 <= top_k <= e
    assert (n_held + 1) * t * top_k < 2**31  # _sorted_by_slot's key: a slot above each position
    assert w_gate is None or w_gate.shape == w_in.shape
    assert score in ("sigmoid", "softmax"), score
    f32, i32 = jnp.float32, jnp.int32

    # leaf scopes, one metric each (benchmark/layer_metrics/moe_<leaf>_ms.py): every op of the
    # layer sits under exactly one of moe.score / moe.sort / moe.count (inside moe.route),
    # moe.layout (whatever is inside neither), moe.gather / moe.products / moe.combine (inside
    # moe.experts), so the leaves of moe.route and of moe.experts add up to what those read.
    # moe.overflow wraps the later chunks: their leaves nest under it, and its own bookkeeping
    # (cond, scan, zeros, the carry's adds) is the one thing under moe.experts with no leaf
    with jax.named_scope("moe.route"):
        with jax.named_scope("moe.score"):
            logits = jnp.dot(
                router_in.astype(f32), router_kernel.astype(f32), precision=lax.Precision.HIGHEST
            )
            scores = jax.nn.sigmoid(logits) if score == "sigmoid" else jax.nn.softmax(logits, axis=-1)  # (T, E)
            _, chosen = lax.top_k(scores + select_bias.astype(f32), top_k)  # (T, K)
            picked = _picked(scores, chosen)
            weights = scaling * picked / jnp.sum(picked, axis=-1, keepdims=True)
        with jax.named_scope("moe.sort"):
            slots = _slots(chosen.reshape(-1), held)  # (T*K,)
            # held experts' assignments first, by expert; the weights ride the same sort
            order, sorted_weights = _sorted_by_slot(slots, weights.reshape(-1))
        with jax.named_scope("moe.count"):
            counts = _counts(slots, n_held + 1)
            group_sizes, absent = counts[:n_held], counts[n_held]
            group_ends = jnp.cumsum(group_sizes)
            landed = group_ends[-1]

    # chunks of the sorted assignments, in whole row tiles: the first is sized by the expected
    # load (chunk_rows), the later ones take whatever a heavier load leaves
    most = t * min(top_k, n_held)
    rows = chunk_rows(t, top_k, n_held, e, block_rows)
    n_chunks = -(-most // rows)
    pad_to = lambda v, fill: jnp.pad(v[:most], (0, n_chunks * rows - most), constant_values=fill)
    with jax.named_scope("moe.layout"):
        sorted_tokens = pad_to(order // top_k, 0)
        sorted_weights = pad_to(sorted_weights, 0.0)

    def sizes_in(first):
        """How many of each held expert's rows lie in [first, first + rows)."""
        clipped = lambda v: jnp.clip(v, first, first + rows)
        return clipped(group_ends) - clipped(group_ends - group_sizes)

    def chunk(first, x, w_in, w_out, sorted_weights, w_gate=None):
        """Rows [first, first + rows) of the sorted assignments -> their
        part of the output (T, D) and how many of them were live."""
        with jax.named_scope("moe.gather"):
            # a row past the last assignment takes token T, out of range: it reads zeros and adds
            # nowhere, no product visits it and each returns it as zeros
            token = jnp.where(
                first + jnp.arange(rows) < landed, lax.dynamic_slice(sorted_tokens, (first,), (rows,)), t
            )
            sizes = sizes_in(first)
            rows_in = rows_of_tokens(x, token, sizes)
        with jax.named_scope("moe.products"):
            product = functools.partial(grouped_matmul, group_sizes=sizes, row_tile=block_rows)
            hidden = product(rows_in, w_in)
            if w_gate is None:
                hidden = relu_squared(hidden).astype(x.dtype)
            else:
                hidden = (jax.nn.silu(product(rows_in, w_gate)) * hidden).astype(x.dtype)
            part, computed = product(hidden, w_out), jnp.sum(sizes)
        with jax.named_scope("moe.combine"):
            part = part * lax.dynamic_slice(sorted_weights, (first,), (rows,))[:, None]
            return tokens_from_rows(part, token, sizes, t), computed

    def later_chunks(*operands):
        """The rare, heavy load: chunk after chunk until the assignments end.
        A chunk past them is skipped; each is recomputed in the backward pass
        so that the scan keeps one chunk's intermediates, not all."""
        # the checkpoint goes round the cond: inside a branch, what it keeps
        # for the backward pass (the weights) would leave the cond as outputs
        # and be stacked once per chunk
        @jax.checkpoint
        def maybe_chunk(first):
            return lax.cond(first < landed, lambda: chunk(first, *operands), nothing)

        def next_chunk(carry, first):
            return jax.tree_util.tree_map(jnp.add, carry, maybe_chunk(first)), None

        return lax.scan(next_chunk, nothing(), jnp.arange(1, n_chunks) * rows)[0]

    def nothing(*_):
        zeros = jnp.zeros((t, d), f32), jnp.zeros((), i32)
        varying = tuple(jax.typeof(x).vma)  # inside shard_map fresh zeros are invariant
        return tuple(lax.pcast(z, varying, to="varying") for z in zeros) if varying else zeros

    with jax.named_scope("moe.experts"):
        with jax.named_scope("moe.products"):
            operands = (x, w_in.astype(x.dtype), w_out.astype(x.dtype), sorted_weights)
            if w_gate is not None:
                operands += (w_gate.astype(x.dtype),)
        # the first chunk nearly always holds every assignment: it runs outside any loop
        out, computed = chunk(0, *operands)
        if n_chunks > 1:
            with jax.named_scope("moe.overflow"):
                more, more_computed = lax.cond(landed > rows, later_chunks, nothing, *operands)
                out, computed = out + more, computed + more_computed
    with jax.named_scope("moe.layout"):
        counters = {
            "held": group_sizes, "absent": absent, "dropped": landed - computed,
            "row_tiles": row_tiles(sizes_in(0), block_rows),
        }
        return out.astype(x.dtype), counters
