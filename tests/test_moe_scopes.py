"""The expert layer's leaf scopes add up (CPU; reads names, runs nothing).

``held_experts_moe`` nests leaf scopes inside ``moe.route`` and ``moe.experts``
and one beside them, and the benchmark has a metric for each
(``benchmark/layer_metrics/moe_<leaf>_ms.py``) that picks a device op by the
leaf's name anywhere on the op's path. ``moe_score_ms + moe_sort_ms +
moe_count_ms = moe_route_ms`` and ``moe_gather_ms + moe_products_ms +
moe_combine_ms = moe_experts_ms`` hold only while every instruction of the
compiled program that names the layer's scope names exactly one of its
leaves: this file holds the next edit of ``parallel/moe.py`` to that, over the
``op_name`` of every instruction in the compiled HLO of a step-like program
(value and gradient of a ``jax.checkpoint``ed layer: forward, recomputation
and backward). It also holds jax to the three markers the benchmark reads the
pass from (``benchmark/layer_metrics/passes.py``).
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest

from benchmark.layer_metrics.passes import pass_of
from network_distributed_pytorch_tpu.ops.rows_to_tokens import rows_of_tokens, tokens_from_rows
from network_distributed_pytorch_tpu.parallel import moe
from network_distributed_pytorch_tpu.parallel.moe import held_experts_moe
from network_distributed_pytorch_tpu.utils.hlo_audit import hlo_text_of_compiled

ROUTE = ("moe.score", "moe.sort", "moe.count")
CHUNK = ("moe.gather", "moe.products", "moe.combine")
LEAVES = ROUTE + CHUNK + ("moe.layout", "moe.overflow")
T, D, E, F, HELD = 32, 16, 8, 8, (1, 4, 6)

# (gated, score, top_k): top_k 3 of 3 held gives three chunks of T rows, top_k 1 gives one
CASES = [
    pytest.param(False, "sigmoid", 3, id="ungated_sigmoid_3_chunks"),
    pytest.param(True, "softmax", 3, id="gated_softmax_3_chunks"),
    pytest.param(False, "softmax", 1, id="ungated_softmax_1_chunk"),
    pytest.param(True, "sigmoid", 1, id="gated_sigmoid_1_chunk"),
]


def step_like(gated, score, top_k):
    """Value and gradient of the ``jax.checkpoint``ed layer, and its operands."""

    @jax.checkpoint
    def layer(x, router, w_in, w_out, w_gate):
        out, _ = held_experts_moe(
            x, x, router, jnp.zeros((E,)), w_in, w_out, HELD, top_k, 2.5, block_rows=8,
            w_gate=w_gate if gated else None, score=score,
        )
        return out

    def loss(*args):
        return jnp.sum(jnp.tanh(layer(*args)) ** 2)

    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    shapes = [(T, D), (D, E), (len(HELD), D, F), (len(HELD), F, D), (len(HELD), D, F)]
    args = [jax.random.normal(k, s) for k, s in zip(keys, shapes)]
    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3)), args


@functools.lru_cache(maxsize=None)
def op_names(gated, score, top_k):
    """The ``op_name`` of every instruction of the compiled program."""
    step, args = step_like(gated, score, top_k)
    program = jax.jit(step).lower(*args).compile()
    return re.findall(r'op_name="([^"]*)"', hlo_text_of_compiled(program))


def equations(jaxpr, outer=""):
    """(primitive, name stack from the top) of every equation, inner jaxprs
    too; a ``pallas_call`` as ``pallas_call:<its name=>``."""
    for eqn in jaxpr.eqns:
        path = f"{outer}/{eqn.source_info.name_stack}"
        yield eqn.primitive.name + (f":{eqn.params['name']}" if eqn.primitive.name == "pallas_call" else ""), path
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(inner, path)


def named(path, names):
    return [n for n in names if n in path]


@pytest.mark.parametrize("gated, score, top_k", CASES)
def test_an_op_of_the_router_names_exactly_one_leaf(gated, score, top_k):
    paths = [p for p in op_names(gated, score, top_k) if "moe.route" in p]
    assert paths
    for path in paths:
        assert len(named(path, ROUTE)) == 1 and not named(path, CHUNK + ("moe.layout", "moe.overflow")), path
    for leaf in ROUTE:
        assert any(leaf in p for p in paths), leaf


@pytest.mark.parametrize("gated, score, top_k", CASES)
def test_an_op_of_the_experts_names_exactly_one_leaf(gated, score, top_k):
    """But for the later chunks' own bookkeeping (the ``cond``, the ``scan``,
    the zeros and the carry's adds: jax names those from the call), which
    ``moe_combine_ms`` counts by that very rule."""
    paths = [p for p in op_names(gated, score, top_k) if "moe.experts" in p]
    assert paths
    for path in paths:
        assert not named(path, ROUTE + ("moe.layout",)), path
        leaves = named(path, CHUNK)
        assert len(leaves) == 1 or (not leaves and "moe.overflow" in path), path
    for leaf in CHUNK:
        assert any(leaf in p for p in paths), leaf


@pytest.mark.parametrize("gated, score, top_k", CASES)
def test_the_layout_is_in_neither_and_the_overflow_only_in_the_experts(gated, score, top_k):
    paths = op_names(gated, score, top_k)
    layout = [p for p in paths if "moe.layout" in p]
    assert layout
    for path in layout:
        assert not named(path, ("moe.route", "moe.experts") + ROUTE + CHUNK + ("moe.overflow",)), path
    overflow = [p for p in paths if "moe.overflow" in p]
    assert bool(overflow) == (top_k > 1)  # one chunk holds every assignment there can be: no cond
    for path in overflow:
        assert "moe.experts" in path, path
    assert any(named(p, CHUNK) for p in overflow) or not overflow  # the chunk's leaves nest under it
    # and no op of the layer is under none of them
    for path in paths:
        if "moe." in path:
            assert named(path, LEAVES), path


@pytest.mark.parametrize("gated, score, top_k", CASES)
def test_the_router_indexes_without_a_gather_or_a_scatter(gated, score, top_k):
    """The router's index work is compares and a sort: the only equations
    that index by data in the layer's value and gradient move (rows, D) data,
    under ``moe.gather`` and ``moe.combine``. Off the chip (this test: the
    CPU path, what the layer was before PR 45) those are the gather of the
    tokens to their rows and the scatter-add of the rows back, forward and
    recomputed, each one's cotangent the other primitive under the same
    name; on the chip the next test."""
    step, args = step_like(gated, score, top_k)
    eqns = list(equations(jax.make_jaxpr(step)(*args).jaxpr))
    indexed = [(primitive, path) for primitive, path in eqns if "gather" in primitive or "scatter" in primitive]
    assert {primitive for primitive, _ in indexed} == {"gather", "scatter-add"}
    for primitive, path in indexed:
        leaves = named(path, ROUTE + CHUNK + ("moe.layout", "moe.route"))
        assert leaves in (["moe.gather"], ["moe.combine"]), (primitive, path)
    # what a gather fetched rides the sort the layer makes anyway: forward, recomputed, and one
    # more for the cotangent
    sorts = [path for primitive, path in eqns if primitive == "sort"]
    assert len(sorts) == 3 and all("moe.sort" in path for path in sorts), sorts


@pytest.mark.parametrize("gated, score, top_k", CASES)
def test_on_the_chip_only_gathers_index_by_data_and_the_adds_are_the_kernel(gated, score, top_k, monkeypatch):
    """What the backend selects on TPU, traced here (nothing is lowered):
    the tokens to their rows and the combine's cotangent are gathers, as off
    the chip (and the grouped products' kernels look their few visits up);
    both adds of rows into tokens (``moe.combine`` forward and recomputed,
    ``moe.gather``'s cotangent) are the ``tokens_from_rows`` kernel, so the layer has no scatter at all; the kernel's ranges are
    compares and a cumulative sum, so the sorts stay three; and the kernel
    with everything it is made from names exactly one leaf."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step, args = step_like(gated, score, top_k)
    eqns = list(equations(jax.make_jaxpr(step)(*args).jaxpr))
    indexed = [(primitive, path) for primitive, path in eqns if "gather" in primitive or "scatter" in primitive]
    assert {primitive for primitive, _ in indexed} == {"gather"}
    for primitive, path in indexed:
        # under moe.products: the grouped products' visit tables (ops.grouped_matmul._visits), (tiles + held) scalars
        leaves = named(path, ROUTE + CHUNK + ("moe.layout", "moe.route"))
        assert leaves in (["moe.gather"], ["moe.combine"], ["moe.products"]), path
    adds = [path for primitive, path in eqns if primitive == "pallas_call:tokens_from_rows"]
    assert {tuple(named(path, CHUNK)) for path in adds} == {("moe.gather",), ("moe.combine",)}, adds
    assert all("transpose(" in path for path in adds if "moe.gather" in path)  # the gather's is a cotangent
    assert any("transpose(" not in path for path in adds if "moe.combine" in path)  # the combine's runs forward
    sorts = [path for primitive, path in eqns if primitive == "sort"]
    assert len(sorts) == 3 and all("moe.sort" in path for path in sorts), sorts
    for primitive, path in eqns:
        if "moe.experts" in path:
            leaves = named(path, CHUNK)
            assert not named(path, ROUTE + ("moe.layout",)), (primitive, path)
            assert len(leaves) == 1 or (not leaves and "moe.overflow" in path), (primitive, path)


@pytest.mark.parametrize("gated, score, top_k", CASES)
def test_an_op_of_the_interpreted_kernel_names_exactly_one_leaf(gated, score, top_k, monkeypatch):
    """The compiled program with the kernel in the Pallas interpreter (its
    copies, loops and row adds as XLA instructions of their own): every
    instruction under ``moe.experts`` still names one leaf, and the kernel's
    are under the two that add rows into tokens."""
    monkeypatch.setattr(moe, "rows_of_tokens", functools.partial(rows_of_tokens, interpret=True))
    monkeypatch.setattr(moe, "tokens_from_rows", functools.partial(tokens_from_rows, interpret=True))
    step, args = step_like(gated, score, top_k)
    program = jax.jit(step).lower(*args).compile()
    paths = [p for p in re.findall(r'op_name="([^"]*)"', hlo_text_of_compiled(program)) if "moe.experts" in p]
    for path in paths:
        assert not named(path, ROUTE + ("moe.layout",)), path
        leaves = named(path, CHUNK)
        assert len(leaves) == 1 or (not leaves and "moe.overflow" in path), path
    kernel = [p for p in paths if "tokens_from_rows" in p]
    assert {tuple(named(p, CHUNK)) for p in kernel} == {("moe.gather",), ("moe.combine",)}, kernel


def test_no_leaf_name_is_part_of_another():
    names = LEAVES + ("moe.route", "moe.experts", "moe.shared")
    for a in names:
        assert re.match(r"^[a-z_]+\.[a-z_]+$", a)  # benchmark/trace/reduce.py's scopes
        assert not [b for b in names if a != b and a in b], a


@pytest.mark.parametrize("gated, score, top_k", CASES[:2])
def test_the_three_passes_are_on_the_paths(gated, score, top_k):
    """A primitive only the forward has is found in the forward proper and,
    where it is inside the checkpoint (the router's ``sort``), in the
    recomputation, never in the backward; a jax that renames
    ``rematted_computation`` or ``transpose(`` fails here, not on the chip."""
    by_pass = {"fwd": [], "remat": [], "bwd": []}
    for path in op_names(gated, score, top_k):
        if path.startswith("jit("):
            by_pass[pass_of(path)].append(path)
    assert all(by_pass.values())
    last = lambda which, under="": {p.rsplit("/", 1)[-1] for p in by_pass[which] if under in p}
    # every pass sorts (the sorted weights' cotangent rides a sort of its own, keyed on the
    # order); only the forward's sort, and its recomputation, reads positions back off its keys
    assert all("sort" in last(which, "moe.sort") for which in by_pass)
    assert "rem" in last("fwd", "moe.sort") and "rem" in last("remat", "moe.sort")
    assert "rem" not in last("bwd", "moe.sort")
    # the loss is outside the checkpoint
    assert "tanh" in last("fwd") and "tanh" not in last("remat") and "tanh" not in last("bwd")
    assert any("moe.combine" in p for p in by_pass["bwd"])
    # the recomputation runs under the backward's transpose: it is told first
    assert all("transpose(" in p for p in by_pass["remat"])
