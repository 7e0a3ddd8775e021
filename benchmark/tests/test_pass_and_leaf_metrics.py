"""The eleven readers of PR 39 (the expert layer's eight leaf scopes, the
three passes) on a hand-made trace whose paths have the forms the cells'
compiled steps have, and ``passes.pass_of`` on a step compiled here: a jax
that writes the recomputation or the backward otherwise fails this file, not
a chip run that silently reads 0."""

import json
import re
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells
from benchmark.layer_metrics import passes
from benchmark.trace import reduce as R

ROUTE = ("moe_score_ms", "moe_sort_ms", "moe_count_ms")
CHUNK = ("moe_gather_ms", "moe_products_ms", "moe_combine_ms")
PASSES = ("fwd_ms", "remat_ms", "bwd_ms")
NEW = ROUTE + CHUNK + ("moe_layout_ms", "moe_overflow_ms") + PASSES
T8K = ("nemotron_psgd16_t8k", "trinity_psgd16_t8k", "qwen3next_psgd16_t8k")

FWD = "jit(sharded_body)/step.grads/jvp(AfmoeLM)/layer_1/mlp/"
REMAT = "jit(sharded_body)/step.grads/transpose(jvp(AfmoeLM))/step.grads/jvp(AfmoeLM)/checkpoint/rematted_computation/layer_1/mlp/"
BWD = "jit(sharded_body)/step.grads/transpose(jvp(AfmoeLM))/step.grads/jvp(AfmoeLM)/checkpoint/layer_1/mlp/"
LATER = "moe.experts/moe.overflow/cond/branch_1_fun/while/body/closed_call/checkpoint/"
# (path, microseconds a step): one expert layer whose load passed T, a flash kernel, the update
OPS = [
    (FWD + "moe.route/moe.score/dot_general", 300), (REMAT + "moe.route/moe.score/top_k", 310),
    (BWD + "moe.route/moe.score/scatter-add", 120),
    (FWD + "moe.route/moe.sort/sort", 500), (REMAT + "moe.route/moe.sort/sort", 510),
    (FWD + "moe.route/moe.count/scatter-add", 40), (REMAT + "moe.route/moe.count/cumsum", 41),
    (FWD + "moe.layout/gather", 70), (BWD + "moe.layout/scatter-add", 90), (FWD + "moe.layout/convert_element_type", 5),
    (FWD + "moe.experts/moe.gather/gather", 200), (BWD + "moe.experts/moe.gather/scatter-add", 600),
    (FWD + "moe.experts/moe.products/jit(_rows_by_groups)/grouped_matmul/pallas_call", 400),
    (REMAT + "moe.experts/moe.products/jit(_rows_by_groups)/grouped_matmul/pallas_call", 410),
    (BWD + "moe.experts/moe.products/jit(_groups_of_rows)/grouped_matmul_tn/pallas_call", 900),
    (FWD + "moe.experts/moe.combine/scatter-add", 700), (BWD + "moe.experts/moe.combine/gather", 150),
    # the later chunks: a chunk's leaves under moe.overflow, and the bookkeeping no leaf reaches
    (FWD + "moe.experts/moe.overflow/cond/branch_1_fun/closed_call/while/body/closed_call/checkpoint/cond/branch_1_fun/moe.gather/gather", 210),
    (BWD + LATER + "rematted_computation/cond/branch_1_fun/moe.products/jit(_rows_by_groups)/grouped_matmul/pallas_call", 420),
    (BWD + LATER + "cond/branch_1_fun/moe.combine/gather", 160),
    (FWD + "moe.experts/moe.overflow/cond", 3), (BWD + "moe.experts/moe.overflow/cond/branch_1_fun/while/body/add_any", 30),
    (FWD + "moe.experts/moe.overflow/add", 80),
    (FWD + "moe.shared/dot_general", 1000),
    (FWD.replace("mlp", "self_attn") + "attn.window/jit(flash_attention)/pallas_call", 3000),
    (BWD.replace("mlp", "self_attn") + "attn.window/jit(flash_attention)/flash_attention_bwd/pallas_call", 6000),
    ("jit(sharded_body)/step.grads/jvp(AfmoeLM)/lm_head/dot_general", 2000),
    ("jit(sharded_body)/step.update/sub", 800),
]


def _event(name, start_us, dur_us):
    return NS(name=name, start_ns=int(start_us * 1000), duration_ns=int(dur_us * 1000))


def reduced(ops, executions=4):
    """``ops`` run back to back in every execution of one program on one
    chip; the window keeps ``executions - 2`` whole steps."""
    hlo = "ENTRY %main (a: f32[4]) -> f32[4] {\n" + "".join(
        f'  %fusion.{i} = f32[4]{{0}} fusion(%a), kind=kLoop, calls=%f{i}, metadata={{op_name="{path}"}}\n'
        for i, (path, _) in enumerate(ops)
    ) + "}\n"
    period = sum(us for _, us in ops) + 100
    events, modules = [], []
    for p in range(executions):
        at = 1000 + period * p
        modules.append(_event("jit_step(1)", at, period - 100))
        for i, (_, us) in enumerate(ops):
            events.append(_event(f"%fusion.{i} = f32[4]{{0}} fusion(%a), kind=kLoop", at, us))
            at += us
    plane = NS(name="/device:TPU:0", lines=[NS(name="XLA Modules", events=modules), NS(name="XLA Ops", events=events)])
    return R.reduce_planes([plane], hlo)


def run_of(ops):
    return NS(trace=reduced(ops) if ops else None)


def read(name, run):
    return cells.module("layer_metrics", name).read(run)


def ms(*fragments, ops=OPS, without=()):
    """By hand: the ops whose path holds every fragment and none of ``without``."""
    return sum(us for path, us in ops if all(f in path for f in fragments) and not any(w in path for w in without)) / 1e3


def test_each_leaf_reads_its_scope_and_the_leaves_add_up(capsys):
    run = run_of(OPS)
    assert run.trace.steps == 2
    got = {name: read(name, run) for name in NEW}
    assert got["moe_score_ms"] == pytest.approx(0.73) == pytest.approx(ms("moe.score"))
    assert got["moe_sort_ms"] == pytest.approx(1.01)
    assert got["moe_count_ms"] == pytest.approx(0.081)
    assert got["moe_layout_ms"] == pytest.approx(0.165)
    assert got["moe_gather_ms"] == pytest.approx(1.01)
    assert got["moe_products_ms"] == pytest.approx(2.13)
    # the combine's own ops and the later chunks' bookkeeping: cond 3, add_any 30, add 80
    assert got["moe_combine_ms"] == pytest.approx(1.01 + 0.113)
    assert got["moe_overflow_ms"] == pytest.approx(0.903) == pytest.approx(ms("moe.overflow"))
    route, experts = read("moe_route_ms", run), read("moe_experts_ms", run)
    assert sum(got[n] for n in ROUTE) == pytest.approx(route, abs=1e-9)
    assert sum(got[n] for n in CHUNK) == pytest.approx(experts, abs=1e-9)
    assert got["moe_layout_ms"] + route + experts == pytest.approx(ms("moe.", without=("moe.shared",)))
    capsys.readouterr()


def test_the_passes_add_up_to_grads_ms_and_print_their_table(capsys):
    run = run_of(OPS)
    got = {name: read(name, run) for name in PASSES}
    assert got["fwd_ms"] == pytest.approx(ms("step.grads", without=("transpose(",)))
    assert got["remat_ms"] == pytest.approx(ms("rematted_computation"))
    assert got["bwd_ms"] == pytest.approx(ms("transpose(", without=("rematted_computation",)))
    assert sum(got.values()) == pytest.approx(read("grads_ms", run), abs=1e-9)
    # the table: one line before the contract's last, every op under step.grads once, by its innermost scope
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("benchmark: passes ")]
    assert len(lines) == 1
    table = json.loads(lines[0][len("benchmark: passes "):])
    assert sum(map(sum, table.values())) == pytest.approx(read("grads_ms", run), abs=1e-3)
    assert table["attn.window"] == [3.0, 0.0, 6.0] and table["moe.shared"] == [1.0, 0.0, 0.0]
    assert table["step.grads"] == [2.0, 0.0, 0.0] and "step.update" not in table
    assert table["moe.products"] == pytest.approx([0.4, 0.41 + 0.42, 0.9])  # the later chunk's recomputation in the backward
    assert table["moe.overflow"] == pytest.approx([0.083, 0.0, 0.03])
    assert list(table)[0] == "attn.window"  # largest first


def test_nothing_to_read():
    untraced = run_of([])
    for name in NEW:
        assert read(name, untraced) is None
    # the parent's program: no leaf inside moe.route / moe.experts, and a model that keeps its activations
    parents = [
        ("jit(sharded_body)/step.grads/jvp(M)/layer_1/moe.route/sort", 500),
        ("jit(sharded_body)/step.grads/jvp(M)/layer_1/moe.experts/gather", 200),
        ("jit(sharded_body)/step.grads/transpose(jvp(M))/layer_1/moe.experts/scatter-add", 600),
    ]
    run = run_of(parents)
    for name in ROUTE + CHUNK + ("moe_layout_ms", "moe_overflow_ms", "remat_ms"):
        assert read(name, run) is None
    assert read("fwd_ms", run) == pytest.approx(0.7) and read("bwd_ms", run) == pytest.approx(0.6)
    assert read("fwd_ms", run) + read("bwd_ms", run) == pytest.approx(read("grads_ms", run))


@pytest.mark.parametrize("path, which", [
    (FWD + "moe.experts/moe.products/jit(_rows_by_groups)/grouped_matmul/pallas_call", "fwd"),
    ("jit(step)/step.grads/jvp(moe.experts)/tanh", "fwd"),
    (REMAT + "moe.route/moe.sort/sort", "remat"),
    (BWD + LATER + "rematted_computation/cond/branch_1_fun/moe.gather/gather", "remat"),
    (BWD + "moe.experts/moe.products/jit(_rows_by_groups)/grouped_matmul_nt/pallas_call", "bwd"),
    ("jit(sharded_body)/step.grads/transpose(jvp(DistilBertForSequenceClassification))/distilbert/layer_0/attention/"
     "jit(flash_attention)/flash_attention_bwd/pallas_call", "bwd"),
    ("jit(step)/step.grads/transpose(jvp())/mul;jit(step)/step.grads/transpose(jvp())/broadcast_in_dim", "bwd"),
])
def test_pass_of_on_the_forms_the_cells_steps_have(path, which):
    assert passes.pass_of(path) == which


@pytest.fixture(scope="module")
def step_paths():
    """The ``op_name``s under ``step.grads`` of a compiled ``make_train_step``
    whose loss checkpoints two blocks, each a grouped product (a
    ``jax.custom_vjp`` over Pallas kernels, interpreted) and a ``tanh``."""
    from network_distributed_pytorch_tpu.ops.grouped_matmul import grouped_matmul
    from network_distributed_pytorch_tpu.parallel.reducers import PowerSGDReducer
    from network_distributed_pytorch_tpu.parallel.trainer import make_train_step
    from network_distributed_pytorch_tpu.utils.hlo_audit import hlo_text_of_compiled

    def loss_fn(params, model_state, batch):
        @jax.checkpoint
        def block(x, w):
            with jax.named_scope("moe.experts"):
                sizes = jnp.array([8, 16, 8], jnp.int32)
                return jnp.tanh(grouped_matmul(x, w, sizes, row_tile=8, interpret=True))

        h = block(block(batch["x"] @ params["embed"], params["w1"]), params["w2"])
        return jnp.mean(jnp.sin(h) ** 2), model_state

    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    params = {"embed": jax.random.normal(keys[0], (16, 16)), "w1": jax.random.normal(keys[1], (3, 16, 16)),
              "w2": jax.random.normal(keys[2], (3, 16, 16))}
    step = make_train_step(loss_fn, PowerSGDReducer(compression_rank=2), params, 0.05, mesh=None)
    batch = {"x": jax.random.normal(keys[3], (32, 16))}
    hlo = hlo_text_of_compiled(step.fn.lower(step.init_state(params), batch).compile())
    return [p for p in re.findall(r'op_name="([^"]*)"', hlo) if "step.grads" in R.scopes_of(p)]


def test_pass_of_on_a_compiled_step(step_paths):
    by_pass = {which: [p for p in step_paths if passes.pass_of(p) == which] for which in passes.PASSES}
    assert all(by_pass.values()), {k: len(v) for k, v in by_pass.items()}
    holds = lambda which, part: any(part in p.split("/") for p in by_pass[which])
    # what only a forward has: in the forward proper and in the recomputation, never in the backward
    for part in ("tanh", "grouped_matmul"):
        assert holds("fwd", part) and holds("remat", part) and not holds("bwd", part), part
    assert holds("fwd", "sin") and not holds("remat", "sin")  # outside the checkpoint
    # the custom VJP's rule runs in the backward, and so read its kernels
    for part in ("grouped_matmul_nt", "grouped_matmul_tn"):
        assert holds("bwd", part) and not holds("fwd", part) and not holds("remat", part), part
    assert all("transpose(" in p for p in by_pass["remat"])  # why rematted_computation is looked for first


def test_the_new_metrics_are_listed_where_they_have_something_to_read():
    for cell in T8K:
        assert set(NEW) <= {m["name"] for m in cells.cell(cell)["per_layer"]}
    for cell in ("imdb_psgd16_b16", "imdb_psgd16_b128", "imdb_psgd16_b16_x4"):
        assert set(NEW) & {m["name"] for m in cells.cell(cell)["per_layer"]} == {"fwd_ms", "bwd_ms"}
    assert not set(NEW) & {m["name"] for m in cells.cell("cifar_psgd4_b128")["per_layer"]}
    listed = {m["name"]: m for m in cells.manifest()["per_layer"]}
    for name in NEW:
        assert {k: listed[name][k] for k in ("unit", "better", "source", "layer", "moves")} == {
            "unit": "ms", "better": "lower", "source": "device_trace", "layer": "step compute", "moves": "step_ms"}
