"""What PR 27 added to the benchmark: the configuration file against its
published source, the required-work functions against hand counts, the
language-model traffic, and the new readers on a made-up run."""

import types

import numpy as np
import pytest

from benchmark import cells, lm_traffic
from benchmark.flops import nemotron_h as flops
from benchmark.layer_metrics import (
    expert_load_max_over_mean,
    moe_experts_roofline,
    scoped,
    ssd_ms,
    ssd_roofline,
)

CELL = "nemotron_psgd16_t8k"


def test_the_configuration_keeps_every_published_width_and_says_what_it_cut():
    cfg = cells.cell(CELL)["config"]
    published = {
        "hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128,
        "conv_kernel": 4, "chunk_size": 128, "num_attention_heads": 32, "num_key_value_heads": 2, "head_dim": 128,
        "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712, "router_width": 128,
        "num_experts_per_tok": 6, "routed_scaling_factor": 2.5, "norm_eps": 1e-5,
    }
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size"]
    # one whole period of the published pattern, which repeats MEMEM*E
    assert cfg["hybrid_override_pattern"] == "MEMEM*E" == cfg["published"]["hybrid_override_pattern"][:7]
    assert cfg["published"]["hybrid_override_pattern"].startswith("MEMEM*E" * 5)
    assert cfg["num_hidden_layers"] == len(cfg["hybrid_override_pattern"]) == 7
    assert cfg["held_experts"] == list(range(8)) and cfg["n_routed_experts"] == 8
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"] == 131072
    assert "16 chips" in cfg["deployment"] and set(cfg["cut"]) >= set(cfg["reduced"]) - {"hybrid_override_pattern"}
    assert not any(k.endswith(("_dim", "_rank")) or "size" in k and k != "vocab_size" for k in cfg["reduced"])


def test_required_operations_against_hand_counts():
    cfg = cells.cell(CELL)["config"]
    mamba = 2 * 2688 * (4096 + 6144 + 64) + 2 * 4096 * 2688 + 2 * 4 * 6144 + 5 * 64 * 64 * 128 + 3 * 64 * 64
    experts = 2 * 2688 * 128 + 4 * 2688 * 3712 + (6 * 8 / 128) * 4 * 2688 * 1856
    attention = 2 * 2688 * (32 + 4) * 128 + 2 * 4096 * 2688 + 2 * 8192 * 32 * 128  # causal: half of 4*T*H*D
    head = 2 * 2688 * 16384
    forward = 3 * mamba + 3 * experts + attention + head
    assert flops.forward_flops_per_token(cfg) == pytest.approx(forward, rel=1e-12)
    assert flops.flops_per_sample(cfg) == pytest.approx(14.41e12, rel=2e-3)  # 0.587 GFLOP a token forward
    ops, moved = flops.ssd_cost(cfg, 8192)
    assert ops == 3 * 8192 * (5 * 64 * 64 * 128 + 3 * 64 * 64)
    assert moved == 8192 * ((2 * 4096 + 2 * 1024) * 2 + 256 + (4 * 4096 + 4 * 1024) * 2 + 512)
    ops, moved = flops.experts_cost(cfg, 3072)
    assert ops == 3 * 3072 * 4 * 2688 * 1856
    assert moved == 3 * 2 * 8 * 2688 * 1856 * 2 + 5 * 3072 * 2688 * 2


def test_lm_sequences_follow_the_workload_file():
    cell = cells.cell(CELL)
    spec = {**cell["workload"]["traffic"], "seq_len": 512, "pool_samples": 64}
    pool = lm_traffic.lm_sequences(spec, 16384, seed=2**31 + 5)
    again = lm_traffic.lm_sequences(spec, 16384, seed=2**31 + 5)
    other = lm_traffic.lm_sequences(spec, 16384, seed=6)
    assert all(np.array_equal(pool[k], again[k]) for k in pool)
    assert not np.array_equal(pool["input_ids"], other["input_ids"])
    ids, labels = pool["input_ids"], pool["labels"]
    assert ids.shape == labels.shape == (64, 512) and ids.dtype == labels.dtype == np.int32
    assert np.array_equal(ids[:, 1:], labels[:, :-1])  # the label is the next id
    assert 0 <= ids.min() and ids.max() < 16384
    counts = np.sort(np.bincount(ids.ravel(), minlength=16384))[::-1]
    assert 0.08 < counts[0] / ids.size < 0.13  # Zipf 1.0 over 16,384: the top id is 1 / H(16384) = 9.7%
    assert counts[:16].sum() / ids.size > 0.28


def fake_run(spans, per_step_s=None):
    cfg = dict(cells.cell(CELL)["config"], per_chip_batch=1)
    trace = None
    if per_step_s is not None:
        op = types.SimpleNamespace(op_name="jit(f)/step.grads/jvp(mamba.ssd)/dot_general", self_s=per_step_s)
        other = types.SimpleNamespace(op_name="jit(f)/step.grads/checkpoint/moe.experts/ragged_dot", self_s=0.004)
        trace = types.SimpleNamespace(
            per_step=lambda pick: sum(o.self_s for o in (op, other) if pick(o)) or None
        )
    return types.SimpleNamespace(
        cfg=cfg, trace=trace, peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        clean_spans=lambda name: [s for s in spans if s["name"] == name],
    )


def counted(held, absent=0, dropped=0):
    layer = {"held": [held], "absent": [absent], "dropped": [dropped]}
    return {"name": "step/loss_sync", "step": 0, "counters": {"layer_1": layer, "layer_3": layer}}


def test_readers_on_a_made_up_run(capsys):
    run = fake_run([counted([600, 300, 300, 300, 300, 300, 300, 672])], per_step_s=0.0622231912)
    assert ssd_ms.read(run) == pytest.approx(62.2231912)
    # three layers, 0.6222 ms each (the bytes bound it), over 62.2 ms: 3%
    assert ssd_roofline.read(run) == pytest.approx(3.0, rel=1e-6)
    # 3072 assignments a layer: 0.9336 ms of MXU each, two layers, over 4 ms
    assert moe_experts_roofline.read(run) == pytest.approx(100 * 2 * 0.93356264869e-3 / 0.004, rel=1e-6)
    assert expert_load_max_over_mean.read(run) == pytest.approx(672 * 8 / 3072)
    assert "0 dropped" in capsys.readouterr().out
    assert scoped.step_counters(run)[0]["layer_1"]["absent"] == 0


def test_readers_return_nothing_where_the_program_has_no_such_scope_or_counter():
    bare = fake_run([{"name": "step/loss_sync", "step": 0}], per_step_s=None)
    for reader in (ssd_ms, ssd_roofline, moe_experts_roofline, expert_load_max_over_mean):
        assert reader.read(bare) is None
    other_model = fake_run([], per_step_s=0.01)
    other_model.cfg = {"per_chip_batch": 48}
    assert ssd_roofline.read(other_model) is None and moe_experts_roofline.read(other_model) is None
