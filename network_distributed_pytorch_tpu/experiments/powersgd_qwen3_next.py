"""PowerSGD-compressed training of a Qwen3-Next language model: three
Gated-DeltaNet linear-attention layers to one gated full-attention layer,
softmax-routed experts with a gated shared expert in every layer
(``models/qwen3_next.py``), one expert-parallel rank's share of the experts.

The experiment is ``experiments/lm.py``'s ``train_lm`` with this model
(``make_train_step`` with ``PowerSGDReducer``, ``train_loop``,
packed Zipf ids, the expert layers' counters on every step's
``step/loss_sync`` span). The model has no selection bias and no buffers.

``preset="small"`` is the test tier's model; ``"full"`` is the published
widths at the four-layer (one period), 16-of-512-experts, 18,992-row cut the
benchmark runs (``benchmark/configs/qwen3-next-80b-a3b.json``).
"""

from __future__ import annotations

from typing import Dict, Optional

from ..models.layers import FULL
from ..models.qwen3_next import LINEAR, Qwen3NextConfig, Qwen3NextLM, qwen3_next_tiny
from ..utils.config import ExperimentConfig
from .lm import default_config, model_kwargs, train_lm


def run(
    config: Optional[ExperimentConfig] = None,
    preset: str = "small",
    mesh=None,
    seq_len: Optional[int] = None,
    pool_sequences: int = 64,
    max_steps_per_epoch: Optional[int] = None,
) -> Dict:
    config = config or default_config()
    if preset == "full":
        model = Qwen3NextLM(Qwen3NextConfig(
            vocab_size=18992, layer_types=(LINEAR, LINEAR, LINEAR, FULL),
            held_experts=tuple(range(16)), remat=True, **model_kwargs(config),
        ))
        seq_len = seq_len or 8192
    else:
        model = qwen3_next_tiny(**model_kwargs(config))
        seq_len = seq_len or 64
    c = model.config
    return train_lm(
        "powersgd_qwen3_next", model, config, mesh, seq_len, pool_sequences, max_steps_per_epoch,
        {"preset": preset, "model": {
            "layer_types": list(c.layer_types), "hidden_size": c.hidden_size,
            "held_experts": len(c.held_experts), "n_routed_experts": c.n_routed_experts,
            "vocab_size": c.vocab_size,
        }},
    )
