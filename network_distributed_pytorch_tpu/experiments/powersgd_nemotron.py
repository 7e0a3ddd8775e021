"""PowerSGD-compressed training of a Nemotron-H language model: Mamba-2,
routed-expert and grouped-query attention layers in one stack
(``models/nemotron_h.py``), one expert-parallel rank's share of the experts.

The experiment is ``experiments/lm.py``'s ``train_lm`` with this model
(``make_train_step`` with ``PowerSGDReducer``, ``train_loop``, packed Zipf
ids, the expert layers' counters on every step's ``step/loss_sync`` span).
The model's selection bias is a buffer the optimizer never touches, zeros
here, so the state carries the counters alone.

``preset="small"`` is the test tier's model; ``"full"`` is the published
widths at the seven-layer (one period), 8-of-128-experts, 16,384-row cut the benchmark
runs (``benchmark/configs/nemotron3-nano-30b-a3b.json``).
"""

from __future__ import annotations

from typing import Dict, Optional

from ..models.nemotron_h import NemotronHConfig, NemotronHLM, nemotron_h_tiny
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
        model = NemotronHLM(NemotronHConfig(
            vocab_size=16384, pattern="MEMEM*E", held_experts=tuple(range(8)),
            remat=True, **model_kwargs(config),
        ))
        seq_len = seq_len or 8192
    else:
        model = nemotron_h_tiny(**model_kwargs(config))
        seq_len = seq_len or 64
    c = model.config
    return train_lm(
        "powersgd_nemotron", model, config, mesh, seq_len, pool_sequences, max_steps_per_epoch,
        {"preset": preset, "model": {
            "pattern": c.pattern, "hidden_size": c.hidden_size, "held_experts": len(c.held_experts),
            "n_routed_experts": c.n_routed_experts, "vocab_size": c.vocab_size,
        }},
    )
