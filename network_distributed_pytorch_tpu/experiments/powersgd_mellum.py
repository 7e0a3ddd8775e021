"""PowerSGD-compressed training of a Mellum 2 mixture-of-experts language
model: sliding-window and YaRN-scaled full attention layers three to one,
both turned by a rotary embedding, softmax-routed gated experts in every
layer (``models/mellum.py``), one expert-parallel rank's share of the experts.

The experiment is ``experiments/lm.py``'s ``train_lm`` with this model
(``make_train_step`` with ``PowerSGDReducer``, ``train_loop``,
packed Zipf ids, the expert layers' counters on every step's
``step/loss_sync`` span). The model publishes no selection bias and no
buffer, so the state carries the counters alone and nothing is balanced:
weights from a seed route a token by its own id because the embedding starts
at unit scale (``models/mellum.py::EMBED_STD``; at 0.02 every token of a
sequence picked the same experts and the held load was a lottery, PERF.md
section 6, PR 44).

``preset="small"`` is the test tier's model; ``"full"`` is the published
widths at the four-layer (one period), 16-of-64-experts, 12,288-row cut the
benchmark runs (``benchmark/configs/mellum2-12b-a2.5b.json``).
"""

from __future__ import annotations

from typing import Dict, Optional

from ..models.layers import FULL, SLIDING
from ..models.mellum import MellumConfig, MellumLM, mellum_tiny
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
        model = MellumLM(MellumConfig(
            vocab_size=12288, layer_types=(SLIDING, SLIDING, SLIDING, FULL),
            held_experts=tuple(range(16)), remat=True, **model_kwargs(config),
        ))
        seq_len = seq_len or 8192
    else:
        model = mellum_tiny(**model_kwargs(config))
        seq_len = seq_len or 64
    c = model.config
    return train_lm(
        "powersgd_mellum", model, config, mesh, seq_len, pool_sequences, max_steps_per_epoch,
        {"preset": preset, "model": {
            "layer_types": list(c.layer_types), "hidden_size": c.hidden_size,
            "held_experts": len(c.held_experts), "n_routed_experts": c.n_routed_experts,
            "vocab_size": c.vocab_size,
        }},
    )
