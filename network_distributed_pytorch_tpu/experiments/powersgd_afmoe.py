"""PowerSGD-compressed training of an afmoe (Trinity) language model:
sliding-window layers with rotary positions beside full layers without,
gated attention, gated experts (``models/afmoe.py``), one expert-parallel
rank's share of the experts.

The experiment is ``experiments/lm.py``'s ``train_lm`` with this model
(``make_train_step`` with ``PowerSGDReducer``, ``train_loop``,
packed Zipf ids, the expert layers' counters on every step's
``step/loss_sync`` span). Its weights come from a seed, so each expert
layer's ``expert_bias`` is set where a run in training keeps it: every
expert chosen equally often on the pool's first four sequences
(``balanced_expert_bias``).

``preset="small"`` is the test tier's model; ``"full"`` is the published
widths at the five-layer (a dense layer and one period), 8-of-128-experts,
25,024-row cut the benchmark runs
(``benchmark/configs/trinity-mini-26b-a3b.json``).
"""

from __future__ import annotations

from typing import Dict, Optional

from ..models.afmoe import AfmoeConfig, AfmoeLM, afmoe_tiny
from ..models.layers import BUFFERS, FULL, SLIDING, balanced_expert_bias
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
        model = AfmoeLM(AfmoeConfig(
            vocab_size=25024, layer_types=(SLIDING, SLIDING, FULL, SLIDING, SLIDING),
            num_dense_layers=1, held_experts=tuple(range(8)), remat=True, **model_kwargs(config),
        ))
        seq_len = seq_len or 8192
    else:
        model = afmoe_tiny(**model_kwargs(config))
        seq_len = seq_len or 64
    c = model.config
    return train_lm(
        "powersgd_afmoe", model, config, mesh, seq_len, pool_sequences, max_steps_per_epoch,
        {"preset": preset, "model": {
            "layer_types": list(c.layer_types), "num_dense_layers": c.num_dense_layers,
            "sliding_window": c.sliding_window, "hidden_size": c.hidden_size,
            "held_experts": len(c.held_experts), "n_routed_experts": c.n_routed_experts,
            "vocab_size": c.vocab_size,
        }},
        collections_of=lambda params, ids: {BUFFERS: balanced_expert_bias(model, params, ids)},
    )
