"""PowerSGD-compressed training of an LFM2 mixture-of-experts language model:
gated short-convolution mixers, three to one grouped-query attention layer,
sigmoid-routed gated experts, a head tied to the embedding
(``models/lfm2.py``), one expert-parallel rank's share of the experts.

The experiment is ``experiments/lm.py``'s ``train_lm`` with this model
(``make_train_step`` with ``PowerSGDReducer``, ``train_loop``,
packed Zipf ids, the expert layers' counters on every step's
``step/loss_sync`` span). Each expert layer's ``expert_bias`` stays at its
published initial value, zeros: with this model's pre-norm blocks weights
from a seed route 0.47-0.55 T assignments a layer to the eight held experts
on every seed measured (PERF.md section 6, PR 41), so nothing is balanced
and the state carries no buffers; a run that loads weights brings its own.

``preset="small"`` is the test tier's model; ``"full"`` is the published
widths at the five-layer (a dense layer and one period), 8-of-64-experts,
16,384-row cut the benchmark runs (``benchmark/configs/lfm2-24b-a2b.json``).
"""

from __future__ import annotations

from typing import Dict, Optional

from ..models.layers import FULL
from ..models.lfm2 import CONV, Lfm2Config, Lfm2LM, lfm2_tiny
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
        model = Lfm2LM(Lfm2Config(
            vocab_size=16384, layer_types=(CONV, FULL, CONV, CONV, CONV),
            num_dense_layers=1, held_experts=tuple(range(8)), remat=True, **model_kwargs(config),
        ))
        seq_len = seq_len or 8192
    else:
        model = lfm2_tiny(**model_kwargs(config))
        seq_len = seq_len or 64
    c = model.config
    return train_lm(
        "powersgd_lfm2", model, config, mesh, seq_len, pool_sequences, max_steps_per_epoch,
        {"preset": preset, "model": {
            "layer_types": list(c.layer_types), "num_dense_layers": c.num_dense_layers,
            "hidden_size": c.hidden_size, "held_experts": len(c.held_experts),
            "n_routed_experts": c.n_routed_experts, "vocab_size": c.vocab_size,
        }},
    )
