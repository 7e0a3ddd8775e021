"""PowerSGD-compressed training of a Phi-4-mini-flash-reasoning language
model: SambaY's decoder-hybrid-decoder — Mamba-1 selective-scan and
sliding-window differential-attention layers, then Gated Memory Units and
cross-attentions that read one layer's scan output and one layer's keys and
values (``models/phi4flash.py``); dense, no positions, a head tied to the
embedding.

The experiment is ``experiments/lm.py``'s ``train_lm`` with this model
(``make_train_step`` with ``PowerSGDReducer``, ``train_loop``, packed Zipf
ids). The model has no expert layer, so the counters' tree that rides
``model_state`` is empty and every step's ``step/loss_sync`` span carries none.

``preset="small"`` is the test tier's model; ``"full"`` is the published
widths at the five-layer (the model's own layers 15-19: sliding, the memory
source, the cache source, a GMU, a cross-attention), 25,008-row cut the
benchmark runs (``benchmark/configs/phi-4-mini-flash-reasoning.json``).
"""

from __future__ import annotations

from typing import Dict, Optional

from ..models.phi4flash import Phi4FlashConfig, Phi4FlashLM, phi4flash_tiny
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
        model = Phi4FlashLM(Phi4FlashConfig(
            vocab_size=25008, layer_indices=(15, 16, 17, 18, 19), remat=True, **model_kwargs(config),
        ))
        seq_len = seq_len or 8192
    else:
        model = phi4flash_tiny(**model_kwargs(config))
        seq_len = seq_len or 64
    c = model.config
    return train_lm(
        "powersgd_phi4flash", model, config, mesh, seq_len, pool_sequences, max_steps_per_epoch,
        {"preset": preset, "model": {
            "layer_indices": list(c.layer_indices), "layer_kinds": list(c.layer_kinds),
            "hidden_size": c.hidden_size, "vocab_size": c.vocab_size,
        }},
    )
