"""PowerSGD-compressed block-diffusion training of an SDAR mixture-of-experts
language model: a Qwen3-MoE decoder (per-head-normed, rotary grouped-query
attention; softmax-routed gated experts in every layer) that learns to denoise
blocks of tokens (``models/sdar.py``), one expert-parallel rank's share of the
experts.

The experiment is ``experiments/lm.py``'s ``train_lm`` with this model, the
masked-token loss (``models.layers.masked_token_loss``) and batches of its own:
every pool sample is noised once from the seed (``data.noising.block_noised``:
a noise level a block, a ``[MASK]`` draw a token, the loss weights), and a
step runs the noised copy beside the clean one, 2 x ``seq_len`` rows, under the
block-wise attention rule. ``[MASK]`` is the vocabulary's last id; the pool's
ids are drawn from the others. The expert layers' counters, ``masked`` among
them, land on every step's ``step/loss_sync`` span.

``preset="small"`` is the test tier's model; ``"full"`` is the published
widths at the four-layer, 16-of-128-experts, 18,992-row cut the benchmark runs
(``benchmark/configs/sdar-30b-a3b.json``).
"""

from __future__ import annotations

from typing import Dict, Optional

from ..data.noising import block_noised
from ..models.layers import masked_token_loss
from ..models.sdar import SdarConfig, SdarLM, sdar_tiny
from ..utils.config import ExperimentConfig
from .lm import default_config, model_kwargs, train_lm

NOISE_FLOOR = 1e-3  # eps: a block's noise level is U(eps, 1)


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
        model = SdarLM(SdarConfig(
            vocab_size=18992, n_layers=4, held_experts=tuple(range(16)), remat=True, **model_kwargs(config),
        ))
        seq_len = seq_len or 8192
    else:
        model = sdar_tiny(**model_kwargs(config))
        seq_len = seq_len or 64
    c = model.config
    mask_id = c.vocab_size - 1
    return train_lm(
        "powersgd_sdar", model, config, mesh, seq_len, pool_sequences, max_steps_per_epoch,
        {"preset": preset, "model": {
            "n_layers": c.n_layers, "hidden_size": c.hidden_size, "block_length": c.block_length,
            "held_experts": len(c.held_experts), "n_routed_experts": c.n_routed_experts,
            "vocab_size": c.vocab_size, "mask_token_id": mask_id,
        }},
        loss_of=masked_token_loss,
        batches_of=lambda ids, rng: block_noised(ids[:, :-1], c.block_length, NOISE_FLOOR, mask_id, rng),
        drawn_ids=mask_id,
    )
