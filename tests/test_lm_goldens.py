"""The five language models' test-tier weights and first loss, bit for bit
against ``tests/goldens/lm_tiny.json``: for each ``*_tiny()`` at seed 0,
fp32, ``attn_impl="einsum"``, the sorted parameter paths with their shapes,
a sha256 over the initialised leaves' bytes in that order, and the first
``next_token_lm_loss`` on a fixed (2, 64) batch.

The file was recorded on d4f8773, before ``models/layers.py`` existed, by
``PYTHONPATH=. python tests/test_lm_goldens.py`` (which writes it anew). It stands for
"the same weights from the same seed": flax draws a scope's parameters in
the order they are created and keys a submodule by its name, so a moved or
merged module that declares another leaf first, or under another name, fails
here. The benchmark's ``correct`` cannot see that (its reference takes the
program's own parameters), and the expert load and with it ``step_ms`` move
with the weights.
"""

import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.traverse_util import flatten_dict

from network_distributed_pytorch_tpu.models import (
    afmoe_tiny, lfm2_tiny, mellum_tiny, nemotron_h_tiny, qwen3_next_tiny,
)
from network_distributed_pytorch_tpu.models.layers import next_token_lm_loss, zero_counters
from network_distributed_pytorch_tpu.parallel.trainer import STEP_COUNTERS

GOLDENS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "goldens", "lm_tiny.json")
TINY = {
    "nemotron_h": nemotron_h_tiny, "afmoe": afmoe_tiny, "qwen3_next": qwen3_next_tiny,
    "lfm2": lfm2_tiny, "mellum": mellum_tiny,
}
SEED, BATCH, SEQ_LEN = 0, 2, 64


def measured(name: str) -> dict:
    model = TINY[name](attn_impl="einsum")
    cfg = model.config
    variables = model.init(jax.random.PRNGKey(SEED), jnp.zeros((1, SEQ_LEN), jnp.int32))
    leaves = sorted(("/".join(path), np.asarray(leaf)) for path, leaf in flatten_dict(variables["params"]).items())
    digest = hashlib.sha256()
    for _, leaf in leaves:
        digest.update(leaf.tobytes())
    ids = np.random.default_rng(SEED).integers(0, cfg.vocab_size, (BATCH, SEQ_LEN + 1)).astype(np.int32)
    others = {k: v for k, v in variables.items() if k != "params"}  # afmoe's zero buffers
    loss, _ = jax.jit(next_token_lm_loss(model))(
        variables["params"], {STEP_COUNTERS: zero_counters(cfg), **others},
        {"input_ids": ids[:, :-1], "labels": ids[:, 1:]},
    )
    loss = np.float32(loss)
    return {
        "leaves": [[path, list(leaf.shape), str(leaf.dtype)] for path, leaf in leaves],
        "sha256": digest.hexdigest(),
        "loss": float(loss),
        "loss_bits": loss.tobytes().hex(),
    }


@pytest.mark.parametrize("name", sorted(TINY))
def test_tiny_model_has_the_recorded_leaves_weights_and_first_loss(name):
    with open(GOLDENS) as f:
        want = json.load(f)[name]
    got = measured(name)
    assert got["leaves"] == want["leaves"]  # names, order, shapes: what the reducer walks
    assert got["sha256"] == want["sha256"]  # every leaf's bytes: the same draws in the same order
    assert got["loss_bits"] == want["loss_bits"], (got["loss"], want["loss"])


if __name__ == "__main__":
    from network_distributed_pytorch_tpu.hostenv import force_cpu_devices

    force_cpu_devices(8, replace=False)
    os.makedirs(os.path.dirname(GOLDENS), exist_ok=True)
    text = json.dumps({name: measured(name) for name in sorted(TINY)}, indent=1, sort_keys=True)
    text = re.sub(r'\[\s+("[^"]+"),\s+\[([^\]]*)\],\s+("\w+")\s+\]', lambda m: f"[{m[1]}, [{' '.join(m[2].split())}], {m[3]}]", text)
    with open(GOLDENS, "w") as f:  # a leaf a line
        f.write(text + "\n")
    print("wrote", GOLDENS)
