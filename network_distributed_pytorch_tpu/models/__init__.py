"""First-party flax models.

The reference has no first-party models (SURVEY §1: torchvision ResNet-50/152,
HuggingFace DistilBERT); this package provides TPU-native equivalents plus the
small models the test tier needs.
"""

from .mlp import MLP  # noqa: F401
from .cnn import SmallCNN  # noqa: F401
from .resnet import ResNet, resnet18, resnet50, resnet152  # noqa: F401
from .distilbert import (  # noqa: F401
    DistilBertConfig,
    DistilBertEncoder,
    DistilBertForSequenceClassification,
    distilbert_base,
    distilbert_tiny,
    distilbert_wide,
)
from .gpt import (  # noqa: F401
    GPTConfig,
    GPTLM,
    generate,
    gpt_decode_step,
    gpt_embed_apply,
    gpt_head_apply,
    gpt_small,
    gpt_tiny,
    init_gpt_cache,
    make_gpt_pipeline_train_fn,
    make_gpt_stage_fn,
    next_token_loss,
    split_gpt_params,
    stack_gpt_layer_params,
    unstack_gpt_layer_params,
)
from .afmoe import AfmoeConfig, AfmoeLM, afmoe_tiny  # noqa: F401
from .lfm2 import Lfm2Config, Lfm2LM, lfm2_tiny  # noqa: F401
from .mellum import MellumConfig, MellumLM, mellum_tiny  # noqa: F401
from .phi4flash import Phi4FlashConfig, Phi4FlashLM, phi4flash_tiny  # noqa: F401
from .sdar import SdarConfig, SdarLM, sdar_tiny  # noqa: F401
from .qwen3_next import Qwen3NextConfig, Qwen3NextLM, qwen3_next_tiny  # noqa: F401
from .nemotron_h import NemotronHConfig, NemotronHLM, nemotron_h_tiny  # noqa: F401
from .layers import next_token_lm_loss  # noqa: F401
