"""The reference's four guides (plus its single-node baseline and the
bandwidth study they were all built for), as library entry points."""

from . import (  # noqa: F401
    bandwidth_study,
    bare_init,
    diloco_cifar10,
    exact_cifar10,
    gpt_generate,
    gpt_lm,
    gpt_moe,
    gpt_pp,
    gpt_sp,
    gpt_tp,
    imdb_baseline,
    powersgd_afmoe,
    powersgd_cifar10,
    powersgd_imdb,
    powersgd_lfm2,
    powersgd_mellum,
    powersgd_nemotron,
    powersgd_phi4flash,
    powersgd_qwen3_next,
    powersgd_sdar,
)
