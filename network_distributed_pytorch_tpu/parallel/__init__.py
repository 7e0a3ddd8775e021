"""Parallelism layers: mesh (L1), comm (L2), packing, reducers (L3), trainer (L4)."""

from .mesh import (  # noqa: F401
    DATA_AXIS,
    DistributedConfig,
    initialize_distributed,
    make_mesh,
    data_sharding,
    replicated_sharding,
)
from .comm import (  # noqa: F401
    n_bits,
    all_reduce_sum,
    all_reduce_mean,
    all_gather,
    all_gather_replicated,
    fence,
    tagged_all_reduce_mean,
)
from .packing import TensorPacker  # noqa: F401
from .hierarchical import (  # noqa: F401
    CompiledHierarchical,
    HierarchicalReducer,
    HierarchicalState,
    make_hierarchical_train_fn,
)
from .localsgd import (  # noqa: F401
    CompiledDiLoCo,
    CompiledLocalSGD,
    CompiledStreamingDiLoCo,
    make_diloco_train_fn,
    make_local_sgd_train_fn,
    make_streaming_diloco_train_fn,
)
from .reducers import ExactReducer, PowerSGDReducer  # noqa: F401
from .compression import (  # noqa: F401
    TopKReducer,
    SignSGDReducer,
    QSGDReducer,
)
from .pipeline import (  # noqa: F401
    make_pipeline_fn,
    make_pipeline_train_fn,
    pipeline_apply,
    stacked_stage_params,
)
from .moe import (  # noqa: F401
    MoEOutput,
    stacked_expert_params,
    switch_moe,
)
from .fsdp import (  # noqa: F401
    FSDPState,
    make_fsdp_train_step,
    shard_params,
    unshard_params,
)
