"""SPMD device-mesh layer: the TPU-native replacement for the reference's
MirroredStrategy/NCCL distribution config (reference: model.py:114-121, utils.py:6-8)."""

from tensorflowdistributedlearning_tpu.parallel.mesh import (
    BATCH_AXIS,
    MODEL_AXIS,
    SEQUENCE_AXIS,
    available_devices,
    batch_sharding,
    local_batch_size,
    make_mesh,
    replicate,
    replicated_sharding,
    shard_batch,
)
from tensorflowdistributedlearning_tpu.parallel.planner import (
    Layout,
    ParallelPlan,
    PlanError,
    Topology,
    plan,
    plan_for_config,
    render_plan_table,
    validate_config,
)
from tensorflowdistributedlearning_tpu.parallel.collectives import (
    pmean_tree,
    psum_tree,
    vma_of,
)
from tensorflowdistributedlearning_tpu.parallel.spatial import (
    halo_exchange,
    reduce_scatter,
    ring_all_gather,
    spatial_conv2d,
)
from tensorflowdistributedlearning_tpu.parallel.expert import (
    moe_apply,
    top1_dispatch,
)
from tensorflowdistributedlearning_tpu.parallel.ring_attention import (
    attention_reference,
    make_ring_attention,
    ring_attention,
)
from tensorflowdistributedlearning_tpu.parallel.pipeline import (
    make_pipeline_fn,
    pipeline_apply,
    stack_stage_params,
)
from tensorflowdistributedlearning_tpu.parallel.tensor import (
    make_train_step_gspmd,
    shard_state_tensor_parallel,
    shard_state_weight_update,
    tensor_parallel_specs,
)
from tensorflowdistributedlearning_tpu.parallel.zero import (
    apply_gradients_sharded,
    weight_update_spec,
    weight_update_specs,
)
from tensorflowdistributedlearning_tpu.parallel.multihost import (
    global_shard_batch,
    initialize as initialize_multihost,
    process_info,
)

__all__ = [
    "halo_exchange",
    "reduce_scatter",
    "ring_all_gather",
    "spatial_conv2d",
    "attention_reference",
    "make_ring_attention",
    "ring_attention",
    "global_shard_batch",
    "make_pipeline_fn",
    "moe_apply",
    "top1_dispatch",
    "make_train_step_gspmd",
    "pipeline_apply",
    "stack_stage_params",
    "shard_state_tensor_parallel",
    "shard_state_weight_update",
    "tensor_parallel_specs",
    "apply_gradients_sharded",
    "weight_update_spec",
    "weight_update_specs",
    "initialize_multihost",
    "process_info",
    "vma_of",
    "BATCH_AXIS",
    "MODEL_AXIS",
    "SEQUENCE_AXIS",
    "available_devices",
    "batch_sharding",
    "local_batch_size",
    "make_mesh",
    "replicate",
    "replicated_sharding",
    "shard_batch",
    "pmean_tree",
    "psum_tree",
]
