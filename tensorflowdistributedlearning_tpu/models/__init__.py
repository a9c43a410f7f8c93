from tensorflowdistributedlearning_tpu.models.layers import (
    SplitSeparableConv2D,
    fixed_padding,
    subsample,
    upsample,
)
from tensorflowdistributedlearning_tpu.models.resnet import (
    ResNetBackbone,
    ResNetClassifier,
    ResNetSegmentation,
    build_model,
    sample_input,
)
from tensorflowdistributedlearning_tpu.models.vit import (
    TransformerBlock,
    ViTClassifier,
    pipeline_stage_fn,
    stack_vit_block_params,
)
from tensorflowdistributedlearning_tpu.models.xception import (
    Xception41,
    XceptionBackbone,
    XceptionSegmentation,
)

__all__ = [
    "SplitSeparableConv2D",
    "fixed_padding",
    "subsample",
    "upsample",
    "ResNetBackbone",
    "ResNetClassifier",
    "ResNetSegmentation",
    "build_model",
    "sample_input",
    "TransformerBlock",
    "ViTClassifier",
    "pipeline_stage_fn",
    "stack_vit_block_params",
    "Xception41",
    "XceptionBackbone",
    "XceptionSegmentation",
]
