"""tensorflowdistributedlearning_tpu — a TPU-native (JAX/XLA/Flax) re-design of the
capabilities of gf712/TensorflowDistributedLearning.

The reference is a TF1 tf.contrib-era multi-GPU (MirroredStrategy) K-fold training
harness for binary semantic segmentation (reference: model.py:27-136). This package
provides the same capabilities designed TPU-first:

- SPMD data parallelism over a `jax.sharding.Mesh` (reference: model.py:115-121 used
  per-GPU towers + NCCL; here gradients are `psum`-reduced over the ICI mesh inside a
  single `shard_map`-ped train step).
- Flax ResNet-v2-beta + DeepLabV3+-style segmentation head and a fixed Xception-41
  backbone (reference: core/resnet.py, core/xception.py).
- Lovász hinge loss and Kaggle-style thresholded mIOU metrics as fixed-shape,
  jittable ops (reference: core/losses.py, core/metric.py).
- On-device augmentation with per-image PRNG keys (reference:
  preprocessing/preprocessing.py did host-side tf.data with a graph-time numpy RNG bug).
- K-fold orchestration, Orbax checkpointing with best-k export, and TTA prediction
  (reference: model.py:138-255).
"""

import importlib.util as _ilu

from tensorflowdistributedlearning_tpu.config import ModelConfig, TrainConfig

__version__ = "0.1.0"


def __getattr__(name):
    # lazy: the trainer pulls in the full model/data stack
    if name == "Model" and _ilu.find_spec(
        "tensorflowdistributedlearning_tpu.train.trainer"
    ):
        from tensorflowdistributedlearning_tpu.train.trainer import Model

        return Model
    raise AttributeError(name)

__all__ = [
    "ModelConfig",
    "TrainConfig",
    "__version__",
]
