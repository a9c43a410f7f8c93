from tensorflowdistributedlearning_tpu.train.state import TrainState, create_train_state
from tensorflowdistributedlearning_tpu.train.step import (
    ClassificationTask,
    SegmentationTask,
    SequenceTask,
    make_eval_step,
    make_optimizer,
    make_predict_step,
    make_train_step,
)

__all__ = [
    "TrainState",
    "create_train_state",
    "ClassificationTask",
    "SegmentationTask",
    "SequenceTask",
    "make_eval_step",
    "make_optimizer",
    "make_predict_step",
    "make_train_step",
]
