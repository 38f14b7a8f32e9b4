from pytorch_distributed_rnn_tpu.models.attention import AttentionClassifier
from pytorch_distributed_rnn_tpu.models.attention_lm import AttentionLM
from pytorch_distributed_rnn_tpu.models.char_rnn import (
    CharRNN,
    char_rnn_50m,
    num_params,
)
from pytorch_distributed_rnn_tpu.models.mla_moe_lm import MlaMoeLM
from pytorch_distributed_rnn_tpu.models.moe import MoEClassifier
from pytorch_distributed_rnn_tpu.models.moe_lm import MoELM
from pytorch_distributed_rnn_tpu.models.motion import MotionModel
from pytorch_distributed_rnn_tpu.models.toy import ToyModel

__all__ = [
    "AttentionClassifier",
    "AttentionLM",
    "CharRNN",
    "char_rnn_50m",
    "num_params",
    "MlaMoeLM",
    "MoEClassifier",
    "MoELM",
    "MotionModel",
    "ToyModel",
]
