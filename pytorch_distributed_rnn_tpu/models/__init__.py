"""The models.  A model family of the CLI is its model class.

The six classes ``training/families.py:FAMILIES`` lists (``MotionModel``,
``CharRNN``, ``AttentionClassifier``, ``MoEClassifier``, ``MlaMoeLM``,
``HybridSsmMoeLM``; the two decoder LMs share ``decoder_common.py``, and
``HybridSsmMoeLM`` builds more than one published model from its pattern of
residual parts)
carry, by convention and with no base class, all that the program knows
of a family:

- ``family``: its name under ``--model``; ``family_help``: its part of
  that flag's help; ``data_kind``: ``"har"`` (feature windows and labels,
  ``data.MotionDataset``) or ``"tokens"`` ((T + 1)-token windows,
  ``data/text.py``);
- ``add_flags(parser)``, where it has flags that only it reads (flags
  that several families read are ``main.py``'s);
- ``from_args(args, training_set)``: the model, or ``SystemExit`` naming
  each flag it cannot honour;
- ``init(key)``, ``apply(params, x)``, and ``dropout`` where it has one;
- ``loss_and_metrics(params, batch, dropout_key=None, weights=None) ->
  (loss, {"correct": ..., ...})``: the one loss every trainer and every
  strategy differentiates and evaluates (``Trainer._loss_and_metrics``);
  ``weights`` is the whole-run program's per-example padding mask;
- ``resolved_impl()``: what its ``impl`` switch resolves to on this
  backend, ``None`` if it has none.
"""

from pytorch_distributed_rnn_tpu.models.attention import AttentionClassifier
from pytorch_distributed_rnn_tpu.models.attention_lm import AttentionLM
from pytorch_distributed_rnn_tpu.models.char_rnn import (
    CharRNN,
    char_rnn_50m,
    num_params,
)
from pytorch_distributed_rnn_tpu.models.hybrid_ssm_moe_lm import (
    HybridSsmMoeLM,
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
    "HybridSsmMoeLM",
    "MlaMoeLM",
    "MoEClassifier",
    "MoELM",
    "MotionModel",
    "ToyModel",
]
