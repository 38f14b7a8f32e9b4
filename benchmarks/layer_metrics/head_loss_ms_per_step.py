"""Device time per optimizer step of the output head and the loss: scopes
``head`` and ``loss`` (the final norm, the logits, the cross entropy and
their backward; a prediction module's head too).  Evaluation passes are
inside the figure."""

from benchmarks import scope_time

NAME = "head_loss_ms_per_step"
LAYER = "model_ops"
UNIT = "ms"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"


def read(context):
    return scope_time.scope_ms_per_step(context, scopes={"head", "loss"})
