"""Device programs launched per epoch, counted on the trace's
``XLA Modules`` line over the traced calls: the scanned epoch, the
remainder step, the validation pass, and whatever small programs the host
loop launches beside them."""

NAME = "programs_per_epoch"
LAYER = "trainer_loop"
UNIT = "programs"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"


def read(context):
    return context["trace"]["launches"] / context["counters"]["traced_epochs"]
