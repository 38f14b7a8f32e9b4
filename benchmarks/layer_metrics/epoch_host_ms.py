"""Host time of one epoch: the wall of each traced ``train(epochs=K)`` call
minus the time the first chip was busy inside it, over K, as a mean over
the traced calls.  Dispatch, fetching losses, logging, the per-epoch
dropout keys, and the host side of the once-a-call test evaluation."""

NAME = "epoch_host_ms"
LAYER = "trainer_loop"
UNIT = "ms"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"


def read(context):
    trace = context["trace"]
    host = [span["end_s"] - span["start_s"] - busy
            for span, busy in zip(trace["spans"], trace["span_busy_s"])]
    return 1e3 * sum(host) / context["counters"]["traced_epochs"]
