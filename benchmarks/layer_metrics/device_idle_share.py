"""Share of the traced window in which no instruction ran on a chip, mean
over chips: 100 x (1 - busy / window)."""

NAME = "device_idle_share"
LAYER = "device"
UNIT = "%"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"


def read(context):
    trace = context["trace"]
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
