"""Time per optimizer step during which a collective was in flight on a
chip: the union of the collective instructions' intervals, mean over
chips."""

NAME = "collective_ms_per_step"
LAYER = "strategy"
UNIT = "ms"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"
WORKLOADS = ["har_dp_4chip"]


def read(context):
    trace = context["trace"]
    if trace["device_count"] < 2:
        return None
    return 1e3 * trace["collective_s"] / context["counters"]["traced_steps"]
