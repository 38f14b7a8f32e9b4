"""Share of the collectives' time during which no compute instruction ran
on that chip: what overlap could still hide."""

NAME = "collective_exposed_share"
LAYER = "strategy"
UNIT = "%"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"
WORKLOADS = ["har_dp_4chip"]


def read(context):
    trace = context["trace"]
    if trace["device_count"] < 2 or trace["collective_s"] <= 0:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["collective_s"]
