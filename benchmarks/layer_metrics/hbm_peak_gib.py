"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip after the
window, in GiB."""

NAME = "hbm_peak_gib"
LAYER = "device"
UNIT = "GiB"
MOVES = "train_seq_per_s"
SOURCE = "program_counter"


def read(context):
    peak = context["counters"]["memory_peak_bytes"]
    return peak / 2**30 if peak else None
