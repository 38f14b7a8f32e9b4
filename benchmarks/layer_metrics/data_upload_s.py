"""Set-up time in placing the data sets on the device: the program's
``input.upload`` spans (training set at the first epoch, validation and
test set at their first evaluation) that ended before the window.  The
copies are not fenced, so this is the host's share of them; what is still
in flight is waited for by the first program that reads the arrays."""

from benchmarks import program_spans

NAME = "data_upload_s"
LAYER = "input_path"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "host_clock"


def read(context):
    return program_spans.before_window_s(context, {"input.upload"})
