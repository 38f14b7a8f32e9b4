"""Device time per optimizer step of the training programs' first forward
pass (``spans.classify``'s phase ``forward`` in ``jit_train_epoch``,
``jit_train_step``, ``jit_train_run``): every instruction, kernels included,
that is neither transposed, nor ``jax.checkpoint``'s second forward, nor
under the update's scopes.  The evaluation programs are a phase of their
own and in none of the four."""

from benchmarks import scope_time

NAME = "fwd_ms_per_step"
LAYER = "device"
UNIT = "ms"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"


def read(context):
    return scope_time.phase_ms_per_step(context, "forward")
