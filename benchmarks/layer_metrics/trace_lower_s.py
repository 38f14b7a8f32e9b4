"""Set-up time in JAX's tracing and lowering, which no compile cache saves:
what the program's ``compile.trace`` and ``compile.lower`` spans
(``jax.monitoring``'s ``jaxpr_trace_duration`` and
``jaxpr_to_mlir_module_duration``) that ended before the window cover: the
warm-up ``train`` calls, which ``compile_s`` times from outside, and
``model.init`` and the optimizer's state before them, which it does not.
A jit traced inside another's trace counts once."""

from benchmarks import program_spans

NAME = "trace_lower_s"
LAYER = "run_setup"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "host_clock"


def read(context):
    return program_spans.before_window_s(
        context, {"compile.trace", "compile.lower"})
