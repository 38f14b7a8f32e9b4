"""Device time of the traced window by phase and by scope, for the
per-layer readers: the reduced trace's self times (``context["trace"]["ops"]``,
each under ``program/instruction opcode result``) joined with the table the
program keeps of its own compiled programs
(``pytorch_distributed_rnn_tpu/obs/spans.py``: ``program_scopes()`` gives
every instruction's ``op_name``, ``classify()`` the phase and the scope that
path means).  The rule is the program's; nothing here names a scope.

A program that keeps no table (every commit before PR 36) gives ``None``,
as ``program_spans.program_log`` does for the span log; the readers then
return ``None`` and the metric is left out of the line.

The join is by program and instruction name.  The CPU rehearsal's trace
names no program: there an instruction goes to the first registered program
that holds its name (no number of a rehearsal is a measurement).
"""

from __future__ import annotations

_MEMO = "_scope_time_rows"


def program_table():
    """``(program_scopes(), classify)`` of the running program, or ``None``
    where it keeps no table."""
    try:
        from pytorch_distributed_rnn_tpu.obs import spans

        return spans.program_scopes(), spans.classify
    except (ImportError, AttributeError):
        return None


def _instruction(label_rest: str) -> str:
    return label_rest.partition(" ")[0].lstrip("%")


def classified(trace: dict, table: dict, classify, scopes=None) -> list:
    """``[(program, instruction, phase, scope, seconds), ...]``, one row per
    instruction of the reduced ``trace``."""
    rows = []
    for label, row in trace["ops"].items():
        program, named, rest = label.partition("/")
        if not named:
            rest = program
            program = next((candidate for candidate, names in table.items()
                            if _instruction(rest) in names), "?")
        op_name = table.get(program, {}).get(_instruction(rest))
        phase, scope = classify(program, rest.lstrip("%"), op_name, scopes)
        rows.append((program, rest, phase, scope, row["self_s"]))
    return rows


def rows(context):
    """:func:`classified` of the harness's ``context`` against the running
    program's table, made once a run; ``None`` without a table."""
    if _MEMO not in context:
        found = program_table()
        context[_MEMO] = None if found is None else classified(
            context["trace"], *found)
    return context[_MEMO]


def ms_per_step(context, keep):
    """Self time in ms per traced optimizer step of the instructions
    ``keep(program, phase, scope)`` accepts (0.0 where it accepts none:
    the scope took no time); ``None`` without a table."""
    found = rows(context)
    if found is None:
        return None
    seconds = sum(s for program, _, phase, scope, s in found
                  if keep(program, phase, scope))
    return 1e3 * seconds / context["counters"]["traced_steps"]


def scope_ms_per_step(context, scopes=(), prefixes=()):
    """:func:`ms_per_step` of the XLA code under the named scopes (a Pallas
    kernel is classed by its own name, so it is outside every scope)."""
    return ms_per_step(
        context, lambda program, phase, scope:
        scope in scopes or scope.startswith(tuple(prefixes)))


def phase_ms_per_step(context, wanted: str):
    """:func:`ms_per_step` of one phase of the training programs, kernels
    included."""
    return ms_per_step(
        context, lambda program, phase, scope: phase == wanted)
