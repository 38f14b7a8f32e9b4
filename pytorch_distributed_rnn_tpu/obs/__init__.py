"""Structured run telemetry (the observability spine).

- :mod:`.recorder`: rank-tagged JSONL event stream, buffered off the
  training hot path (:class:`MetricsRecorder` / :data:`NULL_RECORDER`).
- :mod:`.spans`: the span primitives - ``recorder.emit_span`` (a
  deferred JSONL duration event) and ``spans.span``, the program span
  the trainer puts at its layer boundaries: profiler annotation,
  in-process log with parents (``spans.log()``), and the JSONL event,
  from one call site.
- :mod:`.profile`: step-bounded ``jax.profiler`` capture
  (``--profile-steps A:B``), which leaves the program as it is.
- :mod:`.summary`: sidecar loading, summaries, diffs, stragglers,
  per-rank liveness (``rank_health``).
- :mod:`.timeline`: cross-rank clock alignment, Chrome-trace/Perfetto
  export + validator, phase attribution.
- :mod:`.tracectx`: the distributed request-trace context
  (:class:`TraceContext`) minted at the serving edge and carried on the
  serve wire protocol; :mod:`.trace` assembles the recorded spans from
  router + replica sidecars into trees with critical-path attribution
  (``pdrnn-metrics trace``).
- :mod:`.flops`: analytic per-step FLOP/byte counts off abstract
  jaxprs (no data, no compile) - the efficiency ledger's MFU numerator.
- :mod:`.ledger`: the efficiency ledger - exhaustive wall-clock phase
  accounting (fractions sum to 1), goodput, MFU/HFU vs the
  ``utils/hw.py`` peak table, fault tax, and the
  ``ledger_history.jsonl`` + ``pdrnn-metrics regress`` cross-run gate.
- :mod:`.live`: the live plane - rolling windows, digest exporter (no
  thread of its own: rides the recorder's writer thread), and the
  per-process ``LivePlane`` wiring (``--live`` / ``PDRNN_LIVE``).
- :mod:`.aggregator`: rank-0/master digest aggregation + the stdlib
  HTTP server behind ``GET /metrics`` (Prometheus), ``/health``,
  ``/events`` and ``/fleet``.
- :mod:`.watchdog`: in-run anomaly detection (stall / NaN streak / loss
  spike / serving SLO) with all-thread stack dumps, plus the SIGUSR2
  on-demand dump hook every long-lived entrypoint installs.
- :mod:`.cli`: the ``pdrnn-metrics`` CLI over all of the above
  (including ``watch``, the live fleet table).

This package imports neither jax nor the training stack at module
import time, so CLI startup and jax-free tooling stay cheap.
"""

from pytorch_distributed_rnn_tpu.obs.aggregator import (
    Aggregator,
    AggregatorServer,
    render_prometheus,
)
from pytorch_distributed_rnn_tpu.obs.live import (
    LIVE_ENV,
    LatencyHistogram,
    LiveExporter,
    LivePlane,
    RollingWindow,
)
from pytorch_distributed_rnn_tpu.obs.flops import (
    closed_jaxpr_flop_stats,
    entry_flop_report,
    trace_flop_stats,
)
from pytorch_distributed_rnn_tpu.obs.ledger import (
    FRACTION_TOL,
    LEDGER_PHASES,
    append_history,
    check_history,
    history_record,
    ledger_events,
    ledger_file,
    ledger_run,
    load_history,
)
from pytorch_distributed_rnn_tpu.obs.profile import StepTraceCapture
from pytorch_distributed_rnn_tpu.obs.recorder import (
    METRICS_ENV,
    METRICS_HEARTBEAT_ENV,
    METRICS_SAMPLE_ENV,
    NULL_RECORDER,
    SCHEMA_VERSION,
    MetricsRecorder,
    NullRecorder,
    rank_suffixed,
)
from pytorch_distributed_rnn_tpu.obs.summary import (
    MalformedMetricsError,
    detect_stragglers,
    diff_summaries,
    load_events,
    rank_files,
    rank_health,
    summarize_events,
    summarize_file,
    summarize_run,
)
from pytorch_distributed_rnn_tpu.obs.watchdog import (
    AnomalyWatchdog,
    dump_stacks,
    install_stack_dump_handler,
)
from pytorch_distributed_rnn_tpu.obs.trace import (
    MalformedTraceError,
    TraceTree,
    assemble_traces,
    build_trace_tree,
    collect_trace_spans,
    format_trace_tree,
    validate_trace_tree,
)
from pytorch_distributed_rnn_tpu.obs.tracectx import (
    TraceContext,
    should_sample,
)
from pytorch_distributed_rnn_tpu.obs.timeline import (
    attribute_rank,
    attribute_run,
    attribute_stragglers,
    build_chrome_trace,
    estimate_clock_offsets,
    load_run,
    validate_chrome_trace,
    write_chrome_trace,
)

__all__ = [
    "Aggregator",
    "AggregatorServer",
    "AnomalyWatchdog",
    "LIVE_ENV",
    "LiveExporter",
    "LivePlane",
    "METRICS_ENV",
    "METRICS_HEARTBEAT_ENV",
    "METRICS_SAMPLE_ENV",
    "NULL_RECORDER",
    "RollingWindow",
    "SCHEMA_VERSION",
    "LatencyHistogram",
    "MalformedMetricsError",
    "MalformedTraceError",
    "MetricsRecorder",
    "NullRecorder",
    "StepTraceCapture",
    "TraceContext",
    "TraceTree",
    "dump_stacks",
    "install_stack_dump_handler",
    "render_prometheus",
    "FRACTION_TOL",
    "LEDGER_PHASES",
    "append_history",
    "assemble_traces",
    "attribute_rank",
    "attribute_run",
    "attribute_stragglers",
    "build_chrome_trace",
    "build_trace_tree",
    "check_history",
    "closed_jaxpr_flop_stats",
    "collect_trace_spans",
    "detect_stragglers",
    "diff_summaries",
    "entry_flop_report",
    "estimate_clock_offsets",
    "format_trace_tree",
    "history_record",
    "ledger_events",
    "ledger_file",
    "ledger_run",
    "load_events",
    "load_history",
    "load_run",
    "trace_flop_stats",
    "rank_files",
    "rank_health",
    "rank_suffixed",
    "should_sample",
    "summarize_events",
    "summarize_file",
    "summarize_run",
    "validate_chrome_trace",
    "write_chrome_trace",
]
