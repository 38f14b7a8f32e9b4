"""Perf-line parsing and measurement aggregation.

The regex is the notebooks' own (``Experiments.ipynb`` cell 2), extended to
capture every rank's line rather than only rank 0's so per-node and
aggregate memory plots (cells 5-7) are both derivable.
"""

from __future__ import annotations

import json
import re
from pathlib import Path

import pandas as pd

# The machine-readable telemetry contract (formatter.py:27).  Rank is part
# of the line; the notebooks anchored on rank 0 ('0: Memory Usage: ...').
# The value pattern is wider than the notebooks' \d+\.\d+ on purpose:
# performance_message formats RAW floats, so a sub-millisecond duration
# renders as '5e-05' and an integer-valued memory as '700' - the original
# regex silently dropped both (the formatter<->parser round-trip test in
# tests/test_evaluation.py pins the contract).
_FLOAT = r"\d+(?:\.\d+)?(?:[eE][+-]?\d+)?"
PERF_LINE_RE = re.compile(
    rf"(\d+): Memory Usage: ({_FLOAT}), Training Duration: ({_FLOAT})"
)

TRAIN_SIZE_RE = re.compile(r"Training set of size (\d+)")

# The benchmark workload's training-set size (the reference's 7352
# windows after its x96 truncation): used to derive
# seq/s when a run's log does not state its dataset size.
DEFAULT_NUM_SEQUENCES = 6912


def parse_perf_lines(text: str):
    """All ``(rank, memory_mb, duration_s)`` tuples in a captured stream."""
    return [
        (int(rank), float(mem), float(dur))
        for rank, mem, dur in PERF_LINE_RE.findall(text or "")
    ]


def _structured_measurements(run):
    """``[(rank, memory_mb, duration_s, extras), ...]`` from the run's
    metrics JSONL sidecar (``obs/``), or ``None`` when the run carries no
    usable sidecar - the caller then falls back to the perf-line regex.

    The sidecar is the structured-first path: unlike the regex it
    survives crashed runs' partial telemetry, and it carries the numbers
    the perf line never had (step times, data-wait fraction, collective
    traffic, HBM peaks), surfaced as extra dataframe columns.
    """
    path = run.get("metrics_path") or (
        (run.get("parameters") or {}).get("metrics")
    )
    if not path:
        return None
    from pytorch_distributed_rnn_tpu.obs.summary import (
        MalformedMetricsError,
        summarize_events,
    )
    from pytorch_distributed_rnn_tpu.obs.timeline import (
        attribute_rank,
        load_run,
    )

    # one parse per rank file: summary and phase attribution both fold
    # off the same in-memory event lists (per-step sidecars get large)
    try:
        by_rank = load_run(path)
    except MalformedMetricsError:
        return None
    summaries = []
    attributions = {}
    for rank in sorted(by_rank):
        summaries.append(summarize_events(by_rank[rank], path=path))
        # per-rank phase attribution (obs/timeline.py): where the
        # sampled step time went - surfaced as phase_* fraction columns
        # so sweep dataframes can separate input-bound from
        # exchange-bound rows
        attr = attribute_rank(by_rank[rank])
        if attr is not None:
            attributions[rank] = attr["fractions"]
    measurements = []
    for s in summaries:
        if s.get("duration_s") is None or s.get("memory_mb") is None:
            continue  # run died before its run_summary event
        phases = {
            f"phase_{name}_frac": frac
            for name, frac in attributions.get(s["rank"], {}).items()
        }
        measurements.append((
            s["rank"], s["memory_mb"], s["duration_s"],
            {
                "step_s_mean": s.get("step_s_mean"),
                "data_wait_frac": s.get("data_wait_frac"),
                "collective_bytes_per_step": s.get(
                    "collective_bytes_per_step"
                ),
                "device_peak_mb": s.get("device_peak_mb"),
                "telemetry": True,
                **phases,
            },
        ))
    return measurements or None


def create_measurement_df(results) -> pd.DataFrame:
    """Measurement dataframe from launcher results (the ``create_measurement_df``
    analogue, one row per (run, rank)).

    ``results`` is the list the launcher appends to ``results_*.json`` — or a
    path to such a file.  Structured-first: a run whose entry names a
    metrics sidecar (``metrics_path`` / the ``--metrics`` parameter) is
    measured from the sidecar, no regex involved; legacy stderr-only
    entries fall back to the perf-line regex.  Runs with neither (crashes
    predating telemetry) are dropped, exactly as the notebooks' regex
    silently skipped them.
    """
    if isinstance(results, (str, Path)):
        with open(results) as f:
            results = json.load(f)

    rows = []
    for run_id, run in enumerate(results):
        text = (run.get("stderr") or "") + "\n" + (run.get("stdout") or "")
        structured = _structured_measurements(run)
        if structured is not None:
            perf = [(r, m, d) for r, m, d, _ in structured]
            extras = [e for _, _, _, e in structured]
        else:
            perf = parse_perf_lines(text)
            extras = [{} for _ in perf]
        size_match = TRAIN_SIZE_RE.search(text)
        num_sequences = (
            int(size_match.group(1)) if size_match else DEFAULT_NUM_SEQUENCES
        )
        params = run.get("parameters", {})
        epochs = int(params.get("epochs", 1))
        for (rank, memory, duration), extra in zip(perf, extras):
            rows.append(
                {
                    "run": run_id,  # position in the results file: repeated
                    # sweep runs of the same config stay distinguishable
                    "trainer": run.get("trainer"),
                    "devices": run.get("devices", 1),
                    "slots": run.get("slots", 1),
                    "world": run.get("devices", 1) * run.get("slots", 1),
                    "batch_size": params.get("batch-size"),
                    # model family ("rnn" = the reference's motion model);
                    # seq/s is NOT comparable across families
                    "model": params.get("model", "rnn"),
                    "rule_type": run.get("rule_type"),
                    "rule_value": run.get("rule_value"),
                    "rank": rank,
                    "memory_mb": memory,
                    "duration_s": duration,
                    "num_sequences": num_sequences,
                    "seq_per_sec": num_sequences * epochs / duration
                    if duration > 0
                    else float("nan"),
                    **extra,
                }
            )
    return pd.DataFrame(rows)


def aggregate_measurements(df: pd.DataFrame) -> pd.DataFrame:
    """Mean over repeats of rank-0 rows, grouped by run configuration —
    the number the reference reported (rank 0's line)."""
    if df.empty:
        return df
    rank0 = df[df["rank"] == 0]
    grouped = (
        rank0.groupby(
            ["trainer", "devices", "slots", "batch_size"], dropna=False
        )
        .agg(
            duration_s=("duration_s", "mean"),
            memory_mb=("memory_mb", "mean"),
            seq_per_sec=("seq_per_sec", "mean"),
            repeats=("duration_s", "size"),
        )
        .reset_index()
    )
    return grouped


def scaling_table(df: pd.DataFrame, baseline_trainer: str = "local") -> pd.DataFrame:
    """Scaling study: speedup and efficiency vs the 1-device baseline.

    The reference's derived figures ("DDP scaling efficiency 1→8
    nodes"): for each (trainer, batch_size), speedup = t_baseline / t_N
    and efficiency = speedup / N.  The baseline is the ``local`` trainer at
    the same batch size when present, else the trainer's own 1-device row.
    """
    agg = aggregate_measurements(df)
    if agg.empty:
        return agg

    baselines = {}
    for _, row in agg.iterrows():
        if row["trainer"] == baseline_trainer and row["devices"] == 1:
            baselines[row["batch_size"]] = row["duration_s"]

    def _baseline_for(row):
        if row["batch_size"] in baselines:
            return baselines[row["batch_size"]]
        own = agg[
            (agg["trainer"] == row["trainer"])
            & (agg["devices"] == 1)
            & (agg["batch_size"] == row["batch_size"])
        ]
        return own["duration_s"].iloc[0] if len(own) else float("nan")

    agg = agg.copy()
    agg["speedup"] = agg.apply(
        lambda r: _baseline_for(r) / r["duration_s"], axis=1
    )
    agg["efficiency"] = agg["speedup"] / (agg["devices"] * agg["slots"])
    return agg
