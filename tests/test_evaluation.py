"""Evaluation-layer tests: the notebooks' data contract survives.

The regex, dataframe shape, and derived scaling figures mirror
``/root/reference/evaluation/Experiments.ipynb`` (cell 2 regex and its
derived figures).  The round-trip test feeds results entries shaped exactly
like the launcher's output.
"""

import json

import pandas as pd
import pytest

from pytorch_distributed_rnn_tpu.evaluation import (
    PERF_LINE_RE,
    aggregate_measurements,
    create_measurement_df,
    parse_perf_lines,
    plot_scaling,
    scaling_table,
)


def _run(trainer, devices, duration, memory, batch=1440, repeats_suffix="",
         rule_type=None, rule_value=0.0, ranks=1):
    stderr_lines = ["INFO:root:Training set of size 6912"]
    for rank in range(ranks):
        stderr_lines.append(
            f"{rank}: Memory Usage: {memory + rank:.6f}, "
            f"Training Duration: {duration + rank / 10:.6f}"
        )
    return {
        "trainer": trainer,
        "devices": devices,
        "slots": 1,
        "parameters": {"batch-size": batch, "epochs": 1},
        "rule_type": rule_type,
        "rule_value": rule_value,
        "command": f"cmd-{trainer}-{devices}-{batch}-{duration}{repeats_suffix}",
        "returncode": 0,
        "stdout": "",
        "stderr": "\n".join(stderr_lines),
        "wall_seconds": duration + 1.0,
    }


def test_perf_line_regex_matches_reference_contract():
    # byte-identical to the line format the reference notebooks parse
    line = "0: Memory Usage: 727.90625, Training Duration: 145.123456"
    (match,) = PERF_LINE_RE.findall(line)
    assert match == ("0", "727.90625", "145.123456")


def test_perf_line_regex_accepts_scientific_and_integer_floats():
    """The formatter prints RAW floats: a sub-millisecond duration
    renders as '5e-05' and an integer-valued memory as '700' - the
    notebooks' \\d+\\.\\d+ regex silently dropped both (ISSUE 4
    satellite: the perf-line contract hole)."""
    assert parse_perf_lines(
        "0: Memory Usage: 700, Training Duration: 5e-05"
    ) == [(0, 700.0, 5e-05)]
    assert parse_perf_lines(
        "3: Memory Usage: 1.5e+3, Training Duration: 2E-3"
    ) == [(3, 1500.0, 0.002)]


def test_formatter_parser_round_trip_property():
    """Property test over the formatter<->parser pair: EVERY
    (memory, duration) the formatter can emit must survive the parse
    with value equality - including the scientific/integer renderings
    the original regex dropped."""
    import random

    from pytorch_distributed_rnn_tpu.training.formatter import (
        TrainingMessageFormatter,
    )

    rng = random.Random(123456789)
    cases = [
        (727.90625, 145.123456),  # the reference's own shape
        (700, 5e-05),  # integer memory, scientific duration
        (1e-12, 1e12),
        (0.0, 0.0),
    ]
    for _ in range(200):
        # log-uniform over the magnitudes float formatting renders
        # differently (fixed-point vs scientific, either side of 1e16)
        mem = 10 ** rng.uniform(-12, 12)
        dur = 10 ** rng.uniform(-12, 12)
        if rng.random() < 0.2:
            mem = float(int(mem))  # integer-VALUED float ('700.0')
        if rng.random() < 0.1:
            mem = int(mem)  # true int ('700')
        cases.append((mem, dur))
    for rank in (0, 7):
        formatter = TrainingMessageFormatter(num_epochs=1, rank=rank)
        for mem, dur in cases:
            line = formatter.performance_message(mem, dur)
            parsed = parse_perf_lines(line)
            assert parsed == [(rank, float(mem), float(dur))], (
                f"round-trip lost {line!r} -> {parsed}"
            )


def test_parse_perf_lines_multi_rank():
    text = (
        "noise\n0: Memory Usage: 100.5, Training Duration: 10.0\n"
        "1: Memory Usage: 90.25, Training Duration: 9.5\n"
    )
    parsed = parse_perf_lines(text)
    assert parsed == [(0, 100.5, 10.0), (1, 90.25, 9.5)]


def test_create_measurement_df_drops_crashed_runs():
    results = [
        _run("local", 1, 100.0, 700.0),
        {"trainer": "distributed", "devices": 8, "slots": 1,
         "parameters": {"batch-size": 1440}, "returncode": 1,
         "stdout": "", "stderr": "Traceback ...", "command": "x"},
    ]
    df = create_measurement_df(results)
    assert len(df) == 1
    assert df.iloc[0]["trainer"] == "local"
    assert df.iloc[0]["num_sequences"] == 6912
    assert df.iloc[0]["seq_per_sec"] == pytest.approx(6912 / 100.0)


def test_aggregate_means_over_repeats():
    results = [
        _run("local", 1, 100.0, 700.0, repeats_suffix="-a"),
        _run("local", 1, 110.0, 720.0, repeats_suffix="-b"),
    ]
    agg = aggregate_measurements(create_measurement_df(results))
    assert len(agg) == 1
    assert agg.iloc[0]["duration_s"] == pytest.approx(105.0)
    assert agg.iloc[0]["memory_mb"] == pytest.approx(710.0)
    assert agg.iloc[0]["repeats"] == 2


def test_scaling_table_efficiency_vs_local():
    # local 1 dev: 144s; ddp 8 dev: 33s -> speedup 4.36, efficiency ~0.545
    # (the reference's scaling-table shape)
    results = [
        _run("local", 1, 144.0, 700.0),
        _run("distributed", 8, 33.0, 220.0, ranks=1),
    ]
    table = scaling_table(create_measurement_df(results))
    ddp = table[table["trainer"] == "distributed"].iloc[0]
    assert ddp["speedup"] == pytest.approx(144.0 / 33.0)
    assert ddp["efficiency"] == pytest.approx(144.0 / 33.0 / 8)


def test_scaling_table_falls_back_to_own_1dev_baseline():
    results = [
        _run("distributed", 1, 150.0, 700.0),
        _run("distributed", 4, 50.0, 300.0),
    ]
    table = scaling_table(create_measurement_df(results))
    four = table[table["devices"] == 4].iloc[0]
    assert four["speedup"] == pytest.approx(3.0)


def test_multi_rank_aggregation_uses_rank0():
    results = [_run("distributed", 2, 50.0, 400.0, ranks=2)]
    agg = aggregate_measurements(create_measurement_df(results))
    assert agg.iloc[0]["duration_s"] == pytest.approx(50.0)
    assert agg.iloc[0]["memory_mb"] == pytest.approx(400.0)


def test_network_rule_columns_survive():
    results = [
        _run("parameter-server", 2, 60.0, 300.0, rule_type="delay",
             rule_value=100.0),
    ]
    df = create_measurement_df(results)
    assert df.iloc[0]["rule_type"] == "delay"
    assert df.iloc[0]["rule_value"] == 100.0


def test_cli_and_plot_round_trip(tmp_path):
    results = [
        _run("local", 1, 144.0, 700.0),
        _run("distributed", 2, 80.0, 490.0),
        _run("distributed", 8, 33.0, 220.0),
        _run("horovod", 8, 49.0, 224.0),
    ]
    results_path = tmp_path / "results.json"
    results_path.write_text(json.dumps(results))

    from pytorch_distributed_rnn_tpu.evaluation.__main__ import main

    csv_path = tmp_path / "scaling.csv"
    png_path = tmp_path / "scaling.png"
    rc = main([str(results_path), "--csv", str(csv_path),
               "--plot", str(png_path)])
    assert rc == 0
    table = pd.read_csv(csv_path)
    assert set(table["trainer"]) == {"local", "distributed", "horovod"}
    assert png_path.exists() and png_path.stat().st_size > 0


def test_plot_requires_measurements(tmp_path):
    with pytest.raises(ValueError):
        plot_scaling(create_measurement_df([]), tmp_path / "x.png")


def test_network_plot_round_trip(tmp_path):
    """plot_network renders delay/loss panels from fault-rule runs whose
    perf lines come from worker ranks (PS masters never train), via the
    CLI's --network-plot."""
    results = [
        _run("parameter-server", 2, 20.0, 300.0, rule_type="delay",
             rule_value=v, ranks=3)
        for v in (0.0, 100.0, 400.0)
    ] + [
        _run("parameter-server", 2, 22.0, 300.0, rule_type="loss",
             rule_value=v, ranks=3)
        for v in (0.05, 0.15)
    ]
    results_path = tmp_path / "results_network.json"
    results_path.write_text(json.dumps(results))

    from pytorch_distributed_rnn_tpu.evaluation.__main__ import main

    png_path = tmp_path / "network.png"
    rc = main([str(results_path), "--network-plot", str(png_path)])
    assert rc == 0
    assert png_path.exists() and png_path.stat().st_size > 0


def test_network_plot_requires_fault_rules(tmp_path):
    from pytorch_distributed_rnn_tpu.evaluation.plots import plot_network

    with pytest.raises(ValueError):
        plot_network(
            create_measurement_df([_run("local", 1, 10.0, 100.0)]),
            tmp_path / "x.png",
        )


def test_bubble_plot_needs_no_results(tmp_path):
    """--bubble-plot is pure timetable accounting: runs with no results
    files; bare invocation without either still errors."""
    from pytorch_distributed_rnn_tpu.evaluation.__main__ import main

    png_path = tmp_path / "bubble.png"
    rc = main(["--bubble-plot", str(png_path)])
    assert rc == 0
    assert png_path.exists() and png_path.stat().st_size > 0

    with pytest.raises(SystemExit):
        main([])
