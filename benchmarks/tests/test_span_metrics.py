"""The eight per-layer metrics that read the program's spans, on a hand-made
span log whose answers are worked out in the comments; the window rule of
``program_spans.py``; ``span_gaps.py`` on the hand-made trace of
``test_trace_reduce.py`` with two program spans added; and the rule the
kernels' names had to fit."""

import re

import pytest
from jax.profiler import ProfileData

from benchmarks import harness, program_spans, span_gaps
from benchmarks import trace_reduce as tr
from benchmarks.tests.test_trace_reduce import F1, plane

MS = 1_000_000  # ns


def call(first_id, t0, *, slow=1, uncovered_ms=2):
    """One ``train(epochs=2)`` call starting at ``t0`` ms, as the trainer
    logs it (children before parents), every duration times ``slow``.

    Per epoch (ms): indices 2, dropout_keys 1, launch 3 with a 1 ms trace
    inside it, fetch 40, fetch 0, a second launch 1 and two more fetches
    4 + 0, then an evaluation of 10 = launch 1 + fetch 8 + fetch 0 + 1 of
    its own; ``uncovered_ms`` of the epoch's own before its first child.
    After the epochs a test evaluation of 20 = launch 2 + fetch 18.  So an
    epoch is 61 + uncovered, and the call 2 x that + 20 + 1 of its own.
    """
    ids = iter(range(first_id, first_id + 100))
    entries = []
    clock = [t0 * MS]

    def leaf(name, ms, parent, **attrs):
        start = clock[0]
        clock[0] += ms * slow * MS
        entries.append((next(ids), parent, name, start, clock[0], attrs))

    def evaluation(parent, launch, fetch, own):
        span_id, start = next(ids), clock[0]
        leaf("eval.launch", launch, span_id)
        leaf("eval.fetch", fetch, span_id)
        leaf("eval.fetch", 0, span_id)
        clock[0] += own * slow * MS
        entries.append((span_id, parent, "eval", start, clock[0], {}))

    train_id, train_start = next(ids), clock[0]
    for epoch in range(2):
        epoch_id, epoch_start = next(ids), clock[0]
        clock[0] += uncovered_ms * slow * MS
        leaf("epoch.indices", 2, epoch_id, steps=5)
        leaf("epoch.dropout_keys", 1, epoch_id)
        launch_id, launch_start = next(ids), clock[0]
        clock[0] += 3 * slow * MS
        # noted by the compile listener: ended inside the launch
        entries.append((next(ids), launch_id, "compile.trace",
                        launch_start + 1 * slow * MS,
                        launch_start + 2 * slow * MS, {}))
        entries.append((launch_id, epoch_id, "epoch.launch", launch_start,
                        clock[0], {"program": "train_epoch"}))
        leaf("epoch.fetch", 40, epoch_id)
        leaf("epoch.fetch", 0, epoch_id)
        leaf("epoch.launch", 1, epoch_id, program="train_step")
        leaf("epoch.fetch", 4, epoch_id)
        leaf("epoch.fetch", 0, epoch_id)
        evaluation(epoch_id, 1, 8, 1)
        entries.append((epoch_id, train_id, "epoch", epoch_start, clock[0],
                        {"epoch": epoch, "path": "scan"}))
    evaluation(train_id, 2, 18, 0)
    clock[0] += 1 * slow * MS
    entries.append((train_id, None, "train", train_start, clock[0],
                    {"epochs": 2}))
    return entries


@pytest.fixture
def log():
    """Set-up, one warm-up call, three window calls; the second window
    call ran under the Python tracer, four times slower."""
    setup = [
        # model.init traced outside every span; an inner jit traced inside
        # an outer one's trace counts once: 0..30 and 10..20 -> 30 ms
        (1, None, "compile.trace", 0, 30 * MS, {"fun_name": "init"}),
        (2, None, "compile.trace", 10 * MS, 20 * MS, {"fun_name": "inner"}),
        (3, None, "compile.lower", 30 * MS, 45 * MS, {}),
        (4, None, "compile.cache_read", 46 * MS, 50 * MS, {}),
    ]
    warm = call(100, 100, uncovered_ms=30)
    # the warm-up call uploads the data and reads the cache some more
    warm_train = warm[-1]
    warm += [
        (190, warm_train[0], "input.upload", 101 * MS, 108 * MS, {}),
        (191, warm_train[0], "input.upload", 110 * MS, 112 * MS, {}),
        (192, warm_train[0], "compile.cache_read", 113 * MS, 119 * MS, {}),
    ]
    window = (call(200, 1000) + call(300, 2000, slow=4)
              + call(400, 4000, uncovered_ms=4))
    # after the window the harness compares gradients: more compiles
    after = [(500, None, "compile.trace", 9000 * MS, 9500 * MS, {})]
    return setup + warm + window + after


def context_of(entries, monkeypatch, warmups=1, calls=3):
    monkeypatch.setattr(program_spans, "program_log", lambda: entries)
    return {"counters": {"warmup_call_s": [0.0] * warmups, "calls": calls}}


def read(metric, context):
    return harness.load_layer_metric(metric).read(context)


def test_window_is_the_calls_after_the_warm_up(log):
    cut = program_spans.cut(log, 1, 3)
    assert [c[0][program_spans.ID] for c in cut] == [200, 300, 400]
    # a call's list is its root and everything under it
    assert all(len(c) == len(call(0, 0)) for c in cut)
    # what was traced before the first call, in the warm-up call or after
    # the window is in no window call
    in_a_call = {e[program_spans.ID] for c in cut for e in c}
    assert not in_a_call & {1, 2, 3, 4, 100, 190, 191, 192, 500}
    # two warm-up calls (the four-chip cell): one window call fewer fits
    assert program_spans.cut(log, 2, 3) is None
    two = program_spans.cut(log, 2, 2)
    assert [c[0][program_spans.ID] for c in two] == [300, 400]


def test_a_truncated_log_gives_nothing(log, monkeypatch):
    # the log dropped its oldest entries (it is ordered by end time).  The
    # warm-up call's root is gone: the second root would be taken for the
    # first window call
    assert program_spans.cut([e for e in log if e[0] >= 200], 1, 3) is None
    # a full log has dropped something from before the window, however
    # little: the window stands, the set-up metrics have nothing
    from pytorch_distributed_rnn_tpu.obs import spans

    ordered = sorted(log, key=lambda e: e[program_spans.END])
    kept = ordered[1:]
    monkeypatch.setattr(spans, "LOG_CAPACITY", len(kept))
    context = context_of(kept, monkeypatch)
    assert read("epoch_prepare_ms", context) == pytest.approx(3.0)
    assert read("trace_lower_s", context) is None
    assert read("data_upload_s", context) is None
    # the first window call's root is there but its first children are not
    kept = [e for e in ordered if e[program_spans.END] > 1010 * MS]
    assert program_spans.cut(kept, 1, 3) is None
    assert read("epoch_prepare_ms", context_of(kept, monkeypatch)) is None


def test_a_program_without_the_log_gives_nothing(monkeypatch):
    monkeypatch.setattr(program_spans, "program_log", lambda: None)
    context = {"counters": {"warmup_call_s": [0.0], "calls": 3}}
    for metric in ("epoch_prepare_ms", "epoch_launch_ms",
                   "host_fetches_per_epoch", "eval_wall_share",
                   "train_uncovered_share", "trace_lower_s", "cache_read_s",
                   "data_upload_s"):
        assert read(metric, context) is None


def test_program_log_reads_the_running_program():
    from pytorch_distributed_rnn_tpu.obs import spans

    spans.clear()
    with spans.span("train"):
        pass
    entries = program_spans.program_log()
    assert [e[program_spans.NAME] for e in entries] == ["train"]
    assert program_spans.self_times(entries) == spans.self_times(entries)
    spans.clear()


def test_epoch_metrics_are_medians_over_the_windows_epochs(log, monkeypatch):
    context = context_of(log, monkeypatch)
    # six epochs: indices 2 + keys 1 = 3 ms in four of them, 12 in the two
    # of the slowed call: the median is 3
    assert read("epoch_prepare_ms", context) == pytest.approx(3.0)
    # launches 3 + 1 and the evaluation's 1 = 5 ms an epoch (the compile
    # span inside a launch is part of it; the test evaluation's launch is
    # under no epoch)
    assert read("epoch_launch_ms", context) == pytest.approx(5.0)


def test_fetches_per_epoch_counts_the_test_evaluation_in(log, monkeypatch):
    # per call: 2 epochs x (4 + 2) fetches + 2 of the test evaluation
    assert read("host_fetches_per_epoch",
                context_of(log, monkeypatch)) == pytest.approx(14 / 2)


def test_shares_are_medians_over_the_windows_calls(log, monkeypatch):
    context = context_of(log, monkeypatch)
    # calls one and two: epochs of 63 ms, 2 x 63 + 20 + 1 = 147 (the slowed
    # call is 4 x all of it, the same shares); call three: 2 x 65 + 21 = 151
    # evaluation: 2 x 10 + 20 = 40 ms -> 27.21 %, 27.21 %, 26.49 %
    assert read("eval_wall_share", context) == pytest.approx(100 * 40 / 147)
    # uncovered: 2 ms an epoch, 1 ms in each epoch's evaluation, 1 ms of the
    # call's own = 7 ms -> 4.76 %, 4.76 %; call three 11 of 151 = 7.28 %
    assert read("train_uncovered_share", context) == pytest.approx(
        100 * 7 / 147)


def test_set_up_metrics_read_what_ended_before_the_window(log, monkeypatch):
    # before the first call 45 ms of tracing and lowering and a cache read
    # of 4; in the warm-up call 1 ms of tracing in each of its two scanned
    # launches, two uploads, one cache read of 6.  Neither the tracing
    # inside the window's launches nor the 500 ms after the window count
    context = context_of(log, monkeypatch)
    assert read("trace_lower_s", context) == pytest.approx(0.045 + 0.002)
    assert read("cache_read_s", context) == pytest.approx(0.004 + 0.006)
    assert read("data_upload_s", context) == pytest.approx(0.007 + 0.002)
    # with two warm-up calls the first window call of before is set-up too
    context = context_of(log, monkeypatch, warmups=2, calls=2)
    assert read("trace_lower_s", context) == pytest.approx(0.045 + 0.004)
    # a jit traced inside another's trace counts once: 0..30 and 10..20
    assert program_spans.covered_seconds(
        log[:3], {"compile.trace", "compile.lower"}) == pytest.approx(0.045)


# -- span_gaps ---------------------------------------------------------------------


def test_span_gaps_charges_idle_time_to_the_deepest_program_span(tmp_path):
    # test_trace_reduce.py's hand-made trace with the Python frames taken
    # out and program spans put in.  Chip 0 is busy 100..1100 and
    # 1300..1500 in the first call and 2200..2800 in the second, so it
    # idles 0..100 (midpoint 50: epoch.indices, inside epoch inside train),
    # 1100..1300 (1200: eval, no leaf open), 1500..2200 (1850: between the
    # calls), 2800..3000 (2900: a bare bench.train_call, its train span
    # ended at 2850) and 1 us at 2199 (under 2 us: not attributed)
    text = "\n".join([
        plane("/device:TPU:0", {
            "XLA Modules": [("jit_train_epoch(1)", 100, 1100),
                            ("jit_eval_step(2)", 1300, 1500),
                            ("jit_train_epoch(1)", 2200, 2800)],
            "XLA Ops": [(F1, 100, 1100), (F1, 1300, 1500), (F1, 1501, 1502),
                        (F1, 2200, 2800)],
        }),
        plane("/host:CPU", {
            "python3": [
                ("bench.train_call", 0, 1700),
                ("train", 5, 1690), ("epoch", 10, 1650),
                ("epoch.indices", 20, 90), ("PjitFunction(f)", 30, 60),
                ("epoch.launch", 92, 99), ("epoch.fetch", 99, 1110),
                ("eval", 1150, 1600), ("eval.fetch", 1310, 1590),
                ("bench.train_call", 2100, 3000),
                ("train", 2105, 2850), ("epoch.fetch", 2150, 2840)],
        }),
    ])
    path = tmp_path / "plugins" / "profile" / "run" / "t.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    assert span_gaps.span_gaps(path) == pytest.approx({
        "bench.train_call > epoch.indices": 100e-6,
        "bench.train_call > eval": 200e-6,
        "(outside every bench span)": 698e-6,
        "bench.train_call": 200e-6,
        "(gaps under 2 us between device instructions)": 1e-6,
    })
    assert span_gaps.main([str(tmp_path)]) == 0


# -- the names the kernels had to fit ---------------------------------------------


def matched_by(label):
    return {name for name, pattern in (("fwd", tr.LSTM_FWD_KERNEL),
                                       ("bwd", tr.LSTM_BWD_KERNEL))
            if re.search(pattern, label)}


def test_the_kernel_labels_the_program_gives_are_split_correctly():
    """``name=`` on the forward pallas_call, under no other scope, and no
    name on the backward one, reach the chip as these instruction names
    (compiled for a described v5e, PR 23): the forward under a gradient,
    the backward, the forward in evaluation and under ``jax.checkpoint``."""
    labels = {
        "jit_train_epoch/jvp_lstm_fwd_.14 tpu_custom_call f32[128,8704,32]":
            {"fwd"},
        "jit_train_epoch/transpose_jvp___.15 tpu_custom_call "
        "f32[128,8704,128]": {"bwd"},
        "jit_train_step/transpose_jvp___.3 tpu_custom_call "
        "f32[128,4608,128]": {"bwd"},
        "jit_eval_step/lstm_fwd.2 tpu_custom_call f32[128,4416,32]": {"fwd"},
        # what is not a kernel is neither
        "jit_train_epoch/fusion.217 fusion:kOutput f32[128,8640,128]": set(),
        "jit_train_epoch/pallas_call.19 get-tuple-element f32[8704,32]":
            set(),
        # the parent's labels keep working
        "jit_epoch/jvp__.14 tpu_custom_call f32[128,8704,32]": {"fwd"},
    }
    for label, expected in labels.items():
        assert matched_by(label) == expected, label


def test_a_named_backward_kernel_would_be_counted_twice():
    """Why the backward kernel has no name: ``transpose(jvp(lstm_bwd))``
    reaches the chip as ``transpose_jvp_lstm_bwd__.N``, which fits, but
    under any enclosing scope or ``jax.checkpoint`` the compiler names the
    kernel after the innermost scope alone, and ``LSTM_FWD_KERNEL`` takes
    every custom call whose name does not start with ``transpose_jvp``.
    ``lstm_fwd_roofline`` would fall from 36 % to about 13 % with no
    change to the chip (ISSUE 23)."""
    fits = ("jit_train_epoch/transpose_jvp_lstm_bwd__.15 tpu_custom_call "
            "f32[128,8704,128]")
    assert matched_by(fits) == {"bwd"}
    bare = "jit_train_epoch/lstm_bwd.15 tpu_custom_call f32[128,8704,128]"
    assert matched_by(bare) == {"fwd", "bwd"}
    # under --remat the unnamed backward kernel reads `checkpoint.N`, here
    # as on the parent: no cell runs it, and the patterns do not hold there
    assert matched_by(
        "jit_train_epoch/checkpoint.2 tpu_custom_call f32[128,8704,128]"
    ) == {"fwd"}
