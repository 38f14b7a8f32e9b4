"""``trace_reduce.py`` on a hand-made trace whose answers are worked out in
the comments, and on a recorded one.

``data/v5e_4chip_har_epoch.xplane.pb`` is the first epoch (scanned epoch,
remainder step, validation pass: 215 ms) of a ``har_dp_4chip`` run on four
v5e chips with the Python tracer on (PR 22), cut down to the device planes
and the driving thread, event statistics dropped.
"""

from pathlib import Path

import pytest
from jax.profiler import ProfileData

from benchmarks import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
ROOT = Path(__file__).resolve().parents[2]
US = 1_000_000  # picoseconds


def plane(name, lines):
    """Text-proto plane; ``lines`` maps a line name to
    ``[(event name, start_us, end_us)]``."""
    names = sorted({n for events in lines.values() for n, _, _ in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    out = [f'planes {{ name: "{name}"']
    for i, (line_name, events) in enumerate(lines.items()):
        out.append(f'lines {{ id: {i + 1} name: "{line_name}"')
        out += [f"events {{ metadata_id: {ids[n]} offset_ps: {s * US} "
                f"duration_ps: {(e - s) * US} }}" for n, s, e in events]
        out.append("}")
    for n, i in ids.items():
        escaped = n.replace('"', '\\"')
        out.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{escaped}" }} }}')
    out.append("}")
    return "\n".join(out)


def hlo(name, opcode, extra=""):
    return f"%{name} = f32[8,4]{{1,0}} {opcode}(f32[8,4]{{1,0}} %p){extra}"


KERNEL = ', custom_call_target="tpu_custom_call"'
WHILE, F1, F2 = hlo("while.1", "while"), hlo("fusion.1", "fusion", ", kind=kLoop"), \
    hlo("fusion.2", "fusion", ", kind=kOutput")
AR_START, AR_DONE, AR_SYNC = hlo("all-reduce-start.1", "all-reduce-start"), \
    hlo("all-reduce-done.1", "all-reduce-done"), hlo("all-reduce.2", "all-reduce")
BWD, EVAL_FWD = hlo("transpose_jvp___.1", "custom-call", KERNEL), \
    hlo("_loss_and_metrics.1", "custom-call", KERNEL)


@pytest.fixture(scope="module")
def handmade(tmp_path_factory):
    text = "\n".join([
        plane("/device:TPU:0", {
            "XLA Modules": [("jit_epoch(1)", 100, 1100),
                            ("jit_eval(2)", 1300, 1500),
                            ("jit_epoch(1)", 2200, 2800)],
            "XLA Ops": [
                (WHILE, 100, 1100), (F1, 100, 400), (AR_START, 400, 410),
                (F2, 410, 700), (AR_DONE, 700, 800), (AR_SYNC, 800, 900),
                (BWD, 900, 1100), (EVAL_FWD, 1300, 1500),
                (WHILE, 2200, 2800), (F1, 2200, 2800)],
            "Async XLA Ops": [("ignored", 0, 3000)],
        }),
        plane("/device:TPU:1", {
            "XLA Modules": [("jit_epoch(1)", 100, 600)],
            "XLA Ops": [(F1, 100, 600)],
        }),
        plane("/host:CPU", {
            "other thread": [("noise", 0, 3000)],
            "python3": [
                ("bench.train_call", 0, 1700),
                ("$prog.py:1 _train_epoch", 20, 1200),
                ("$jaxlib.py:9 dispatch", 30, 90),
                ("$prog.py:2 _evaluate", 1250, 1600),
                ("bench.train_call", 2100, 3000),
                ("$prog.py:1 _train_epoch", 2100, 2900)],
        }),
    ])
    path = tmp_path_factory.mktemp("trace") / "handmade.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return tr.reduce_trace(path, lambda name: name.startswith("$prog.py"))


def test_window_busy_and_launches(handmade):
    # spans run 0..1700 and 2100..3000 us: the window is 0..3000
    assert handmade["window_s"] == pytest.approx(3000e-6)
    # chip 0: 100..1100 + 1300..1500 + 2200..2800 = 1800 us (the while's
    # children are inside it, the async line is not read); chip 1: 500 us
    assert handmade["busy_s"] == pytest.approx((1800e-6 + 500e-6) / 2)
    assert handmade["device_count"] == 2
    assert handmade["launches"] == 3
    # busy time of the first chip inside each span
    assert handmade["span_busy_s"] == pytest.approx([1200e-6, 600e-6])


def test_self_time_takes_children_out(handmade):
    ops = handmade["ops"]
    # a container's own time is what its children leave: nothing
    assert ops["jit_epoch/while.1 while f32[8,4]"]["self_s"] == pytest.approx(0)
    # fusion.1: 300 + 600 us on chip 0, 500 us on chip 1, mean over chips
    row = ops["jit_epoch/fusion.1 fusion:kLoop f32[8,4]"]
    assert row["self_s"] == pytest.approx((900e-6 + 500e-6) / 2)
    assert row["count"] == pytest.approx(1.5)
    assert tr.op_seconds(handmade, tr.LSTM_BWD_KERNEL) == pytest.approx(100e-6)
    assert tr.op_seconds(handmade, tr.LSTM_FWD_KERNEL) == pytest.approx(100e-6)
    assert tr.top({k: v["self_s"] for k, v in ops.items()}, k=1)[0][0] == (
        "jit_epoch/fusion.1 fusion:kLoop f32[8,4]")


def test_collective_time_and_its_exposed_part(handmade):
    # chip 0: the async pair is in flight 400..800, the synchronous
    # all-reduce 800..900: 500 us.  fusion.2 runs 410..700 under the pair,
    # so 400..410 and 700..900 are exposed: 210 us.  Chip 1 has none.
    assert handmade["collective_s"] == pytest.approx(500e-6 / 2)
    assert handmade["collective_exposed_s"] == pytest.approx(210e-6 / 2)


def test_idle_gaps_go_to_what_the_host_was_doing(handmade):
    # chip 0 idles 0..100 (midpoint inside _train_epoch; the frame inside
    # JAX is not a program frame), 1100..1300 and 2800..3000 (inside a span,
    # no program frame open), 1500..2200 (midpoint between the spans)
    assert handmade["gaps"] == pytest.approx({
        "bench.train_call > $prog.py:1 _train_epoch": 100e-6,
        "bench.train_call": 400e-6,
        "(outside every bench span)": 700e-6,
    })


def test_interval_arithmetic():
    assert tr.union([[5, 7], [1, 3], [2, 4], [7, 8], [9, 9]]) == [[1, 4], [5, 8]]
    assert tr.subtract([[0, 10]], [[1, 2], [4, 6], [9, 12]]) == [
        [0, 1], [2, 4], [6, 9]]
    assert tr.subtract([[0, 4], [6, 8]], [[3, 7]]) == [[0, 3], [7, 8]]
    assert tr.total(tr.clip([[0, 4], [6, 8]], 3, 7)) == 2


def test_op_label():
    text = ('%fusion.217 = (f32[128,8640,128]{2,1,0:T(8,128)}, u32[2]{0}) '
            'fusion(f32[8640,128,9]{2,1,0:T(8,128)} %x), kind=kOutput, '
            'calls=%fused_computation.22')
    assert tr.op_label(text) == "fusion.217 fusion:kOutput f32[128,8640,128]"
    assert tr.op_label(BWD) == "transpose_jvp___.1 tpu_custom_call f32[8,4]"
    assert tr.op_label("not hlo") == "not hlo"


def test_a_trace_without_spans_or_device_work_is_refused(tmp_path):
    for name, text in {
        "no_spans": plane("/device:TPU:0", {"XLA Ops": [(F1, 0, 10)]}),
        "no_device": plane("/host:CPU", {"t": [("bench.train_call", 0, 10)]}),
    }.items():
        path = tmp_path / f"{name}.xplane.pb"
        path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
        with pytest.raises(ValueError):
            tr.reduce_trace(path)


def test_recorded_v5e_trace():
    frames = tr.program_frame_filter(
        [ROOT / "pytorch_distributed_rnn_tpu", ROOT / "benchmarks"])
    trace = tr.reduce_trace(DATA / "v5e_4chip_har_epoch.xplane.pb", frames)
    assert trace["device_count"] == 4
    assert trace["window_s"] == pytest.approx(0.214829798)
    assert 0.18 < trace["busy_s"] < trace["window_s"]
    # the chip the host drives also runs its small programs (dropout keys)
    assert trace["launches"] == 8
    # both kernels of both layers are found under the names the chip gives
    # them, in the epoch, the remainder step and the validation pass
    kernels = [k for k in trace["ops"] if "tpu_custom_call" in k]
    assert {k.split("/")[0] for k in kernels} == {
        "jit__epoch", "jit__step", "jit__loss_and_metrics"}
    fwd = tr.op_seconds(trace, tr.LSTM_FWD_KERNEL)
    bwd = tr.op_seconds(trace, tr.LSTM_BWD_KERNEL)
    assert fwd == pytest.approx(0.0289, rel=0.01)
    assert bwd == pytest.approx(0.0452, rel=0.01)
    assert fwd + bwd == pytest.approx(
        sum(trace["ops"][k]["self_s"] for k in kernels))
    # 5 optimizer steps, two 56 KB all-reduces each, and three scalar ones
    reduces = {k: v for k, v in trace["ops"].items() if " all-reduce " in k}
    assert sum(v["count"] for v in reduces.values()) == pytest.approx(13)
    assert trace["collective_s"] == pytest.approx(85.07e-6, rel=0.01)
    assert trace["collective_exposed_s"] == pytest.approx(
        trace["collective_s"])
    # idle time is charged to the trainer's own frames
    assert sum(trace["gaps"].values()) == pytest.approx(
        trace["window_s"] - trace["span_busy_s"][0], rel=1e-6)
    assert any("_epoch_dropout_keys" in k for k in trace["gaps"])
