"""Program spans (obs/spans.py): the primitive, the trainer's span sites,
the names on programs, scopes and kernels, and the profile capture that no
longer changes the program (ISSUE 23)."""

import logging
import re
import threading
import time

import jax
import numpy as np
import pytest

from pytorch_distributed_rnn_tpu.data import MotionDataset
from pytorch_distributed_rnn_tpu.data.synthetic import generate_har_arrays
from pytorch_distributed_rnn_tpu.models import MotionModel
from pytorch_distributed_rnn_tpu.obs import (
    MetricsRecorder,
    NULL_RECORDER,
    StepTraceCapture,
    spans,
)
from pytorch_distributed_rnn_tpu.obs.ledger import ledger_events
from pytorch_distributed_rnn_tpu.obs.summary import load_events
from pytorch_distributed_rnn_tpu.obs.timeline import write_chrome_trace
from pytorch_distributed_rnn_tpu.parallel.mesh import make_mesh
from pytorch_distributed_rnn_tpu.training import DDPTrainer, Trainer

SEED = 123456789
ID, PARENT, NAME, START, END, ATTRS = range(6)


@pytest.fixture(autouse=True)
def fresh_log():
    spans.clear()
    yield
    spans.clear()


@pytest.fixture(scope="module")
def datasets():
    x, y = generate_har_arrays(200, seq_length=16, seed=0)
    return MotionDataset(x, y), MotionDataset(x[:48], y[:48])


@pytest.fixture
def info_logging():
    """The CLI's level: what selects the scanned epoch in the trainer."""
    root = logging.getLogger()
    previous = root.level
    root.setLevel(logging.INFO)
    yield
    root.setLevel(previous)


def small_trainer(datasets, cls=Trainer, **kwargs):
    train, validation = datasets
    model = MotionModel(input_dim=9, hidden_dim=8, layer_dim=2, output_dim=6,
                        dropout=0.1)
    # 200 windows at batch 48: four full steps and one of 8
    return cls(model, train, batch_size=48, learning_rate=2.5e-3, seed=SEED,
               validation_set=validation, test_set=validation, **kwargs)


def by_name(entries, name):
    return [e for e in entries if e[NAME] == name]


def program_spans(entries):
    """Without the compile.* spans, whose number depends on what JAX has
    traced before."""
    return [e for e in entries if not e[NAME].startswith("compile.")]


# -- the primitive -------------------------------------------------------------


class TestPrimitive:
    def test_nesting_gives_parent_ids_and_child_first_order(self):
        with spans.span("train", epochs=1) as outer:
            with spans.span("epoch", epoch=0) as middle:
                with spans.span("epoch.launch", program="train_epoch"):
                    pass
            with spans.span("eval"):
                pass
        log = spans.log()
        # logged as they END: a child before its parent
        assert [e[NAME] for e in log] == [
            "epoch.launch", "epoch", "eval", "train"]
        launch, epoch, evaluation, train = log
        assert train[PARENT] is None
        assert epoch[PARENT] == evaluation[PARENT] == train[ID] == outer.id
        assert launch[PARENT] == epoch[ID] == middle.id
        assert train[ID] < epoch[ID] < launch[ID] < evaluation[ID]
        assert train[START] <= epoch[START] <= launch[START]
        assert launch[END] <= epoch[END] <= evaluation[START]
        assert evaluation[END] <= train[END]
        assert launch[ATTRS] == {"program": "train_epoch"}
        assert train[ATTRS] == {"epochs": 1}

    def test_a_span_is_logged_when_it_ends_not_before(self):
        with spans.span("epoch.indices") as indices:
            assert spans.log() == []
        logged, = spans.log()
        assert logged[:3] == (indices.id, None, "epoch.indices")
        assert logged[START] == indices.start_ns <= logged[END]
        assert logged[ATTRS] == {}

    def test_a_raising_block_is_logged_and_closes_its_span(self):
        with pytest.raises(ValueError):
            with spans.span("train"):
                with spans.span("epoch"):
                    raise ValueError("boom")
        assert [e[NAME] for e in spans.log()] == ["epoch", "train"]
        with spans.span("next"):
            pass
        assert spans.log()[-1][PARENT] is None  # nothing was left open

    def test_self_time_is_a_span_less_what_its_children_cover(self):
        # a: 0..100 with children 10..30 and 20..50 (overlapping: 40 ns
        # covered once) and a compile span noted as -5..8 (starts before
        # its parent: 8 ns of it count), so a's self time is 100 - 48
        entries = [
            (2, 1, "b", 10, 30, {}),
            (3, 1, "c", 20, 50, {}),
            (4, 1, "compile.trace", -5, 8, {}),
            (5, 3, "d", 25, 45, {}),  # grandchild: c's business, not a's
            (1, None, "a", 0, 100, {}),
        ]
        assert spans.self_times(entries) == {1: 52, 2: 20, 3: 10, 4: 13,
                                             5: 20}

    def test_the_log_is_bounded_and_keeps_the_newest(self):
        for i in range(spans.LOG_CAPACITY + 10):
            with spans.span("x", i=i):
                pass
        log = spans.log()
        assert len(log) == spans.LOG_CAPACITY == 16384
        assert log[0][ATTRS] == {"i": 10}
        assert log[-1][ATTRS] == {"i": spans.LOG_CAPACITY + 9}

    def test_a_thread_has_its_own_stack(self):
        """A span on the async-checkpoint thread does not adopt the span
        the trainer's thread has open."""
        def worker():
            with spans.span("checkpoint.write"):
                with spans.span("checkpoint.write.inner"):
                    pass

        with spans.span("train") as train:
            thread = threading.Thread(target=worker)
            thread.start()
            thread.join(timeout=10)
            assert not thread.is_alive()
            with spans.span("epoch"):
                pass
        log = spans.log()
        outer, = by_name(log, "checkpoint.write")
        inner, = by_name(log, "checkpoint.write.inner")
        assert outer[PARENT] is None
        assert inner[PARENT] == outer[ID]
        assert by_name(log, "epoch")[0][PARENT] == train.id

    def test_note_finished_ends_now_under_the_open_span(self):
        with spans.span("epoch.launch") as launch:
            before = time.perf_counter_ns()
            spans.note_finished("compile.trace", 0.25, fun_name="f")
            after = time.perf_counter_ns()
        noted, _ = spans.log()
        assert noted[NAME] == "compile.trace"
        assert noted[PARENT] == launch.id
        assert before <= noted[END] <= after
        assert noted[END] - noted[START] == 250_000_000
        assert noted[ATTRS] == {"fun_name": "f"}

    def test_recorder_gets_the_wire_format_plus_ids(self, tmp_path):
        path = tmp_path / "m.jsonl"
        recorder = MetricsRecorder(path)
        with spans.span("eval", recorder, cat="eval", epoch=3) as outer:
            with spans.span("eval.launch", recorder, cat="eval"):
                pass
        with spans.span("train"):  # no recorder: the log only
            pass
        recorder.close()
        events = [e for e in load_events(path) if e["kind"] == "span"]
        assert [e["name"] for e in events] == ["eval.launch", "eval"]
        launch, evaluation = events
        # the `span` event of obs/recorder.py's schema, plus the two ids
        assert set(evaluation) == {"kind", "t", "tm", "rank", "name", "cat",
                                   "dur_s", "epoch", "span", "parent"}
        assert evaluation["cat"] == "eval" and evaluation["epoch"] == 3
        assert evaluation["span"] == outer.id
        assert evaluation["parent"] is None
        assert launch["parent"] == outer.id
        logged = by_name(spans.log(), "eval")[0]
        assert evaluation["tm"] == pytest.approx(logged[START] / 1e9)
        assert evaluation["dur_s"] == pytest.approx(
            (logged[END] - logged[START]) / 1e9)

    def test_a_disabled_recorder_emits_nothing_and_still_logs(self):
        with spans.span("eval", NULL_RECORDER, cat="eval"):
            pass
        assert [e[NAME] for e in spans.log()] == ["eval"]

    def test_the_package_still_imports_without_jax(self):
        import subprocess
        import sys

        out = subprocess.run(
            [sys.executable, "-c",
             "import sys; import pytorch_distributed_rnn_tpu.obs.spans; "
             "print('jax' in sys.modules)"],
            capture_output=True, text=True, timeout=60, check=True)
        assert out.stdout.strip() == "False"


# -- the trainer's span sites -------------------------------------------------------


class TestTrainerSpans:
    def test_two_epochs_on_the_scan_path(self, datasets, info_logging):
        trainer = small_trainer(datasets)
        trainer.train(epochs=2)
        log = spans.log()
        train, = by_name(log, "train")
        assert train[PARENT] is None and train[ATTRS] == {"epochs": 2}
        epochs = by_name(log, "epoch")
        assert [e[ATTRS] for e in epochs] == [
            {"epoch": 0, "path": "scan"}, {"epoch": 1, "path": "scan"}]
        parent = {e[ID]: e[PARENT] for e in log}

        def under(entry, ancestor):
            above = entry[PARENT]
            while above is not None and above != ancestor[ID]:
                above = parent[above]
            return above is not None

        for number, epoch in enumerate(epochs):
            assert epoch[PARENT] == train[ID]
            inside = [e for e in program_spans(log) if under(e, epoch)]
            names = [e[NAME] for e in inside]
            # the steady epoch, read off _train_epoch and _evaluate: the
            # scanned program, the remainder step and the validation pass
            # launched back to back, ONE wait for the training programs'
            # values, then the evaluation's
            steady = (["epoch.launch"] * 2
                      + ["eval.launch", "epoch.fetch", "eval", "eval.fetch"])
            # an epoch's inputs are made during the epoch before; the
            # call's first epoch makes its own as well, and uploads
            own = (["input.upload"] * 2
                   + ["epoch.indices", "epoch.dropout_keys"]
                   if number == 0 else [])
            ahead = (["epoch.indices", "epoch.dropout_keys"]
                     if number + 1 < len(epochs) else [])
            assert sorted(names) == sorted(steady + own + ahead)
            assert len(inside) + 1 <= 16  # with the epoch span itself
        assert [e[ATTRS] for e in by_name(log, "epoch.indices")] == [
            {"ahead": 0}, {"ahead": 1}]
        assert [e[ATTRS]["program"] for e in by_name(log, "epoch.fetch")] == [
            "train_epoch+train_step"] * 2
        launches = by_name(log, "epoch.launch")
        assert [e[ATTRS]["program"] for e in launches] == [
            "train_epoch", "train_step"] * 2
        # the test evaluation hangs off the call, not off an epoch
        splits = [(e[ATTRS]["split"], e[PARENT] == train[ID])
                  for e in by_name(log, "eval")]
        assert splits[-1][1] and not any(direct for _, direct in splits[:-1])
        uploads = by_name(log, "input.upload")
        # (the test set here IS the validation set: one upload for both)
        assert [e[ATTRS] for e in uploads] == [
            {"split": "train"}, {"split": "validation"}]

    @pytest.mark.parametrize("cls", [Trainer, DDPTrainer])
    @pytest.mark.parametrize("batch_size", [48, 50, 200, 1000])
    def test_steps_per_epoch_from_the_sizes_alone(self, datasets, cls,
                                                  batch_size):
        """What tells a --profile-steps capture which epoch holds its
        steps, without drawing the epoch's permutation a second time."""
        train, _ = datasets
        model = MotionModel(input_dim=9, hidden_dim=8, layer_dim=1,
                            output_dim=6)
        kwargs = {"mesh": make_mesh()} if cls is DDPTrainer else {}
        trainer = cls(model, train, batch_size=batch_size,
                      learning_rate=2.5e-3, seed=SEED, **kwargs)
        assert trainer._steps_per_epoch() == len(
            trainer._epoch_index_batches())

    def test_a_recorder_changes_the_path_and_little_else(
            self, datasets, info_logging, tmp_path):
        """With --metrics on the epoch runs per step (D4 in ROADMAP): the
        span tree keeps its shape but for the launches, the children
        reach the sidecar, the timeline stays validator-clean and the
        ledger counts evaluation once."""
        small_trainer(datasets).train(epochs=2)
        plain = [e[NAME] for e in program_spans(spans.log())]
        spans.clear()
        path = tmp_path / "m.jsonl"
        recorder = MetricsRecorder(path, sample_every=2)
        small_trainer(datasets, recorder=recorder).train(epochs=2)
        recorder.close()
        log = spans.log()
        assert {e[ATTRS]["path"] for e in by_name(log, "epoch")} == {"step"}
        recorded = [e[NAME] for e in program_spans(log)]
        # per step nothing, per epoch the one fetch the scan path makes
        # after its two launches
        assert "epoch.launch" not in recorded
        assert recorded.count("epoch.fetch") == 2
        assert plain.count("epoch.launch") == 4
        unchanged = ("train", "epoch", "epoch.fetch", "epoch.indices",
                     "epoch.dropout_keys", "eval", "eval.launch",
                     "eval.fetch", "input.upload")
        for name in unchanged:
            assert recorded.count(name) == plain.count(name), name

        events = load_events(path)
        emitted = [e for e in events if e["kind"] == "span"]
        names = [e["name"] for e in emitted]
        # train and epoch ride on run_summary's and epoch's own durations
        assert "train" not in names and "epoch" not in names
        assert names.count("eval") == 3 and names.count("eval.launch") == 3
        assert names.count("epoch.indices") == 2
        evals = {e["span"]: e for e in emitted if e["name"] == "eval"}
        assert names.count("eval.fetch") == 3
        # every wait for an evaluation's values lies inside its `eval`;
        # so does the test evaluation's launch, while a validation pass
        # is launched behind the epoch's steps, before its `eval` opens
        inside = [e for e in emitted if e["name"] == "eval.fetch"
                  or (e["name"] == "eval.launch" and e["parent"] in evals)]
        assert [e["name"] for e in inside].count("eval.launch") == 1
        for child in inside:
            outer = evals[child["parent"]]
            assert outer["tm"] <= child["tm"]
            assert (child["tm"] + child["dur_s"]
                    <= outer["tm"] + outer["dur_s"])
        behind = [e for e in emitted if e["name"] == "eval.launch"
                  and e["parent"] not in evals]
        validations = sorted(
            (e for e in evals.values() if e["epoch"] is not None),
            key=lambda e: e["tm"])
        assert len(behind) == len(validations) == 2
        for launch, outer in zip(behind, validations):
            assert launch["tm"] + launch["dur_s"] <= outer["tm"]
        write_chrome_trace(path, tmp_path / "m.trace.json")  # validates
        ledger = ledger_events(events)
        assert ledger["phase_s"]["eval"] == pytest.approx(
            sum(e["dur_s"] for e in evals.values()))

    @pytest.mark.parametrize("cls", [Trainer, DDPTrainer])
    @pytest.mark.parametrize("batch_size", [48, 40],
                             ids=["remainder", "no_remainder"])
    def test_an_epoch_is_enqueued_whole_before_anything_is_read(
            self, datasets, info_logging, cls, batch_size):
        """ISSUE 31: every launch of an epoch comes before its first
        fetch, the epochs after a call's first find their inputs made
        ahead (the same batches as drawn in their own epoch), and an
        evaluation's `eval` span holds its wait."""
        train, validation = datasets
        model = MotionModel(input_dim=9, hidden_dim=8, layer_dim=2,
                            output_dim=6, dropout=0.1)
        kwargs = {"mesh": make_mesh()} if cls is DDPTrainer else {}
        trainer = cls(model, train, batch_size=batch_size,
                      learning_rate=2.5e-3, seed=SEED,
                      validation_set=validation, test_set=validation,
                      **kwargs)
        taken = []

        def spying(program):
            def launch(params, opt_state, features, labels, idx, *extra):
                taken.append(np.asarray(idx))
                return program(params, opt_state, features, labels, idx,
                               *extra)
            return launch

        trainer._epoch_fn = spying(trainer._build_epoch_fn())
        trainer._idx_step_fn = spying(trainer._build_idx_train_step())
        trainer.train(epochs=4)
        log = program_spans(spans.log())
        train_span, = by_name(log, "train")
        epochs = by_name(log, "epoch")
        assert len(epochs) == 4

        # (i) launches first, then the waits
        remainder = batch_size == 48
        for epoch in epochs:
            inside = [e for e in log if e[PARENT] == epoch[ID]]
            launches = [e for e in inside if e[NAME].endswith(".launch")]
            fetches = [e for e in log if e[NAME].endswith(".fetch")
                       and epoch[START] <= e[START] <= epoch[END]]
            assert [e[NAME] for e in launches] == (
                ["epoch.launch"] * (2 if remainder else 1) + ["eval.launch"])
            assert [e[NAME] for e in fetches] == ["epoch.fetch", "eval.fetch"]
            assert max(e[END] for e in launches) <= min(
                e[START] for e in fetches)

        # (ii) made ahead everywhere but in the call's first epoch, inside
        # the epoch before and between its last launch and its wait
        indices = by_name(log, "epoch.indices")
        assert [e[ATTRS]["ahead"] for e in indices] == [0, 1, 1, 1]
        assert [e[PARENT] for e in indices] == [
            epochs[0][ID], epochs[0][ID], epochs[1][ID], epochs[2][ID]]
        for made, epoch in zip(indices[1:], epochs):
            launch = [e for e in log if e[PARENT] == epoch[ID]
                      and e[NAME] == "eval.launch"][0]
            fetch = [e for e in log if e[PARENT] == epoch[ID]
                     and e[NAME] == "epoch.fetch"][0]
            assert launch[END] <= made[START] and made[END] <= fetch[START]
        # ... and the batches are the ones each epoch draws by itself
        expected = []
        for epoch in range(4):
            trainer.sampler.set_epoch(epoch)
            batches = trainer._epoch_index_batches()
            full = batches[:-1] if remainder else batches
            expected.append(np.stack(full))
            expected += batches[len(full):]
        assert len(taken) == len(expected)
        for got, want in zip(taken, expected):
            np.testing.assert_array_equal(got, want)

        # (iii) an `eval` span per evaluation, its wait inside it
        evals = by_name(log, "eval")
        assert [(e[ATTRS]["split"], e[ATTRS]["epoch"]) for e in evals] == [
            ("validation", 0), ("validation", 1), ("validation", 2),
            ("validation", 3), ("validation", None)]  # the test set IS it
        assert [e[PARENT] for e in evals] == [
            e[ID] for e in epochs] + [train_span[ID]]
        waits = by_name(log, "eval.fetch")
        assert [e[PARENT] for e in waits] == [e[ID] for e in evals]
        for wait, outer in zip(waits, evals):
            assert outer[START] <= wait[START] and wait[END] <= outer[END]

    def test_compiles_are_noted_under_the_launch_that_caused_them(
            self, datasets, info_logging):
        trainer = small_trainer(datasets)
        trainer.train(epochs=1)
        log = spans.log()
        launch = by_name(log, "epoch.launch")[0]
        caused = [e for e in log if e[PARENT] == launch[ID]]
        names = {e[NAME] for e in caused}
        # traced and lowered in this process whatever the compile cache
        # holds; the compiler's own time is no span (compile_s has it)
        assert {"compile.trace", "compile.lower"} <= names
        assert names <= {"compile.trace", "compile.lower",
                         "compile.cache_read"}
        assert any(e[ATTRS].get("fun_name") == "train_epoch"
                   for e in caused if e[NAME] == "compile.trace")
        for entry in caused:
            assert entry[END] <= launch[END]

    @pytest.mark.parametrize("cls", [Trainer, DDPTrainer])
    def test_one_vocabulary_of_program_names(self, datasets, cls,
                                             info_logging):
        kwargs = {"mesh": make_mesh()} if cls is DDPTrainer else {}
        trainer = small_trainer(datasets, cls=cls, **kwargs)
        trainer.train(epochs=1)
        assert trainer._epoch_fn.__name__ == "train_epoch"
        assert trainer._idx_step_fn.__name__ == "train_step"
        assert trainer._eval_step_fn.__name__ == "eval_step"
        assert trainer._build_train_step().__name__ == "train_step"
        assert trainer._build_run_fn().__name__ == "train_run"
        # and the program names are what JAX compiles them under
        text = trainer._eval_step_fn.lower(
            trainer.params,
            trainer._prepare_batch(*trainer.validation_set[np.arange(8)]),
        ).as_text()
        assert "module @jit_eval_step" in text


    @pytest.mark.parametrize("cls", [Trainer, DDPTrainer])
    def test_programs_lower_alike_from_device_and_host_inputs(
            self, datasets, cls, info_logging):
        """The epoch's indices and keys now reach the programs as device
        arrays, placed as the programs take them in; the programs
        themselves are what the host's arrays gave (ISSUE 31: the lowered
        text is the parent's)."""
        kwargs = {"mesh": make_mesh()} if cls is DDPTrainer else {}
        trainer = small_trainer(datasets, cls=cls, **kwargs)
        features, labels = trainer._device_train_data()
        inputs = trainer._prepare_epoch(0, ahead=False)
        key_mat, key = inputs.keys
        for program, idx, keys in (
                (trainer._build_epoch_fn(), inputs.idx_mat, key_mat),
                (trainer._build_idx_train_step(), inputs.remainder, key)):
            assert isinstance(idx, jax.Array) and isinstance(keys, jax.Array)
            state = (trainer.params, trainer.opt_state, features, labels)
            from_device = program.lower(*state, idx, keys).as_text()
            from_host = program.lower(
                *state, np.asarray(idx), np.asarray(keys)).as_text()
            if cls is DDPTrainer:
                # a placed argument says so in the entry point's
                # signature, and there alone: what shard_map's in_specs
                # asked of the host's array is now the array's own
                placed = re.compile(
                    r' \{sdy\.sharding = #sdy\.sharding<@mesh, '
                    r'\[(\{[^{}]*\}(, )?)*\]>\}')
                assert len(placed.findall(from_device)) == len(
                    placed.findall(from_host)) + 2
                from_device, from_host = (
                    placed.sub("", text) for text in (from_device, from_host))
            assert from_device == from_host


# -- names on the device ----------------------------------------------------------


def name_stacks(jaxpr, outer=""):
    """``[(primitive, name stack)]`` of every equation, sub-programs
    included; a sub-program's stacks are relative to its equation's."""
    found = []
    for eqn in jaxpr.eqns:
        stack = "/".join(
            part for part in (outer, str(eqn.source_info.name_stack)) if part)
        found.append((eqn.primitive.name, stack))
        for value in eqn.params.values():
            sub = getattr(value, "jaxpr", value)
            if hasattr(sub, "eqns"):
                found += name_stacks(sub, stack)
    return found


class TestDeviceNames:
    @pytest.fixture(scope="class")
    def stacks(self, datasets):
        train, _ = datasets
        model = MotionModel(input_dim=9, hidden_dim=8, layer_dim=2,
                            output_dim=6, impl="fused", dropout=0.1)
        trainer = Trainer(model, train, batch_size=48, learning_rate=2.5e-3,
                          seed=SEED)
        features, labels = trainer._device_train_data()
        closed = jax.make_jaxpr(trainer._make_idx_train_step())(
            trainer.params, trainer.opt_state, features, labels,
            np.arange(48), jax.random.PRNGKey(0))
        return name_stacks(closed.jaxpr)

    def test_the_training_step_carries_the_scope_names(self, stacks):
        seen = {stack for _, stack in stacks}
        for scope in ("jvp(lstm_layer0/input_proj)",
                      "jvp(lstm_layer0/recurrence)",
                      "jvp(lstm_layer1/input_proj)", "jvp(dropout)",
                      "jvp(head)", "jvp(loss)",
                      "transpose(jvp(lstm_layer0/input_proj))",
                      "transpose(jvp(lstm_layer0/recurrence))/"
                      "recurrence_wgrad",
                      "transpose(jvp(head))", "optimizer"):
            assert any(stack.startswith(scope) for stack in seen), scope

    def test_both_kernels_have_a_name_inside_their_layer_s_scope(
            self, stacks):
        """The chip's compiler names a Pallas call after the innermost
        scope, which is the kernel's own ``name=``: ``lstm_fwd.N`` and
        ``lstm_bwd.N`` on the device (compiled for a described v5e by
        hand, PR 36), which ``benchmarks/trace_reduce.py`` tells apart by
        those names.  The layer's scope round the calls is what
        ``spans.classify`` reads their phase from."""
        kernels = sorted(stack for name, stack in stacks
                         if name == "pallas_call")
        assert kernels == [
            "jvp(lstm_layer0/recurrence)/lstm_fwd",
            "jvp(lstm_layer1/recurrence)/lstm_fwd",
            "transpose(jvp(lstm_layer0/recurrence))/lstm_bwd",
            "transpose(jvp(lstm_layer1/recurrence))/lstm_bwd"]

    def test_under_remat_the_kernels_keep_their_names(self):
        """``jax.checkpoint`` puts its own wrappers FIRST in the name
        stack, so a kernel's name still comes last."""
        from pytorch_distributed_rnn_tpu.ops.losses import cross_entropy_loss

        x = np.zeros((8, 16, 9), np.float32)
        y = np.zeros((8,), np.int32)
        model = MotionModel(input_dim=9, hidden_dim=8, layer_dim=1,
                            output_dim=6, impl="fused", remat=True)
        params = model.init(jax.random.PRNGKey(0))
        closed = jax.make_jaxpr(jax.grad(
            lambda p: cross_entropy_loss(model.apply(p, x), y)))(params)
        kernels = [s for n, s in name_stacks(closed.jaxpr)
                   if n == "pallas_call"]
        # forward, rematerialized forward, backward
        assert kernels == [
            "jvp(lstm_layer0/recurrence)/lstm_fwd",
            "transpose(jvp(jvp()))/rematted_computation/lstm_layer0/"
            "recurrence/lstm_fwd",
            "transpose(jvp(jvp()))/lstm_layer0/recurrence/lstm_bwd"]

    def test_gru_kernels_and_the_scan_path_are_named_too(self):
        from pytorch_distributed_rnn_tpu.ops.losses import cross_entropy_loss

        x = np.zeros((8, 16, 9), np.float32)
        y = np.zeros((8,), np.int32)
        for impl, expected in (
            ("fused", ["jvp(gru_layer0/recurrence)/gru_fwd",
                       "transpose(jvp(gru_layer0/recurrence))/gru_bwd"]),
            ("scan", []),
        ):
            model = MotionModel(input_dim=9, hidden_dim=8, layer_dim=1,
                                output_dim=6, cell="gru", impl=impl)
            params = model.init(jax.random.PRNGKey(0))
            closed = jax.make_jaxpr(jax.grad(
                lambda p: cross_entropy_loss(model.apply(p, x), y)))(params)
            stacks = name_stacks(closed.jaxpr)
            assert sorted(s for n, s in stacks
                          if n == "pallas_call") == expected
            assert any(s.startswith("jvp(gru_layer0/recurrence)")
                       for _, s in stacks)


# -- in the profiler's trace -------------------------------------------------------


def host_events(trace_dir):
    """``{thread line: [(name, start_ns, end_ns)]}`` of a CPU trace."""
    from jax.profiler import ProfileData

    path, = trace_dir.glob("plugins/profile/*/*.xplane.pb")
    lines = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            lines[line.name] = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for e in line.events]
    return lines


def assert_spans_nest_in_train_on_one_thread(trace_dir):
    lines = host_events(trace_dir)
    driving = [events for events in lines.values()
               if any(name == "train" for name, _, _ in events)]
    assert len(driving) == 1
    events, = driving
    trains = [(s, e) for name, s, e in events if name == "train"]
    children = [(name, s, e) for name, s, e in events
                if name.startswith(("epoch", "eval"))]
    assert {"epoch", "epoch.indices", "epoch.launch", "epoch.fetch",
            "eval", "eval.launch", "eval.fetch"} <= {
        name for name, _, _ in children}
    for name, start, end in children:
        assert any(s <= start and end <= e for s, e in trains), name
    return events


class TestProfilerTrace:
    def test_spans_lie_on_the_driving_thread_inside_train(
            self, datasets, info_logging, tmp_path):
        trainer = small_trainer(datasets)
        trainer.train(epochs=1)  # compile outside the trace
        with jax.profiler.trace(str(tmp_path)):
            trainer.train(epochs=2)
        events = assert_spans_nest_in_train_on_one_thread(tmp_path)
        assert sum(name == "epoch" for name, _, _ in events) == 2

    def test_profile_steps_leaves_the_scan_path_alone(
            self, datasets, info_logging, tmp_path):
        """--profile-steps 0:2 --profile DIR at INFO: the capture opens
        before the epoch that holds steps 0 and 1 and closes after its
        fetches; the epoch is still one scanned program."""
        capture = StepTraceCapture(tmp_path, 0, 2)
        trainer = small_trainer(datasets, profile_steps=capture)
        trainer.train(epochs=2)
        assert [e[ATTRS]["path"] for e in by_name(spans.log(), "epoch")] == [
            "scan", "scan"]
        assert trainer._steps_done == 10
        assert capture.close()["captured"] is True
        events = host_events(tmp_path)
        names = {name for line in events.values() for name, _, _ in line}
        # the first epoch and nothing the second runs: its two programs
        # and its one wait, and between them the second epoch's inputs,
        # which are made behind the first's programs
        assert {"epoch.launch", "epoch.fetch"} <= names
        driving, = [line for line in events.values()
                    if any(name == "epoch.launch" for name, _, _ in line)]
        counts = {name: sum(n == name for n, _, _ in driving)
                  for name in ("epoch.launch", "epoch.fetch",
                               "epoch.indices")}
        assert counts == {"epoch.launch": 2, "epoch.fetch": 1,
                          "epoch.indices": 2}

    def test_a_capture_starts_at_the_first_epoch_holding_its_steps(
            self, tmp_path, monkeypatch):
        started, stopped = [], []
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda *a, **k: started.append(a))
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            lambda: stopped.append(1))
        capture = StepTraceCapture(tmp_path, 7, 12)
        capture.on_step_start(0, count=5)  # steps 0..4
        capture.on_step_end(4)
        assert not started
        capture.on_step_start(5, count=5)  # steps 5..9 hold step 7
        assert len(started) == 1
        capture.on_step_end(9)
        assert not stopped  # step 11 is still to come
        capture.on_step_start(10, count=5)
        capture.on_step_end(14)
        assert len(started) == 1 and len(stopped) == 1
        capture.on_step_start(15, count=5)
        assert len(started) == 1  # one capture a run
