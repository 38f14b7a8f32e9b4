"""Device scopes (obs/spans.py): the names a ``scope`` remembers, the
registration of a launched program at the launch that compiled it and at no
other, the instruction-to-``op_name`` table read off compiled text, the one
rule from an instruction to its phase and scope, and the join of a CPU
rehearsal's trace with that table (ISSUE 36)."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_rnn_tpu.data import MotionDataset
from pytorch_distributed_rnn_tpu.data.synthetic import generate_har_arrays
from pytorch_distributed_rnn_tpu.models import MotionModel
from pytorch_distributed_rnn_tpu.obs import spans
from pytorch_distributed_rnn_tpu.training import Trainer, base

SEED = 123456789
BODY = "jit(train_epoch)/while/body/closed_call/"
SCOPES = frozenset({"experts", "moe", "optimizer", "grad_reduce", "head",
                    "gqa", "rope"})


@pytest.fixture(autouse=True)
def fresh_tables(monkeypatch):
    """Each test sees no registered program and an empty table."""
    monkeypatch.setattr(spans, "_registered", [])
    monkeypatch.setattr(spans, "_op_names", {})
    spans.clear()


@pytest.fixture
def info_logging():
    root = logging.getLogger()
    previous = root.level
    root.setLevel(logging.INFO)
    yield
    root.setLevel(previous)


@pytest.fixture(scope="module")
def datasets():
    x, y = generate_har_arrays(200, seq_length=16, seed=0)
    return MotionDataset(x, y), MotionDataset(x[:48], y[:48])


def small_trainer(datasets):
    train, validation = datasets
    model = MotionModel(input_dim=9, hidden_dim=8, layer_dim=2, output_dim=6,
                        dropout=0.1)
    # 200 windows at batch 48: four full steps and one of 8
    return Trainer(model, train, batch_size=48, learning_rate=2.5e-3,
                   seed=SEED, validation_set=validation, test_set=validation)


class FakeCompiled:
    """What ``program_scopes`` asks of a jitted callable."""

    def __init__(self, text):
        self.text, self.lowered = text, 0

    def lower(self, *args):
        self.lowered += 1
        return self

    def compile(self):
        return self

    def as_text(self):
        return self.text


# -- registration --------------------------------------------------------------

class TestRegistration:
    def test_the_launch_that_compiles_registers_and_no_other_does(
            self, datasets, info_logging, monkeypatch):
        """Warm-up registers every program of the scan path once; a second
        call of the same shapes registers nothing and turns no argument
        into a shape (the window's launches walk no tree)."""
        walked = []
        abstract = spans._abstract
        monkeypatch.setattr(
            spans, "_abstract", lambda v: walked.append(1) or abstract(v))
        trainer = small_trainer(datasets)
        trainer.train(epochs=1)
        first = [jitted for jitted, _ in spans._registered]
        # dropout keys, the scanned epoch, the remainder's step, and the
        # evaluation step (validation and test set are one shape here)
        assert first == [trainer._key_fn, trainer._epoch_fn,
                         trainer._idx_step_fn, trainer._eval_step_fn]
        assert walked
        del walked[:]
        trainer.train(epochs=2)
        assert [j for j, _ in spans._registered] == first
        assert not walked

    def test_a_registered_argument_is_its_shape_donated_or_not(self):
        kept = jnp.ones((4, 3))
        donated = jnp.ones((2,), jnp.int32)
        donated.delete()
        spans.register_program("f", ({"w": kept}, donated, np.uint32(7), 5))
        (_, (tree, gone, scalar, static)), = spans._registered
        assert tree["w"] == jax.ShapeDtypeStruct((4, 3), jnp.float32)
        assert gone == jax.ShapeDtypeStruct((2,), jnp.int32)
        assert scalar == jax.ShapeDtypeStruct((), np.uint32)
        assert static == 5  # a static argument goes as it is

    def test_a_launch_that_compiled_nothing_tests_one_flag(self, monkeypatch):
        class Launch:
            reads = 0

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return None

            @property
            def compiled(self):
                self.reads += 1
                return False

        registered = []
        monkeypatch.setattr(
            spans, "register_program", lambda *a: registered.append(a))
        launch = Launch()
        assert base._launch(launch, lambda a, b: a + b, 1, 2) == 3
        assert launch.reads == 1 and not registered

    def test_compile_notes_mark_the_open_span_and_only_it(self):
        with spans.span("epoch") as epoch:
            with spans.span("epoch.launch") as launch:
                spans.note_finished("compile.lower", 0.1, fun_name="f")
            with spans.span("epoch.launch") as warm:
                pass
            with spans.span("epoch.fetch") as fetch:
                spans.note_finished("host.wait", 0.1)
        assert launch.compiled is True
        assert (epoch.compiled, warm.compiled, fetch.compiled) == (
            False, False, False)


# -- the compile cache's key ---------------------------------------------------------

class TestLayoutStamp:
    SOURCE = """
from pytorch_distributed_rnn_tpu.obs import spans

def part(x):{comment}
    with spans.scope("experts"):
        y = x * 2
    return y{tail}

def other(x):
    return x{other}
"""

    def digest(self, tmp_path, **edits):
        edits = {"comment": "", "tail": "", "other": "", **edits}
        (tmp_path / "model.py").write_text(self.SOURCE.format(**edits))
        (tmp_path / "plain.py").write_text("def f():\n    return 1\n")
        return spans.layout_digest(tmp_path)

    def test_the_digest_follows_the_functions_that_enter_a_scope(
            self, tmp_path):
        base = self.digest(tmp_path)
        assert len(base) == 12 and base == self.digest(tmp_path)
        # a comment, another function: the same layout
        assert self.digest(tmp_path, comment="  # why") == base
        assert self.digest(tmp_path, other=" + 1") == base
        # the function with the scope changed: what lies under the scope
        # may have, so its programs compile afresh
        assert self.digest(tmp_path, tail=" + 1") != base

    def test_every_launched_program_carries_the_digest(self, datasets):
        """The persistent cache keys a program without its debug
        information (the ``op_name`` paths): the stamp is what keeps an
        executable compiled under other scope sites from being served."""
        trainer = small_trainer(datasets)
        features, labels = trainer._device_train_data()
        batch = trainer._prepare_batch(*datasets[1][np.arange(48)])
        step = jax.jit(trainer._make_idx_train_step()).lower(
            trainer.params, trainer.opt_state, features, labels,
            np.arange(48), jax.random.PRNGKey(0))
        evaluation = trainer._build_eval_step().lower(trainer.params, batch)
        stamp = f'pdrnn_scope_layout = "{spans.layout_digest()}"'
        assert step.as_text().count(stamp) == 1
        assert evaluation.as_text().count(stamp) == 1

    def test_the_stamp_leaves_the_value_alone(self):
        x = jnp.float32(0.1) * 3
        assert spans.stamp(x) == x
        assert jax.grad(spans.stamp)(x) == 1.0


# -- the table -------------------------------------------------------------------

def text(program, *lines):
    return "\n".join([f"HloModule {program}, is_scheduled=true", "",
                      "ENTRY %main.1 (p: f32[4]) -> f32[4] {", *lines, "}"])


def instruction(name, opcode, operand, op_name=None):
    metadata = f', metadata={{op_name="{op_name}"}}' if op_name else ""
    return f"  %{name} = f32[4]{{0:T(128)}} {opcode}(%{operand}){metadata}"


class TestProgramScopes:
    def test_a_fused_instruction_reads_under_its_scope_and_once(
            self, monkeypatch):
        def f(w, x):
            with spans.scope("experts"):
                y = jnp.tanh(x @ w) * 2.0 + 1.0
            with spans.scope("head"):
                return jnp.sum(y * y, axis=1)

        jitted = jax.jit(f)
        args = (jnp.ones((16, 16)), jnp.ones((8, 16)))
        jitted(*args)
        spans.register_program(jitted, args)
        assert {"experts", "head"} <= spans.scope_names()
        parsed = []
        parse = spans.hlo_op_names
        monkeypatch.setattr(
            spans, "hlo_op_names", lambda t: parsed.append(1) or parse(t))
        table = spans.program_scopes()
        assert list(table) == ["jit_f"]
        fused = {name: op_name for name, op_name in table["jit_f"].items()
                 if "fusion" in name and op_name}
        assert fused  # XLA fused the elementwise tails
        found = {spans.classify("jit_f", name, op_name)[1]
                 for name, op_name in fused.items()}
        assert found <= {"experts", "head"} and found
        # memoised: asked again, nothing is lowered or parsed
        assert spans.program_scopes() is table and parsed == [1]

    def test_two_compilations_under_one_name_keep_what_agrees(self):
        first = text(
            "jit_eval_step",
            instruction("fusion.1", "fusion", "p", "jit(eval_step)/head/dot"),
            instruction("fusion.2", "fusion", "fusion.1",
                        "jit(eval_step)/head/add"),
            instruction("copy.3", "copy", "p"),
            instruction("copy.4", "copy", "fusion.2"))
        second = text(
            "jit_eval_step",
            instruction("fusion.1", "fusion", "p", "jit(eval_step)/head/dot"),
            instruction("fusion.2", "fusion", "fusion.1",
                        "jit(eval_step)/loss/sub"),
            instruction("copy.3", "copy", "p", "jit(eval_step)/transpose"),
            instruction("fusion.5", "fusion", "p", "jit(eval_step)/loss/exp"))
        for compiled in (first, second):
            spans.register_program(FakeCompiled(compiled), ())
        assert spans.program_scopes() == {"jit_eval_step": {
            "fusion.1": "jit(eval_step)/head/dot",
            "fusion.2": spans.AMBIGUOUS,
            # no op_name in one compilation, one in the other
            "copy.3": spans.AMBIGUOUS,
            # XLA's own copy takes its operand's: the data it moves
            "copy.4": "jit(eval_step)/head/add",
            "fusion.5": "jit(eval_step)/loss/exp",
        }}

    def test_what_jax_makes_outside_every_name_reads_under_its_caller(self):
        """A ``lax.cond`` branch returns zeros in place of the other
        branch's residuals; JAX makes them under no name, so their path
        stops short of the ``conditional`` that runs them (65,536 x 1,536
        floats a layer in the LFM2 cell: 4 % of its device time)."""
        body = "jit(train_epoch)/while/body/closed_call"
        cond = body + "/jvp(moe)/experts/cond"
        compiled = "\n".join([
            "HloModule jit_train_epoch, is_scheduled=true", "",
            "%branch_1 (p: f32[4]) -> f32[4] {",
            instruction("gather.1", "gather", "p",
                        cond + "/branch_1_fun/gather"),
            instruction("broadcast.2", "broadcast", "constant.9", body),
            "}", "",
            "%fused (p: f32[4]) -> f32[4] {",
            instruction("multiply.3", "multiply", "p", body + "/jvp()/mul"),
            "}", "",
            "ENTRY %main.1 (p: f32[4]) -> f32[4] {",
            "  %conditional.4 = f32[4]{0} conditional(%p, %p, %p), "
            "branch_computations={%branch_0, %branch_1}, "
            f'metadata={{op_name="{cond}"}}',
            "  %fusion.5 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused, "
            f'metadata={{op_name="{body}/jvp(gqa)/mul"}}',
            "}"])
        program, names = spans.hlo_op_names(compiled)
        assert program == "jit_train_epoch"
        assert names["gather.1"] == cond + "/branch_1_fun/gather"
        assert names["broadcast.2"] == f"{cond} > {body}"
        # a fusion's computation never runs as instructions of its own
        assert names["multiply.3"] == body + "/jvp()/mul"
        assert spans.classify(
            program, "broadcast.2 broadcast f32[65536,1536]",
            names["broadcast.2"], SCOPES) == ("forward", "experts")

    def test_a_later_registration_joins_the_table(self):
        spans.register_program(FakeCompiled(text(
            "jit_train_epoch", instruction("a.1", "add", "p", "x/add"))), ())
        assert list(spans.program_scopes()) == ["jit_train_epoch"]
        late = FakeCompiled(text(
            "jit_eval_step", instruction("b.1", "add", "p", "y/add")))
        spans.register_program(late, ())
        assert list(spans.program_scopes()) == [
            "jit_train_epoch", "jit_eval_step"]
        spans.program_scopes()
        assert late.lowered == 1

    def test_the_written_table_carries_the_scope_names(self, tmp_path):
        import json

        with spans.scope("experts"):
            pass
        spans.register_program(FakeCompiled(text(
            "jit_train_epoch",
            instruction("a.1", "add", "p", "jvp(experts)/add"),
            instruction("copy.2", "copy", "p"))), ())
        spans.write_program_scopes(tmp_path / "program_scopes.json")
        written = json.loads((tmp_path / "program_scopes.json").read_text())
        assert "experts" in written["scopes"]
        assert written["programs"] == {
            "jit_train_epoch": {"a.1": "jvp(experts)/add"}}


# -- the rule ----------------------------------------------------------------------

CASES = [
    # op_name paths as a v5e's compiler printed them (PR 35's LFM2 program)
    ("jit_train_epoch", "fusion.12 fusion:kLoop f32[16384,2048]",
     BODY + "jvp(experts)/cond/branch_1_fun/jit(_gmm)/gather",
     ("forward", "experts")),
    ("jit_train_epoch", "fusion.13 fusion:kLoop f32[16384,2048]",
     BODY + "transpose(jvp(jvp()))/checkpoint/rematted_computation/experts/"
     "cond/branch_1_fun/gather", ("recompute", "experts")),
    ("jit_train_epoch", "fusion.14 fusion:kOutput f32[16384,2048]",
     BODY + "transpose(jvp(jvp()))/checkpoint/experts/cond/branch_1_fun/"
     "scatter-add", ("backward", "experts")),
    ("jit_train_step", "fusion.15 fusion:kLoop f32[2048]",
     "jit(train_step)/optimizer/add", ("optimizer", "optimizer")),
    ("jit_train_epoch", "all-reduce.2 all-reduce f32[14150]",
     BODY + "grad_reduce/psum", ("optimizer", "grad_reduce")),
    # the deepest scope wins
    ("jit_train_epoch", "fusion.16 fusion:kLoop f32[2,8192,32,64]",
     BODY + "jvp(gqa)/rope/mul", ("forward", "rope")),
    ("jit_train_epoch", "fusion.17 fusion:kLoop f32[2,8192,2048]",
     BODY + "jvp(moe)/experts/gather", ("forward", "experts")),
    ("jit_train_epoch", "fusion.18 fusion:kLoop f32[2,8192,2048]",
     BODY + "transpose(jvp(transpose(jvp(head))))/mul",
     ("backward", "head")),
    # evaluation is a phase of its own whatever the path says
    ("jit_eval_step", "fusion.19 fusion:kLoop f32[2,8192,2048]",
     "jit(eval_step)/experts/gather", ("eval", "experts")),
    # a Pallas kernel keeps its phase and is classed by its own name
    ("jit_train_epoch", "moe_gmm.12 tpu_custom_call f32[32768,1536]",
     BODY + "jvp(experts)/cond/branch_1_fun/jit(_gmm)/pallas_call",
     ("forward", "kernel moe_gmm")),
    ("jit_train_epoch", "gqa_flash_dkv.3 tpu_custom_call f32[2,32,8192,64]",
     BODY + "transpose(jvp(jvp()))/checkpoint/gqa/gqa_flash_dkv/pallas_call",
     ("backward", "kernel gqa_flash_dkv")),
    ("jit_eval_step", "lstm_fwd.5 tpu_custom_call f32[128,4464,32]", None,
     ("eval", "kernel lstm_fwd")),
    # a copy XLA inserted from data no scope made; another program's work
    ("jit_train_epoch", "copy-done.4 copy-done f32[65536]", None,
     (None, spans.XLA_COPY)),
    ("jit_train_epoch", "fusion.20 fusion:kLoop f32[8]", None,
     (None, spans.NO_SCOPE)),
    ("jit_train_epoch", "fusion.21 fusion:kLoop f32[2,8192,2048]",
     BODY + "jvp()/mul", ("forward", spans.NO_SCOPE)),
    ("jit_eval_step", "fusion.22 fusion:kLoop f32[8]", spans.AMBIGUOUS,
     ("eval", spans.AMBIGUOUS)),
    ("jit_train_epoch", "fusion.23 fusion:kLoop f32[8]", spans.AMBIGUOUS,
     (None, spans.AMBIGUOUS)),
    ("jit_dropout_keys", "fusion.24 fusion:kLoop u32[4,2]",
     "jit(dropout_keys)/vmap(fold_in)/threefry2x32", (None, spans.NO_SCOPE)),
]


@pytest.mark.parametrize(
    "program, instruction_, op_name, expected", CASES,
    ids=[f"{c[3][0]}-{c[3][1]}-{i}" for i, c in enumerate(CASES)])
def test_classify(program, instruction_, op_name, expected):
    assert spans.classify(
        program, instruction_, op_name, SCOPES) == expected


def test_classify_knows_the_scopes_the_program_entered():
    with spans.scope("lstm_layer7/input_proj"):
        pass
    assert {"lstm_layer7", "input_proj"} <= spans.scope_names()
    assert spans.classify(
        "jit_train_step", "fusion.1",
        "jit(train_step)/jvp(lstm_layer7/input_proj)/dot_general") == (
            "forward", "input_proj")


# -- a CPU rehearsal ------------------------------------------------------------------

def test_a_rehearsal_s_classes_sum_to_its_instructions_time(
        datasets, info_logging, tmp_path):
    """One traced ``train`` call on the CPU, reduced as the benchmark
    reduces it and joined with the table: every instruction lands in
    exactly one class, so the classes add up to the instructions' self
    time.  A CPU trace names no program, so an instruction goes to the
    first registered program that holds its name (the evaluation step's
    mostly read as a training program's: only a device trace tells them
    apart); scopes and training phases are there."""
    from benchmarks import scope_time, trace_reduce

    trainer = small_trainer(datasets)
    trainer.train(epochs=1)  # compile, and register, outside the trace
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.train_call"):
            trainer.train(epochs=2)
    trace, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    reduced = trace_reduce.reduce_trace(trace)
    rows = scope_time.classified(
        reduced, spans.program_scopes(), spans.classify)
    total = sum(row["self_s"] for row in reduced["ops"].values())
    by_class = {}
    for _, _, phase, scope, seconds in rows:
        by_class[phase, scope] = by_class.get((phase, scope), 0.0) + seconds
    assert sum(by_class.values()) == pytest.approx(total, rel=1e-9)
    # the CPU's profiler shows few instructions (a scan as one event),
    # enough to see the join at work: a phase and a named scope
    assert "forward" in {phase for phase, _ in by_class}
    assert any(not scope.startswith("(") for _, scope in by_class)
