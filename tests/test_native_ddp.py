"""Process-per-rank DDP over the native TCP collectives.

The reference's core invariants, checked across real OS processes:
rank parity (identical final params on every rank, reference README.md:9)
and global-batch invariance (N-rank training matches single-process
training with the same global batch and seed).
"""

import json
import re

import numpy as np
import pytest

from pytorch_distributed_rnn_tpu.data.synthetic import (
    write_synthetic_har_dataset,
)
from pytorch_distributed_rnn_tpu.training.native_ddp import (
    NativeDDPTrainer,
    _wire_dtype,
    launch_world,
)

PERF_RE = re.compile(r"(\d+): Memory Usage: ([\d.]+), Training Duration: ([\d.]+)")
PARAM_RE = re.compile(r"(\d+): parameters: (-?[\d.]+)")


def _dataset(tmp_path):
    data_dir = tmp_path / "data"
    write_synthetic_har_dataset(data_dir, num_train=128, num_test=16,
                                seq_length=32)
    return data_dir


def _args(tmp_path, data_dir, extra=()):
    return [
        "--epochs", "2", "--seed", "123456789",
        "--dataset-path", str(data_dir),
        "--checkpoint-directory", str(tmp_path / "models"),
        "--output-path", str(tmp_path / "cache"),
        "--batch-size", "48", "--no-validation",
        "--hidden-units", "8", "--stacked-layer", "1",
        *extra,
    ]


# ---------------------------------------------------------------------------
# Wire contract (in-process): what actually rides the TCP ring, per step
# ---------------------------------------------------------------------------


class _RecordingComm:
    """Single-process stand-in for the C++ ring that records every
    collective call as ``(method, dtype name, nbytes)``.  Reduction math
    is identity (the other ranks' contributions don't matter for the
    wire-shape contract pinned here)."""

    def __init__(self, world_size):
        self.rank = 0
        self.world_size = world_size
        self.calls = []

    def _rec(self, method, data):
        self.calls.append((method, np.dtype(data.dtype).name, data.nbytes))

    def broadcast(self, data, root=0):
        self._rec("broadcast", data)
        return data

    def allreduce(self, data, op="sum"):
        self._rec("allreduce", data)
        return data

    def reduce_scatter(self, data, op="sum"):
        self._rec("reduce_scatter", data)
        return data[: data.shape[0] // self.world_size].copy()

    def allgather(self, data):
        self._rec("allgather", data)
        return np.stack([data] * self.world_size)


class TestWireContract:
    def test_wire_dtype_rides_native_dtype_when_ring_supports_it(self):
        import ml_dtypes

        # the ring's supported dtypes pass through untouched...
        assert _wire_dtype(np.float32) == np.dtype(np.float32)
        assert _wire_dtype(np.float64) == np.dtype(np.float64)
        assert _wire_dtype(ml_dtypes.bfloat16) == np.dtype(ml_dtypes.bfloat16)
        # ...everything else falls back to the old f32 upcast
        assert _wire_dtype(np.float16) == np.dtype(np.float32)
        assert _wire_dtype(np.int32) == np.dtype(np.float32)

    def _train(self, sharded, world=4):
        from pytorch_distributed_rnn_tpu.data import MotionDataset
        from pytorch_distributed_rnn_tpu.data.synthetic import (
            generate_har_arrays,
        )
        from pytorch_distributed_rnn_tpu.models import MotionModel

        comm = _RecordingComm(world)
        trainer = NativeDDPTrainer(
            comm=comm,
            model=MotionModel(input_dim=9, hidden_dim=8, layer_dim=1,
                              output_dim=6),
            training_set=MotionDataset(
                *generate_har_arrays(96, seq_length=12, seed=0)
            ),
            batch_size=48,
            learning_rate=1e-3,
            seed=123456789,
            sharded_update=sharded,
        )
        trainer.train(epochs=1)
        return trainer, comm

    def test_sharded_step_wire_bytes_are_reduce_scatter_plus_allgather(self):
        """Satellite regression pin: per step the sharded flavor moves one
        padded gradient vector DOWN (reduce-scatter) and one param shard
        UP (allgather) - total (1 + 1/world) x params - instead of the
        replicated flavor's full allreduce, and everything rides the
        params' native dtype (f32 here, 4 B/elem - no silent upcast)."""
        trainer, comm = self._train(sharded=True)
        su = trainer._shard_update
        # the motion model's 662 params don't divide a 4-rank world, so
        # this also pins the pad-to-equal-shards path
        assert su.size % comm.world_size != 0
        assert su.padded == su.shard * comm.world_size > su.size

        bcasts = [c for c in comm.calls if c[0] == "broadcast"]
        steps = [c for c in comm.calls if c[0] != "broadcast"]
        # exactly one construction-time param broadcast, full vector
        assert bcasts == [("broadcast", "float32", su.size * 4)]
        # per step: one reduce-scatter (padded grads) + one allgather
        # (this rank's param shard); never an allreduce, never f64
        assert steps, "no training steps recorded"
        assert steps == [
            ("reduce_scatter", "float32", su.padded * 4),
            ("allgather", "float32", su.shard * 4),
        ] * (len(steps) // 2)

    def test_replicated_step_wire_bytes_are_one_full_allreduce(self):
        trainer, comm = self._train(sharded=False)
        assert trainer._shard_update is None
        size = 662  # motion model 9/8/1/6 parameter count
        bcasts = [c for c in comm.calls if c[0] == "broadcast"]
        steps = [c for c in comm.calls if c[0] != "broadcast"]
        assert bcasts == [("broadcast", "float32", size * 4)]
        assert steps == [("allreduce", "float32", size * 4)] * len(steps)
        # both flavors run the same number of optimizer steps
        assert len(steps) == 2


@pytest.mark.slow
def test_two_rank_world_trains_and_logs_perf_lines(tmp_path):
    data_dir = _dataset(tmp_path)
    results = launch_world(2, _args(tmp_path, data_dir),
                           master_port=29561, cwd=tmp_path)
    assert len(results) == 2
    # every rank emits its own rank-tagged perf line (reference contract)
    ranks_seen = set()
    for code, out, err in results:
        m = PERF_RE.search(err)
        assert m, err[-1500:]
        ranks_seen.add(int(m.group(1)))
    assert ranks_seen == {0, 1}
    # rank parity: the final parameter sum is IDENTICAL on every rank
    # (reference README.md:9 success criterion)
    sums = {}
    for code, out, err in results:
        m = PARAM_RE.search(err)
        assert m, err[-1500:]
        sums[int(m.group(1))] = m.group(2)
    assert sums[0] == sums[1], sums
    # rank 0 wrote history.json with 2 epochs of losses
    history = json.loads((tmp_path / "history.json").read_text())
    assert len(history["train_history"]) == 2


@pytest.mark.slow
def test_global_batch_invariance_across_world_sizes(tmp_path):
    """2-rank training lands on (nearly) the same parameters as the
    single-process run: the strided shards of one global permutation make
    every global batch the same example SET, so the averaged gradients
    agree up to float summation order (the reference's determinism
    harness, fabfile.py:54-58).  Rank-0's logged loss is its LOCAL
    half-batch mean (reference behavior), so histories are compared
    loosely and parameters tightly."""
    data_dir = _dataset(tmp_path)

    one = tmp_path / "w1"
    two = tmp_path / "w2"
    one.mkdir()
    two.mkdir()
    r1 = launch_world(1, _args(one, data_dir), master_port=29562, cwd=one)
    r2 = launch_world(2, _args(two, data_dir), master_port=29563, cwd=two)

    p1 = float(PARAM_RE.search(r1[0][2]).group(2))
    p2 = float(PARAM_RE.search(r2[0][2]).group(2))
    np.testing.assert_allclose(p1, p2, rtol=1e-4)

    h1 = json.loads((one / "history.json").read_text())["train_history"]
    h2 = json.loads((two / "history.json").read_text())["train_history"]
    np.testing.assert_allclose(h1, h2, rtol=0.05)


@pytest.mark.slow
def test_char_family_two_rank_world(tmp_path):
    """The char-LM over the C++ TCP transport (the
    strategy that rides the transport never saw the family that stresses
    it): 2-rank world trains with rank parity and per-rank perf lines."""
    (tmp_path / "corpus.txt").write_bytes(bytes(range(256)) * 40)
    args = [
        "--epochs", "2", "--seed", "123456789",
        "--dataset-path", str(tmp_path),
        "--checkpoint-directory", str(tmp_path / "models"),
        "--batch-size", "32", "--no-validation",
        "--hidden-units", "8", "--stacked-layer", "1",
        "--dropout", "0", "--model", "char", "--seq-length", "15",
    ]
    results = launch_world(2, args, master_port=29567, cwd=tmp_path)
    sums = {}
    for code, out, err in results:
        assert PERF_RE.search(err), err[-1500:]
        m = PARAM_RE.search(err)
        sums[int(m.group(1))] = m.group(2)
    assert sums[0] == sums[1], sums
    history = json.loads((tmp_path / "history.json").read_text())
    assert len(history["train_history"]) == 2
    assert history["train_history"][-1] < history["train_history"][0]


def _param_sums(results):
    """rank -> the rank-parity observable (10-decimal param sum string)."""
    sums = {}
    for code, out, err in results:
        m = PARAM_RE.search(err)
        assert m, err[-1500:]
        sums[int(m.group(1))] = m.group(2)
    return sums


@pytest.mark.slow
def test_sharded_update_matches_replicated_across_ranks(tmp_path):
    """The sharded weight update (2004.13336) on the real TCP transport:
    default (sharded) and --no-sharded-update runs land on IDENTICAL
    final parameters on every rank - the C++ reduce-scatter reuses the
    allreduce's accumulation order, so the flavors are bitwise twins."""
    data_dir = _dataset(tmp_path)
    sh_dir = tmp_path / "sharded"
    rep_dir = tmp_path / "replicated"
    sh_dir.mkdir()
    rep_dir.mkdir()
    r_sh = launch_world(2, _args(sh_dir, data_dir),
                        master_port=29571, cwd=sh_dir)
    r_rep = launch_world(2, _args(rep_dir, data_dir,
                                  extra=("--no-sharded-update",)),
                         master_port=29572, cwd=rep_dir)
    sh = _param_sums(r_sh)
    rep = _param_sums(r_rep)
    # rank parity within each flavor AND parity across flavors
    assert sh[0] == sh[1] == rep[0] == rep[1], (sh, rep)
    # the loss histories agree too (rank-0 local means, same batches)
    h_sh = json.loads((sh_dir / "history.json").read_text())
    h_rep = json.loads((rep_dir / "history.json").read_text())
    assert h_sh["train_history"] == h_rep["train_history"]


@pytest.mark.slow
@pytest.mark.chaos
def test_sharded_world_kill_then_resume_keeps_rank_parity(
    tmp_path, monkeypatch
):
    """Chaos drill on the sharded ring: every rank SIGKILLed at the start
    of epoch 1 (after the epoch-0 checkpoint's collective opt-state
    gather), then a --resume auto relaunch restores the UNSHARDED
    checkpoint layout into per-rank shards and finishes with all ranks
    bitwise-identical to the uninterrupted run."""
    # the suite's persistent XLA compile cache flakily SEGFAULTS resumed
    # runs on XLA:CPU (see test_resilience.TestKillAndResumeCLI) - the
    # chaos subprocesses compile fresh instead
    monkeypatch.setenv("JAX_ENABLE_COMPILATION_CACHE", "0")
    data_dir = _dataset(tmp_path)
    ref_dir = tmp_path / "ref"
    chaos_dir = tmp_path / "chaos"
    ref_dir.mkdir()
    chaos_dir.mkdir()

    # uninterrupted 2-epoch reference
    r_ref = launch_world(
        2, _args(ref_dir, data_dir, extra=("--checkpoint-every", "1")),
        master_port=29573, cwd=ref_dir,
    )
    ref = _param_sums(r_ref)

    # chaos run: the unqualified kill fires on EVERY rank, so the whole
    # world dies (rc -9) and spawn_world reports the failed ranks
    with pytest.raises(RuntimeError, match="world ranks failed"):
        launch_world(
            2,
            _args(chaos_dir, data_dir,
                  extra=("--checkpoint-every", "1",
                         "--faults", "epoch:1:kill")),
            master_port=29574, cwd=chaos_dir,
        )
    ckpts = sorted(p.name for p in (chaos_dir / "models").iterdir())
    assert "checkpoint-epoch-1.ckpt" in ckpts, ckpts

    # relaunch with --resume auto (no faults): every rank restores the
    # shared epoch-1 checkpoint, re-shards the opt state, and completes
    r_res = launch_world(
        2,
        _args(chaos_dir, data_dir,
              extra=("--checkpoint-every", "1", "--resume", "auto")),
        master_port=29575, cwd=chaos_dir,
    )
    res = _param_sums(r_res)
    assert res[0] == res[1], res
    # resumed world matches the uninterrupted one exactly (checkpoints
    # store exact host arrays; the host loop replays the same batches)
    assert res[0] == ref[0], (res, ref)
    history = json.loads((chaos_dir / "history.json").read_text())
    assert len(history["train_history"]) == 1  # only epoch 1 remained


@pytest.mark.slow
def test_attention_family_two_rank_world(tmp_path):
    data_dir = _dataset(tmp_path)
    results = launch_world(
        2,
        _args(tmp_path, data_dir,
              extra=("--model", "attention", "--dropout", "0")),
        master_port=29568, cwd=tmp_path,
    )
    sums = {}
    for code, out, err in results:
        m = PARAM_RE.search(err)
        assert m, err[-1500:]
        sums[int(m.group(1))] = m.group(2)
    assert sums[0] == sums[1], sums


@pytest.mark.slow
def test_moe_family_two_rank_world(tmp_path):
    """Dense-exact MoE over the C++ TCP transport: expert gradients are
    ordinary pytree leaves on the ring allreduce, so the family gets the
    same rank-parity guarantee as the others (the last strategy x family
    matrix hole - moe was rejected here before r3)."""
    data_dir = _dataset(tmp_path)
    results = launch_world(
        2,
        _args(tmp_path, data_dir,
              extra=("--model", "moe", "--dropout", "0")),
        master_port=29569, cwd=tmp_path,
    )
    sums = {}
    for code, out, err in results:
        m = PARAM_RE.search(err)
        assert m, err[-1500:]
        sums[int(m.group(1))] = m.group(2)
    assert sums[0] == sums[1], sums
    history = json.loads((tmp_path / "history.json").read_text())
    assert len(history["train_history"]) == 2
