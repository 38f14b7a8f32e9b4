"""Sharded weight update (PAPERS.md 2004.13336): the correctness bar.

The contract pinned here: reduce-scatter + 1/world optimizer apply +
allgather is BITWISE-identical to allreduce + replicated apply at every
world size - divisible param counts or not - and checkpoints always
carry the unsharded ``optimizer.init(params)`` layout, so the flag never
leaks into the on-disk format.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from pytorch_distributed_rnn_tpu.data import MotionDataset
from pytorch_distributed_rnn_tpu.data.synthetic import generate_har_arrays
from pytorch_distributed_rnn_tpu.models import MotionModel
from pytorch_distributed_rnn_tpu.parallel import make_mesh
from pytorch_distributed_rnn_tpu.parallel.sharded_update import ShardedUpdate
from pytorch_distributed_rnn_tpu.training import DDPTrainer, HorovodTrainer, Trainer

SEED = 123456789


def small_model():
    return MotionModel(input_dim=9, hidden_dim=8, layer_dim=1, output_dim=6)


@pytest.fixture(scope="module")
def motion_set():
    X, y = generate_har_arrays(96, seq_length=12, seed=0)
    return MotionDataset(X, y)


def _tree_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        if not np.array_equal(np.asarray(la), np.asarray(lb)):
            return False
    return True


# ---------------------------------------------------------------------------
# The layer itself (shard_map property sweep, non-divisible param counts)
# ---------------------------------------------------------------------------


def _toy_params():
    # 13*7 + 7 + 1 = 99 elements: 99 % 2 == 1 and 99 % 4 == 3, so every
    # tested world size exercises the uneven-shard padding path
    key = jax.random.PRNGKey(0)
    return {
        "w": jax.random.normal(key, (13, 7)),
        "b": jnp.zeros((7,)),
        "c": jnp.ones(()),
    }


def _toy_loss(p, batch):
    x, y = batch
    pred = x @ p["w"] + p["b"] + p["c"]
    return jnp.mean((pred - y) ** 2)


class TestShardedUpdateLayer:
    @pytest.mark.parametrize("world", [1, 2, 4])
    def test_parity_vs_replicated_apply(self, world):
        """5 steps of the sharded shard_map body vs a replicated apply of
        the same padded-flat optimizer program (pmean'd grads, full
        vector), both fed identical per-replica gradients: params and
        the checkpoint-layout view of the optimizer state agree to the
        last ulp.  Cross-PROGRAM equality can wobble one ulp on XLA:CPU
        (psum_scatter's ring order vs psum's tree order at world 4; FMA
        contraction of adam's nu for shard- vs full-sized operands) -
        the BITWISE end-to-end bar lives in TestTrainerParity below,
        where both flavors train the real model."""
        from functools import partial

        from jax import shard_map
        from jax.flatten_util import ravel_pytree

        mesh = make_mesh({"dp": world})
        p0 = _toy_params()
        opt = optax.adam(1e-3)
        su = ShardedUpdate(opt, p0, world, axis="dp")
        assert su.size == 99 and su.padded == su.shard * world
        st_sh = su.init_opt_state(p0, mesh=mesh)
        st_rep = su.init_opt_state(p0)  # same flat layout, replicated
        st_specs = su.opt_state_specs()
        unravel = ravel_pytree(p0)[1]
        pad = su.padded - su.size
        # per-replica grads ride in stacked on a leading (world,) axis
        gspec = jax.tree.map(lambda _: P("dp"), p0)

        @partial(shard_map, mesh=mesh,
                 in_specs=(P(), st_specs, gspec),
                 out_specs=(P(), st_specs), check_vma=False)
        def step_sh(p, st, gstack):
            grads = jax.tree.map(lambda l: l[0], gstack)
            return su.apply(p, grads, st)

        @partial(shard_map, mesh=mesh,
                 in_specs=(P(), P(), gspec),
                 out_specs=(P(), P()), check_vma=False)
        def step_rep(p, st, gstack):
            grads = jax.tree.map(
                lambda l: jax.lax.pmean(l[0], "dp"), gstack
            )
            flat_g = jnp.pad(ravel_pytree(grads)[0], (0, pad))
            flat_p = jnp.pad(ravel_pytree(p)[0], (0, pad))
            updates, st = opt.update(flat_g, st, flat_p)
            flat_p = optax.apply_updates(flat_p, updates)
            return unravel(flat_p[: su.size]), st

        def tree_close(a, b):
            for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b),
                              strict=True):
                np.testing.assert_allclose(
                    np.asarray(la), np.asarray(lb), rtol=1e-5, atol=1e-10
                )

        grad_fn = jax.jit(jax.grad(_toy_loss))
        rng = np.random.default_rng(3)
        p_sh = p_rep = p0
        for _ in range(5):
            tree_close(p_sh, p_rep)
            gstack = [
                grad_fn(p_sh, (
                    jnp.asarray(rng.standard_normal((4, 13)), jnp.float32),
                    jnp.asarray(rng.standard_normal((4, 7)), jnp.float32),
                ))
                for _ in range(world)
            ]
            gstack = jax.tree.map(lambda *ls: jnp.stack(ls), *gstack)
            p_sh, st_sh = jax.jit(step_sh)(p_sh, st_sh, gstack)
            p_rep, st_rep = jax.jit(step_rep)(p_rep, st_rep, gstack)
        tree_close(p_sh, p_rep)
        tree_close(su.replicated_opt_state(st_sh),
                   su.replicated_opt_state(st_rep))

    @pytest.mark.parametrize("world", [2, 4])
    def test_psum_scatter_is_slice_of_pmean(self, world):
        """The identity parity rests on: psum_scatter(tiled)/world IS the
        matching slice of pmean, bitwise - checked inside ONE program so
        compilation cannot differ."""
        from functools import partial

        from jax import shard_map

        mesh = make_mesh({"dp": world})
        n = 12 * world

        @partial(shard_map, mesh=mesh, in_specs=P("dp"),
                 out_specs=(P("dp"), P("dp")), check_vma=False)
        def both(x):
            sc = jax.lax.psum_scatter(
                x[0], "dp", scatter_dimension=0, tiled=True
            ) / world
            full = jax.lax.pmean(x[0], "dp")
            r = jax.lax.axis_index("dp")
            ref = jax.lax.dynamic_slice(
                full, (r * (n // world),), (n // world,)
            )
            return sc[None], ref[None]

        x = jnp.asarray(
            np.random.default_rng(0).standard_normal((world, n)),
            jnp.float32,
        )
        sc, ref = jax.jit(both)(x)
        assert np.array_equal(np.asarray(sc), np.asarray(ref))

    @pytest.mark.parametrize("world", [1, 2, 4])
    def test_layout_bijection_roundtrip(self, world):
        """sharded flat <-> standard optimizer.init(params) layout is an
        exact bijection in both directions."""
        p0 = _toy_params()
        opt = optax.adam(1e-3)
        su = ShardedUpdate(opt, p0, world)
        flat_state = su.init_opt_state(p0)
        std = su.replicated_opt_state(flat_state)
        # standard layout really is optimizer.init's structure
        assert jax.tree.structure(std) == jax.tree.structure(opt.init(p0))
        assert _tree_equal(su.flat_opt_state(std), flat_state)
        assert _tree_equal(su.replicated_opt_state(su.flat_opt_state(std)),
                           std)

    def test_opt_state_specs_shard_only_param_vectors(self):
        p0 = _toy_params()
        su = ShardedUpdate(optax.adam(1e-3), p0, 4, axis="dp")
        specs = jax.tree.leaves(
            su.opt_state_specs(),
            is_leaf=lambda l: isinstance(l, P),
        )
        shapes = jax.tree.leaves(su.abstract_opt_state())
        sharded = [s for s in specs if s == P("dp")]
        # adam: mu + nu sharded; count (scalar) replicated
        assert len(sharded) == 2
        for spec, leaf in zip(specs, shapes, strict=True):
            if spec == P("dp"):
                assert leaf.shape == (su.padded,)
            else:
                assert leaf.shape != (su.padded,)

    @pytest.mark.parametrize("world", [2, 4])
    def test_native_shard_and_gather_roundtrip(self, world):
        """The native-ring converters: per-rank shard states reassemble
        (via a fake allgather) into exactly the standard layout, and
        re-sharding the standard layout returns each rank's state."""
        p0 = _toy_params()
        opt = optax.adam(1e-3)
        su = ShardedUpdate(opt, p0, world)
        # a fresh rank's shard state agrees with sharding the standard init
        for r in range(world):
            assert _tree_equal(su.shard_opt_state(opt.init(p0), r),
                               su.init_shard_opt_state(p0, r))
        # populate mu/nu with distinct non-zero values (an all-zeros init
        # would make the roundtrip vacuous)
        std, params = opt.init(p0), p0
        for i in range(3):
            grads = jax.tree.map(
                lambda l: jnp.full_like(l, 0.1 * (i + 1)), params
            )
            updates, std = opt.update(grads, std, params)
            params = optax.apply_updates(params, updates)
        shards = [su.shard_opt_state(std, r) for r in range(world)]

        def fake_allgather(vec):
            # stack rank 0's leaf and the OTHER ranks' matching leaf -
            # exactly Communicator.allgather's (world, len) contract.
            # Leaves are matched by position: each rank's state has the
            # same treedef, and gather_opt_state hands us rank 0's leaf.
            pos = next(
                i for i, leaf in enumerate(jax.tree.leaves(shards[0]))
                if np.asarray(leaf).shape == vec.shape
                and np.array_equal(np.asarray(leaf), vec)
            )
            return np.stack([
                np.asarray(jax.tree.leaves(shards[r])[pos])
                for r in range(world)
            ])

        gathered = su.gather_opt_state(shards[0], fake_allgather)
        assert _tree_equal(gathered, std)


# ---------------------------------------------------------------------------
# The SPMD trainers (the flag end to end)
# ---------------------------------------------------------------------------


class TestTrainerParity:
    @pytest.mark.parametrize("world", [1, 2, 4])
    def test_ddp_sharded_matches_replicated_bitwise(self, motion_set, world):
        """--sharded-update vs --no-sharded-update on a dp mesh: final
        parameters and loss history identical BITWISE (the acceptance
        bar - the motion model's 662 params are not divisible by 4)."""
        runs = {}
        for sharded in (True, False):
            t = DDPTrainer(
                small_model(), motion_set, batch_size=48,
                learning_rate=2.5e-3, seed=SEED,
                mesh=make_mesh({"dp": world}), sharded_update=sharded,
            )
            _, hist, _ = t.train(epochs=2)
            runs[sharded] = (t, hist)
        assert runs[True][1] == runs[False][1]
        assert _tree_equal(runs[True][0].params, runs[False][0].params)

    def test_horovod_sharded_matches_replicated_bitwise(self, motion_set):
        runs = {}
        for sharded in (True, False):
            t = HorovodTrainer(
                small_model(), motion_set, batch_size=48,
                learning_rate=2.5e-3, seed=SEED,
                mesh=make_mesh({"dp": 4}), sharded_update=sharded,
            )
            _, hist, _ = t.train(epochs=2)
            runs[sharded] = (t, hist)
        assert runs[True][1] == runs[False][1]
        assert _tree_equal(runs[True][0].params, runs[False][0].params)

    def test_checkpoint_round_trips_unsharded_layout(self, motion_set,
                                                     tmp_path):
        """A sharded trainer's checkpoint is indistinguishable from a
        replicated one's: a --no-sharded-update trainer resumes from it
        bitwise, and a sharded trainer resumes from a replicated
        checkpoint - the flag never leaks into the on-disk format."""
        mesh = make_mesh({"dp": 4})

        def run(sharded, ckpt_dir):
            t = DDPTrainer(
                small_model(), motion_set, batch_size=48,
                learning_rate=2.5e-3, seed=SEED, mesh=mesh,
                sharded_update=sharded, checkpoint_dir=ckpt_dir,
                checkpoint_every=2,
            )
            t.train(epochs=2)
            return t

        run(True, tmp_path / "sh")
        ref = run(False, tmp_path / "rep")
        ckpt_sh = tmp_path / "sh" / "checkpoint-epoch-2.ckpt"
        ckpt_rep = tmp_path / "rep" / "checkpoint-epoch-2.ckpt"
        assert ckpt_sh.exists() and ckpt_rep.exists()
        # both flavors trained identically -> identical checkpoint bytes
        # would be too strong (flax msgpack key order is stable, but pin
        # the semantic contract instead): a replicated trainer restores
        # the sharded trainer's file to the replicated run's exact state
        resumed_rep = DDPTrainer(
            small_model(), motion_set, batch_size=48, learning_rate=2.5e-3,
            seed=0, mesh=mesh, sharded_update=False,
        )
        meta = resumed_rep.resume_from(ckpt_sh)
        assert meta["epoch"] == 2
        assert _tree_equal(resumed_rep.params, ref.params)
        assert _tree_equal(resumed_rep.opt_state, ref.opt_state)
        # ... and a sharded trainer restores the replicated file: its
        # live (sharded-layout) state re-gathers to the same standard view
        resumed_sh = DDPTrainer(
            small_model(), motion_set, batch_size=48, learning_rate=2.5e-3,
            seed=0, mesh=mesh, sharded_update=True,
        )
        resumed_sh.resume_from(ckpt_rep)
        assert _tree_equal(resumed_sh.params, ref.params)
        assert _tree_equal(
            resumed_sh._shard_update.replicated_opt_state(
                resumed_sh.opt_state),
            ref.opt_state,
        )

    def test_local_trainer_ignores_flag(self, motion_set):
        """SUPPORTS_SHARDED_UPDATE=False strategies (local, zero, mesh)
        silently keep the replicated apply - default-on must not change
        single-process training."""
        a = Trainer(small_model(), motion_set, batch_size=48,
                    learning_rate=2.5e-3, seed=SEED, sharded_update=True)
        b = Trainer(small_model(), motion_set, batch_size=48,
                    learning_rate=2.5e-3, seed=SEED, sharded_update=False)
        _, ha, _ = a.train(epochs=1)
        _, hb, _ = b.train(epochs=1)
        assert ha == hb
        assert _tree_equal(a.params, b.params)


# ---------------------------------------------------------------------------
# Non-finite guard under sharding (the global-skip-verdict hazard)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestGuardParity:
    def test_injected_nan_skipped_identically(self, motion_set):
        """apply_if_finite under sharding: each shard's wrapper only sees
        its slice, so the poison-broadcast must make every shard take the
        SAME skip decision - pinned by bitwise parity of a guarded
        injected-NaN run against the replicated guarded run."""
        from pytorch_distributed_rnn_tpu.resilience import FaultSchedule

        runs = {}
        for sharded in (True, False):
            t = DDPTrainer(
                small_model(), motion_set, batch_size=48,
                learning_rate=2.5e-3, seed=SEED,
                mesh=make_mesh({"dp": 4}), sharded_update=sharded,
                max_bad_steps=3, faults=FaultSchedule.parse("step:1:nan"),
            )
            _, hist, _ = t.train(epochs=2)
            assert t.guard.total_skipped == 1
            runs[sharded] = (t, hist)
        assert _tree_equal(runs[True][0].params, runs[False][0].params)
        for leaf in jax.tree.leaves(runs[True][0].params):
            assert np.isfinite(np.asarray(leaf)).all()


# ---------------------------------------------------------------------------
# Observability: per-phase collective bytes (pdrnn-metrics diff fields)
# ---------------------------------------------------------------------------


class TestPhaseBytes:
    def test_phase_bytes_helper(self):
        from pytorch_distributed_rnn_tpu.obs.summary import _phase_bytes

        ops = {
            "all-reduce": {"count": 2, "bytes": 8},
            "reduce-scatter": {"count": 1, "bytes": 1324},
            "all-gather": {"count": 1, "bytes": 2648},
        }
        assert _phase_bytes({"ops": ops}, ("all-reduce",)) == 8
        assert _phase_bytes(
            {"ops": ops}, ("reduce-scatter", "all-gather")) == 3972
        # host-loop steps record the event with ops=None -> no split
        assert _phase_bytes({"ops": None}, ("all-reduce",)) is None
        assert _phase_bytes(None, ("all-reduce",)) is None

    def test_sharded_run_reports_update_phase_bytes(self, motion_set,
                                                    tmp_path):
        """The telemetry sidecar of a sharded run splits traced traffic
        into gradient (all-reduce scalars only) and update
        (reduce-scatter + all-gather) phases; the replicated run's update
        phase is zero - the diffable signature of 2004.13336."""
        from pytorch_distributed_rnn_tpu.obs import (
            MetricsRecorder,
            load_events,
            summarize_events,
        )

        summaries = {}
        for sharded in (True, False):
            path = tmp_path / f"m_{sharded}.jsonl"
            rec = MetricsRecorder(path)
            DDPTrainer(
                small_model(), motion_set, batch_size=48,
                learning_rate=2.5e-3, seed=SEED,
                mesh=make_mesh({"dp": 2}), sharded_update=sharded,
                recorder=rec,
            ).train(epochs=1)
            rec.close()
            summaries[sharded] = summarize_events(load_events(path))
        sh, rep = summaries[True], summaries[False]
        assert sh["collective_update_bytes_per_step"] > 0
        assert rep["collective_update_bytes_per_step"] == 0
        # replicated grad all-reduce carries the full param vector; the
        # sharded flavor's all-reduces are the loss/metric scalars
        assert rep["collective_grad_bytes_per_step"] > \
            sh["collective_grad_bytes_per_step"]
        # per-device update-phase movement: RS (1/N) + AG (full) vs
        # AR (2x full logical traffic) - the ~N/2-fold reduce-scatter
        # drop shows up as update bytes < replicated grad bytes
        assert sh["collective_update_bytes_per_step"] < \
            rep["collective_grad_bytes_per_step"] * 2

    def test_diff_gates_phase_fields(self):
        """pdrnn-metrics diff regresses on the per-phase fields - but a
        replicated baseline (update bytes 0/None) can never flag the
        sharded candidate."""
        from pytorch_distributed_rnn_tpu.obs.summary import diff_summaries

        base = {"collective_grad_bytes_per_step": 1000,
                "collective_update_bytes_per_step": 0}
        cand = {"collective_grad_bytes_per_step": 1500,
                "collective_update_bytes_per_step": 4000}
        regs = diff_summaries(base, cand, threshold_pct=10.0)
        metrics = {r["metric"] for r in regs}
        assert "collective_grad_bytes_per_step" in metrics
        # base 0 -> skipped, never a false regression
        assert "collective_update_bytes_per_step" not in metrics
