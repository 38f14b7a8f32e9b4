"""HLO collective-traffic report (evaluation/collectives.py): the
communication side of the scaling model, measured from compiled programs
(what one chip/virtual mesh CAN measure honestly)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_rnn_tpu.evaluation.collectives import (
    _shape_bytes,
    collective_stats,
    compiled_text,
    param_bytes,
)
from pytorch_distributed_rnn_tpu.parallel import make_mesh


class TestHLOParsing:
    def test_shape_bytes(self):
        assert _shape_bytes("f32[8,128]{1,0}") == 8 * 128 * 4
        assert _shape_bytes("bf16[16]{0}") == 32
        assert _shape_bytes("(f32[4]{0}, u32[2]{0})") == 16 + 8
        assert _shape_bytes("token[]") == 0

    def test_collective_stats_counts_ops(self):
        hlo = "\n".join([
            "  %ar = f32[128]{0} all-reduce(f32[128]{0} %x), ...",
            "  %cp = bf16[2,8]{1,0} collective-permute(%y), ...",
            "  %ag = f32[64]{0} all-gather(%z), ...",
            "  %unrelated = f32[4]{0} add(%a, %b)",
        ])
        stats = collective_stats(hlo)
        assert stats["all-reduce"] == {"count": 1, "bytes": 512}
        assert stats["collective-permute"] == {"count": 1, "bytes": 32}
        assert stats["all-gather"] == {"count": 1, "bytes": 256}
        assert "add" not in stats

    def test_async_pairs_count_once(self):
        hlo = "\n".join([
            "  %s = f32[128]{0} all-reduce-start(f32[128]{0} %x), ...",
            "  %d = f32[128]{0} all-reduce-done(f32[128]{0} %s), ...",
        ])
        stats = collective_stats(hlo)
        assert stats["all-reduce"]["count"] == 1


class TestCompiledPrograms:
    def test_dp_psum_allreduces_at_least_grad_bytes(self):
        """The dp=8 gradient pmean must move at least one full parameter
        tree's bytes through all-reduce per step - the invariant the
        scaling model's communication term is built on."""
        from jax.sharding import PartitionSpec as P
        from jax import shard_map

        mesh = make_mesh({"dp": 8})
        w = jnp.zeros((64, 64), jnp.float32)

        from functools import partial

        @partial(shard_map, mesh=mesh, in_specs=(P(), P("dp")),
                 out_specs=P(), check_vma=False)
        def loss(w, x):
            return jax.lax.pmean(jnp.sum((x @ w) ** 2), "dp")

        def step(w, x):
            return jax.grad(loss)(w, x)

        x = jnp.zeros((16, 64), jnp.float32)
        stats = collective_stats(compiled_text(step, w, x))
        assert stats["all-reduce"]["bytes"] >= w.size * 4

    def test_traced_scan_collectives_carry_trip_count(self):
        """A ppermute inside lax.scan compiles to ONE HLO op in a while
        body but executes `length` times per step - the traced stats must
        multiply the trip count in (the committed report's correctness
        depends on this; plain HLO parsing undercounts)."""
        from functools import partial

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from pytorch_distributed_rnn_tpu.evaluation.collectives import (
            trace_collective_stats,
        )

        mesh = make_mesh({"sp": 4})
        perm = [(i, (i + 1) % 4) for i in range(4)]

        @partial(shard_map, mesh=mesh, in_specs=(P("sp"),),
                 out_specs=P("sp"), check_vma=False)
        def relay(x):
            def turn(c, _):
                return jax.lax.ppermute(c, "sp", perm), None

            out, _ = jax.lax.scan(turn, x, None, length=5)
            return out

        x = jnp.zeros((8, 16), jnp.float32)  # (2, 16) per shard
        stats = trace_collective_stats(relay, x)
        cp = stats["collective-permute"]
        assert cp["count"] == 5
        assert cp["bytes"] == 5 * 2 * 16 * 4  # per-shard bytes x trips

    def test_report_row_shape(self):
        from pytorch_distributed_rnn_tpu.evaluation.collectives import (
            _char_sp_program,
            trace_collective_stats,
        )

        fn, call_args, params = _char_sp_program(2, 4)
        stats = trace_collective_stats(fn, *call_args)
        # the sp relay's carry hops are collective-permutes executed once
        # per relay turn (sp=4 turns x fwd+bwd x (h, c) leaves x layers)
        assert stats.get("collective-permute", {}).get("count", 0) >= 8
        # the dp grad reduction must move at least one parameter tree
        ar = stats.get("all-reduce", {}).get("bytes", 0)
        assert ar >= param_bytes(params)
