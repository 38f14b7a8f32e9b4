"""pdrnn-lint --deep: jaxpr-level rule fixtures (each PD2xx rule fires
on a known-bad traced program and stays silent on a known-good one),
the trace-registry contract (>= 6 entry points across >= 3 trainer
families, all CPU-traceable), and the package gate (zero new PD2xx
findings with the committed baseline)."""

import json
import re
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from pytorch_distributed_rnn_tpu.lint import load_baseline, run_lint
from pytorch_distributed_rnn_tpu.lint.cli import main as lint_main
from pytorch_distributed_rnn_tpu.lint.core import _NOQA_RE
from pytorch_distributed_rnn_tpu.lint.jaxpr_pass import (
    deep_rules,
    run_deep,
)
from pytorch_distributed_rnn_tpu.lint.trace_registry import (
    TraceEntry,
    load_entries,
    sds,
)
from pytorch_distributed_rnn_tpu.parallel.mesh import make_mesh
from jax import shard_map

REPO_ROOT = Path(__file__).resolve().parents[1]
PACKAGE = REPO_ROOT / "pytorch_distributed_rnn_tpu"
BASELINE = REPO_ROOT / "lint_baseline.json"
THIS_FILE = "tests/test_lint_deep.py"


def fixture_entry(name, build, **kw):
    kw.setdefault("family", "fixture")
    kw.setdefault("path", THIS_FILE)
    kw.setdefault("mesh_axes", {})
    return TraceEntry(name=name, build=build, **kw)


def deep(entries, **kw):
    findings, stats = run_deep(entries=entries, root=REPO_ROOT, **kw)
    return findings


def codes(findings):
    return [f.rule for f in findings]


def file_noqa(path, line):
    """The same inline-directive semantics run_lint wires in, for
    fixtures driven through run_deep directly."""
    try:
        text = (REPO_ROOT / path).read_text().splitlines()[line - 1]
    except (OSError, IndexError):
        return set()
    m = _NOQA_RE.search(text)
    return set(re.findall(r"[A-Z]{2}\d{3}", m.group(1))) if m else set()


# ---------------------------------------------------------------------------
# PD201 unreduced-gradient


def _dp_step_program(reduce_grads: bool):
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])

    @partial(shard_map, mesh=mesh, in_specs=(P(), P("dp")),
             out_specs=(P(), P()), check_vma=False)
    def step(params, batch):
        def loss(p):
            return jnp.sum((batch @ p) ** 2)

        grads = jax.grad(loss)(params)
        if reduce_grads:
            grads = lax.pmean(grads, "dp")
        params = params - 0.1 * grads
        return params, lax.pmean(loss(params), "dp")

    return step, (sds((8, 8), jnp.float32), sds((4, 8), jnp.float32))


class TestPD201UnreducedGradient:
    def test_unreduced_step_fires(self):
        entry = fixture_entry(
            "fixture.bad_dp_step",
            lambda: _dp_step_program(reduce_grads=False),
            mesh_axes={"dp": 2}, data_axis="dp",
        )
        findings = deep([entry])
        assert codes(findings) == ["PD201"]
        assert "dp" in findings[0].message
        assert findings[0].symbol == "fixture.bad_dp_step"

    def test_reduced_step_is_silent(self):
        entry = fixture_entry(
            "fixture.good_dp_step",
            lambda: _dp_step_program(reduce_grads=True),
            mesh_axes={"dp": 2}, data_axis="dp",
        )
        assert codes(deep([entry])) == []

    def test_gspmd_step_without_annotations_fires(self):
        def build():
            def step(params, batch):
                grads = jax.grad(
                    lambda p: jnp.sum((batch @ p) ** 2))(params)
                return params - 0.1 * grads, jnp.float32(0)

            return jax.jit(step), (sds((8, 8), jnp.float32),
                                   sds((4, 8), jnp.float32))

        entry = fixture_entry(
            "fixture.bare_gspmd_step", build,
            mesh_axes={"dp": 2}, data_axis="dp", gspmd=True,
        )
        findings = deep([entry])
        assert codes(findings) == ["PD201"]
        assert "sharding annotation" in findings[0].message

    def test_gspmd_step_with_constraint_is_silent(self):
        mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])

        def build():
            from jax.sharding import NamedSharding

            def step(params, batch):
                batch = jax.lax.with_sharding_constraint(
                    batch, NamedSharding(mesh, P("dp")))
                grads = jax.grad(
                    lambda p: jnp.sum((batch @ p) ** 2))(params)
                return params - 0.1 * grads, jnp.float32(0)

            return jax.jit(step), (sds((8, 8), jnp.float32),
                                   sds((4, 8), jnp.float32))

        entry = fixture_entry(
            "fixture.constrained_gspmd_step", build,
            mesh_axes={"dp": 2}, data_axis="dp", gspmd=True,
        )
        assert codes(deep([entry])) == []


# ---------------------------------------------------------------------------
# PD202 collective-axis-mismatch


class TestPD202CollectiveAxisMismatch:
    def test_collective_over_absent_axis_fires_at_trace(self):
        """The acceptance demo: a psum over an axis the mesh does not
        carry is caught from the TRACE (the jaxpr-level ground truth the
        AST rule PD101 approximates)."""

        def build():
            mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])

            @partial(shard_map, mesh=mesh, in_specs=P("dp"),
                     out_specs=P("dp"), check_vma=False)
            def forward(x):
                return lax.psum(x, "ep")  # mesh only has dp

            return forward, (sds((4, 8), jnp.float32),)

        entry = fixture_entry(
            "fixture.wrong_axis", build,
            mesh_axes={"dp": 2}, kind="forward",
        )
        findings = deep([entry])
        assert codes(findings) == ["PD202"]
        assert '"ep"' in findings[0].message
        assert "dp" in findings[0].message

    def test_matching_axis_is_silent(self):
        def build():
            mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])

            @partial(shard_map, mesh=mesh, in_specs=P("dp"),
                     out_specs=P(), check_vma=False)
            def forward(x):
                return lax.pmean(x, "dp")

            return forward, (sds((4, 8), jnp.float32),)

        entry = fixture_entry(
            "fixture.right_axis", build,
            mesh_axes={"dp": 2}, kind="forward",
        )
        assert codes(deep([entry])) == []


# ---------------------------------------------------------------------------
# PD203 dtype-promotion-leak


class TestPD203DtypePromotionLeak:
    def test_bf16_upcast_fires(self):
        def build():
            def forward(x):
                return x.astype(jnp.float32) * 2.0

            return forward, (sds((4, 8), jnp.bfloat16),)

        entry = fixture_entry("fixture.upcast", build, kind="forward")
        findings = deep([entry])
        assert codes(findings) == ["PD203"]
        # anchored to the real source line of the convert
        assert findings[0].path == THIS_FILE
        assert "astype" in findings[0].snippet

    def test_noqa_on_the_upcast_line_suppresses(self):
        def build():
            def forward(x):
                return x.astype(jnp.float32) * 2.0  # noqa: PD203

            return forward, (sds((4, 8), jnp.bfloat16),)

        entry = fixture_entry("fixture.upcast_ok", build, kind="forward")
        assert codes(deep([entry], noqa=file_noqa)) == []

    def test_non_bf16_convert_is_silent(self):
        def build():
            def forward(x):
                return x.astype(jnp.float32) * 2.0  # int -> f32: fine

            return forward, (sds((4, 8), jnp.int32),)

        entry = fixture_entry("fixture.no_bf16", build, kind="forward")
        assert codes(deep([entry])) == []


# ---------------------------------------------------------------------------
# PD204 dead-computation


class TestPD204DeadComputation:
    def test_large_unused_matmul_chain_fires(self):
        def build():
            def step(x):
                unused = (x @ x) @ (x @ x) + 1.0  # never returned
                return jnp.sum(x)

            return step, (sds((64, 64), jnp.float32),)

        entry = fixture_entry("fixture.dead_matmuls", build,
                              kind="forward")
        findings = deep([entry])
        assert codes(findings) == ["PD204"]
        assert "never used" in findings[0].message

    def test_small_elementwise_residue_is_silent(self):
        """Autodiff-style scalar guard residue must not fire - only
        clusters with real compute above the element threshold do."""

        def build():
            def step(x):
                unused = jnp.where(jnp.isfinite(x), x, 0.0) + 1.0
                return jnp.sum(x)

            return step, (sds((4, 4), jnp.float32),)

        entry = fixture_entry("fixture.small_dead", build,
                              kind="forward")
        assert codes(deep([entry])) == []


# ---------------------------------------------------------------------------
# PD205 donation-mismatch


class TestPD205DonationMismatch:
    def test_donated_unreturned_buffer_fires(self):
        def build():
            def step(params, batch):
                return params + jnp.sum(batch)

            # batch is donated but no output matches its shape/dtype
            return jax.jit(step, donate_argnums=(1,)), (
                sds((8, 8), jnp.float32), sds((32,), jnp.float32))

        entry = fixture_entry("fixture.bad_donate", build,
                              donate=(1,), kind="update")
        findings = deep([entry])
        assert codes(findings) == ["PD205"]
        assert "argument 1" in findings[0].message

    def test_donated_updated_state_is_silent(self):
        def build():
            def step(params, batch):
                return params + jnp.sum(batch)

            return jax.jit(step, donate_argnums=(0,)), (
                sds((8, 8), jnp.float32), sds((32,), jnp.float32))

        entry = fixture_entry("fixture.good_donate", build,
                              donate=(0,), kind="update")
        assert codes(deep([entry])) == []


# ---------------------------------------------------------------------------
# PD200 trace-failure


class TestPD200TraceFailure:
    def test_broken_build_fires(self):
        def build():
            raise RuntimeError("entry rotted away")

        entry = fixture_entry("fixture.broken", build)
        findings = deep([entry])
        assert codes(findings) == ["PD200"]
        assert "rotted away" in findings[0].message

    def test_select_can_drop_trace_failures(self):
        def build():
            raise RuntimeError("nope")

        entry = fixture_entry("fixture.broken2", build)
        assert codes(deep([entry], ignore=["PD200"])) == []


# ---------------------------------------------------------------------------
# Trace registry contract + package gate


class TestTraceRegistry:
    def test_rules_registered(self):
        assert sorted(deep_rules()) == [
            "PD200", "PD201", "PD202", "PD203", "PD204", "PD205"]

    def test_registry_breadth(self):
        """The acceptance bar: >= 6 entry points across >= 3 trainer
        families, every one declared with abstract specs."""
        entries = load_entries()
        assert len(entries) >= 6
        assert len({e.family for e in entries}) >= 3
        # strategy coverage: the three interchangeable distribution
        # strategies the paper ships all declare a step
        families = {e.family for e in entries}
        assert {"ddp", "zero", "moe"} <= families

    def test_all_entries_trace_on_cpu(self):
        findings, stats = run_deep(root=REPO_ROOT)
        assert stats["traced"] >= 6, stats
        assert stats["skipped"] == []
        assert not any(f.rule == "PD200" for f in findings), [
            f.render() for f in findings]

    def test_package_deep_gate_zero_new_findings(self):
        """The CI contract, deep layer included: tracing every
        registered entry point yields zero non-baselined findings."""
        result = run_lint([PACKAGE], root=REPO_ROOT,
                          baseline=load_baseline(BASELINE), deep=True)
        assert result.findings == [], (
            "new deep-lint findings (fix them, # noqa with the contract,"
            " or regenerate lint_baseline.json):\n"
            + "\n".join(f.render() for f in result.findings)
        )
        assert result.deep is not None
        assert result.deep["traced"] >= 6
        assert len(result.deep["families"]) >= 3

    def test_deep_stats_ride_the_json_report(self, capsys):
        rc = lint_main([str(PACKAGE), "--deep", "--baseline",
                        str(BASELINE), "--format", "json"])
        report = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert report["deep"]["traced"] >= 6
        by_name = {e["entry"]: e for e in report["deep"]["entries"]}
        assert {"dp.spmd_train_step", "zero.fsdp_train_step",
                "moe.mesh_train_step"} <= set(by_name)
        # the artifact carries per-entry collective traffic (the
        # evaluation walker reused on the traced step): the dp grad
        # pmean shows as all-reduce, the moe dispatch as all-to-all
        assert "all-reduce" in by_name["dp.spmd_train_step"]["collectives"]
        assert "all-to-all" in by_name["moe.mesh_train_step"]["collectives"]


class TestCollectiveGate:
    """The CI collective-traffic gate (lint/collective_check.py): the
    sharded weight update's wire contract (2004.13336) is checked-in as
    per-entry expectations, and a fresh trace must match them exactly."""

    def test_fresh_report_matches_expectations_and_drift_fails(
        self, tmp_path
    ):
        from pytorch_distributed_rnn_tpu.lint import collective_check

        result = run_lint([PACKAGE], root=REPO_ROOT,
                          baseline=load_baseline(BASELINE), deep=True)
        by_name = {e["entry"]: e for e in result.deep["entries"]}
        # every sharded-update flavor registered and traced: RS+AG update
        # phase on the SPMD entries, collective-free device program on
        # the native ring's
        for name in ("dp.spmd_train_step_sharded",
                     "dp.spmd_train_step_sharded_hvd",
                     "dp.spmd_epoch_fn_sharded"):
            assert "reduce-scatter" in by_name[name]["collectives"], name
            assert "all-gather" in by_name[name]["collectives"], name
        assert by_name["native_ddp.apply_update_sharded"]["collectives"] == {}

        report = tmp_path / "lint-deep-report.json"
        report.write_text(json.dumps({"deep": result.deep}))
        assert collective_check.main([str(report)]) == 0

        # regrown update-phase traffic must fail the gate: double the
        # sharded entry's reduce-scatter bytes and re-check
        tampered = json.loads(report.read_text())
        for row in tampered["deep"]["entries"]:
            if row["entry"] == "dp.spmd_train_step_sharded":
                row["collectives"]["reduce-scatter"]["bytes"] *= 2
        drifted = tmp_path / "drifted.json"
        drifted.write_text(json.dumps(tampered))
        assert collective_check.main([str(drifted)]) == 1

    def test_bucketed_entry_gated_and_collective_free(self):
        result = run_lint([PACKAGE], root=REPO_ROOT,
                          baseline=load_baseline(BASELINE), deep=True)
        by_name = {e["entry"]: e for e in result.deep["entries"]}
        # the overlapped per-bucket update program: registered, traced,
        # and collective-free (the ring rides the host comm worker)
        assert by_name["native_ddp.apply_update_bucketed"]["collectives"] \
            == {}

    def test_native_wire_sum_invariant_tamper_fails(self, tmp_path):
        """The bucketed wire contract: the checked-in per-bucket bytes
        must sum EXACTLY to the monolithic collective's - editing any
        bucket row (or the monolithic total) fails the gate, and
        check_native_wire names the sum violation."""
        from pytorch_distributed_rnn_tpu.lint.collective_check import (
            EXPECTATIONS_PATH,
            check_native_wire,
        )

        expectations = json.loads(EXPECTATIONS_PATH.read_text())
        # the shipped file passes, and genuinely holds >1 bucket
        assert check_native_wire(expectations) == []
        assert len(expectations["native_wire"]["buckets"]) > 1

        tampered = json.loads(EXPECTATIONS_PATH.read_text())
        tampered["native_wire"]["buckets"][0]["reduce_scatter_bytes"] += 4
        problems = check_native_wire(tampered)
        assert any("sum to" in p for p in problems)

        # consistent-but-wrong tamper (bucket AND monolithic edited
        # together) still fails: the plan replayed from the stored
        # config is the ground truth
        tampered = json.loads(EXPECTATIONS_PATH.read_text())
        tampered["native_wire"]["buckets"][0]["reduce_scatter_bytes"] += 8
        tampered["native_wire"]["monolithic"]["reduce_scatter_bytes"] += 8
        problems = check_native_wire(tampered)
        assert any("drifted from the plan" in p for p in problems)

        # a missing section is itself a finding (the contract cannot be
        # silently un-gated)
        assert check_native_wire({}) != []


class TestDeepFindingPlumbing:
    """Deep findings ride the shared reporting path: fingerprints,
    baseline suppression, select/ignore."""

    def _bad_entry(self):
        def build():
            def forward(x):
                return x.astype(jnp.float32) * 2.0

            return forward, (sds((4, 8), jnp.bfloat16),)

        return fixture_entry("fixture.plumbing", build, kind="forward")

    def test_fingerprints_are_stable_across_runs(self):
        from pytorch_distributed_rnn_tpu.lint.baseline import fingerprint

        first = deep([self._bad_entry()])
        second = deep([self._bad_entry()])
        assert [fingerprint(f) for f in first] == [
            fingerprint(f) for f in second]

    def test_select_and_ignore_filter_deep_rules(self):
        entry = self._bad_entry()
        assert codes(deep([entry], select=["PD203"])) == ["PD203"]
        assert codes(deep([entry], select=["PD204"])) == []
        assert codes(deep([entry], ignore=["PD203"])) == []

    def test_duplicate_findings_from_sibling_entries_collapse(self):
        """Two entries tracing the same shared loss fn must not report
        the same source site twice."""
        findings = deep([self._bad_entry(),
                         fixture_entry("fixture.plumbing2",
                                       self._bad_entry().build,
                                       kind="forward")])
        assert codes(findings) == ["PD203"]

    def test_subset_path_run_still_honors_out_of_path_noqa(self):
        """The deep pass traces the whole registry regardless of which
        paths were linted, so noqa directives in files OUTSIDE the
        linted subset (the tp.py/strategy.py PD203 allowlists) must
        still suppress."""
        result = run_lint([PACKAGE / "parallel" / "ep.py"],
                          root=REPO_ROOT, select=["PD203"], deep=True)
        assert [f.render() for f in result.findings] == []
        assert result.deep["traced"] >= 6  # the whole registry ran

    def test_empty_active_deep_rule_set_skips_tracing(self):
        """--deep with only AST rules selected must not pay the trace."""
        result = run_lint([PACKAGE], root=REPO_ROOT,
                          baseline=load_baseline(BASELINE),
                          select=["PD101"], deep=True)
        assert result.deep == {"entries": [], "traced": 0,
                               "skipped": [], "families": [],
                               "devices": 0}

    def test_selecting_deep_rule_without_deep_is_usage_error(self, capsys):
        """--select PD201 without --deep would exit vacuously green."""
        rc = lint_main([str(PACKAGE), "--select", "PD201",
                        "--no-baseline"])
        assert rc == 2
        assert "needs --deep" in capsys.readouterr().err
        # ignoring a deep rule without --deep stays legal (harmless)
        assert lint_main([str(PACKAGE), "--ignore", "PD201",
                          "--baseline", str(BASELINE)]) == 0

    def test_trace_session_restores_env_in_fresh_process(self):
        """cpu_trace_session must leave JAX_PLATFORMS/XLA_FLAGS as it
        found them (child processes spawned later inherit the caller's
        platform choice), while still yielding the virtual devices."""
        import subprocess
        import sys

        script = (
            "import os\n"
            "os.environ.pop('JAX_PLATFORMS', None)\n"
            "os.environ.pop('XLA_FLAGS', None)\n"
            "from pytorch_distributed_rnn_tpu.lint.trace_registry "
            "import cpu_trace_session\n"
            "with cpu_trace_session() as n:\n"
            "    assert n == 8, n\n"
            "    assert os.environ['JAX_PLATFORMS'] == 'cpu'\n"
            "assert 'JAX_PLATFORMS' not in os.environ\n"
            "assert 'XLA_FLAGS' not in os.environ\n"
            "print('restored')\n"
        )
        env = {k: v for k, v in __import__("os").environ.items()
               if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
        env["PYTHONPATH"] = str(REPO_ROOT)
        proc = subprocess.run(
            [sys.executable, "-c", script], env=env, cwd=REPO_ROOT,
            capture_output=True, text=True, timeout=300,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "restored" in proc.stdout

    def test_prune_without_deep_preserves_deep_entries(self, tmp_path,
                                                       capsys):
        """A PD2xx baseline entry must survive an AST-only prune: the
        deep layer never ran, so it would wrongly look stale."""
        from pytorch_distributed_rnn_tpu.lint.baseline import (
            load_baseline as load,
            write_baseline,
        )

        findings = deep([self._bad_entry()])
        assert codes(findings) == ["PD203"]
        baseline = tmp_path / "baseline.json"
        write_baseline(baseline, findings)
        before = load(baseline)
        rc = lint_main([str(PACKAGE / "parallel" / "ep.py"),
                        "--baseline", str(baseline), "--prune-baseline"])
        assert rc == 0
        assert "pruned 0 stale" in capsys.readouterr().out
        assert load(baseline) == before

    def test_write_without_deep_preserves_deep_entries(self, tmp_path):
        """--write-baseline without --deep must carry accepted PD2xx
        entries over instead of silently deleting the deep layer."""
        from pytorch_distributed_rnn_tpu.lint.baseline import (
            load_baseline as load,
            write_baseline,
        )

        findings = deep([self._bad_entry()])
        baseline = tmp_path / "baseline.json"
        write_baseline(baseline, findings)
        fp = set(load(baseline))
        rc = lint_main([str(PACKAGE / "parallel" / "ep.py"),
                        "--baseline", str(baseline),
                        "--write-baseline"])
        assert rc == 0
        after = load(baseline)
        assert fp <= set(after)  # the PD203 entry survived the rewrite
