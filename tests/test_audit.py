"""Tests for the compiled-program contract auditor (ISSUE 10 layer 1).

Three tiers:

* pure unit tests of the diff/glob/policy machinery (no jax compile);
* in-process fixture programs with KNOWN broken contracts (host callback,
  donation present/absent) that ``extract_contract`` must flag;
* subprocess runs of the real CLI gate: ``--check`` green against the
  committed ``AUDIT_contracts.json``, and the seeded regressions
  (``--inject f64_noise`` / ``--inject no_donate``) trip it with a
  per-contract diff — the acceptance criterion of the issue.

Plus the meta-test: the committed baseline covers every production
executor (all four executors + recon + fit), so a new executor cannot
land without a contract.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import audit
from repro.analysis.audit import (INJECT_MODES, PROGRAMS,
                                  SCATTER_REDUCTION_COLLECTIVES,
                                  diff_contracts, expand_contract_names,
                                  extract_contract, policy_violations,
                                  program_names)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO, "AUDIT_contracts.json")


def _clean_contract(**over):
    c = {"collectives": {}, "dtypes": ["f32", "s32"], "scatter_dtypes": [],
         "donated_args": 0, "realized_aliases": 0, "host_calls": 0,
         "recompiles": 0}
    c.update(over)
    return c


class TestPolicy:
    def test_clean_contract_passes(self):
        assert policy_violations("p1/single", _clean_contract()) == []

    def test_f64_flagged(self):
        v = policy_violations("p1/single",
                              _clean_contract(dtypes=["f32", "f64"]))
        assert any("f64" in x for x in v)

    def test_host_calls_flagged(self):
        v = policy_violations("p1/single", _clean_contract(host_calls=2))
        assert any("host call" in x for x in v)

    def test_bf16_scatter_flagged(self):
        v = policy_violations("p1/single",
                              _clean_contract(scatter_dtypes=["bf16"]))
        assert any("accumulate" in x for x in v)

    def test_recompiles_flagged(self):
        v = policy_violations("p1/single", _clean_contract(recompiles=1))
        assert any("recompil" in x for x in v)

    def test_collective_in_local_program_flagged(self):
        """No registered single-device strategy declares collectives, so an
        all-reduce in p1/batched is a policy failure, not just drift."""
        v = policy_violations(
            "p1/batched", _clean_contract(collectives={"all-reduce": 1}))
        assert any("collective" in x for x in v)

    def test_declared_distributed_collectives_allowed(self):
        c = _clean_contract(collectives={"reduce-scatter": 2,
                                         "all-to-all": 2})
        assert policy_violations("p1/distributed_psum", c) == []

    def test_undeclared_distributed_collective_flagged(self):
        c = _clean_contract(collectives={"all-gather": 1})
        v = policy_violations("p1/distributed_psum", c)
        assert any("all-gather" in x for x in v)

    def test_strategy_table_kinds_are_real(self):
        from repro.analysis.hlo import COLLECTIVE_KINDS

        for kinds in SCATTER_REDUCTION_COLLECTIVES.values():
            assert set(kinds) <= set(COLLECTIVE_KINDS)


class TestDiffMachinery:
    BASE = {"p1/a": _clean_contract(), "p1/b": _clean_contract()}

    def test_identical_passes(self, capsys):
        assert diff_contracts(self.BASE, dict(self.BASE)) == 0
        assert "ok" in capsys.readouterr().out

    def test_field_drift_fails_with_diff(self, capsys):
        fresh = {"p1/a": _clean_contract(donated_args=3),
                 "p1/b": _clean_contract()}
        assert diff_contracts(self.BASE, fresh) == 1
        out = capsys.readouterr().out
        assert "p1/a: FAIL" in out
        assert "donated_args: 0 -> 3" in out

    def test_missing_fresh_contract_fails(self, capsys):
        assert diff_contracts(self.BASE, {"p1/a": _clean_contract()}) == 1
        assert "MISSING" in capsys.readouterr().out

    def test_new_contract_warns_not_fails(self, capsys):
        fresh = dict(self.BASE)
        fresh["p1/new"] = _clean_contract()
        assert diff_contracts(self.BASE, fresh) == 0
        assert "(new" in capsys.readouterr().out

    def test_policy_violation_fails_even_when_baseline_matches(self):
        """A baselined regression cannot be grandfathered: f64 in BOTH
        baseline and fresh still fails the policy layer."""
        bad = {"p1/a": _clean_contract(dtypes=["f32", "f64"])}
        assert diff_contracts(dict(bad), dict(bad)) == 1

    def test_glob_gates_subset(self, capsys):
        fresh = {"p1/a": _clean_contract(donated_args=9),
                 "p1/b": _clean_contract()}
        # gating only p1/b ignores the drifted p1/a
        assert diff_contracts(self.BASE, fresh, patterns=["p1/b"]) == 0

    def test_glob_matching_nothing_fails(self, capsys):
        assert diff_contracts(self.BASE, dict(self.BASE),
                              patterns=["p9/*"]) == 1
        assert "matched no" in capsys.readouterr().err

    def test_expand_names_mirror_check_regression_semantics(self, capsys):
        base, fresh = {"p1/a": {}}, {"p1/a": {}, "p1/c": {}}
        assert expand_contract_names(["p1/*"], base, fresh) == ["p1/a",
                                                               "p1/c"]
        # a glob matching only FRESH names gates nothing run-after-run
        assert expand_contract_names(["p1/c*"], base, fresh) == []
        # plain names pass through even when absent (reported MISSING later)
        assert expand_contract_names(["p1/zzz"], base, fresh) == ["p1/zzz"]


class TestFixturePrograms:
    """Known-contract fixture programs, extracted in-process."""

    def test_host_callback_fixture_flagged(self):
        def f(x):
            return jax.pure_callback(
                lambda a: a, jax.ShapeDtypeStruct(x.shape, x.dtype), x) * 2.0

        c = extract_contract(jax.jit(f), lambda i: (jnp.ones(8) * i,))
        assert c["host_calls"] >= 1
        assert any("host call" in v
                   for v in policy_violations("p1/fixture", c))

    def test_donation_fixture_contract(self):
        def f(x):
            return x * 2.0

        c = extract_contract(jax.jit(f, donate_argnums=(0,)),
                             lambda i: (jnp.ones((8, 8)) + i,))
        assert c["donated_args"] == 1
        assert c["realized_aliases"] == 1
        c0 = extract_contract(jax.jit(f), lambda i: (jnp.ones((8, 8)) + i,))
        assert c0["donated_args"] == 0

    def test_extra_allreduce_fixture_flagged(self):
        """A deliberate collective in a 'local' program — built with a
        1-device psum under shard_map — must trip the local policy."""
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(jax.devices("cpu")[:1], ("d",))

        def body(x):
            return jax.lax.psum(x, "d")

        f = jax.jit(shard_map(body, mesh=mesh, in_specs=P("d"),
                              out_specs=P()))
        c = extract_contract(f, lambda i: (jnp.ones(8) + i,))
        assert c["collectives"].get("all-reduce", 0) >= 1
        assert any("collective" in v
                   for v in policy_violations("p1/fixture", c))

    def test_f64_fixture_flagged_under_x64(self):
        def f(x):
            return (x.astype(jnp.float64) * jnp.float64(1.5)  # repro-lint: disable=f64-literal
                    ).astype(jnp.float32)

        c = extract_contract(jax.jit(f), lambda i: (jnp.ones(8),), x64=True)
        assert "f64" in c["dtypes"]
        assert any("f64" in v for v in policy_violations("p1/fixture", c))


class TestBaselineCoverage:
    """Meta-tests: the committed baseline must cover the full production
    surface, so new executors can't land contract-free."""

    def test_baseline_exists_and_loads(self):
        contracts = audit.load_baseline(BASELINE)
        assert contracts

    def test_baseline_covers_every_program(self):
        contracts = audit.load_baseline(BASELINE)
        missing = [n for n in program_names((1, 3))
                   if n not in contracts]
        assert not missing, (
            f"AUDIT_contracts.json lacks {missing}; refresh with "
            "`python -m repro.analysis.audit --update`")

    def test_programs_cover_build_sim_graph_executors(self):
        """Every executor module that builds the production graph has an
        audited program. If a new `make_*` executor appears in a core
        module calling build_sim_graph, it must be added to
        audit.PROGRAMS (and the baseline) or this inventory fails."""
        covered = {p.name for p in PROGRAMS}
        # executor entry point -> audited program(s)
        inventory = {
            "repro.core.pipeline.make_sim_fn": {"single", "recon"},
            "repro.core.batch.make_batched_sim_fn": {"batched"},
            "repro.launch.sim.make_streaming_sim_fn": {"streaming"},
            "repro.core.distributed.make_distributed_sim": {
                "distributed_psum", "distributed_halo"},
            "repro.core.fit.make_fit_loss": {"fit_loss", "fit_grad"},
        }
        for entry, progs in inventory.items():
            assert progs <= covered, f"{entry} not audited"
        # and the inventory itself is current: every core executor factory
        # that exists is listed
        import importlib

        for entry in inventory:
            mod, fn = entry.rsplit(".", 1)
            assert hasattr(importlib.import_module(mod), fn), (
                f"{entry} vanished; update the audit inventory + PROGRAMS")

    def test_baseline_contracts_satisfy_policy(self):
        """The committed baseline itself must be violation-free — a bad
        baseline would bless regressions."""
        contracts = audit.load_baseline(BASELINE)
        for name, c in contracts.items():
            assert policy_violations(name, c) == [], name

    def test_streaming_contract_pins_donation(self):
        """The property the no_donate injection breaks: the streaming
        executor donates its full packed batch (6 EventBatch leaves +
        keys)."""
        contracts = audit.load_baseline(BASELINE)
        assert contracts["p1/streaming"]["donated_args"] == 7
        assert contracts["p3/streaming"]["donated_args"] == 7

    def test_stacked_distributed_contract_matches_single_plane(self):
        """PR 9's amortization property, now pinned as data: the 3-plane
        stacked distributed program runs the SAME collective counts as the
        1-plane program."""
        contracts = audit.load_baseline(BASELINE)
        assert (contracts["p3/distributed_psum"]["collectives"]
                == contracts["p1/distributed_psum"]["collectives"])


def _run_audit(*args, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis.audit", *args],
        env=env, capture_output=True, text=True, timeout=timeout, cwd=REPO)


@pytest.mark.subprocess
class TestCLIGate:
    """The real gate, end to end in fresh interpreters (the audit pins its
    own fake-device env before importing jax, so it needs a clean
    process)."""

    def test_check_passes_against_committed_baseline(self):
        proc = _run_audit("--check", "--planes", "1",
                          "--programs", "p1/single",
                          "--programs", "p1/streaming")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "p1/single: ok" in proc.stdout

    def test_inject_f64_noise_fails_with_diff(self):
        proc = _run_audit("--check", "--planes", "1", "--quiet",
                          "--inject", "f64_noise",
                          "--programs", "p1/single")
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "p1/single: FAIL" in proc.stdout
        assert "f64" in proc.stdout  # the per-field dtype diff names it
        assert "policy" in proc.stdout

    def test_inject_no_donate_fails_with_diff(self):
        proc = _run_audit("--check", "--planes", "1", "--quiet",
                          "--inject", "no_donate",
                          "--programs", "p1/streaming")
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "p1/streaming: FAIL" in proc.stdout
        assert "donated_args: 7 -> 0" in proc.stdout

    def test_json_artifact_written(self, tmp_path):
        out = tmp_path / "contracts_fresh.json"
        proc = _run_audit("--check", "--planes", "1", "--quiet",
                          "--programs", "p1/single", "--json", str(out))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        data = json.loads(out.read_text())
        assert "p1/single" in data["contracts"]

    def test_unknown_inject_mode_rejected(self):
        proc = _run_audit("--check", "--inject", "nonsense")
        assert proc.returncode == 2  # argparse choices error
        assert "--inject" in proc.stderr

    def test_inject_modes_documented(self):
        assert set(INJECT_MODES) == {"f64_noise", "x64", "no_donate",
                                     "host_callback", "extra_collective"}
