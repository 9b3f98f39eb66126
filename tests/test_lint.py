"""graftlint: per-rule true positives on fixtures, suppressions, baseline
workflow, full-package-clean, the runtime retrace guard (ISSUE 2), and the
distributed-correctness layer — dataflow engine, use-after-donate /
collective-consistency / durable-store-protocol rules, --changed scoping,
SARIF output, and the runtime donation guard (ISSUE 17)."""

import json
import os
import shutil
import subprocess

import numpy as np
import pytest

from deeplearning4j_tpu.analysis import donation_guard
from deeplearning4j_tpu.analysis import lint as lint_mod
from deeplearning4j_tpu.analysis import retrace_guard
from deeplearning4j_tpu.analysis import rules as rules_mod
from deeplearning4j_tpu.analysis.engine import Index
from deeplearning4j_tpu.utils import bucketing

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures", "graftlint")
PACKAGE = os.path.join(os.path.dirname(HERE), "deeplearning4j_tpu")


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    for var in ("DL4J_TPU_BUCKETING", "DL4J_TPU_BUCKETS",
                "DL4J_TPU_BUCKET_MIN", "DL4J_TPU_BUCKET_GROWTH",
                "DL4J_TPU_DEVICE_PREFETCH", "DL4J_TPU_RETRACE_GUARD",
                "DL4J_TPU_STRICT_RETRACE", "DL4J_TPU_DONATION_GUARD"):
        monkeypatch.delenv(var, raising=False)
    bucketing.telemetry().reset()
    retrace_guard.reset_warnings()
    donation_guard.reset_warnings()
    yield


@pytest.fixture(scope="module")
def fixture_findings():
    return rules_mod.run(Index(FIXTURES))


def _hits(findings, rule, filename, func):
    return [f for f in findings
            if f.rule == rule and f.path.endswith(filename) and f.func == func]


# ---------------------------------------------------------------------------
# one fixture-proven true positive per rule class
# ---------------------------------------------------------------------------


class TestRuleTruePositives:
    def test_host_sync(self, fixture_findings):
        fs = fixture_findings
        assert _hits(fs, "host-sync", "host_sync_bad.py", "serve")
        assert _hits(fs, "host-sync", "host_sync_bad.py", "serve_scalar")
        assert _hits(fs, "host-sync", "host_sync_bad.py", "serve_item")
        assert _hits(fs, "host-sync", "host_sync_bad.py", "serve_get")

    def test_retrace_hazard(self, fixture_findings):
        fs = fixture_findings
        assert _hits(fs, "retrace-hazard", "retrace_bad.py", "train")
        assert _hits(fs, "retrace-hazard", "retrace_bad.py", "build")
        assert _hits(fs, "retrace-hazard", "retrace_bad.py", "call_fresh")
        assert _hits(fs, "retrace-hazard", "retrace_bad.py", "scaled")

    def test_jit_purity(self, fixture_findings):
        fs = fixture_findings
        msgs = " ".join(
            f.message for f in _hits(fs, "jit-purity", "purity_bad.py",
                                     "noisy_step"))
        assert "time.time" in msgs
        assert "numpy.random.rand" in msgs
        assert "_CALLS" in msgs

    def test_numpy_on_tracer(self, fixture_findings):
        fs = fixture_findings
        assert _hits(fs, "numpy-on-tracer", "tracer_np_bad.py", "bad_norm")
        # metadata-only numpy stays allowed
        assert not _hits(fs, "numpy-on-tracer", "tracer_np_bad.py", "ok_shape")

    def test_lock_discipline(self, fixture_findings):
        fs = fixture_findings
        assert _hits(fs, "lock-discipline", "locks_bad.py", "put_unlocked")
        assert _hits(fs, "lock-discipline", "locks_bad.py", "pop_unlocked")
        # mutation under the lock is clean
        assert not _hits(fs, "lock-discipline", "locks_bad.py", "put_locked")

    def test_lock_discipline_hot_sync(self, fixture_findings):
        """The serving-scheduler sub-check: no host sync / jitted dispatch
        while holding a lock (serve/scheduler.py's admission loop)."""
        fs = fixture_findings
        assert _hits(fs, "lock-discipline", "locks_hot_bad.py",
                     "dispatch_under_lock")
        under = _hits(fs, "lock-discipline", "locks_hot_bad.py",
                      "sync_under_lock")
        msgs = " ".join(f.message for f in under)
        assert "float()" in msgs            # scalar coercion under the lock
        assert "np.asarray" in msgs         # materialization under the lock
        assert "device_get" in msgs         # explicit transfer under the lock
        # the same syncs with the lock released are this rule's GOOD shape
        # (host-sync still owns them on the dispatch path)
        assert not _hits(fs, "lock-discipline", "locks_hot_bad.py",
                         "sync_outside_lock")
        assert not _hits(fs, "lock-discipline", "locks_hot_bad.py",
                         "sync_suppressed")

    def test_monotonic_clock(self, fixture_findings):
        fs = fixture_findings
        assert _hits(fs, "monotonic-clock", "clock_bad.py", "elapsed_direct")
        # both the deadline arithmetic and the ordering compare flag
        assert len(_hits(fs, "monotonic-clock", "clock_bad.py",
                         "deadline_compare")) == 2
        # value-only timestamps and the monotonic clock stay allowed
        assert not _hits(fs, "monotonic-clock", "clock_bad.py",
                         "timestamp_only")
        assert not _hits(fs, "monotonic-clock", "clock_bad.py",
                         "monotonic_ok")

    def test_cost_analysis_off_hot_path(self, fixture_findings):
        fs = fixture_findings
        rule = "cost-analysis-off-hot-path"
        assert _hits(fs, rule, "cost_analysis_bad.py", "step")
        assert _hits(fs, rule, "cost_analysis_bad.py", "step_mem")
        # trace export inside a traced body
        assert _hits(fs, rule, "cost_analysis_bad.py", "step_traced.body")
        # fleet federation (snapshot publish / collector scan) per dispatch
        assert _hits(fs, rule, "cost_analysis_bad.py", "step_publish")
        assert _hits(fs, rule, "cost_analysis_bad.py", "step_collect")
        # plain dict lookups on the dispatch path stay allowed
        assert not _hits(fs, rule, "cost_analysis_bad.py", "step_ok")

    def test_step_wiring(self, fixture_findings):
        fs = fixture_findings
        rule = "step-wiring"
        assert _hits(fs, rule, "step_wiring_bad.py", "make_step")
        assert _hits(fs, rule, "step_wiring_bad.py", "make_step_kw")
        # a non-donating jit is not a step executable — stays allowed
        assert not _hits(fs, rule, "step_wiring_bad.py", "make_output")

    def test_inline_suppressions(self, fixture_findings):
        fs = fixture_findings
        for rule, filename, func in (
            ("host-sync", "host_sync_bad.py", "serve_suppressed"),
            ("retrace-hazard", "retrace_bad.py", "suppressed_loop"),
            ("jit-purity", "purity_bad.py", "quiet_step"),
            ("numpy-on-tracer", "tracer_np_bad.py", "suppressed"),
            ("lock-discipline", "locks_bad.py", "put_suppressed"),
            ("monotonic-clock", "clock_bad.py", "suppressed"),
            ("cost-analysis-off-hot-path", "cost_analysis_bad.py",
             "step_suppressed"),
            ("step-wiring", "step_wiring_bad.py", "make_step_suppressed"),
            ("use-after-donate", "donate_bad.py", "read_suppressed"),
            ("collective-consistency", "collective_bad.py",
             "ranky_suppressed"),
            ("collective-consistency", "collective_bad.py",
             "switch_unverifiable_suppressed"),
            ("durable-store-protocol", "store_bad.py", "save_suppressed"),
        ):
            assert not _hits(fs, rule, filename, func), (rule, func)


# ---------------------------------------------------------------------------
# distributed-correctness rule families (ISSUE 17)
# ---------------------------------------------------------------------------


class TestUseAfterDonate:
    RULE = "use-after-donate"

    def test_read_after_donate(self, fixture_findings):
        hits = _hits(fixture_findings, self.RULE, "donate_bad.py",
                     "read_after_donate")
        assert hits and "donated" in hits[0].message

    def test_loop_carry(self, fixture_findings):
        hits = _hits(fixture_findings, self.RULE, "donate_bad.py",
                     "loop_carry_bad")
        assert hits and "loop" in hits[0].message

    def test_alias_kills_base(self, fixture_findings):
        hits = _hits(fixture_findings, self.RULE, "donate_bad.py",
                     "alias_bad")
        assert hits and "model.params" in hits[0].message

    def test_interprocedural_summary(self, fixture_findings):
        hits = _hits(fixture_findings, self.RULE, "donate_bad.py",
                     "interproc_bad")
        assert hits and "_helper_step" in hits[0].message

    def test_field_sensitive_self_attr(self, fixture_findings):
        hits = _hits(fixture_findings, self.RULE, "donate_bad.py",
                     "Trainer.fit_bad")
        assert hits and "self.params" in hits[0].message

    def test_good_shapes_stay_clean(self, fixture_findings):
        for func in ("rebind_ok", "barrier_ok", "loop_carry_ok",
                     "alias_copy_ok", "interproc_ok", "Trainer.fit_ok"):
            assert not _hits(fixture_findings, self.RULE, "donate_bad.py",
                             func), func


class TestCollectiveConsistency:
    RULE = "collective-consistency"

    def test_rank_dependent_collective(self, fixture_findings):
        hits = _hits(fixture_findings, self.RULE, "collective_bad.py",
                     "ranky_bad")
        assert hits and "rank-dependent" in hits[0].message

    def test_axis_not_bound_by_shard_map(self, fixture_findings):
        hits = _hits(fixture_findings, self.RULE, "collective_bad.py",
                     "_step_wrong_axis")
        assert hits and "'model'" in hits[0].message

    def test_duplicate_axis(self, fixture_findings):
        hits = _hits(fixture_findings, self.RULE, "collective_bad.py",
                     "_step_dup_axis")
        assert hits and "repeats" in hits[0].message

    def test_divergent_cond_arms(self, fixture_findings):
        hits = _hits(fixture_findings, self.RULE, "collective_bad.py",
                     "cond_divergent_bad")
        assert hits and "different collective sequences" in hits[0].message

    def test_unresolvable_rank_selected_switch(self, fixture_findings):
        hits = _hits(fixture_findings, self.RULE, "collective_bad.py",
                     "switch_unverifiable_bad")
        assert hits and "statically" in hits[0].message

    def test_good_shapes_stay_clean(self, fixture_findings):
        for func in ("_step_ok", "ranky_hoisted_ok", "cond_matching_ok"):
            assert not _hits(fixture_findings, self.RULE,
                             "collective_bad.py", func), func


class TestDurableStoreProtocol:
    RULE = "durable-store-protocol"

    def test_raw_open_w(self, fixture_findings):
        hits = _hits(fixture_findings, self.RULE, "store_bad.py", "save_bad")
        assert hits and "os.replace" in hits[0].message

    def test_np_save(self, fixture_findings):
        hits = _hits(fixture_findings, self.RULE, "store_bad.py",
                     "save_np_bad")
        assert hits and "not atomic" in hits[0].message

    def test_exclusive_create_spelling(self, fixture_findings):
        hits = _hits(fixture_findings, self.RULE, "store_bad.py",
                     "exclusive_bad")
        assert hits and "os.link" in hits[0].message

    def test_interprocedural_path_taint(self, fixture_findings):
        # the helper itself writes; the durable marker is in its CALLER
        hits = _hits(fixture_findings, self.RULE, "store_bad.py",
                     "_write_raw")
        assert hits

    def test_good_shapes_stay_clean(self, fixture_findings):
        for func in ("save_good", "exclusive_good", "transient_ok"):
            assert not _hits(fixture_findings, self.RULE, "store_bad.py",
                             func), func


class TestProtocolSafeSinks:
    """The netstore client is a protocol-safe durable sink: it frames and
    CRCs payloads end-to-end itself, so a durable key flowing into one of
    its functions is the protocol being honored, not bypassed — durable
    param taint must stop at the module boundary."""

    @staticmethod
    def _make_pkg(tmp_path, modname):
        pkg = tmp_path / "p"
        pkg.mkdir()
        (pkg / f"{modname}.py").write_text(
            "def nset(key, data):\n"
            "    with open(key, 'w') as f:\n"
            "        f.write('x')\n")
        (pkg / "caller.py").write_text(
            f"from p.{modname} import nset\n\n"
            "def publish():\n"
            "    nset('bundle/params_0.npz', b'x')\n")
        return Index(str(pkg))

    def test_netstore_callee_not_tainted(self, tmp_path):
        df = self._make_pkg(tmp_path, "netstore").dataflow
        assert "p.netstore::nset" not in df.durable_params

    def test_same_shape_elsewhere_still_tainted(self, tmp_path):
        df = self._make_pkg(tmp_path, "diskstore").dataflow
        assert 0 in df.durable_params["p.diskstore::nset"]

    def test_real_netstore_module_clean(self):
        findings = rules_mod.run(Index(os.path.join(PACKAGE, "parallel")))
        hits = [f for f in findings
                if f.rule == "durable-store-protocol"
                and f.path.endswith("netstore.py")]
        assert not hits, [f.message for f in hits]


class TestDataflow:
    """Unit tests on the interprocedural field-sensitive layer itself."""

    @pytest.fixture(scope="class")
    def df(self):
        return Index(FIXTURES).dataflow

    def test_param_donation_summary(self, df):
        # _helper_step forwards its params/opt positional args into a
        # donating jit -> interprocedural summary says params 0 and 1 die
        q = "graftlint.donate_bad::_helper_step"
        assert sorted(df.param_donations[q]) == [0, 1]

    def test_field_sensitive_class_attr(self, df):
        # Trainer.__init__ binds self._step to a default-donating
        # StepProgram; the per-class attr table carries it
        table = df.class_attr_donations[("graftlint.donate_bad", "Trainer")]
        assert table["_step"].positions == (0, 1, 2)

    def test_global_donation_binding(self, df):
        don = df.global_donations[("graftlint.donate_bad", "_jstep")]
        assert don.positions == (0, 1)

    def test_durable_param_taint_crosses_calls(self, df):
        # save_via_helper passes a bundle-marked path into _write_raw
        q = "graftlint.store_bad::_write_raw"
        assert 0 in df.durable_params[q]

    def test_dispatch_site_keys(self, df):
        idx = df.index
        fi = idx.functions["graftlint.donate_bad::Trainer.fit_bad"]
        (site,) = df.dispatch_sites(fi)
        assert [(p, k) for p, k, _ in site.donated] == [
            (0, ("attr", "self", "params")),
            (1, ("attr", "self", "opt")),
            (2, ("attr", "self", "state")),
        ]

    def test_non_literal_donate_argnums_skipped(self, tmp_path):
        # a computed donate spec must not be guessed at
        pkg = tmp_path / "p"
        pkg.mkdir()
        (pkg / "m.py").write_text(
            "import jax\n\n"
            "def f(a, b):\n    return a + b\n\n"
            "def make(donate):\n"
            "    return jax.jit(f, donate_argnums=(0,) if donate else ())\n")
        df = Index(str(pkg)).dataflow
        assert "p.m::make" not in df.factory_returns


# ---------------------------------------------------------------------------
# CLI + baseline workflow
# ---------------------------------------------------------------------------


class TestCli:
    def test_fixtures_fail_without_baseline(self, capsys):
        assert lint_mod.main([FIXTURES, "--no-baseline"]) == 1
        out = capsys.readouterr()
        assert "[host-sync]" in out.out
        assert "new finding(s)" in out.err

    def test_fix_baseline_then_clean(self, tmp_path, capsys):
        bl = str(tmp_path / "baseline.json")
        assert lint_mod.main([FIXTURES, "--baseline", bl,
                              "--fix-baseline"]) == 0
        data = json.load(open(bl))
        assert data["allowed"] and all(
            c >= 1 for c in data["allowed"].values())
        assert lint_mod.main([FIXTURES, "--baseline", bl]) == 0
        out = capsys.readouterr()
        assert "clean" in out.out

    def test_stale_baseline_entries_reported_not_fatal(self, tmp_path, capsys):
        bl = tmp_path / "baseline.json"
        lint_mod.main([FIXTURES, "--baseline", str(bl), "--fix-baseline"])
        data = json.load(open(bl))
        data["allowed"]["gone.py::host-sync::f::x = y"] = 1
        bl.write_text(json.dumps(data))
        assert lint_mod.main([FIXTURES, "--baseline", str(bl)]) == 0
        assert "stale" in capsys.readouterr().out

    def test_rule_subset_and_unknown_rule(self, capsys):
        assert lint_mod.main([FIXTURES, "--no-baseline",
                              "--rules", "lock-discipline"]) == 1
        out = capsys.readouterr().out
        assert "[lock-discipline]" in out and "[host-sync]" not in out
        assert lint_mod.main([FIXTURES, "--rules", "no-such-rule"]) == 2

    def test_missing_target(self):
        assert lint_mod.main(["/no/such/path"]) == 2

    def test_package_lints_clean_against_checked_in_baseline(self):
        # the tier-1 CI gate: the shipped package vs the shipped baseline
        assert lint_mod.main([PACKAGE]) == 0

    def test_fingerprints_survive_line_shifts(self, tmp_path):
        src = (
            "import jax\nimport numpy as np\n\n"
            "def fwd(x):\n    return x\n\n_jf = jax.jit(fwd)\n\n"
            "def serve(x):\n    out = _jf(x)\n    return np.asarray(out)\n"
        )
        pkg = tmp_path / "minipkg"
        pkg.mkdir()
        (pkg / "m.py").write_text(src)
        bl = str(tmp_path / "bl.json")
        assert lint_mod.main([str(pkg), "--baseline", bl,
                              "--fix-baseline"]) == 0
        # shift every line down: same finding, different line number
        (pkg / "m.py").write_text("# a comment\n# another\n" + src)
        assert lint_mod.main([str(pkg), "--baseline", bl]) == 0


_VIOLATION_SRC = (
    "import time\n\n"
    "def age(t0):\n"
    "    return time.time() - t0\n")


class TestChangedScope:
    """--changed: only findings in git-modified/untracked files can fail."""

    @pytest.fixture()
    def repo(self, tmp_path):
        if shutil.which("git") is None:
            pytest.skip("git unavailable")
        env = dict(os.environ,
                   GIT_AUTHOR_NAME="t", GIT_AUTHOR_EMAIL="t@t",
                   GIT_COMMITTER_NAME="t", GIT_COMMITTER_EMAIL="t@t")

        def git(*args):
            subprocess.run(["git", "-C", str(tmp_path)] + list(args),
                           check=True, capture_output=True, env=env)

        pkg = tmp_path / "pkg"
        pkg.mkdir()
        # a committed file that already violates monotonic-clock
        (pkg / "old.py").write_text(_VIOLATION_SRC)
        git("init", "-q")
        git("add", "-A")
        git("commit", "-q", "-m", "seed")
        return pkg, git

    def test_only_changed_files_can_fail(self, repo, capsys):
        pkg, git = repo
        # clean tree: the committed violation is out of scope
        assert lint_mod.main([str(pkg), "--no-baseline", "--changed"]) == 0
        capsys.readouterr()
        # an untracked violating file IS in scope
        (pkg / "new.py").write_text(_VIOLATION_SRC.replace("age", "lag"))
        assert lint_mod.main([str(pkg), "--no-baseline", "--changed"]) == 1
        out = capsys.readouterr().out
        assert "new.py" in out and "old.py" not in out
        # once committed, the tree is quiet again on the pre-commit path
        git("add", "-A")
        git("commit", "-q", "-m", "more")
        assert lint_mod.main([str(pkg), "--no-baseline", "--changed"]) == 0

    def test_changed_outside_a_repo_is_a_usage_error(self, tmp_path):
        pkg = tmp_path / "norepo"
        pkg.mkdir()
        (pkg / "m.py").write_text("x = 1\n")
        assert lint_mod.main([str(pkg), "--changed"]) == 2

    def test_fix_baseline_rejects_changed(self, repo):
        pkg, _git = repo
        assert lint_mod.main([str(pkg), "--changed", "--fix-baseline"]) == 2


# Enough of the SARIF 2.1.0 schema to catch structural regressions without
# vendoring the full OASIS document.
_SARIF_SCHEMA = {
    "type": "object",
    "required": ["version", "runs"],
    "properties": {
        "version": {"const": "2.1.0"},
        "runs": {
            "type": "array", "minItems": 1,
            "items": {
                "type": "object",
                "required": ["tool", "results"],
                "properties": {
                    "tool": {
                        "type": "object", "required": ["driver"],
                        "properties": {"driver": {
                            "type": "object", "required": ["name", "rules"],
                            "properties": {"rules": {
                                "type": "array",
                                "items": {
                                    "type": "object",
                                    "required": ["id"],
                                }}}}},
                    },
                    "results": {
                        "type": "array",
                        "items": {
                            "type": "object",
                            "required": ["ruleId", "level", "message",
                                         "locations"],
                            "properties": {
                                "level": {"enum": ["error", "note",
                                                   "warning", "none"]},
                                "message": {
                                    "type": "object",
                                    "required": ["text"],
                                },
                                "baselineState": {
                                    "enum": ["new", "unchanged", "updated",
                                             "absent"]},
                                "locations": {
                                    "type": "array", "minItems": 1},
                            },
                        },
                    },
                },
            },
        },
    },
}


class TestSarif:
    def test_sarif_log_is_valid_and_marks_new(self, tmp_path):
        jsonschema = pytest.importorskip("jsonschema")
        out = tmp_path / "out.sarif"
        assert lint_mod.main([FIXTURES, "--no-baseline",
                              "--sarif", str(out)]) == 1
        doc = json.loads(out.read_text())
        jsonschema.validate(doc, _SARIF_SCHEMA)
        results = doc["runs"][0]["results"]
        assert results
        assert all(r["level"] == "error" and r["baselineState"] == "new"
                   for r in results)
        rule_ids = {r["id"] for r in doc["runs"][0]["tool"]["driver"]["rules"]}
        assert {r["ruleId"] for r in results} <= rule_ids
        assert all(r["partialFingerprints"]["graftlint/v1"]
                   for r in results)

    def test_sarif_grandfathered_are_notes(self, tmp_path):
        jsonschema = pytest.importorskip("jsonschema")
        bl = str(tmp_path / "bl.json")
        assert lint_mod.main([FIXTURES, "--baseline", bl,
                              "--fix-baseline"]) == 0
        out = tmp_path / "out.sarif"
        assert lint_mod.main([FIXTURES, "--baseline", bl,
                              "--sarif", str(out)]) == 0
        doc = json.loads(out.read_text())
        jsonschema.validate(doc, _SARIF_SCHEMA)
        results = doc["runs"][0]["results"]
        assert results
        assert all(r["level"] == "note" and r["baselineState"] == "unchanged"
                   for r in results)


class TestDonationGuard:
    """DL4J_TPU_DONATION_GUARD=1 poisons donated host refs after dispatch.

    The guard exists for backends that IGNORE ``donate_argnums`` (the leaf
    survives and a use-after-donate silently reads stale data). XLA:CPU
    honors donation when an output can reuse the buffer, so the tests force
    the forgiving path with a donated input whose shape matches no output —
    the backend must leave it alive, and the guard must kill it.
    """

    @staticmethod
    def _program():
        import jax.numpy as jnp

        from deeplearning4j_tpu.nn.step_program import StepProgram

        def body(params, opt, state, x):
            # output "w" is (2,); the donated (5,) input can't be reused
            return ({"w": params["w"][:2]}, opt, state,
                    jnp.sum(params["w"]))

        return StepProgram(body, "test.guard", aot_wrap=False), jnp

    def test_check_after_dispatch_poisons_live_leaf(self, monkeypatch):
        import jax.numpy as jnp
        arr = jnp.ones((3,))
        before = donation_guard._trips.value()
        monkeypatch.setenv("DL4J_TPU_DONATION_GUARD", "1")
        trips = donation_guard.check_after_dispatch(
            "unit.site", [{"w": arr}], (0,), outputs=jnp.zeros(()))
        assert [t.position for t in trips] == [0]
        assert trips[0].shape == (3,)
        assert arr.is_deleted()
        assert donation_guard._trips.value() == before + 1
        # second sweep over the same (now dead) leaf is a no-op
        assert donation_guard.check_after_dispatch(
            "unit.site", [{"w": arr}], (0,), outputs=jnp.zeros(())) == []

    @pytest.mark.filterwarnings("ignore:Some donated buffers")
    def test_guard_poisons_through_step_program(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_DONATION_GUARD", "1")
        prog, jnp = self._program()
        params = {"w": jnp.ones((5,))}
        leaf = params["w"]
        before = donation_guard._trips.value()
        new_p, opt, state, loss = prog(params, {}, {}, jnp.ones((4,)))
        assert leaf.is_deleted()
        assert donation_guard._trips.value() > before
        # outputs stay usable: the guard only kills the donated INPUT refs
        assert float(loss) == 5.0
        with pytest.raises(RuntimeError):
            float(leaf[0])

    @pytest.mark.filterwarnings("ignore:Some donated buffers")
    def test_guard_off_by_default(self):
        prog, jnp = self._program()
        params = {"w": jnp.ones((5,))}
        leaf = params["w"]
        before = donation_guard._trips.value()
        prog(params, {}, {}, jnp.ones((4,)))
        # the backend couldn't reuse the buffer and nobody poisoned it:
        # exactly the silent-survival mode the guard exists to expose
        assert not leaf.is_deleted()
        assert donation_guard._trips.value() == before

    def test_guard_zero_disables(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_DONATION_GUARD", "0")
        assert not donation_guard.enabled()


# ---------------------------------------------------------------------------
# runtime retrace guard
# ---------------------------------------------------------------------------


def _bn_model(seed=11):
    from deeplearning4j_tpu.nn.input_type import InputType
    from deeplearning4j_tpu.nn.layers import BatchNorm, Dense, OutputLayer
    from deeplearning4j_tpu.nn.model import (
        MultiLayerConfiguration, MultiLayerNetwork)

    conf = MultiLayerConfiguration(
        layers=(
            Dense(n_out=16, activation="identity"),
            BatchNorm(),
            Dense(n_out=8, activation="tanh"),
            OutputLayer(n_out=2, activation="softmax"),
        ),
        input_type=InputType.feed_forward(4),
        updater={"type": "sgd", "lr": 0.1},
        seed=seed,
    )
    return MultiLayerNetwork(conf).init()


class _FreshKey:
    """Hashable but never equal across instances: every call with a new
    instance is a fresh jit cache entry — a deliberate retrace."""


class TestRetraceGuard:
    def test_predicts_exact_compiles_on_bucket_scenario(self, monkeypatch):
        # acceptance: the test_bucketing one-compile-per-bucket scenario —
        # sizes 3..8 hit buckets {4, 8}; 9 and 12 hit 16: exactly 3 compiles
        monkeypatch.setenv("DL4J_TPU_RETRACE_GUARD", "1")
        m = _bn_model()
        x = np.random.RandomState(0).randn(12, 4).astype(np.float32)
        for n in (3, 4, 5, 6, 7, 8, 9, 12):
            m.output(x[:n])
        tel = bucketing.telemetry()
        assert retrace_guard.predicted_compiles("mln.output") == 3
        assert tel.compiles("mln.output") == 3
        rep = retrace_guard.check("mln.output")
        assert rep.ok and rep.compiles == rep.predicted == 3

    def test_guard_disabled_by_default(self):
        assert retrace_guard.check_if_enabled("mln.output") is None

    def test_strict_raises_on_unhashable_static_arg(self, monkeypatch):
        # acceptance: a static arg that hashes fresh per instance forces an
        # extra trace beyond the single bucket the traffic used
        monkeypatch.setenv("DL4J_TPU_STRICT_RETRACE", "1")
        monkeypatch.setenv("DL4J_TPU_BUCKETS", "8")
        g = retrace_guard.RetraceGuard(
            lambda x, key: x * 2.0, "guard.static", static_argnums=(1,))
        x = np.ones((8, 3), np.float32)
        g(x, _FreshKey())                     # compile 1, bucket {8}: ok
        assert g.report.ok
        with pytest.raises(retrace_guard.RetraceError, match="guard.static"):
            g(x, _FreshKey())                 # compile 2, still bucket {8}

    def test_nonstrict_warns_once_per_site(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_RETRACE_GUARD", "1")
        monkeypatch.setenv("DL4J_TPU_BUCKETS", "8")
        g = retrace_guard.RetraceGuard(
            lambda x, key: x + 1.0, "guard.warn", static_argnums=(1,))
        x = np.ones((8, 3), np.float32)
        g(x, _FreshKey())
        with pytest.warns(retrace_guard.RetraceWarning, match="guard.warn"):
            g(x, _FreshKey())
        import warnings as _w
        with _w.catch_warnings():
            _w.simplefilter("error")          # second violation: warn-once
            g(x, _FreshKey())
        assert not g.report.ok

    def test_extra_allowed_budget(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_STRICT_RETRACE", "1")
        tel = bucketing.telemetry()
        tel.record_hit("guard.budget", 4, 8)
        tel.record_trace("guard.budget", (8,))
        tel.record_trace("guard.budget", (8,))
        assert retrace_guard.check("guard.budget", extra_allowed=1).ok is True
        with pytest.raises(retrace_guard.RetraceError):
            retrace_guard.check("guard.budget")

    def test_fit_guard_clean_on_padded_stream(self, monkeypatch):
        # the wired mln.step/mln.fit pairing: a padded fit (one executable,
        # one bucket) passes the strict guard end to end
        monkeypatch.setenv("DL4J_TPU_STRICT_RETRACE", "1")
        monkeypatch.setenv("DL4J_TPU_CHAIN_STEPS", "0")
        rs = np.random.RandomState(0)
        x = rs.randn(20, 4).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[rs.randint(0, 2, 20)]
        m = _bn_model()
        m.fit((x, y), epochs=2, batch_size=8)   # 20 % 8 != 0: padded tail
        tel = bucketing.telemetry()
        assert tel.compiles("mln.step") == 1
        assert retrace_guard.check("mln.step", hits_site="mln.fit").ok
