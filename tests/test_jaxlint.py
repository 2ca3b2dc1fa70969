"""The device hot-path analyzers (tidy/jaxlint.py + tidy/absint.py):
host-sync/retrace/reduction lints, the limb-width interval proofs, the
unified tools/check.py entry, and the compile-count runtime guard
(CompileRegistry → profile_e2e/bench → tools/bench_gate.py).

Fixture modules under tests/fixtures/jaxlint/ carry one seeded
violation per rule; the tests assert EXACT findings so a rule that
drifts (fires twice, goes silent, moves passes) fails loudly.
"""

import importlib.util
import json
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "fixtures" / "jaxlint"


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        f"tool_{name}", REPO / "tools" / f"{name}.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --- the repo itself is clean (the CI gate covers the new passes) --------


def test_repo_clean_under_device_passes():
    """host-sync, retrace, reduction, absint over the real repo: zero
    findings — every sanctioned sync/wrap is annotated where it lives,
    and the baseline ships EMPTY."""
    from tigerbeetle_tpu import tidy
    from tigerbeetle_tpu.tidy.findings import load_baseline

    findings = tidy.run_passes(
        REPO, ["host-sync", "retrace", "reduction", "absint"]
    )
    assert findings == [], [f.render() for f in findings]
    assert load_baseline() == {}


def test_check_tool_json_runs_clean():
    """`tools/check.py --json` — the single static-analysis entry — exits
    0 on the repo with every pass selected and an empty baseline."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check.py"), "--json"],
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert report["ok"] is True
    assert set(report["passes"]) == {
        "ownership", "determinism", "markers",
        "host-sync", "retrace", "reduction", "absint",
        "native-layout", "native-abi", "native-absint",
        "vsrlint", "quorum", "protomodel",
    }
    assert report["suppressed"] == []  # empty baseline: nothing suppressed


# --- host-sync pass ------------------------------------------------------


def test_hostsync_fixture_exact_findings():
    from tigerbeetle_tpu.tidy import jaxlint

    findings = jaxlint.analyze_file(
        FIXTURES / "hostsync_bad.py", REPO, passes=("host-sync",)
    )
    got = [(f.code, f.scope, f.subject) for f in findings]
    assert got == [
        ("traced-branch", "bad_kernel", "if"),
        ("host-sync", "bad_kernel", "float"),
        ("host-sync", "bad_kernel", "np.asarray"),
        ("host-sync", "bad_kernel", ".item"),
        ("unfenced-sync", "bad_dispatch", "block_until_ready"),
        ("host-sync", "bad_materialize", "bool"),
    ], findings
    # Sync findings explain the cost, not just the rule.
    assert "sync" in findings[1].message


def test_hostsync_seam_exempts_sanctioned_sites():
    """The same materialization inside a seam-listed function is clean:
    the seam IS the design (docs/COMMIT_PIPELINE.md dispatch/finish)."""
    from tigerbeetle_tpu.tidy import jaxlint

    rel = "tests/fixtures/jaxlint/hostsync_bad.py"
    findings = jaxlint.analyze_file(
        FIXTURES / "hostsync_bad.py", REPO, passes=("host-sync",),
        seam=frozenset({(rel, "bad_dispatch"), (rel, "bad_materialize")}),
    )
    assert [f.scope for f in findings] == ["bad_kernel"] * 4


# --- retrace pass --------------------------------------------------------


def test_retrace_fixture_exact_findings():
    from tigerbeetle_tpu.tidy import jaxlint

    findings = jaxlint.analyze_file(
        FIXTURES / "retrace_bad.py", REPO, passes=("retrace",)
    )
    got = [(f.code, f.scope, f.subject) for f in findings]
    assert got == [
        ("retrace-shape", "feed", "create_transfers_fast"),
        ("retrace-shape", "feed", "create_transfers_fast"),
        ("retrace-static-arg", "feed", "create_transfers_exact.max_sweeps"),
        ("retrace-kwargs", "feed", "create_transfers_fast"),
        ("retrace-shape", "feed_named", "create_transfers_fast"),
    ], findings
    # The named-temporary finding anchors at the CONSTRUCTION line (where
    # the padding fix — or a precise allow= — belongs), not the call.
    named = findings[-1]
    assert "tmp" in named.message
    src = (FIXTURES / "retrace_bad.py").read_text().splitlines()
    assert "np.zeros" in src[named.line - 1]


def test_commit_entry_is_compile_gated():
    """The commit kernel is a registered jit entry: batch-sized arrays
    reaching it are flagged (a retrace per batch length, i.e. a fresh XLA
    compile inside a request on the commit thread), while a sanctioned
    pad helper's pow-2 buckets pass clean."""
    from tigerbeetle_tpu.tidy import jaxlint, manifest

    # The real kernel + its gate are registered, not just the fixture's.
    assert "create_transfers_fast" in manifest.JIT_ENTRIES
    assert {"_device_batch", "_pad_slots"} <= manifest.JAXLINT_PAD_HELPERS
    sm = "tigerbeetle_tpu/models/state_machine.py"
    assert (sm, "StateMachine.create_transfers_finish") in manifest.JAXLINT_SYNC_SEAM
    # The exact kernel's results are taken back in its finish half, from
    # the single-phase path and from create_transfers_finish alike; what
    # runs before the device is done stays outside the seam.
    assert (sm, "StateMachine._exact_finish") in manifest.JAXLINT_SYNC_SEAM
    for outside in ("create_transfers_dispatch", "_exact_stage", "_exact_dispatch",
                    "_create_transfers_exact", "_ct_dispatch_stage"):
        assert (sm, f"StateMachine.{outside}") not in manifest.JAXLINT_SYNC_SEAM
    from tigerbeetle_tpu.models.state_machine import StateMachine

    for name in {q.split(".")[1] for f, q in manifest.JAXLINT_SYNC_SEAM if f == sm}:
        assert callable(getattr(StateMachine, name)), name  # no stale entry

    findings = jaxlint.analyze_file(
        FIXTURES / "retrace_commit_batch.py", REPO, passes=("retrace",)
    )
    got = [(f.code, f.scope, f.subject) for f in findings]
    assert got == [
        ("retrace-shape", "commit_ungated", "create_transfers_fast"),
    ] * 4, findings
    # No finding in commit_gated: _pad_slots' result is shape-stabilized.
    assert all(f.scope != "commit_gated" for f in findings)


# --- reduction pass ------------------------------------------------------


def test_reduction_fixture_exact_findings():
    from tigerbeetle_tpu.tidy import jaxlint

    findings = jaxlint.analyze_file(
        FIXTURES / "reduction_bad.py", REPO, passes=("reduction",)
    )
    got = [(f.code, f.subject) for f in findings]
    assert got == [
        ("float-dtype", "float32"),
        ("unordered-reduce", ".at.add"),
        ("unordered-reduce", "segment_sum"),
        ("axis-order", "psum"),
    ], findings


# --- absint pass ---------------------------------------------------------


def test_absint_fixture_exact_findings():
    from tigerbeetle_tpu.tidy import absint

    findings = absint.analyze_file(FIXTURES / "absint_bad.py", REPO, 32)
    got = [(f.code, f.scope) for f in findings]
    assert got == [
        ("limb-overflow", "unsafe_add"),
        ("limb-overflow", "unsafe_shift"),
        ("limb-underflow", "unsafe_sub"),
        ("range-obligation", "overflowing_call"),
    ], findings
    # Messages carry the intervals — the proof state, not just a verdict.
    assert "[0,4294967295]" in findings[0].message


def test_absint_proves_u128_inwidth():
    """The acceptance bar: every arithmetic op in ops/u128.py proves
    in-width from the annotated entry ranges (intentional carry wraps
    carry inline allow= reasons), and the interpreter demonstrably
    VISITED the arithmetic (checked-op count, not a silent skip)."""
    from tigerbeetle_tpu.tidy import absint

    findings, checked = absint.prove_file(
        REPO / "tigerbeetle_tpu" / "ops" / "u128.py", REPO, 32
    )
    assert findings == [], [f.render() for f in findings]
    assert checked >= 15, checked  # mul_u32 hi-sum alone is 4 proven adds

    findings64, checked64 = absint.prove_file(
        REPO / "tigerbeetle_tpu" / "lsm" / "scan.py", REPO, 64
    )
    assert findings64 == [], [f.render() for f in findings64]
    assert checked64 >= 2, checked64  # fold56 hi-fold shift + tag<<56


def test_absint_range_annotation_parsing():
    from tigerbeetle_tpu.tidy.absint import Iv, parse_ranges
    from tigerbeetle_tpu.tidy.annotations import LineAnnotations

    a = LineAnnotations(1, {"range": "x:0..0xFF,y:16..32"}, "")
    assert parse_ranges(a) == {"x": Iv(0, 255), "y": Iv(16, 32)}
    bad = LineAnnotations(1, {"range": "x=0..5"}, "")
    with pytest.raises(ValueError):
        parse_ranges(bad)


# --- clean-inverse fixture ------------------------------------------------


def test_clean_fixture_zero_findings_all_passes():
    from tigerbeetle_tpu.tidy import absint, jaxlint

    findings = jaxlint.analyze_file(
        FIXTURES / "clean.py", REPO,
        passes=("host-sync", "retrace", "reduction"),
    )
    assert findings == [], [f.render() for f in findings]
    assert absint.analyze_file(FIXTURES / "clean.py", REPO, 32) == []


# --- compile-count runtime guard -----------------------------------------


class TestCompileRegistry:
    def test_shape_unstable_call_trips_the_guard(self):
        """A deliberately shape-unstable jit call after the snapshot is a
        nonzero delta — the condition profile_e2e asserts against and
        bench_gate gates."""
        jax = pytest.importorskip("jax")
        import jax.numpy as jnp

        from tigerbeetle_tpu.tidy.jaxlint import CompileRegistry

        reg = CompileRegistry()
        assert reg.install()

        f = jax.jit(lambda x: x * 2 + 1)
        reg.track("f", f)
        f(jnp.ones(8, dtype=jnp.uint32))  # warmup compile
        snap = reg.snapshot()

        f(jnp.ones(8, dtype=jnp.uint32))  # same shape: cache hit
        assert reg.delta(snap)["f"] == 0

        f(jnp.ones(16, dtype=jnp.uint32))  # retrace
        f(jnp.ones(32, dtype=jnp.uint32))  # retrace
        delta = reg.delta(snap)
        assert delta["f"] == 2
        assert reg.total_delta(snap) >= 2  # global monitor saw them too

    def test_tracked_default_entries_resolve(self):
        pytest.importorskip("jax")
        from tigerbeetle_tpu.tidy.jaxlint import CompileRegistry

        reg = CompileRegistry()
        reg.track_default_entries()
        counts = reg.counts()
        # The repo's module-level jit entries all expose cache sizes.
        for name in ("create_transfers_fast", "register_accounts",
                     "write_balances", "read_balances",
                     "create_transfers_exact"):
            assert name in counts, counts


# --- bench_gate: the compile-count CI gate --------------------------------


class TestBenchGateCompiles:
    BASE = {
        "end_to_end": {
            "load_accepted_tx_per_s": 300000.0,
            "perceived_p50_ms": 80.0,
            "perceived_p99_ms": 200.0,
        },
        "config5_lsm": {
            "ingest_rows_per_s": 4.0e6,
            "major_compaction_rows_per_s": 2.0e6,
        },
        "config1_default": {"posted_per_s": 1.0e6, "steady_compiles": 0},
        "config2_zipf": {"posted_per_s": 1.0e6, "steady_compiles": 0},
    }

    def _gate(self, tmp_path, monkeypatch, current_extra):
        gate = _load_tool("bench_gate")
        (tmp_path / "BENCH_r98.json").write_text(
            json.dumps({"parsed": {"extra": self.BASE}})
        )
        monkeypatch.setattr(gate, "REPO", str(tmp_path))
        current = json.dumps({"extra": current_extra})
        return gate.main([
            "--current-json", current,
            "--devhub", str(tmp_path / "devhub.jsonl"),
        ])

    def test_matching_compile_count_passes(self, tmp_path, monkeypatch):
        assert self._gate(tmp_path, monkeypatch, self.BASE) == 0

    def test_compile_drift_fails(self, tmp_path, monkeypatch):
        """An injected shape-unstable run (steady_compiles 0 → 3) fails
        the gate even with every perf number unchanged."""
        cur = json.loads(json.dumps(self.BASE))
        cur["config1_default"]["steady_compiles"] = 3
        assert self._gate(tmp_path, monkeypatch, cur) == 1

    def test_missing_gated_section_fails(self, tmp_path, monkeypatch):
        cur = json.loads(json.dumps(self.BASE))
        del cur["config5_lsm"]
        assert self._gate(tmp_path, monkeypatch, cur) == 1

    def test_no_baseline_is_a_clear_error(self, tmp_path, monkeypatch, capsys):
        """No BENCH_r*.json: exit 2 with an actionable message, never a
        traceback, never a silent pass."""
        gate = _load_tool("bench_gate")
        monkeypatch.setattr(gate, "REPO", str(tmp_path))
        rc = gate.main([
            "--current-json", json.dumps({"extra": self.BASE}),
            "--devhub", str(tmp_path / "devhub.jsonl"),
        ])
        assert rc == 2
        assert "no BENCH_r*.json baseline" in capsys.readouterr().err

    def test_list_flag_prints_thresholds(self, tmp_path, monkeypatch, capsys):
        gate = _load_tool("bench_gate")
        (tmp_path / "BENCH_r98.json").write_text(
            json.dumps({"parsed": {"extra": self.BASE}})
        )
        monkeypatch.setattr(gate, "REPO", str(tmp_path))
        assert gate.main(["--list"]) == 0
        out = capsys.readouterr().out
        assert "steady_compiles" in out
        assert "exact" in out
        assert "load_accepted_tx_per_s" in out
