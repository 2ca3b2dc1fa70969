"""The continuous-benchmarking devhub (docs/DEVHUB.md): environment
fingerprints (tigerbeetle_tpu/envprofile.py), like-for-like gating in
tools/bench_gate.py, the change-point detector + trajectory tooling in
tools/devhub.py, bench.py --sections partial runs, and the devhub pass
of tools/check.py.

The detector suite pins exact change-point indices on synthetic series
(single step up/down, two steps, pure noise at the measured container
variance, lone outliers/spikes, short series) AND on the repo's real
devhub.jsonl: the known r01→r02 end-to-end jump (157k→412k accepted
tx/s) must be detected at row 1 and the flat config1 head/tail must
stay step-free around the acknowledged round-6 host change at row 9.
"""

import importlib.util
import json
import pathlib
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from tigerbeetle_tpu import envprofile  # noqa: E402


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        f"tool_{name}_dh", REPO / "tools" / f"{name}.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def devhub():
    return _load_tool("devhub")


# --- environment fingerprints --------------------------------------------


class TestEnvProfile:
    def test_fingerprint_is_stable_and_stamped(self):
        a = envprofile.fingerprint()
        b = envprofile.fingerprint()
        assert a["profile_id"] == b["profile_id"]
        assert len(a["profile_id"]) == 12
        for key in envprofile.PROFILE_ID_FIELDS:
            assert key in a
        assert a["cpu_count"] >= 1

    def test_profile_id_tracks_identity_fields_only(self):
        base = dict(envprofile.LEGACY_PROFILE)
        pid = envprofile.profile_id_from(base)
        assert pid == envprofile.legacy_profile_id()
        # hashed field changes the id ...
        assert envprofile.profile_id_from(
            dict(base, cpu_count=96)
        ) != pid
        assert envprofile.profile_id_from(
            dict(base, accel_kind="TPU v4", accel_backend="tpu",
                 accel_count=4)
        ) != pid
        # ... recorded-not-hashed facts do not
        assert envprofile.profile_id_from(
            dict(base, jax="99.0", python="3.99")
        ) == pid

    def test_no_jax_probe_is_cpu_only(self):
        fp = envprofile.fingerprint(allow_jax=False)
        assert fp["accel_backend"] == "none"
        assert fp["accel_count"] == 0
        assert "jax" not in fp

    def test_failed_probe_raises(self, monkeypatch):
        """A probe that was asked for and fails must not come back as a
        cpu-only profile (a chip host whose chip another process holds
        would be stamped accel_backend "none")."""
        import jax

        def held(*a, **k):
            raise RuntimeError("The TPU is already in use by process 1")

        monkeypatch.setattr(jax, "devices", held)
        with pytest.raises(RuntimeError, match="already in use"):
            envprofile.fingerprint()
        # ... while the jax-free probe never asks
        assert envprofile.fingerprint(allow_jax=False)["accel_count"] == 0

    def test_with_accelerator_takes_what_the_server_reported(self):
        base = envprofile.fingerprint(allow_jax=False)
        tpu = envprofile.with_accelerator(base, "tpu", "TPU v5 lite", 1)
        assert (tpu["accel_backend"], tpu["accel_kind"], tpu["accel_count"]) \
            == ("tpu", "TPU v5 lite", 1)
        assert tpu["profile_id"] != base["profile_id"]
        assert tpu["profile_id"] == envprofile.profile_id_from(tpu)
        # XLA-CPU and the numpy backend's "none" both stay cpu-only
        for platform in ("cpu", "none"):
            same = envprofile.with_accelerator(base, platform, platform, 1)
            assert same["profile_id"] == base["profile_id"]
            assert same["accel_backend"] == "none"

    def test_record_profile_id_precedence(self):
        env = {"profile_id": "abc123abc123"}
        assert envprofile.record_profile_id(
            {"extra": {"env": env}}
        ) == "abc123abc123"
        assert envprofile.record_profile_id(
            {"profile_id": "def456def456"}
        ) == "def456def456"
        # legacy rows (no stamp anywhere) adopt the dev-container profile
        assert envprofile.record_profile_id(
            {"extra": {"end_to_end": {}}}
        ) == envprofile.legacy_profile_id()


# --- the step detector on synthetic series -------------------------------


class TestDetector:
    def _noisy(self, vals, seed, amp=0.04):
        rng = np.random.default_rng(seed)
        return [v * (1 + rng.uniform(-amp, amp)) for v in vals]

    def test_single_step_up_exact_index(self, devhub):
        for seed in range(8):
            vals = self._noisy([100.0] * 12 + [150.0] * 12, seed)
            assert devhub.detect_change_points(vals) == [12], seed

    def test_single_step_down_exact_index(self, devhub):
        for seed in range(8):
            vals = self._noisy([100.0] * 12 + [60.0] * 12, 50 + seed)
            assert devhub.detect_change_points(vals) == [12], seed

    def test_step_near_edges(self, devhub):
        for seed in range(8):
            vals = self._noisy([100.0] * 3 + [200.0] * 21, 100 + seed)
            assert devhub.detect_change_points(vals) == [3], seed
            vals = self._noisy([100.0] * 20 + [70.0] * 4, 150 + seed)
            assert devhub.detect_change_points(vals) == [20], seed

    def test_first_run_regime(self, devhub):
        """The r01→r02 shape: a single first run is its own regime."""
        for seed in range(8):
            vals = self._noisy([157.0] + [400.0] * 11, 200 + seed)
            assert devhub.detect_change_points(vals) == [1], seed

    def test_two_steps_exact_indices(self, devhub):
        for seed in range(12):
            vals = self._noisy(
                [100.0] * 8 + [160.0] * 8 + [80.0] * 8, 300 + seed
            )
            assert devhub.detect_change_points(vals) == [8, 16], seed

    def test_pure_noise_zero_false_positives(self, devhub):
        """Uniform ±10% (the container's documented run noise) and
        gaussian 5%: no change-points, ever."""
        for seed in range(25):
            rng = np.random.default_rng(400 + seed)
            assert devhub.detect_change_points(
                list(100 * rng.uniform(0.9, 1.1, 40))
            ) == [], seed
            rng = np.random.default_rng(500 + seed)
            assert devhub.detect_change_points(
                list(rng.normal(100.0, 5.0, 40))
            ) == [], seed

    def test_lone_trailing_outlier_is_not_a_step(self, devhub):
        """A regime needs 2 runs of evidence: the newest lone outlier
        never confirms a step (it is a suspect instead)."""
        for seed in range(12):
            rng = np.random.default_rng(600 + seed)
            vals = list(100 * rng.uniform(0.96, 1.04, 15)) + [55.0]
            assert devhub.detect_change_points(vals) == [], seed

    def test_mid_series_spike_is_not_a_step(self, devhub):
        for seed in range(12):
            rng = np.random.default_rng(700 + seed)
            vals = list(100 * rng.uniform(0.96, 1.04, 20))
            vals[9] = 170.0
            assert devhub.detect_change_points(vals) == [], seed

    def test_short_series_never_segmented(self, devhub):
        assert devhub.detect_change_points([]) == []
        assert devhub.detect_change_points([100.0]) == []
        assert devhub.detect_change_points([100.0, 300.0, 300.0, 300.0]) == []

    def test_flat_series(self, devhub):
        assert devhub.detect_change_points([5.0] * 20) == []

    def test_exact_metric_step_from_zero_baseline(self, devhub):
        """steady_compiles-style series: 0 0 0 0 ... then a drift."""
        assert devhub.detect_change_points(
            [0.0] * 8 + [3.0] * 3
        ) == [8]

    def test_suspect_flags_newest_deviating_run(self, devhub):
        pts = [(i, v, None, None) for i, v in enumerate(
            [100.0, 101.0, 99.0, 100.0, 55.0]
        )]
        s = devhub.trailing_suspect(pts, [], higher_better=True)
        assert s is not None and s["index"] == 4
        # same deviation in the GOOD direction: not a suspect
        pts_up = [(i, v, None, None) for i, v in enumerate(
            [100.0, 101.0, 99.0, 100.0, 180.0]
        )]
        assert devhub.trailing_suspect(pts_up, [], True) is None


# --- the real repo trajectory --------------------------------------------


class TestRealTrajectory:
    """Backfill tolerance + the known history, against the repo's real
    devhub.jsonl (pre-round-8 rows lack git stamps, early rows lack
    perceived_*/overload/recovery keys — gaps, never crashes)."""

    @pytest.fixture(scope="class")
    def analysis(self, devhub):
        return devhub.analyze(
            str(REPO / "devhub.jsonl"), str(REPO / "devhub_ack.json")
        )

    def _metric(self, analysis, label):
        for prof in analysis["profiles"]:
            if prof["profile_id"] == envprofile.legacy_profile_id():
                for m in prof["metrics"]:
                    if m["metric"] == label:
                        return m
        raise AssertionError(f"metric {label} missing from legacy profile")

    def test_every_row_parses(self, devhub):
        rows, bad = devhub.load_rows(str(REPO / "devhub.jsonl"))
        assert bad == 0
        assert len(devhub.bench_rows(rows)) >= 13

    def test_r01_r02_jump_detected(self, analysis):
        m = self._metric(analysis, "end_to_end.load_accepted_tx_per_s")
        steps_at = {s["index"]: s for s in m["steps"]}
        assert 1 in steps_at, f"r01→r02 step missing: {m['steps']}"
        s = steps_at[1]
        # the old regime is the single 157k r01 run; the new one ~340k+
        assert s["before_median"] < 200_000 < s["after_median"]
        assert not s["regression"]

    def test_missing_keys_are_gaps(self, analysis):
        """perceived_p50 only exists from round-8 rows on: the series
        has gaps for every earlier row, and they are not points."""
        m = self._metric(analysis, "end_to_end.perceived_p50_ms")
        assert m["gaps"] >= 7
        assert m["n"] + m["gaps"] == 13 or m["n"] + m["gaps"] > 13

    def test_flat_config1_head_and_tail_clean(self, analysis):
        """config1 ran ~11-12M flat for rows 0-8, then the round-6 host
        change dropped it to ~1M: exactly ONE step (row 9), nothing in
        the flat head, nothing in the noisy-but-stepless tail."""
        m = self._metric(analysis, "config1_default.posted_per_s")
        assert [s["index"] for s in m["steps"]] == [9]
        assert m["steps"][0]["regression"]
        assert m["steps"][0]["ack"], "host change must be acknowledged"

    def test_host_change_steps_all_acknowledged(self, devhub, analysis):
        assert devhub.check_failures(analysis, strict_new=True) == []

    def test_report_and_check_cli(self, devhub, capsys):
        assert devhub.main(["report"]) == 0
        out = capsys.readouterr().out
        assert "end_to_end.load_accepted_tx_per_s" in out
        assert "↑@1" in out
        assert devhub.main(["check", "--strict-new"]) == 0

    def test_html_dashboard(self, devhub, tmp_path, capsys):
        out_file = tmp_path / "devhub.html"
        assert devhub.main(["html", "--out", str(out_file)]) == 0
        doc = out_file.read_text()
        # one annotated sparkline per gated metric with recorded data
        assert doc.count("<svg") >= 15
        assert doc.count("<polyline") >= 5
        assert "config1_default.posted_per_s" in doc
        assert "▼" in doc  # step annotation is icon+text, not color alone
        assert "<table>" in doc  # table view fallback
        assert "prefers-color-scheme: dark" in doc
        # ack annotates but never flips direction: the acknowledged
        # host-change regressions stay red-class regressions, and the
        # r01→r02 improvement is labeled improvement
        assert 'class="reg"' in doc and "regression (acknowledged:" in doc
        assert "— improvement" in doc


# --- bench_gate: like-for-like profiles ----------------------------------


class TestBenchGateProfiles:
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
    TPU_ENV = {
        "system": "Linux", "machine": "x86_64", "cpu_count": 96,
        "accel_backend": "tpu", "accel_kind": "TPU v4", "accel_count": 4,
    }

    def _gate(self, tmp_path, monkeypatch, baselines, current_record,
              extra_args=()):
        gate = _load_tool("bench_gate")
        for name, extra in baselines.items():
            (tmp_path / name).write_text(
                json.dumps({"parsed": {"extra": extra}})
            )
        monkeypatch.setattr(gate, "REPO", str(tmp_path))
        rc = gate.main([
            "--current-json", json.dumps(current_record),
            "--devhub", str(tmp_path / "devhub.jsonl"), *extra_args,
        ])
        return rc

    def _with_env(self, extra, env_fields):
        out = json.loads(json.dumps(extra))
        env = dict(env_fields)
        env["profile_id"] = envprofile.profile_id_from(env)
        out["env"] = env
        return out

    def test_mismatch_is_na_exit2_naming_both(self, tmp_path, monkeypatch,
                                              capsys):
        cur = self._with_env(self.BASE, self.TPU_ENV)
        rc = self._gate(tmp_path, monkeypatch,
                        {"BENCH_r98.json": self.BASE},
                        {"extra": cur})
        captured = capsys.readouterr()
        assert rc == 2
        assert "n/a (profile mismatch)" in captured.out
        assert envprofile.legacy_profile_id() in captured.err
        assert cur["env"]["profile_id"] in captured.err

    def test_mismatch_even_when_numbers_regress(self, tmp_path, monkeypatch):
        """A cross-profile 50% 'regression' must NOT be a numeric fail."""
        cur = self._with_env(self.BASE, self.TPU_ENV)
        cur["end_to_end"]["load_accepted_tx_per_s"] = 150000.0
        rc = self._gate(tmp_path, monkeypatch,
                        {"BENCH_r98.json": self.BASE}, {"extra": cur})
        assert rc == 2

    def test_legacy_baseline_adopts_dev_container_profile(
            self, tmp_path, monkeypatch):
        """A fingerprinted run on the dev container gates numerically
        against the un-fingerprinted BENCH_r05-era baselines."""
        cur = self._with_env(self.BASE, envprofile.LEGACY_PROFILE)
        rc = self._gate(tmp_path, monkeypatch,
                        {"BENCH_r98.json": self.BASE}, {"extra": cur})
        assert rc == 0

    def test_profile_flag_selects_matching_baseline(self, tmp_path,
                                                    monkeypatch, capsys):
        """--profile: a TPU-profiled candidate auto-selects the TPU
        trajectory file, not the newest dev-container round."""
        tpu_base = self._with_env(self.BASE, self.TPU_ENV)
        cur = json.loads(json.dumps(tpu_base))
        rc = self._gate(
            tmp_path, monkeypatch,
            {"BENCH_r99.json": self.BASE, "BENCH_tpu_r01.json": tpu_base},
            {"extra": cur}, extra_args=["--profile"],
        )
        captured = capsys.readouterr()
        assert rc == 0
        assert "BENCH_tpu_r01.json" in captured.out

    def test_profile_flag_legacy_candidate_picks_round_files(
            self, tmp_path, monkeypatch, capsys):
        tpu_base = self._with_env(self.BASE, self.TPU_ENV)
        rc = self._gate(
            tmp_path, monkeypatch,
            {"BENCH_r99.json": self.BASE, "BENCH_tpu_r01.json": tpu_base},
            {"extra": self.BASE}, extra_args=["--profile"],
        )
        captured = capsys.readouterr()
        assert rc == 0
        assert "BENCH_r99.json" in captured.out

    def test_profile_flag_without_match_is_exit2(self, tmp_path,
                                                 monkeypatch, capsys):
        cur = self._with_env(self.BASE, self.TPU_ENV)
        rc = self._gate(tmp_path, monkeypatch,
                        {"BENCH_r98.json": self.BASE}, {"extra": cur},
                        extra_args=["--profile"])
        assert rc == 2
        assert "no BENCH_*.json baseline with profile" in \
            capsys.readouterr().err

    def test_list_shows_baseline_profile(self, tmp_path, monkeypatch,
                                         capsys):
        gate = _load_tool("bench_gate")
        (tmp_path / "BENCH_r98.json").write_text(
            json.dumps({"parsed": {"extra": self.BASE}})
        )
        monkeypatch.setattr(gate, "REPO", str(tmp_path))
        assert gate.main(["--list"]) == 0
        out = capsys.readouterr().out
        assert f"profile={envprofile.legacy_profile_id()}" in out
        # The query-engine keys are part of the gated surface.
        assert "query_p50_ms" in out
        assert "query_p99_ms" in out
        assert "scan_rows_per_s" in out

    QUERY = {
        "query_p50_ms": 10.0,
        "query_p99_ms": 40.0,
        "scan_rows_per_s": 2.0e6,
    }

    def test_query_keys_gate(self, tmp_path, monkeypatch):
        """query_p50/p99 (lower better) and scan_rows_per_s (higher
        better) follow the 10% rule like every other gated key."""
        base = json.loads(json.dumps(self.BASE))
        base["query"] = dict(self.QUERY)
        good = json.loads(json.dumps(base))
        assert self._gate(tmp_path, monkeypatch,
                          {"BENCH_r98.json": base}, {"extra": good}) == 0
        slow = json.loads(json.dumps(base))
        slow["query"]["query_p99_ms"] = 50.0  # +25% > 10% budget
        assert self._gate(tmp_path, monkeypatch,
                          {"BENCH_r98.json": base}, {"extra": slow}) == 1
        starved = json.loads(json.dumps(base))
        starved["query"]["scan_rows_per_s"] = 1.0e6  # -50%
        assert self._gate(tmp_path, monkeypatch,
                          {"BENCH_r98.json": base}, {"extra": starved}) == 1

    def test_query_na_against_pre_query_baseline(self, tmp_path,
                                                 monkeypatch, capsys):
        """A pre-query-engine baseline has no query section: the three
        keys report n/a, not MISSING-fail."""
        cur = json.loads(json.dumps(self.BASE))
        cur["query"] = dict(self.QUERY)
        rc = self._gate(tmp_path, monkeypatch,
                        {"BENCH_r98.json": self.BASE}, {"extra": cur})
        assert rc == 0
        assert "n/a" in capsys.readouterr().out

    def test_query_missing_from_full_run_fails_closed(self, tmp_path,
                                                      monkeypatch, capsys):
        """Once a baseline carries the query section, a full (non
        --sections) run that crashed before recording it is MISSING →
        exit 1, never a silent pass."""
        base = json.loads(json.dumps(self.BASE))
        base["query"] = dict(self.QUERY)
        rc = self._gate(tmp_path, monkeypatch,
                        {"BENCH_r98.json": base}, {"extra": self.BASE})
        assert rc == 1
        assert "MISSING" in capsys.readouterr().out

    def test_corrupt_baseline_file_fails_loudly(self, tmp_path, monkeypatch,
                                                capsys):
        """A truncated newest BENCH_r*.json must not silently demote the
        gate to an older round: exit 2 naming the corrupt file."""
        gate = _load_tool("bench_gate")
        (tmp_path / "BENCH_r98.json").write_text(
            json.dumps({"parsed": {"extra": self.BASE}})
        )
        (tmp_path / "BENCH_r99.json").write_text('{"parsed": {"ex')
        monkeypatch.setattr(gate, "REPO", str(tmp_path))
        rc = gate.main([
            "--current-json", json.dumps({"extra": self.BASE}),
            "--devhub", str(tmp_path / "devhub.jsonl"),
        ])
        assert rc == 2
        assert "BENCH_r99.json" in capsys.readouterr().err

    def test_partial_run_skipped_section_is_na(self, tmp_path, monkeypatch):
        """bench.py --sections runs gate their measured sections and
        report the skipped ones n/a — not MISSING-fail."""
        cur = {"end_to_end": dict(self.BASE["end_to_end"])}
        rec = {"extra": cur, "partial": True, "sections": ["end_to_end"]}
        rc = self._gate(tmp_path, monkeypatch,
                        {"BENCH_r98.json": self.BASE}, rec)
        assert rc == 0

    def test_partial_run_without_e2e_still_gates(self, tmp_path,
                                                 monkeypatch):
        """--sections=config1_default gates the compile count it did
        measure; every e2e/config5 key is n/a (section skipped), not a
        'no end_to_end block' usage error."""
        rec = {
            "extra": {"config1_default": {"posted_per_s": 1.0e6,
                                          "steady_compiles": 0}},
            "partial": True, "sections": ["config1_default"],
        }
        assert self._gate(tmp_path, monkeypatch,
                          {"BENCH_r98.json": self.BASE}, rec) == 0
        # and the exact gate still arms on what WAS measured
        rec["extra"]["config1_default"]["steady_compiles"] = 3
        assert self._gate(tmp_path, monkeypatch,
                          {"BENCH_r98.json": self.BASE}, rec) == 1

    def test_parallel_trajectory_not_tripped_by_legacy_rounds(
            self, tmp_path, monkeypatch, capsys):
        """--profile on a BENCH_tpu_r01 trajectory must not be blocked
        by the repo's ancient legacy-schema BENCH_r02 (round counters
        restart per trajectory prefix)."""
        tpu_base = self._with_env(self.BASE, self.TPU_ENV)
        baselines = {
            "BENCH_r98.json": self.BASE,
            "BENCH_tpu_r01.json": tpu_base,
        }
        gate = _load_tool("bench_gate")
        for name, extra in baselines.items():
            (tmp_path / name).write_text(
                json.dumps({"parsed": {"extra": extra}})
            )
        # legacy pre-section file: higher round than tpu_r01, different
        # trajectory — benign
        (tmp_path / "BENCH_r02.json").write_text(
            json.dumps({"parsed": {"extra": {"batch_ms_avg": 1.0}}})
        )
        monkeypatch.setattr(gate, "REPO", str(tmp_path))
        rc = gate.main([
            "--current-json", json.dumps({"extra": tpu_base}),
            "--devhub", str(tmp_path / "devhub.jsonl"), "--profile",
        ])
        assert rc == 0
        assert "BENCH_tpu_r01.json" in capsys.readouterr().out

    def test_raw_bench_json_line_gates_as_partial(self, tmp_path,
                                                  monkeypatch):
        """The `BENCH_JSON {...}` line exactly as cli.py benchmark
        prints it gates the serving path directly — the wrapper marks
        it partial so config5/recovery/overload are n/a, not MISSING."""
        gate = _load_tool("bench_gate")
        (tmp_path / "BENCH_r98.json").write_text(
            json.dumps({"parsed": {"extra": self.BASE}})
        )
        monkeypatch.setattr(gate, "REPO", str(tmp_path))
        line = "BENCH_JSON " + json.dumps(dict(self.BASE["end_to_end"]))
        rc = gate.main([
            "--current-json", f"some human output\n{line}\ntrailer\n",
            "--devhub", str(tmp_path / "devhub.jsonl"),
        ])
        assert rc == 0

    def test_newer_wrong_shape_baseline_refuses_demotion(
            self, tmp_path, monkeypatch, capsys):
        """A parsable-but-sectionless newest round file must not quietly
        hand the gate an older baseline (the parsable twin of the
        corrupt-file refusal); ancient pre-section BENCH_r01/r02-style
        files below the selected round stay benign."""
        gate = _load_tool("bench_gate")
        (tmp_path / "BENCH_r98.json").write_text(
            json.dumps({"parsed": {"extra": self.BASE}})
        )
        # older legacy shape: fine
        (tmp_path / "BENCH_r01.json").write_text(
            json.dumps({"parsed": {"extra": {"batch_ms_avg": 1.0}}})
        )
        monkeypatch.setattr(gate, "REPO", str(tmp_path))
        rc = gate.main([
            "--current-json", json.dumps({"extra": self.BASE}),
            "--devhub", str(tmp_path / "devhub.jsonl"),
        ])
        assert rc == 0
        capsys.readouterr()
        # newer wrong shape: refusal
        (tmp_path / "BENCH_r99.json").write_text(
            json.dumps({"parsed": {"extra": {"recovery": {}}}})
        )
        rc = gate.main([
            "--current-json", json.dumps({"extra": self.BASE}),
            "--devhub", str(tmp_path / "devhub.jsonl"),
        ])
        assert rc == 2
        assert "BENCH_r99.json" in capsys.readouterr().err

    def test_full_run_missing_section_still_fails(self, tmp_path,
                                                  monkeypatch):
        """MISSING-fails-closed semantics unchanged for full runs."""
        cur = {"end_to_end": dict(self.BASE["end_to_end"])}
        rc = self._gate(tmp_path, monkeypatch,
                        {"BENCH_r98.json": self.BASE}, {"extra": cur})
        assert rc == 1


# --- bench.py --sections + record building --------------------------------


class TestBenchSections:
    @pytest.fixture(scope="class")
    def bench(self):
        spec = importlib.util.spec_from_file_location(
            "bench_mod_dh", REPO / "bench.py"
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_select_subset_preserves_registry_order(self, bench):
        sel = bench.select_sections("overload,end_to_end")
        assert [n for n, _ in sel] == ["end_to_end", "overload"]

    def test_select_default_is_full_matrix(self, bench):
        assert bench.select_sections(None) == bench.SECTIONS
        assert bench.select_sections("") == bench.SECTIONS

    def test_unknown_section_raises(self, bench):
        with pytest.raises(ValueError, match="unknown bench section"):
            bench.select_sections("end_to_end,bogus")

    def test_partial_record_marks_itself(self, bench):
        sel = bench.select_sections("end_to_end")
        rec = bench.build_record(
            {"end_to_end": {"load_accepted_tx_per_s": 1.0},
             "bench_wall_s": 1.0}, sel,
        )
        assert rec["partial"] is True
        assert rec["sections"] == ["end_to_end"]
        # no config1 section ran: no fake 0.0 headline value
        assert rec["value"] is None
        env = rec["extra"]["env"]
        assert env["profile_id"]
        assert rec["extra"]["end_to_end"]["profile_id"] == env["profile_id"]

    def test_full_record_is_not_partial(self, bench):
        results = {n: {"posted_per_s": 5.0} for n, _ in bench.SECTIONS}
        rec = bench.build_record(results, bench.SECTIONS)
        assert "partial" not in rec
        assert rec["value"] == 5.0
        assert rec["extra"]["env"]["profile_id"]


# --- check.py devhub pass + fabricated series ----------------------------


def _series_file(tmp_path, e2e_values):
    path = tmp_path / "devhub.jsonl"
    with open(path, "w") as f:
        for v in e2e_values:
            f.write(json.dumps({
                "metric": "posted_transfers_per_sec", "value": 1.0,
                "unit": "tx/s", "git": "deadbee",
                "extra": {"end_to_end": {"load_accepted_tx_per_s": v}},
            }) + "\n")
        # corrupt line: must be tolerated, never fatal
        f.write("{truncated\n")
    return path


class TestCheckIntegration:
    def test_repo_devhub_pass_is_green(self):
        check = _load_tool("check")
        rep = check.check_devhub(strict_new=True)
        assert rep["ran"] is True
        assert rep["failures"] == []
        assert rep["steps"] >= 1  # the real history has known steps

    def test_errored_devhub_pass_fails_closed(self, monkeypatch, tmp_path):
        """A malformed devhub_ack.json must not neutralize the strict
        trajectory gate: check.py's devhub pass reports the error AS a
        failure (fail-closed), matching devhub.py's own exit-2."""
        check = _load_tool("check")
        tools_dir = str(REPO / "tools")
        if tools_dir not in sys.path:
            sys.path.insert(0, tools_dir)
        import devhub as devhub_mod

        bad = tmp_path / "ack.json"
        bad.write_text("{broken json")
        monkeypatch.setattr(devhub_mod, "DEFAULT_ACK", str(bad))
        rep = check.check_devhub(strict_new=True)
        assert rep["ran"] is False
        assert rep["failures"], "errored pass must fail closed"
        assert "fails closed" in rep["failures"][0]

    def test_confirmed_regression_fails_check(self, devhub, tmp_path):
        series = _series_file(
            tmp_path, [100.0, 101.0, 99.0, 100.0, 102.0, 60.0, 61.0, 59.0]
        )
        rc = devhub.main([
            "check", "--devhub", str(series),
            "--ack", str(tmp_path / "no_acks.json"),
        ])
        assert rc == 1

    def test_ack_clears_the_failure(self, devhub, tmp_path):
        series = _series_file(
            tmp_path, [100.0, 101.0, 99.0, 100.0, 102.0, 60.0, 61.0, 59.0]
        )
        ack = tmp_path / "acks.json"
        ack.write_text(json.dumps({"acks": [{
            "metric": "end_to_end.load_accepted_tx_per_s",
            "index": 5, "reason": "intentional trade-off",
        }]}))
        rc = devhub.main(["check", "--devhub", str(series),
                          "--ack", str(ack)])
        assert rc == 0

    def test_bare_list_ack_file_accepted(self, devhub, tmp_path):
        """devhub_ack.json as a top-level array (no {'acks': ...}
        wrapper) is a documented accepted shape — not a crash."""
        series = _series_file(
            tmp_path, [100.0, 101.0, 99.0, 100.0, 102.0, 60.0, 61.0, 59.0]
        )
        ack = tmp_path / "acks.json"
        ack.write_text(json.dumps([{
            "metric": "end_to_end.load_accepted_tx_per_s",
            "index": 5, "reason": "accepted trade-off",
        }]))
        assert devhub.main(["check", "--devhub", str(series),
                            "--ack", str(ack)]) == 0

    def test_malformed_ack_file_is_usage_error(self, devhub, tmp_path):
        series = _series_file(tmp_path, [100.0] * 6)
        for payload in ('{"acks": 7}', '"just a string"'):
            ack = tmp_path / "acks.json"
            ack.write_text(payload)
            assert devhub.main(["report", "--devhub", str(series),
                                "--ack", str(ack)]) == 2

    def test_git_match_acknowledges_too(self, devhub, tmp_path):
        series = _series_file(
            tmp_path, [100.0, 101.0, 99.0, 100.0, 102.0, 60.0, 61.0, 59.0]
        )
        ack = tmp_path / "acks.json"
        ack.write_text(json.dumps({"acks": [{
            "metric": "end_to_end.load_accepted_tx_per_s",
            "git": "deadbee", "reason": "host swap",
        }]}))
        assert devhub.main(["check", "--devhub", str(series),
                            "--ack", str(ack)]) == 0

    def test_suspect_only_fails_under_strict_new(self, devhub, tmp_path):
        """One new bad run: advisory check passes (2-run evidence rule),
        --strict-new flags it — the slow-drift tripwire."""
        series = _series_file(
            tmp_path, [100.0, 101.0, 99.0, 100.0, 102.0, 55.0]
        )
        no_acks = str(tmp_path / "no_acks.json")
        assert devhub.main(["check", "--devhub", str(series),
                            "--ack", no_acks]) == 0
        assert devhub.main(["check", "--strict-new", "--devhub",
                            str(series), "--ack", no_acks]) == 1

    def test_missing_series_is_usage_error(self, devhub, tmp_path):
        assert devhub.main(["report", "--devhub",
                            str(tmp_path / "nope.jsonl")]) == 2

    def test_unknown_profile_filter_is_usage_error(self, devhub, tmp_path):
        """--profile matching zero rows must not be a green check (a
        typo'd or rotated profile id would pass CI forever)."""
        series = _series_file(tmp_path, [100.0] * 6)
        assert devhub.main([
            "check", "--strict-new", "--profile", "feedfacecafe",
            "--devhub", str(series), "--ack", str(tmp_path / "na.json"),
        ]) == 2

    def test_profile_grouping_separates_hosts(self, devhub, tmp_path):
        """A TPU-host row appended to a dev-container history starts its
        own series: no cross-profile 'regression' is ever detected."""
        path = tmp_path / "devhub.jsonl"
        tpu_env = {
            "system": "Linux", "machine": "x86_64", "cpu_count": 96,
            "accel_backend": "tpu", "accel_kind": "TPU v4",
            "accel_count": 4,
        }
        tpu_env["profile_id"] = envprofile.profile_id_from(tpu_env)
        with open(path, "w") as f:
            for v in [100.0, 101.0, 99.0, 100.0, 102.0, 98.0]:
                f.write(json.dumps({
                    "metric": "posted_transfers_per_sec", "value": 1.0,
                    "extra": {"end_to_end": {"load_accepted_tx_per_s": v}},
                }) + "\n")
            for v in [5000.0, 5100.0]:
                f.write(json.dumps({
                    "metric": "posted_transfers_per_sec", "value": 1.0,
                    "extra": {
                        "end_to_end": {"load_accepted_tx_per_s": v},
                        "env": tpu_env,
                    },
                }) + "\n")
        analysis = devhub.analyze(str(path), str(tmp_path / "no_acks.json"))
        assert len(analysis["profiles"]) == 2
        for prof in analysis["profiles"]:
            for m in prof["metrics"]:
                assert m["steps"] == [], (prof["profile_id"], m)
