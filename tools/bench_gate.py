"""Bench regression gate: compare a fresh `bench.py` run against the
latest recorded round benchmark (BENCH_r*.json) and fail on a >10%
regression in the e2e metrics (accepted throughput, client-perceived
p50/p99, the lifecycle queue-wait/service totals, the commit-window
occupancy commit_inflight_mean) or the LSM store
metrics (config5 ingest / major-compaction rates), the recovery-time
objectives (per-scenario recovery_time_s / degraded_throughput_pct from
the chaos-at-load section — docs/CHAOS.md), the front-door overload
objectives (accepted throughput + perceived p99 at the 1x saturation
point of the open-loop curve — docs/FRONT_DOOR.md), or the
cluster-plane objectives (replication-lag and quorum-straggler p99 on
a 3-process cluster with one delayed backup link —
docs/OBSERVABILITY.md). Lifecycle/recovery/
overload/cluster-plane metrics absent from an older baseline are n/a,
not failures;
occupancy is recorded but not gated (throughput × latency has no
monotone-good direction).
Steady-state jit compile counts (`steady_compiles`, recorded per device
workload by bench.py via the tidy compile registry) are gated EXACTLY:
any drift from the baselined value means a retrace crept into the hot
path, which fails the gate the same way a >10% perf drop does.

Like-for-like gating (docs/DEVHUB.md): every bench run carries an
environment fingerprint (tigerbeetle_tpu/envprofile.py — host + the
accelerator jax would use, hashed into a stable `profile_id`). The gate
REFUSES a numeric verdict when candidate and baseline profiles differ:
a TPU-host run "regressing" against a 2-core-container baseline (or the
reverse "improving") is a hardware difference, not a code change, so
every row reports `n/a (profile mismatch)` and the exit is 2 — not
pass, not fail. Baselines recorded before fingerprinting existed
are adopted as the dev-container profile
(envprofile.LEGACY_PROFILE) so the existing trajectory keeps gating.
`--profile` switches baseline selection from "newest BENCH_r*.json" to
"newest BENCH_*.json whose profile matches the candidate" — the
like-for-like selector for hosts that keep parallel trajectories
(BENCH_r06.json next to BENCH_tpu_r01.json).

A run produced by `bench.py --sections=...` marks itself partial: gated
keys in sections it deliberately skipped report `n/a (section skipped)`
instead of MISSING — the fail-closed MISSING semantics are unchanged
for full runs (a crashed section still fails against any baseline that
recorded it).

Usage:
    python bench.py | tee /tmp/bench.json
    python tools/bench_gate.py /tmp/bench.json         # file with the JSON line
    python bench.py | python tools/bench_gate.py -     # stdin
    python tools/bench_gate.py --current-json '<json>' # inline
    python tools/bench_gate.py --profile /tmp/bench.json  # like-for-like baseline
    python tools/bench_gate.py --list                  # gated metrics + thresholds

Exit codes: 0 pass, 1 regression, 2 usage/missing-data (no baseline
recorded, no parsable bench output, profile mismatch). Every gate run
appends a record to devhub.jsonl so the pass/fail history rides the
same series as the bench numbers (reference devhub.zig:36-52).

The e2e bar this repo is chasing (ROADMAP.md open items): end_to_end
load_accepted_tx_per_s ≥ 1,000,000 and perceived_p50_ms ≤ 10 — the gate
stops REGRESSIONS on the way there; it does not assert the bar itself.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# >10% worse than the recorded round fails the gate.
THROUGHPUT_REGRESSION = 0.10
LATENCY_REGRESSION = 0.10

GATED = (
    # (section, key, higher_is_better). Sections are blocks of bench.py's
    # `extra` dict; end_to_end guards the serving path, config5_lsm the
    # store tier (the async store stage moved its cost off the commit
    # path — this keeps the work itself from silently regressing).
    # perceived_p99_ms rides the same rule now that the observability
    # layer reports tail latency (a p50-only gate lets the tail rot).
    ("end_to_end", "load_accepted_tx_per_s", True),
    ("end_to_end", "perceived_p50_ms", False),
    ("end_to_end", "perceived_p99_ms", False),
    # Lifecycle decomposition (server-side, from the /lifecycle scrape):
    # aggregate queue-wait and service time per op. Absent from
    # pre-lifecycle BENCH_r*.json baselines — that is n/a, not a failure;
    # the gate arms once a baseline records them. The occupancy_* fields
    # are recorded but deliberately NOT gated: by Little's law occupancy
    # = throughput × latency, so it has no monotone-good direction (a
    # genuine latency win at constant throughput LOWERS it) — both of
    # its factors are already gated above.
    ("end_to_end", "queue_wait_total_p50_ms", False),
    ("end_to_end", "service_total_p50_ms", False),
    # Cross-batch commit pipelining (depth-N dispatch window): mean
    # in-flight batches through the commit stage, sampled once per
    # processed batch (vsr/replica._stage_note_inflight → /lifecycle
    # flat). Higher is better — a regression means the window stopped
    # forming (dispatch refusals, a serialized seam, or the adaptive
    # default silently collapsing to depth 1). Absent from pre-depth
    # baselines: n/a, not failure; a crashed e2e section records no key
    # → MISSING → fail-closed once a baseline carries it. commit_depth
    # itself is recorded (not gated) so cross-host A/Bs can see which
    # depth the adaptive default picked.
    ("end_to_end", "commit_inflight_mean", True),
    # Store-stage hot row (device query-index pipeline, PR 8): mean
    # per-batch cost of the secondary-index key build + memtable insert
    # on the store thread, scraped from the registry's sm.store.query
    # span via /lifecycle. Absent from pre-PR-8 baselines: n/a, not a
    # failure. store_stall_ms_per_wait is recorded alongside but NOT
    # gated (its count is wait events, not batches — load-shape noise).
    ("end_to_end", "store_query_ms_per_batch", False),
    ("config5_lsm", "ingest_rows_per_s", True),
    ("config5_lsm", "major_compaction_rows_per_s", True),
    # Streaming compaction under load (ISSUE 16, docs/COMMIT_PIPELINE.md
    # "Streaming compaction"): a forced all-level storm drained through
    # the per-op beats while the same state machine serves an open-loop
    # transfer stream. The fold rate (rows queued / wall time to drain,
    # serving included) is higher-better; the serving dip while the
    # storm ran lower-better — gated together so a "faster" storm that
    # starves commits (or a gentler one that never finishes) both fail.
    # Absent from pre-PR-16 baselines: n/a, not failure; a crashed
    # sub-section records neither key → MISSING → fail-closed. The
    # bloom_build_ms_per_table / serving_tx_per_s_* fields are recorded
    # but NOT gated (the bloom pass measures the work fusion REMOVED —
    # its absolute cost tracks table size, not code quality — and both
    # serving rates already gate through the dip).
    ("config5_lsm", "compaction_under_load.major_compaction_rows_per_s", True),
    ("config5_lsm", "compaction_under_load.e2e_dip_pct", False),
    # Recovery-time objectives (bench.py `recovery` section: the chaos
    # scenarios of testing/chaos.py, docs/CHAOS.md). Keys are dotted
    # paths into the per-scenario blocks. Lower is better for both: how
    # long until the cluster is whole again, and what fraction of
    # baseline throughput was lost while it recovered. replay_ops_per_s
    # is recorded but NOT gated (a torn crash can legitimately replay 0
    # WAL ops, and catch-up rate scales with how far behind the fault
    # left the replica — no stable baseline). Absent from pre-recovery
    # BENCH_r*.json baselines: n/a, not failure.
    ("recovery", "kill_restart.recovery_time_s", False),
    ("recovery", "kill_restart.degraded_throughput_pct", False),
    ("recovery", "state_sync.recovery_time_s", False),
    ("recovery", "state_sync.degraded_throughput_pct", False),
    ("recovery", "grid_storm.recovery_time_s", False),
    ("recovery", "grid_storm.degraded_throughput_pct", False),
    ("recovery", "torn_checkpoint.recovery_time_s", False),
    ("recovery", "torn_checkpoint.degraded_throughput_pct", False),
    # Primary-failover objectives (ISSUE 11, docs/CHAOS.md): the one
    # fault class users actually notice. view_change_time_s is the
    # election blackout (primary crash → new view serving with commits
    # past the fault tip); degraded_throughput_pct the dip across the
    # whole fault→redundancy-restored window. Lower better, same >10%
    # rule; n/a against pre-failover baselines; a crashed scenario
    # records neither key → MISSING → fail-closed. primary_flap /
    # partition_primary metrics are recorded but NOT gated (flap's
    # worst-election and the partition's rejoin time scale with the
    # scripted cycle counts, not with code quality).
    ("recovery", "primary_kill.view_change_time_s", False),
    ("recovery", "primary_kill.degraded_throughput_pct", False),
    # Front-door overload objectives (bench.py `overload` section: the
    # open-loop harness of testing/loadgen.py, docs/FRONT_DOOR.md). The
    # 1x point is the anchor: accepted throughput at the measured
    # saturation ceiling and the perceived tail there. The 2x/5x points
    # and the churn-run fields are recorded but NOT gated (they measure
    # degradation shape, which the accepted_5x_over_1x_pct acceptance
    # check in tests covers; their absolute values swing with host
    # noise). Absent from pre-overload baselines: n/a, not failure. A
    # crashed overload run records no gated keys → MISSING → fail-closed.
    ("overload", "accepted_tx_per_s_at_1x", True),
    ("overload", "perceived_p99_ms_at_1x", False),
    # Cluster-plane objectives (bench.py `cluster_plane` section: a real
    # 3-process cluster with one NetFault-delayed backup link —
    # docs/OBSERVABILITY.md "cluster plane"). replication_lag_p99_ms is
    # the broadcast→prepare_ok arrival tail over every remote ack;
    # quorum_straggler_p99_ms the q-th-arrival→straggler overhang. The
    # injected delay dominates both, so the >10% rule tracks the
    # replication plane and its telemetry rather than host noise. Absent
    # from pre-cluster-plane baselines: n/a, not failure; a crashed
    # section records neither key → MISSING → fail-closed. The per-peer
    # separation evidence (delayed vs healthy peer p99, straggler
    # attribution) is recorded but NOT gated (the acceptance test
    # asserts the separation; its ratio swings with scheduler jitter).
    ("cluster_plane", "replication_lag_p99_ms", False),
    ("cluster_plane", "quorum_straggler_p99_ms", False),
    # Multi-predicate query engine (ISSUE 17, bench.py `query` section,
    # docs/QUERY.md): Zipf-hot 3-predicate filters through the full
    # StateMachine.query_transfers wire path over a 10M-row preloaded
    # store. Latency tails lower-better; scan_rows_per_s (driver
    # candidate rows examined per second of engine wall time in the
    # like-for-like A/B) higher-better. intersect_speedup_x and
    # query_hits_avg are recorded but NOT gated (the speedup is an
    # acceptance-time A/B whose ratio swings with grid-cache residency;
    # hits track the Zipf draw, not code quality). Absent from pre-query
    # baselines: n/a, not failure; a crashed query section records no
    # keys → MISSING → fail-closed.
    ("query", "query_p50_ms", False),
    ("query", "query_p99_ms", False),
    ("query", "scan_rows_per_s", True),
    # Device-plane observability (ISSUE 18, bench.py `device` section,
    # docs/OBSERVABILITY.md "Device plane"): a traced jax StateMachine
    # workload with a forced depth-2 dispatch window. The transfer-
    # bandwidth p50s (achieved GB/s over the dispatch→finish windows,
    # per direction) are higher-better; device_mem_high_water_bytes —
    # the owner-tagged ledger's peak — is lower-better (footprint
    # regression guard; the workload is fixed, so growth means a leaked
    # scratch bucket or run handle). The per-entry achieved-GB/s keys
    # (cost-model bytes over measured wall time) are higher-better but
    # only recorded when the backend's cost_analysis reports byte
    # counts — absent on such backends: n/a, not failure. All keys
    # absent from pre-device-plane baselines (BENCH_r06 and earlier):
    # n/a, not failure; a crashed device section records no gated keys
    # → MISSING → fail-closed once a baseline has them.
    ("device", "xfer_h2d_gbps_p50", True),
    ("device", "xfer_d2h_gbps_p50", True),
    ("device", "device_mem_high_water_bytes", False),
    ("device", "create_transfers_fast_gbps", True),
    ("device", "read_balances_gbps", True),
)


def lookup(section: dict, key: str):
    """Resolve a possibly-dotted key ("kill_restart.recovery_time_s")
    inside a section block; None when any path element is absent."""
    cur = section
    for part in key.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur

GATED_EXACT = (
    # (section, key): must EQUAL the baselined value. Steady-state jit
    # compile counts per device workload — zero in a healthy run; any
    # nonzero delta means a retrace regression (shape/dtype instability
    # or a leaked Python-scalar capture) on the measured path.
    ("config1_default", "steady_compiles"),
    ("config2_zipf", "steady_compiles"),
)


def profile_of_extra(extra: dict) -> str:
    """The profile_id a bench `extra` block belongs to. Fingerprinted
    runs carry it in extra["env"] (a bare BENCH_JSON wrapped as
    {"end_to_end": rec} carries it inside the section); legacy
    artifacts adopt the dev-container profile
    (envprofile.LEGACY_PROFILE) so the r01-r05 trajectory keeps gating
    on the host it was recorded on."""
    from tigerbeetle_tpu import envprofile

    for block in (extra or {}), (extra or {}).get("end_to_end") or {}:
        env = block.get("env")
        if isinstance(env, dict) and env.get("profile_id"):
            return str(env["profile_id"])
    return envprofile.legacy_profile_id()


def baseline_files() -> tuple:
    """(files, errors, skipped): every BENCH_*.json round file as
    (sort_key, name, extra), oldest first. sort_key is (round number
    parsed from the trailing r<NN>, mtime) so BENCH_r05 < BENCH_r06 and
    BENCH_tpu_r01 sorts by its own round counter within the tpu
    trajectory.

    `errors` (name, reason) are UNPARSABLE files — a truncated newest
    baseline must not silently demote the gate to an older round, so
    main() refuses to gate (exit 2) while any exist. `skipped` are
    parsable files without an end_to_end section: legacy pre-sectioned
    schemas (BENCH_r01/r02 predate the section layout) — expected,
    warned about, never fatal."""
    out, errors, skipped = [], [], []
    for path in glob.glob(os.path.join(REPO, "BENCH_*.json")):
        name = os.path.basename(path)
        m = re.search(r"r(\d+)\.json$", name)
        rnd = int(m.group(1)) if m else -1
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError) as e:
            errors.append((name, f"{type(e).__name__}: {e}"))
            continue
        parsed = rec.get("parsed") or rec  # raw bench JSON also accepted
        extra = parsed.get("extra") if isinstance(parsed, dict) else None
        if not isinstance(extra, dict) or "end_to_end" not in extra:
            skipped.append((name, "no end_to_end block (legacy schema)"))
            continue
        try:
            mtime = os.path.getmtime(path)
        except OSError:
            mtime = 0.0
        out.append(((rnd, mtime), name, extra))
    out.sort(key=lambda t: t[0])
    return out, errors, skipped


def select_round(files) -> tuple:
    """(name, extra dict) of the newest BENCH_r*.json among the loaded
    `files` (the default trajectory; profile-agnostic — main() enforces
    the match)."""
    rounds = [
        (key, name, extra)
        for key, name, extra in files
        if re.fullmatch(r"BENCH_r(\d+)\.json", name)
    ]
    if not rounds:
        return None, None
    _, name, extra = rounds[-1]
    return name, extra


def select_matching(files, profile_id: str) -> tuple:
    """(name, extra dict) of the newest file among `files` whose
    profile matches `profile_id` (--profile auto-selection)."""
    matches = [
        (key, name, extra)
        for key, name, extra in files
        if profile_of_extra(extra) == profile_id
    ]
    if not matches:
        return None, None
    _, name, extra = matches[-1]
    return name, extra


def _trajectory_of(name: str) -> tuple:
    """(prefix, round) of a round-file name: BENCH_r05.json →
    ("BENCH_", 5), BENCH_tpu_r01.json → ("BENCH_tpu_", 1). Round
    counters restart per trajectory prefix, so cross-prefix round
    comparison is meaningless; non-round names get round -1."""
    m = re.search(r"r(\d+)\.json$", name)
    if not m:
        return name, -1
    return name[:m.start()], int(m.group(1))


def newer_skipped(skipped, selected_name) -> list:
    """Skipped (legacy-schema) files in the SAME trajectory as the
    selected baseline with a HIGHER round number: the silent-demotion
    hazard — someone saved a partial/wrong-shape run as the newest
    round file, and gating would quietly fall back to an older round.
    Fatal in main(). The ancient pre-section BENCH_r01/r02 sort below
    every modern default-trajectory baseline, and a parallel
    trajectory's files (BENCH_tpu_r*.json) are a different prefix with
    their own round counter — neither trips this."""
    if not selected_name:
        return []
    sel_prefix, sel_rnd = _trajectory_of(selected_name)
    out = []
    for name, reason in skipped:
        prefix, rnd = _trajectory_of(name)
        if prefix == sel_prefix and rnd > sel_rnd:
            out.append((name, reason))
    return out


def extract_record(text: str):
    """Pull the full bench record out of bench.py's output (the JSON
    line may be surrounded by warnings/log noise). A bare end_to_end
    block is accepted too (wrapped as {"extra": {"end_to_end": block}}),
    including the `BENCH_JSON {...}` line exactly as `cli.py benchmark`
    prints it — so a raw driver run gates directly."""
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("BENCH_JSON "):
            line = line[len("BENCH_JSON "):]
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        extra = rec.get("extra")
        if isinstance(extra, dict) and "end_to_end" in extra:
            return rec
        if (isinstance(extra, dict) and rec.get("partial")
                and isinstance(rec.get("sections"), list)):
            # A --sections run that deliberately excluded end_to_end
            # still gates what it DID measure (the e2e keys become
            # n/a (section skipped) downstream).
            return rec
        if "load_accepted_tx_per_s" in rec:
            # A bare driver record measures ONLY the serving path: mark
            # it partial so the other gated sections report n/a
            # (section skipped) instead of MISSING-failing a run that
            # never claimed to cover them.
            return {"extra": {"end_to_end": rec}, "partial": True,
                    "sections": ["end_to_end"]}
    return None


def extract_extra(text: str):
    """Back-compat shim: the `extra` dict of extract_record()."""
    rec = extract_record(text)
    return rec["extra"] if rec is not None else None


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="bench_gate")
    p.add_argument("current", nargs="?", default="-",
                   help="file holding bench.py's JSON output ('-' = stdin)")
    p.add_argument("--current-json", default=None,
                   help="bench JSON passed inline instead of a file")
    p.add_argument("--devhub", default=os.path.join(REPO, "devhub.jsonl"),
                   help="series file to append the gate record to")
    p.add_argument("--profile", action="store_true",
                   help="select the newest BENCH_*.json whose environment "
                        "profile matches the current run (like-for-like; "
                        "legacy files count as the dev-container profile) "
                        "instead of the newest BENCH_r*.json")
    p.add_argument("--list", action="store_true",
                   help="print the gated metrics and current thresholds, then exit")
    args = p.parse_args(argv)

    if args.list:
        files, errors, skipped = baseline_files()
        for bad_name, reason in errors + skipped:
            print(f"bench_gate: WARNING: skipping baseline {bad_name}: "
                  f"{reason}", file=sys.stderr)
        name, baseline = select_round(files)
        src = name if baseline is not None else "(no baseline)"
        base_profile = (
            profile_of_extra(baseline) if baseline is not None else "—"
        )
        print(f"gated metrics (baseline: {src}, profile={base_profile}):")
        for section, key, higher in GATED:
            base = lookup((baseline or {}).get(section) or {}, key)
            rule = ("≥ baseline × 0.90" if higher else "≤ baseline × 1.10")
            base_s = f"{float(base):,.1f}" if base is not None else "—"
            print(f"  {section}.{key:32s} {rule:22s} baseline={base_s}  "
                  f"profile={base_profile}")
        for section, key in GATED_EXACT:
            base = (baseline or {}).get(section, {}).get(key)
            base_s = f"{base}" if base is not None else "—"
            print(f"  {section}.{key:32s} {'== baseline (exact)':22s} "
                  f"baseline={base_s}  profile={base_profile}")
        return 0

    if args.current_json is not None:
        text = args.current_json
    elif args.current == "-":
        text = sys.stdin.read()
    else:
        with open(args.current) as f:
            text = f.read()
    record = extract_record(text)
    if record is None:
        print(
            "bench_gate: no end_to_end block found in the input — expected "
            "bench.py's JSON output line (run `python bench.py | python "
            "tools/bench_gate.py -`)", file=sys.stderr,
        )
        return 2
    current = record["extra"]
    cand_profile = profile_of_extra(current)
    partial_sections = None
    if record.get("partial") and isinstance(record.get("sections"), list):
        partial_sections = set(record["sections"])

    files, bad_baselines, skipped = baseline_files()
    if bad_baselines:
        # Fail loudly rather than quietly gating against an OLDER round:
        # a truncated BENCH_r06.json must not let a PR pass vs BENCH_r05
        # with nobody noticing the intended baseline never loaded.
        for bad_name, reason in bad_baselines:
            print(f"bench_gate: unreadable baseline {bad_name}: {reason}",
                  file=sys.stderr)
        print("bench_gate: fix or remove the corrupt BENCH_*.json file(s) "
              "above — refusing to gate against a possibly-stale older "
              "baseline.", file=sys.stderr)
        return 2

    if args.profile:
        name, baseline = select_matching(files, cand_profile)
        if baseline is None:
            print(
                f"bench_gate: no BENCH_*.json baseline with profile "
                f"{cand_profile} under {REPO} — record one first (save "
                "bench.py's JSON output as BENCH_<host>_r<NN>.json) or gate "
                "against the default trajectory without --profile.",
                file=sys.stderr,
            )
            return 2
    else:
        name, baseline = select_round(files)
        if baseline is None:
            print(
                f"bench_gate: no BENCH_r*.json baseline found under {REPO} — "
                "nothing to gate against. Record one first (save bench.py's "
                "JSON output as BENCH_r<NN>.json) or run --list to see the "
                "gated metrics.", file=sys.stderr,
            )
            return 2
    demoting = newer_skipped(skipped, name)
    if demoting:
        # Same silent-demotion hazard as an unreadable file, parsable
        # edition: a wrong-shape run saved as the newest round must not
        # quietly hand the gate an older baseline.
        for skip_name, reason in demoting:
            print(f"bench_gate: baseline {skip_name} is newer than the "
                  f"selected {name} but unusable: {reason}", file=sys.stderr)
        print("bench_gate: fix or remove the file(s) above (only full "
              "bench.py runs can be round baselines) — refusing to gate "
              "against the older round.", file=sys.stderr)
        return 2
    base_profile = profile_of_extra(baseline)

    if base_profile != cand_profile:
        # Like-for-like refusal: a numeric verdict across hardware
        # profiles compares the machines, not the code. Loud n/a + exit
        # 2 — never pass, never numeric fail (docs/DEVHUB.md).
        print(f"bench gate vs {name}: n/a (profile mismatch)")
        for section, key, _ in GATED:
            print(f"  {section}.{key}  n/a (profile mismatch)")
        for section, key in GATED_EXACT:
            print(f"  {section}.{key}  n/a (profile mismatch)")
        print(
            f"bench_gate: profile mismatch — current run profile="
            f"{cand_profile}, baseline {name} profile={base_profile}: "
            "like-for-like gating refuses a numeric verdict across "
            "environments. Re-run with --profile to auto-select a matching "
            "BENCH_*.json, or record a first baseline for this profile "
            "(docs/DEVHUB.md).", file=sys.stderr,
        )
        try:
            from tigerbeetle_tpu import tracer

            # value=None, not 0: a refused verdict must never read as a
            # clean pass to anyone scanning the series for fail counts.
            tracer.devhub_append(args.devhub, {
                "metric": "bench_gate",
                "value": None,
                "unit": "fail_count",
                "verdict": "profile_mismatch",
                "extra": {
                    "baseline_file": name,
                    "profile_mismatch": {
                        "current": cand_profile, "baseline": base_profile,
                    },
                },
            })
        except OSError:
            pass
        return 2

    failed = []
    rows = []
    for section, key, higher_better in GATED:
        cur_sec = current.get(section) or {}
        base_sec = baseline.get(section) or {}
        label = f"{section}.{key}"
        cur_raw = lookup(cur_sec, key)
        base_raw = lookup(base_sec, key)
        if cur_raw is None:
            base = float(base_raw) if base_raw is not None else None
            if (partial_sections is not None
                    and section not in partial_sections):
                # bench.py --sections deliberately skipped this section:
                # n/a, never a MISSING failure (partial devhub runs don't
                # gate the sections they never measured).
                rows.append((label, None, base, "n/a (section skipped)"))
                continue
            # A section the current run skipped/errored FAILS the gate
            # whenever the baseline recorded it (a crashed bench must
            # not pass as "no regression"); when the baseline never
            # recorded it either, there is nothing to compare (n/a).
            if base is not None:
                failed.append(label)
            rows.append((
                label, None, base,
                "MISSING (section absent from current run)"
                if base is not None else "n/a",
            ))
            continue
        cur = float(cur_raw)
        base = float(base_raw) if base_raw is not None else None
        verdict = "n/a"
        if base is not None and base > 0:
            if higher_better:
                limit = base * (1.0 - THROUGHPUT_REGRESSION)
                ok = cur >= limit
            else:
                limit = base * (1.0 + LATENCY_REGRESSION)
                ok = cur <= limit
            verdict = "ok" if ok else "REGRESSION"
            if not ok:
                failed.append(label)
        rows.append((label, cur, base, verdict))

    for section, key in GATED_EXACT:
        cur_sec = current.get(section) or {}
        base_sec = baseline.get(section) or {}
        label = f"{section}.{key}"
        base = base_sec.get(key)
        cur = cur_sec.get(key)
        if base is None:
            rows.append((label, cur, None, "n/a"))
            continue
        if cur is None:
            if (partial_sections is not None
                    and section not in partial_sections):
                rows.append((label, None, float(base), "n/a (section skipped)"))
                continue
            failed.append(label)
            rows.append((label, None, float(base),
                         "MISSING (section absent from current run)"))
            continue
        ok = int(cur) == int(base)
        if not ok:
            failed.append(label)
        rows.append((
            label, float(cur), float(base),
            "ok" if ok else "COMPILE-COUNT DRIFT (retrace regression)",
        ))

    width = max(len(k) for k, *_ in rows)
    print(f"bench gate vs {name} (>10% regression fails; "
          f"profile={cand_profile}):")
    for label, cur, base, verdict in rows:
        cur_s = f"{cur:,.1f}" if cur is not None else "—"
        base_s = f"{base:,.1f}" if base is not None else "—"
        print(f"  {label:{width}s}  current={cur_s}  baseline={base_s}  {verdict}")

    try:
        from tigerbeetle_tpu import tracer

        tracer.devhub_append(args.devhub, {
            "metric": "bench_gate",
            "value": len(failed),
            "unit": "fail_count",
            "profile_id": cand_profile,
            "extra": {
                "baseline_file": name,
                "current": {
                    f"{s}.{k}": lookup(current.get(s) or {}, k)
                    for s, k in [(s, k) for s, k, _ in GATED] + list(GATED_EXACT)
                },
                "baseline": {
                    f"{s}.{k}": lookup(baseline.get(s) or {}, k)
                    for s, k in [(s, k) for s, k, _ in GATED] + list(GATED_EXACT)
                },
                "failed": failed,
            },
        })
    except OSError:
        pass
    if failed:
        print(f"bench_gate: FAIL ({', '.join(failed)})", file=sys.stderr)
        return 1
    print("bench_gate: PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
