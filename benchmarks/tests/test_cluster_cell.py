"""The cluster cell, driven through the whole of `run.run_cell` on the CPU
at test_min size with three CPU processes (rehearse.py, whose `expect_cpu`
and `distinct_cpu` stand in for the harness's look at the chips, and which
finds the cell's entries under `pending/`: BENCHMARK.json does not hold the
cell yet, PERF.md, Open questions 0): the sound cluster is `correct` on
rows the two survivors served in a later view; its control,
`backups_lossy_scatter`, is not, with every code right; the traced run
reads the seven metrics the deployment brought and the accepted ones on the
primary's page, and holds the quorum check. About 30 s a case.

    python -m pytest benchmarks/tests/test_cluster_cell.py -q -p no:cacheprovider
"""

from test_faults import rehearse

CLUSTER = "cluster3_1m.transfers_sat"
BROUGHT = {"quorum_ms_per_batch", "prepare_ok_ms_per_batch", "replicated_bytes_per_batch",
           "view_changes_in_window", "backup_commit_thread_busy_pct",
           "backup_store_thread_busy_pct", "host_cpu_busy_pct"}
# accepted entries on the primary's page: two that list no cells, three that list cell 1
PRIMARYS = {"wal_ms_per_batch", "store_ms_per_batch", "commit_thread_busy_pct",
            "store_thread_busy_pct", "burst_tx_per_s"}


def test_the_sound_cluster_is_correct_on_what_the_survivors_serve():
    result = rehearse(CLUSTER)
    assert result["correct"] is True and result["failed"] == 0 < result["attempted"]
    # the accepted end-to-end entries that list no cells; `write_p95_ms` is cell 1's alone
    assert {"tx_per_s", "write_p50_ms", "setup_s"} == set(result["metrics"])
    compared = result["compared"]
    assert compared["accounts_compared"][0] == 1000 and compared["transfers_read_back"][0] > 0
    assert compared["survivor_rows_differing"] == [0, 0]
    assert compared["survivors_answered_in_old_view"] == [0, 0]
    device = result["device"]
    assert device["replicas"] == 3 and len(device["chips_held"]) == 3


def test_backups_lossy_scatter_is_not_correct_and_every_code_is_right():
    result = rehearse(CLUSTER, "backups_lossy_scatter")
    assert result["correct"] is False and result["failed"] == 0
    compared = result["compared"]
    assert compared["code_mismatches"][0] == 0 and compared["store_mismatches"][0] == 0
    assert compared["balance_mismatches"][0] > 0, compared  # only the backups' rows can say


def test_the_traced_run_reads_what_the_deployment_brought():
    result = rehearse(CLUSTER, "", "--trace", "1")  # (a quorum check that fails prints no result)
    metrics = result["metrics"]
    assert result["correct"] is True and BROUGHT | PRIMARYS <= set(metrics)
    assert metrics["view_changes_in_window"]["value"] == 0.0
    assert metrics["quorum_ms_per_batch"]["value"] > 0.0
    assert metrics["prepare_ok_ms_per_batch"]["value"] > 0.0
    # the prepares to two backups: twice a batch's 64 x 128 bytes and their headers, at least
    assert metrics["replicated_bytes_per_batch"]["value"] > 2 * 64 * 128 * 0.5
    assert 0.0 < metrics["host_cpu_busy_pct"]["value"] <= 100.0
