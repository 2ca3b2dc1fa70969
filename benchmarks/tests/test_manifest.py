"""BENCHMARK.json against the contract's limits that a typo can break,
and against the files the harness will look for by name."""

import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import rehearse  # noqa: E402

# BENCHMARK.json as it is, and as it would be with each cell kept under tests/pending/
both = pytest.mark.parametrize(
    "pending", [None] + sorted(f[:-5] for f in os.listdir(rehearse.PENDING)))


def manifest(pending=None) -> dict:
    return rehearse.manifest(pending or "")


def line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


@both
def test_keys_names_units_and_lines(pending):
    m = manifest(pending)
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    names = []
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in m["paths"]))
        assert os.path.exists(os.path.join(REPO, c["file"]))
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(REPO, c["file"])) as f:
            assert set(c["reduced"]) == set(json.load(f)["reduced"])
        names.append(c["name"])
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and line(w["why"])
        assert w["config"] in {c["name"] for c in m["configs"]} and w["chips"] in (1, 4)
        assert os.path.exists(os.path.join(REPO, "benchmarks", "traffic", w["traffic"] + ".json"))
        names.append(w["name"])
    assert {w["config"] for w in m["workloads"]} == {c["name"] for c in m["configs"]}
    assert len({(w["config"], w["traffic"]) for w in m["workloads"]}) == len(m["workloads"])
    for metric in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
        assert metric["better"] in ("lower", "higher") and metric["source"] in SOURCES
        names.append(metric["name"])
    assert len(names) == len(set(names))
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert e["source"] in ("host_clock", "device_trace") and 0.01 <= e["bound"] <= 0.25
    assert "setup_s" in {e["name"] for e in m["end_to_end"]}
    assert len(json.dumps(m)) < 64 * 1024


@both
def test_each_layer_metric_has_its_reader_and_moves_what_its_cells_report(pending):
    m = manifest(pending)
    cells = [w["name"] for w in m["workloads"]]
    reported = {e["name"]: set(e.get("workloads", cells)) for e in m["end_to_end"]}
    layers = set()
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert line(p["layer"])
        layers.add(p["layer"])
        for cell in p.get("workloads", cells):
            assert cell in reported[p["moves"]], (p["name"], cell)
        with open(os.path.join(REPO, "benchmarks", "layer_metrics", p["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(REPO, "benchmarks", "readers", spec["reader"] + ".py"))
        if spec.get("needed_work"):
            assert os.path.exists(os.path.join(
                REPO, "benchmarks", "needed_work", spec["needed_work"] + ".py"))
        if p["name"].endswith("_roofline"):
            assert p["unit"] == "%" and p["source"] == "device_trace"
    for cell in cells:  # every cell reports a per-layer metric
        assert any(cell in p.get("workloads", cells) for p in m["per_layer"])
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:  # PERF.md's list of layers has each by that name
        assert f"**{layer}**" in perf, layer


@both
def test_a_read_latency_is_owed_by_the_cells_that_send_reads_and_no_other(pending):
    """`read_p50_ms` (PR 37) lists the cells whose traffic file weighs a read;
    a cell without reads has no such latency to report, and `run.py` fails a
    run whose manifest asks for one."""
    m = manifest(pending)
    sends_reads = set()
    for w in m["workloads"]:
        with open(os.path.join(REPO, "benchmarks", "traffic", w["traffic"] + ".json")) as f:
            if "balance" in json.load(f).get("weights", {}):
                sends_reads.add(w["name"])
    assert sends_reads == {"smallbank_1m.hotspot_balance_sat"}
    for metric in m["end_to_end"] + m["per_layer"]:
        if metric["name"].startswith("read_") or metric.get("moves", "").startswith("read_"):
            assert set(metric["workloads"]) == sends_reads, metric["name"]
    assert {"read_p50_ms", "read_balances_ms_per_read"} <= {
        x["name"] for x in m["end_to_end"] + m["per_layer"]}
