"""BENCHMARK.json resolves to its files by name and keeps the contract's
rules; each driver builds the result line at a tiny size."""

import json
import os
import re

import pytest

import bench_tiny
from bench import harness as H

BENCH = H.load_json(H.ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert os.path.isfile(os.path.join(H.ROOT, BENCH["command"][1]))
    assert 1 <= BENCH["run_seconds"] <= 51
    # every configuration is some cell's; a pair of configuration and
    # traffic appears once
    assert {c["name"] for c in BENCH["configs"]} == \
        {w["config"] for w in BENCH["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("bench", [BENCH], ids=["benchmark"])
def test_names_and_units(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics] + \
        [w["name"] for w in bench["workloads"]] + \
        [c["name"] for c in bench["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] == 1 and len(w["why"]) <= 200
    for m in metrics:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for c in bench["configs"]:
        for k in c["reduced"]:
            assert NAME.match(k)
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    c = H.Cell(BENCH, cell)
    assert os.path.isfile(c.driver_path)
    assert os.path.isfile(os.path.join(bench_tiny.TINY, cell + ".json"))
    assert c.config["kind"] == c.traffic["kind"]
    cfg = next(x for x in BENCH["configs"] if x["name"] == c.entry["config"])
    assert sorted(c.config["reduced"]) == sorted(cfg["reduced"])
    assert c.config["source"] == cfg["source"]
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        # the metric's reader exists, and its cell reports what it moves
        assert os.path.isfile(os.path.join(H.BENCH, "metrics",
                                           m["name"] + ".py"))
        assert m["moves"] in names


def test_layers_and_kernels_named_once():
    layers = {}
    for m in BENCH["per_layer"]:
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
    for k in ("select_topk", "paged_attention", "page_migrate"):
        assert os.path.isfile(os.path.join(H.BENCH, "work", k + ".py"))


@pytest.mark.parametrize("cell", CELLS)
def test_driver_builds_the_result_line(cell):
    out, h, _ = bench_tiny.run_tiny(cell)
    assert out["attempted"] > 0 and out["failed"] == 0
    c = bench_tiny.tiny_cell(cell)
    assert set(out["metrics"]) == {m["name"] for m in c.end_to_end
                                   if m["name"] != "setup_s"}
    line = json.loads(H.result_line(True, out["attempted"], out["failed"],
                                    {k: {"value": v, "unit": "u"}
                                     for k, v in out["metrics"].items()},
                                    h.device(), out["checks"]))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert h.setup_s > 0
