"""BENCHMARK.json keeps to the benchmark's contract, and the harness finds
every cell, configuration, traffic mix, cell setting, metric reader and
reference by name from its own files."""
import json
import os
import re


import chipbench_tiny  # noqa: F401
from harness import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"][1].startswith("chipbench/")
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_every_cell_finds_its_parts():
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        cfg = spec.config(BENCH, w["config"])
        assert cfg["name"] == w["config"]
        assert spec.traffic(w["traffic"])["name"] == w["traffic"]
        c = spec.cell(w["name"])
        assert c["sample"] >= 1 and c["logit_gap_limit"] > 0
        assert hasattr(spec.reference(cfg), "layer")
        for kind in ("end_to_end", "per_layer"):
            assert spec.metrics_of(BENCH, w["name"], kind)
        assert any(m["name"] == "setup_s"
                   for m in spec.metrics_of(BENCH, w["name"], "end_to_end"))


def test_every_metric_has_a_reader_and_keeps_the_contract():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
        assert callable(spec.reader(m["name"]))
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        # the metric it moves is reported in every cell it is read in
        moved = next(x for x in BENCH["end_to_end"] if x["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_roofline_moves_have_an_mfu_beside_them():
    for m in BENCH["per_layer"]:
        if m["name"].split(".")[0].endswith("_roofline"):
            for cell in m["workloads"]:
                assert any("mfu" in x["name"] and x["moves"] == m["moves"]
                           and cell in x["workloads"]
                           for x in BENCH["per_layer"])


def test_config_files_are_distinct_and_reduced_keys_listed():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        assert os.path.exists(os.path.join(spec.CHECKOUT, c["file"]))
        cfg = spec.config(BENCH, c["name"])
        # every key changed from the source: cuts, and departures the
        # program forces
        changed = dict(cfg["reduced"], **cfg.get("departures", {}))
        assert sorted(c["reduced"]) == sorted(changed)
        for k, v in changed.items():
            assert cfg[k] == v["run"] != v["source"]
            assert not k.endswith(("_dim", "_rank", "_size"))
